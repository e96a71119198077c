"""The program's own spans in a real ``jax.profiler`` trace (ISSUE 24):
``telemetry.scope`` always writes a ``ds:<name>`` annotation with its counts
as the event's stats; the engine loop and the serving step emit the names of
``telemetry/names.py``; with telemetry disabled nothing else happens, with it
enabled the ``TraceRecorder`` gets the same spans and counts."""

import ast
import glob
import json
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import llama
from deepspeed_tpu.ops.pallas.paged_attention import kernel_page_loads
from deepspeed_tpu.serving import ServingScheduler
from deepspeed_tpu.telemetry import names
from deepspeed_tpu.telemetry.trace import TRACE_FILE
from deepspeed_tpu.utils import groups

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


class Traced:
    """``with Traced(tmp_path) as t: ...`` then ``t.events``: the ``ds:``
    events of the capture, ``(name, start_ns, end_ns, stats)`` by start."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "xplane")

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        from jax.profiler import ProfileData
        path, = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        self.events = sorted(
            (ev.name[len(names.SPAN_PREFIX):], ev.start_ns,
             ev.start_ns + ev.duration_ns, dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith(names.SPAN_PREFIX))
        self.events.sort(key=lambda e: e[1])

    def named(self, name):
        return [e for e in self.events if e[0] == name]

    def inside(self, child, parent):
        return parent[1] <= child[1] and child[2] <= parent[2]


# ------------------------------------------------------------ the primitive
def test_scope_is_an_annotation_with_counts_and_nesting(tmp_path):
    assert not telemetry.enabled
    with Traced(tmp_path) as t:
        with telemetry.scope(names.SERVE_STEP, step=7) as span:
            with telemetry.scope(names.SERVE_ADMIT):
                pass
            span.set(phase="decode", kind=names.KIND_RAGGED, live_tokens=5)
        linear = telemetry.scope(names.TRAIN_MICRO, step=1,
                                 micro_step=2).begin()
        linear.end()
        telemetry.mark(names.SERVE_ADMITTED, uid="abc")
    step, = t.named(names.SERVE_STEP)
    assert step[3] == {"step": 7, "kind": "ragged", "live_tokens": 5}
    assert t.inside(t.named(names.SERVE_ADMIT)[0], step)
    assert t.named(names.TRAIN_MICRO)[0][3] == {"step": 1, "micro_step": 2}
    assert t.named(names.SERVE_ADMITTED)[0][3] == {"uid": "abc"}
    assert telemetry.get_recorder() is None


def test_enabled_scope_books_the_same_span_in_the_recorder(tmp_path):
    class Cfg:
        trace_dir = str(tmp_path / "tel")

    rec, _ = telemetry.configure(Cfg())
    try:
        assert not hasattr(rec, "device_annotations")
        telemetry.begin_step(3)
        with telemetry.scope(names.SERVE_STEP, cat="serve", step=3) as span:
            with telemetry.scope(names.SERVE_ADMIT):
                pass
            span.set(phase="mixed", live_tokens=9)
        with telemetry.scope(names.TRAIN_MICRO, phase="forward", step=3):
            pass
        record = telemetry.end_step()
        assert set(record["phases"]) == {"mixed", names.SERVE_ADMIT,
                                         "forward"}
        events = {e["name"]: e for e in rec.chrome_trace()["traceEvents"]}
        assert events["mixed"]["args"] == {"step": 3, "live_tokens": 9}
        assert events["mixed"]["cat"] == "serve"
        assert events["forward"]["args"] == {"step": 3}
    finally:
        telemetry.shutdown()


def test_named_program_gives_jit_its_module_name():
    from deepspeed_tpu.inference.v2 import ragged_forward
    from deepspeed_tpu.runtime.engine import _named_program
    fn = _named_program(lambda x: x + 1,
                        names.PROGRAM_MICRO + "overlap+prefetch")
    assert fn.__name__ == "ds_micro_overlap_prefetch"
    assert "jit_ds_micro_overlap_prefetch" in \
        jax.jit(fn).lower(jnp.zeros(2)).as_text()
    assert ragged_forward.llama_ragged_step.__name__ == \
        names.PROGRAM_RAGGED_STEP + "llama"
    assert ragged_forward.decode_burst.__name__ == names.PROGRAM_DECODE_BURST
    # decode_burst inlines the step through the jitted wrapper's function
    assert callable(ragged_forward.llama_ragged_step.__wrapped__)


# ------------------------------------------------------------------ serving
@pytest.fixture(scope="module")
def tiny():
    cfg = llama.llama_tiny(dtype="float32", remat=False,
                           num_key_value_heads=2)
    model = llama.LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _scheduler(tiny, num_blocks=96, decode_burst=4, **serving):
    model, params = tiny
    sm = dict(max_tracked_sequences=16, max_ragged_batch_size=32,
              max_ragged_sequence_count=12, max_context=64, block_size=8,
              num_blocks=num_blocks)
    engine = InferenceEngineV2(
        model, params=params,
        config=dict(dtype="float32", decode_burst=decode_burst,
                    state_manager=sm))
    return ServingScheduler(engine, serving or None)


def test_serving_steps_in_a_real_trace(tiny, tmp_path):
    sched = _scheduler(tiny)
    rng = np.random.default_rng(0)
    uids = [sched.submit(rng.integers(1, 96, size=n).tolist(),
                         max_new_tokens=6) for n in (40, 9, 17, 5)]
    n_steps = 0
    with Traced(tmp_path) as t:
        while not sched.idle:
            sched.step()
            n_steps += 1
        assert sched.step() == {}      # idle: no span
    steps = t.named(names.SERVE_STEP)
    assert len(steps) == n_steps
    for name in names.SERVE_STEP_CHILDREN:
        spans = t.named(name)
        assert spans, name
        assert all(any(t.inside(s, step) for step in steps) for s in spans)
    kinds = set()
    for step in steps:
        c = step[3]
        assert set(names.SERVE_STEP_COUNTS) <= set(c), c
        kinds.add(c["kind"])
        assert 0 < c["live_tokens"] <= c["token_budget"]
        assert c["live_tokens"] == c["prefill_tokens"] + c["decode_tokens"]
        assert c["grid_pages"] > 0 and "live_pages" not in c
        assert c["row_pages"] > 0
        # ragged steps and bursts both say how many loads were short items
        # (none here: heads of 16 stay on the per-token kernel)
        assert c["short_pages"] == 0
        assert (c["burst_k"] >= 1) == (c["kind"] == names.KIND_BURST)
    assert kinds == {names.KIND_RAGGED, names.KIND_BURST}
    # live_tokens is what the engine step consumed: every prompt token and
    # every generated token but each request's last went through a step
    total = sum(len(sched.query(u).prompt) + len(sched.query(u).produced) - 1
                for u in uids)
    assert sum(s[3]["live_tokens"] for s in steps) == total
    # the grid: every budget row times every page of the table: the tiny
    # model's heads of 16 stay on the per-token kernel
    # (tests/unit/ops/test_paged_runs.py counts the run-tiled kernel's loads)
    ragged = [s[3] for s in steps if s[3]["kind"] == names.KIND_RAGGED]
    assert {c["grid_pages"] for c in ragged} == {32 * (64 // 8)}
    assert all(c["row_pages"] > 0 for c in ragged)
    assert {c["token_budget"] for c in ragged} == {32}
    admitted = [e[3]["uid"] for e in t.named(names.SERVE_ADMITTED)]
    assert sorted(admitted) == sorted(uids)
    finished = {e[3]["uid"]: e[3]["tokens"]
                for e in t.named(names.SERVE_FINISHED)}
    assert finished == {u: 6 for u in uids}
    assert not hasattr(sched, "_phase")


class _Spy:
    """Records its calls, and is still a compiled program to whoever asks
    (``jax.stages.Wrapped``: ``__call__``, ``lower``, ``trace``)."""

    def __init__(self, fn, log, name):
        self.fn, self.log, self.name = fn, log, name

    def __call__(self, *args, **kw):
        self.log.append(self.name)
        return self.fn(*args, **kw)

    def lower(self, *args, **kw):
        return self.fn.lower(*args, **kw)

    def trace(self, *args, **kw):
        return self.fn.trace(*args, **kw)


def test_a_turn_launches_the_next_step_before_it_fetches_the_last(
        tiny, tmp_path, monkeypatch):
    """The span contract of the run-ahead turn (ISSUE 36): one
    ``ds:serve.step`` a LAUNCHED step, carrying that step's counts and
    ``launched_ahead``; its one ``ds:serve.fetch`` is the wait for the step
    before (none in the first turn; the last turn, with nothing left to
    launch, fetches its own step too); ``live_tokens`` sums to the rows
    computed.  And the order of the dispatches, the only guard on a CPU that
    a fetch waits for its own step alone: a step's token array is enqueued
    WITH the step, before the next step's programs, and fetched after
    them."""
    from deepspeed_tpu.inference.v2 import engine_v2
    sched = _scheduler(tiny, decode_burst=0)
    engine, log = sched.engine, []
    engine._step_fn = _Spy(engine._step_fn, log, "step")
    for name in ("_tokens_and_counts", "_take_chosen"):
        monkeypatch.setattr(engine_v2, name,
                            _Spy(getattr(engine_v2, name), log, name))
    assert engine.launches_programs
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 96, size=n).tolist() for n in (5, 9, 7)]
    uids = [sched.submit(p, max_new_tokens=5) for p in prompts]
    real = np.asarray
    with Traced(tmp_path) as t:
        monkeypatch.setattr(
            np, "asarray", lambda a, *args, **kw: (
                log.append("fetch") if isinstance(a, jax.Array) else None,
                real(a, *args, **kw))[1])
        turns = sched.drain()
        monkeypatch.undo()
    launch = ["_take_chosen", "step", "_tokens_and_counts"]
    # five steps: the prompts, then four of three decode rows whose ids the
    # device takes from the step before
    assert turns == 5
    assert log == launch[1:] + (launch + ["fetch"]) * 4 + ["fetch"]
    steps = t.named(names.SERVE_STEP)
    assert [s[3]["launched_ahead"] for s in steps] == [0, 1, 1, 1, 1]
    assert sched.steps_launched_ahead == 4
    fetches = t.named(names.SERVE_FETCH)
    assert [sum(t.inside(f, s) for f in fetches) for s in steps] == \
        [0, 1, 1, 1, 2]
    for step in steps:
        assert set(names.SERVE_STEP_COUNTS) <= set(step[3])
        launched, = [e for e in t.named(names.SERVE_LAUNCH)
                     if t.inside(e, step)]
        assert all(launched[2] <= f[1] for f in fetches
                   if t.inside(f, step))
    assert [s[3]["live_tokens"] for s in steps] == [21, 3, 3, 3, 3]
    assert [s[3]["decode_tokens"] for s in steps] == [0, 3, 3, 3, 3]
    assert [len(sched.query(u).produced) for u in uids] == [5, 5, 5]


# --------------------------------------------------- a step's life, one id
# What the turns' ``ds:serve.step`` spans of the drain below carried at the
# parent commit (ISSUE 37: "to the value what they were"), in the order of
# ``_OLD_COUNTS``; ``live_pages`` went with its last reader.
_OLD_COUNTS = ("step", "kind", "running", "queued", "token_budget",
               "live_tokens", "prefill_tokens", "decode_tokens",
               "grid_pages", "row_pages", "short_pages", "burst_k",
               "preempts", "launched_ahead", "context_tokens", "held_blocks",
               "block_size", "summary_pages", "chunks_closed",
               "windows_closed")
_OLD_VALUES = {
    0: [(1, "ragged", 4, 0, 32, 32, 32, 0, 256, 43, 0, 0, 0, 0, 32, 7, 8, 0,
         0, 0),
        (2, "ragged", 4, 0, 32, 32, 29, 3, 256, 77, 0, 0, 0, 1, 64, 10, 8,
         0, 0, 0),
        (3, "ragged", 4, 0, 32, 13, 10, 3, 256, 54, 0, 0, 0, 1, 77, 11, 8,
         0, 0, 0),
        (4, "ragged", 4, 0, 32, 4, 0, 4, 256, 12, 0, 0, 0, 1, 81, 12, 8, 0,
         0, 0),
        (5, "ragged", 4, 0, 32, 4, 0, 4, 256, 13, 0, 0, 0, 1, 85, 13, 8, 0,
         0, 0),
        (6, "ragged", 4, 0, 32, 4, 0, 4, 256, 13, 0, 0, 0, 1, 89, 13, 8, 0,
         0, 0),
        (7, "ragged", 4, 0, 32, 1, 0, 1, 256, 6, 0, 0, 0, 1, 44, 6, 8, 0, 0,
         0),
        (8, "ragged", 1, 0, 32, 1, 0, 1, 256, 6, 0, 0, 0, 1, 45, 6, 8, 0, 0,
         0)],
    4: [(1, "ragged", 4, 0, 32, 32, 32, 0, 256, 43, 0, 0, 0, 0, 32, 7, 8, 0,
         0, 0),
        (2, "ragged", 4, 0, 32, 32, 29, 3, 256, 77, 0, 0, 0, 1, 64, 10, 8,
         0, 0, 0),
        (3, "ragged", 4, 0, 32, 13, 10, 3, 256, 54, 0, 0, 0, 1, 77, 11, 8,
         0, 0, 0),
        (4, "burst", 4, 0, 24, 8, 0, 8, 192, 25, 0, 2, 0, 1, 85, 13, 8, 0, 0,
         0),
        # a least remainder of ONE: a burst of one iteration over the 12 slot
        # rows since ISSUE 55 (a ragged step over the budget's 32 before:
        # token_budget 32, grid_pages 256)
        (5, "burst", 4, 0, 12, 4, 0, 4, 96, 13, 0, 1, 0, 1, 89, 13, 8, 0,
         0, 0),
        (6, "burst", 4, 0, 24, 2, 0, 2, 192, 12, 0, 2, 0, 1, 45, 6, 8, 0, 0,
         0)]}


@pytest.fixture(scope="module")
def drained(tiny, tmp_path_factory):
    """``{decode_burst: (the capture, the scheduler, the streams)}`` of one
    drained scheduler a setting (four prompts, six new tokens each)."""
    made = {}
    for burst in _OLD_VALUES:
        sched = _scheduler(tiny, decode_burst=burst)
        rng = np.random.default_rng(0)
        streams = []
        for n in (40, 9, 17, 5):
            streams.append([])
            sched.submit(rng.integers(1, 96, size=n).tolist(),
                         max_new_tokens=6,
                         on_token=lambda t, d, out=streams[-1]: out.append(t))
        with Traced(tmp_path_factory.mktemp(f"burst{burst}")) as t:
            sched.drain()
        made[burst] = t, sched, streams
    return made


def _in_turns(t, name):
    """The ``name`` spans of a capture, a list for every ``ds:serve.step``."""
    return [[e for e in t.named(name) if t.inside(e, step)]
            for step in t.named(names.SERVE_STEP)]


@pytest.mark.parametrize("burst", list(_OLD_VALUES))
def test_a_fetch_names_the_step_launched_the_turn_before(drained, burst):
    t, sched, _ = drained[burst]
    launches, fetches = (_in_turns(t, n) for n in (names.SERVE_LAUNCH,
                                                   names.SERVE_FETCH))
    assert all(len(turn) == 1 for turn in launches)
    launched = [turn[0][3][names.COUNT_LAUNCH] for turn in launches]
    fetched = [[f[3][names.COUNT_LAUNCH] for f in turn] for turn in fetches]
    # none in the first turn; the last turn, with nothing left to launch,
    # fetches the step before and its own
    assert fetched == [[]] + [[n] for n in launched[:-2]] + [launched[-2:]]
    # within a turn the launch ends before the fetch of the step before it
    # begins: that step ran while the host built and launched this one
    for (launch, ), turn in zip(launches, fetches):
        assert all(launch[2] <= f[1] for f in turn)


@pytest.mark.parametrize("burst", list(_OLD_VALUES))
def test_launch_ids_rise_by_one_over_ragged_steps_and_bursts(drained, burst):
    t, sched, _ = drained[burst]
    launches = t.named(names.SERVE_LAUNCH)
    assert [e[3][names.COUNT_LAUNCH] for e in launches] == \
        list(range(1, len(launches) + 1))
    assert sched.engine.launches == len(launches)
    for e in launches:
        assert set(e[3]) == {names.COUNT_LAUNCH, "kind", "burst_k"}
    kinds = [(e[3]["kind"], e[3]["burst_k"]) for e in launches]
    assert kinds == [(row[1], row[11]) for row in _OLD_VALUES[burst]]
    assert ((names.KIND_BURST, 2) in kinds) == bool(burst)


@pytest.mark.parametrize("burst", list(_OLD_VALUES))
def test_a_turn_keeps_its_old_counts_and_names_its_two_steps(drained, burst):
    t, _, _ = drained[burst]
    steps = t.named(names.SERVE_STEP)
    assert [tuple(s[3][k] for k in _OLD_COUNTS) for s in steps] == \
        _OLD_VALUES[burst]
    # what has joined them since: the pages of the paged kernel's
    # multi-page items (PR 38; these prompts have no run of a block's pages)
    assert set(names.SERVE_STEP_COUNTS) - set(_OLD_COUNTS) == {"block_pages"}
    assert not any(s[3]["block_pages"] for s in steps)
    launches, fetches = (_in_turns(t, n) for n in (names.SERVE_LAUNCH,
                                                   names.SERVE_FETCH))
    for step, (launch, ), turn in zip(steps, launches, fetches):
        ids = {k: v for k, v in step[3].items()
               if k not in names.SERVE_STEP_COUNTS}
        want = {names.COUNT_LAUNCH: launch[3][names.COUNT_LAUNCH]}
        if turn:        # the newer one where the turn collected two
            want[names.COUNT_FETCHED] = turn[-1][3][names.COUNT_LAUNCH]
        assert ids == want and set(ids) <= set(names.SERVE_STEP_IDS)


@pytest.mark.parametrize("burst", list(_OLD_VALUES))
def test_dispatch_and_finished_carry_the_step_whose_tokens_they_book(
        drained, burst):
    t, sched, streams = drained[burst]
    fetches, dispatches = t.named(names.SERVE_FETCH), \
        t.named(names.SERVE_DISPATCH)
    assert [d[3][names.COUNT_LAUNCH] for d in dispatches] == \
        [f[3][names.COUNT_LAUNCH] for f in fetches]
    for d in dispatches:
        assert set(d[3]) == {names.COUNT_LAUNCH}
    assert sum(map(len, streams)) == 4 * 6
    # a request's last token comes with the step that ends it: the mark lies
    # inside that step's dispatch
    finished = t.named(names.SERVE_FINISHED)
    assert len(finished) == 4
    for mark in finished:
        home, = [d for d in dispatches if t.inside(mark, d)]
        assert mark[3][names.COUNT_LAUNCH] == home[3][names.COUNT_LAUNCH]
        assert mark[3]["tokens"] == 6


def _routed(burst, serial=False):
    """A tiny routed model (16 experts, 8 held: ``expert_copies`` and
    ``expert_active`` are counted on the device) behind a scheduler."""
    from deepspeed_tpu.models import cohere2_moe
    if not hasattr(_routed, "made"):
        model = cohere2_moe.Cohere2MoeModel(cohere2_moe.cohere2_moe_tiny())
        _routed.made = model, model.init(
            jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]
    model, params = _routed.made
    sm = dict(max_tracked_sequences=12, max_ragged_batch_size=24,
              max_ragged_sequence_count=6, max_context=128, block_size=8,
              num_blocks=96)
    engine = InferenceEngineV2(model, params=params, config=dict(
        dtype="float32", decode_burst=burst, state_manager=sm))
    if serial:          # the same loop, every step collected where launched
        engine.__class__ = type("Serial", (InferenceEngineV2, ),
                                {"launches_programs": False})
    sched = ServingScheduler(engine)
    rng = np.random.default_rng(1)
    # two prompts of more than two budgets: the first two steps are middle
    # chunks alone, which finish no row and fetch nothing
    for n, new in ((70, 10), (60, 12)):
        sched.submit(rng.integers(1, 96, size=n).tolist(), max_new_tokens=new)
    return sched


def _device_counts_by_launch(t):
    """``{launch: (launches_covered, expert_copies, expert_active)}`` of the
    capture's ``ds:serve.fetch`` spans."""
    keys = (names.COUNT_LAUNCHES_COVERED, names.COUNT_EXPERT_COPIES,
            names.COUNT_EXPERT_ACTIVE)
    return {f[3][names.COUNT_LAUNCH]: tuple(f[3][k] for k in keys)
            for f in t.named(names.SERVE_FETCH)}


@pytest.mark.parametrize("burst", [0, 4])
def test_device_counts_stand_on_the_fetch_of_the_step_that_counted_them(
        tmp_path, burst):
    """Run ahead, a fetch arrives a turn after its step's launch; the serial
    spelling of the same loop fetches every step in the turn that launched
    it.  Launch for launch the two fetch the same counts: they are the
    step's own, whichever turn brings them."""
    with Traced(tmp_path / "ahead") as ahead:
        sched = _routed(burst)
        sched.drain()
    with Traced(tmp_path / "serial") as serial:
        _routed(burst, serial=True).drain()
    got, want = (_device_counts_by_launch(t) for t in (ahead, serial))
    assert got == want and len(got) > 5
    assert all(copies > 0 and active > 0 for _, copies, active in
               got.values())
    # a step that fetched nothing left its counts to the next fetch: every
    # launch is covered once
    assert sum(covers for covers, _, _ in got.values()) == \
        sched.engine.launches > len(got)
    assert [covers for covers, _, _ in got.values()][:2] == [3, 1]
    # the turn in which they arrive still carries them (the readers that
    # are there read ``ds:serve.step``): to the value, the sum of its fetches
    for step, fetches in zip(ahead.named(names.SERVE_STEP),
                             _in_turns(ahead, names.SERVE_FETCH)):
        for key in (names.COUNT_EXPERT_COPIES, names.COUNT_EXPERT_ACTIVE):
            assert step[3].get(key, 0) == sum(f[3][key] for f in fetches)
    # the serial spelling collects in the turn that launched, fetch or none
    assert all(s[3][names.COUNT_FETCHED] == s[3][names.COUNT_LAUNCH]
               for s in serial.named(names.SERVE_STEP))


@pytest.mark.parametrize("burst", [0, 4])
def test_a_turn_that_launches_nothing_loses_no_count(tmp_path, monkeypatch,
                                                     burst):
    """After a ``KVCacheExhausted`` a turn collects what is in flight and
    may find nothing left to build: its ``ds:serve.step`` has no step's
    counts to add the fetched ones to (PERF.md's open question (e) before
    ISSUE 37).  They stand on the turn's ``ds:serve.fetch``."""
    from deepspeed_tpu.inference.v2.ragged import KVCacheExhausted
    with Traced(tmp_path / "plain") as plain:
        _routed(burst).drain()
    sched = _routed(burst)
    engine, real = sched.engine, sched.engine.launch_step
    calls = []      # of launch_step once three steps are launched

    def launch_step(**kw):
        if engine.launches == 3:
            calls.append(engine._uncollected)
            if len(calls) == 1:     # on top of step 3: the pool ran dry,
                raise KVCacheExhausted(1, 0)
            if len(calls) == 2:     # and with step 3 collected, nothing
                return None         # is left to build in this turn
        return real(**kw)

    monkeypatch.setattr(engine, "launch_step", launch_step)
    with Traced(tmp_path / "fault") as t:
        sched.drain()
    assert calls == [1, 0, 0]
    empty, = [s for s in t.named(names.SERVE_STEP)
              if names.COUNT_LAUNCH not in s[3]]
    assert empty[3][names.COUNT_FETCHED] == 3 and "kind" not in empty[3]
    assert names.COUNT_EXPERT_COPIES not in empty[3]
    fetch, = [f for f in t.named(names.SERVE_FETCH) if t.inside(f, empty)]
    assert fetch[3][names.COUNT_LAUNCH] == 3
    assert fetch[3][names.COUNT_EXPERT_COPIES] > 0
    # the same steps ran: launch for launch the plain drain's counts
    assert _device_counts_by_launch(t) == _device_counts_by_launch(plain)


def test_preemption_is_an_event_with_its_uid(tiny, tmp_path):
    sched = _scheduler(tiny, num_blocks=15, decode_burst=0)
    rng = np.random.default_rng(0)
    for _ in range(8):
        sched.submit(rng.integers(1, 96, size=8).tolist(), max_new_tokens=16)
    with Traced(tmp_path) as t:
        sched.drain()
    assert sched.preemptions >= 1
    events = t.named(names.SERVE_PREEMPTED)
    assert len(events) == sched.preemptions
    assert sum(s[3]["preempts"] for s in t.named(names.SERVE_STEP)) == \
        sched.preemptions
    # a preempted request is admitted again
    assert len(t.named(names.SERVE_ADMITTED)) == 8 + sched.preemptions


def _page_counts(engine, pos, slots):
    """``(grid, row, short)`` pages of one layer's call; none of these
    runs is long enough for a block of pages."""
    counts = engine._page_counts(pos, slots)
    assert list(counts) == ["grid_pages", "row_pages", "short_pages",
                            "block_pages"]
    assert counts.pop("block_pages") == 0
    return tuple(counts.values())


def test_page_counts_follow_the_kernel_and_the_window(tiny):
    engine = _scheduler(tiny).engine
    pos = np.array([0, 7, 8, 30, 0, 0, 0, 0], np.int32)
    slots = np.array([1, 2, 3, 4, 0, 0, 0, 0], np.int32)
    # heads of 16: the per-token kernel.  8 rows x 8 pages; contexts span
    # 1, 1, 2 and 4 pages
    assert _page_counts(engine, pos, slots) == (64, 8, 0)
    # the same rows with heads of 128, through the function the engine asks:
    # the run-tiled kernel loads each decode row's live pages, and nothing
    # for a dead row; a decode row's two query rows lie in one slab of 8, so
    # every load's item is short
    assert kernel_page_loads(
        slots, pos, heads=4, kv_heads=2, head_dim=128, kv_dtype=jnp.float32,
        block_size=8, maxb=8) == (8, 0, 8, 0)
    # a burst: k rows of positions
    assert _page_counts(engine, pos[None, :4] + np.arange(2)[:, None],
                               np.broadcast_to(slots[:4], (2, 4))) == (
        64, 1 + 2 + 2 + 4 + 8, 0)
    # heads of 128: the run-tiled kernel loads a run's pages once, and only
    # live ones.  Rows 0-2 are one run of slot 1 (positions 6, 7, 8: pages
    # 0-1), row 3 a decode row at 30 (pages 0-3); three tokens' six query
    # rows lie in one slab and the fourth's two end it: every item is short
    engine.model_config = types.SimpleNamespace(
        num_attention_heads=4, num_key_value_heads=2, head_dim=128)
    pos = np.array([6, 7, 8, 30, 0, 0, 0, 0], np.int32)
    slots = np.array([1, 1, 1, 4, 0, 0, 0, 0], np.int32)
    assert _page_counts(engine, pos, slots) == (2 + 4, 1 + 1 + 2 + 4, 2 + 4)
    # a window of 8 keeps position 30's pages 2-3 and position 8's 0-1
    engine.model_config.sliding_window = 8
    assert _page_counts(engine, pos, slots) == (2 + 2, 1 + 1 + 2 + 2, 2 + 2)
    # a fourth token of the run (eight query rows, then two): the decode
    # row's slab is the next one, the run still fills its own
    pos = np.array([6, 7, 8, 9, 30, 0, 0, 0], np.int32)
    slots = np.array([1, 1, 1, 1, 4, 0, 0, 0], np.int32)
    assert _page_counts(engine, pos, slots)[2] == 2 + 2
    # a fifth: ten rows straddle two slabs, its items compute the tile
    pos = np.array([6, 7, 8, 9, 10, 30, 0, 0], np.int32)
    slots = np.array([1, 1, 1, 1, 1, 4, 0, 0], np.int32)
    assert _page_counts(engine, pos, slots)[2] == 2


# ----------------------------------------------------------------- training
def _train_engine(extra=None):
    groups.reset_mesh()
    cfg = llama.llama_tiny(dtype="float32", remat=False)
    config = {"train_micro_batch_size_per_gpu": 2,
              "gradient_accumulation_steps": 2,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 2},
              "mesh": {"dp": jax.device_count()}}
    config.update(extra or {})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=llama.LlamaModel(cfg), config=config)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size,
        size=(2 * jax.device_count(), 16)).astype(np.int32)
    engine.initialize_parameters(jax.random.PRNGKey(0), ids, ids)
    return engine, ids


def _train(engine, ids, micro_steps):
    losses = []
    for _ in range(micro_steps):
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        losses.append(np.asarray(loss).tobytes())
    return losses


def test_engine_steps_in_a_real_trace_and_nothing_else_when_disabled(
        tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    from deepspeed_tpu.accelerator import get_accelerator
    syncs = []
    monkeypatch.setattr(type(get_accelerator()), "synchronize",
                        lambda self, *a, **k: syncs.append(1))
    engine, ids = _train_engine()
    plain = _train(engine, ids, 2)          # compiles; one optimizer step
    syncs.clear()
    with Traced(tmp_path) as t:
        traced = _train(engine, ids, 6)     # three optimizer steps
    assert not telemetry.enabled and telemetry.get_recorder() is None
    assert syncs == []                      # no device sync was bought
    assert os.listdir(str(tmp_path)) == ["xplane"]      # and no file
    for name in (names.TRAIN_SHARD_BATCH, names.TRAIN_MICRO,
                 names.TRAIN_BACKWARD, names.TRAIN_ACCUMULATE):
        spans = t.named(name)
        assert [s[3]["micro_step"] for s in spans] == list(range(2, 8)), name
        assert [s[3]["step"] for s in spans] == [1, 1, 2, 2, 3, 3]
    for name in (names.TRAIN_APPLY, names.TRAIN_REPORT):
        assert [(s[3]["step"], s[3]["micro_step"]) for s in t.named(name)] \
            == [(1, 3), (2, 5), (3, 7)], name
    assert set(names.TRAIN_SPANS) == {e[0] for e in t.events}
    for acc, bwd in zip(t.named(names.TRAIN_ACCUMULATE),
                        t.named(names.TRAIN_BACKWARD)):
        assert t.inside(acc, bwd)
    # the annotations change no number: the same engine, rebuilt, gives the
    # same losses bit for bit with no profiler session open
    engine2, _ = _train_engine()
    assert _train(engine2, ids, 8) == plain + traced


def test_enabled_recorder_gets_the_engine_spans_under_its_phase_names(
        tmp_path):
    tel = {"telemetry": {"enabled": True, "trace_dir": str(tmp_path)}}
    engine, ids = _train_engine(tel)
    try:
        with_tel = _train(engine, ids, 4)
    finally:
        telemetry.shutdown()
    engine2, _ = _train_engine()
    assert _train(engine2, ids, 4) == with_tel
    trace = json.load(open(os.path.join(str(tmp_path), TRACE_FILE)))
    by_name = {}
    for e in trace["traceEvents"]:
        by_name.setdefault(e["name"], []).append(e)
    # the documented phase columns, fed by the ds: spans, with their counts
    for phase, n in (("forward", 4), ("backward", 4), ("grad_reduce", 4),
                     ("optimizer", 2), (names.TRAIN_SHARD_BATCH, 4),
                     (names.TRAIN_REPORT, 2)):
        assert len(by_name[phase]) == n, phase
        assert set(by_name[phase][0]["args"]) == {"step", "micro_step"}
    assert [e["args"]["step"] for e in by_name["optimizer"]] == [0, 1]


def test_enabled_recorder_gets_the_serving_step_with_its_counts(
        tiny, tmp_path):
    class Cfg:
        trace_dir = str(tmp_path)

    rec, _ = telemetry.configure(Cfg())
    try:
        sched = _scheduler(tiny, decode_burst=0)
        sched.submit(list(range(1, 20)), max_new_tokens=3)
        sched.drain()
        events = rec.chrome_trace()["traceEvents"]
        ahead = telemetry.counter("serving/steps_launched_ahead").value
    finally:
        telemetry.shutdown()
    steps = [e for e in events if e["cat"] == "serve"]
    assert ahead == sum(e["args"]["launched_ahead"] for e in steps) == 2
    phases = [e["name"] for e in steps]
    assert phases[0] == "prefill" and phases[-2:] == ["decode", "decode"]
    assert set(phases) == {"prefill", "decode"}
    assert sum(e["args"]["prefill_tokens"] for e in steps) == 19
    assert set(names.SERVE_STEP_COUNTS) <= set(steps[0]["args"])
    # the ids reach the recorder through the same ``span.set``
    assert [e["args"][names.COUNT_LAUNCH] for e in steps] == [1, 2, 3]
    assert [e["args"].get(names.COUNT_FETCHED) for e in steps] == \
        [None, 1, 3]
    children = {e["name"] for e in events} - {e["name"] for e in steps}
    assert set(names.SERVE_STEP_CHILDREN) <= children


def test_the_registry_counts_the_bursts_of_one(tiny, tmp_path):
    """ISSUE 55: a reply of 4 tokens after its prompt's step: 3 left, a
    burst of 2 and a burst of ONE, which ``serving/bursts_of_one`` counts
    (so a deployment sees without a trace how often a decode-only turn would
    have run the budget-wide ragged step) and the recorder's spans show as
    ``burst_k`` 1."""
    class Cfg:
        trace_dir = str(tmp_path)

    rec, _ = telemetry.configure(Cfg())
    try:
        sched = _scheduler(tiny, decode_burst=4)
        sched.submit(list(range(1, 20)), max_new_tokens=4)
        sched.drain()
        events = rec.chrome_trace()["traceEvents"]
        ones = telemetry.counter("serving/bursts_of_one").value
    finally:
        telemetry.shutdown()
    steps = [e["args"] for e in events if e["cat"] == "serve"]
    assert [(a["kind"], a["burst_k"]) for a in steps] == [
        ("ragged", 0), ("burst", 2), ("burst", 1)]
    assert ones == sched.bursts_of_one == 1


# ------------------------------------------------------------------ kernels
def _pallas_call_names():
    found = []
    base = os.path.join(ROOT, "deepspeed_tpu", "ops", "pallas")
    for f in sorted(os.listdir(base)):
        if not f.endswith(".py"):
            continue
        for node in ast.walk(ast.parse(open(os.path.join(base, f)).read())):
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "attr", "") == "pallas_call":
                kw = {k.arg: k.value for k in node.keywords}
                assert "name" in kw, (f, node.lineno)
                value = kw["name"]
                consts = ([value.body, value.orelse]
                          if isinstance(value, ast.IfExp) else [value])
                for c in consts:
                    assert isinstance(c, ast.Constant), (f, node.lineno)
                    found.append((f, c.value))
    return found


def test_every_pallas_call_has_a_ds_name_listed_in_the_docs():
    found = _pallas_call_names()
    assert len({f + str(i) for i, (f, _) in enumerate(found)}) >= 17
    kernel_names = [n for _, n in found]
    assert len(set(kernel_names)) == len(kernel_names)
    doc = open(os.path.join(ROOT, "docs", "kernels.md")).read()
    for name in kernel_names:
        assert name.startswith(names.KERNEL_PREFIX), name
        assert f"`{name}`" in doc, f"{name} missing from docs/kernels.md"
    assert sum(n.startswith(names.KERNEL_FLASH) for n in kernel_names) == 7
    assert sum(n.startswith(names.KERNEL_PAGED) for n in kernel_names) == 4
    assert sum(n.startswith(names.KERNEL_OPTIMIZER)
               for n in kernel_names) == 4


def test_the_latent_steps_scopes_reach_its_compiled_program():
    """``ds.mla_down``, ``ds.kv_cache`` and ``ds.mla_absorb`` inside
    ``ds.attn``, the three expert scopes inside ``ds.mlp``: the scope paths
    of the compiled latent-attention step (the device trace carries the
    same)."""
    import re
    from deepspeed_tpu.inference.v2 import ragged_forward as rf
    from deepspeed_tpu.inference.v2.ragged import BlockedKVCache
    from deepspeed_tpu.models import pangu_ultra_moe as pm
    cfg = pm.pangu_ultra_moe_tiny()
    model = pm.PanguUltraMoeModel(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    cache = jax.eval_shape(lambda: BlockedKVCache(
        cfg.num_hidden_layers, 6, 8, 0, 0, dtype=jnp.float32,
        latent_dim=cfg.kv_latent_dim).layers)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = rf.pangu_ultra_moe_ragged_step.lower(
        params, cache, i32(16), i32(16), i32(16), i32(3, 4), i32(3), cfg=cfg,
        block_size=8).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(p.startswith("jit(" + names.PROGRAM_RAGGED_STEP
                            + "pangu_ultra_moe)") for p in paths)
    inside = lambda outer, scope: any(
        f"/{outer}/{scope}/" in p or p.endswith(f"/{outer}/{scope}")
        for p in paths)
    for scope in (names.SCOPE_MLA_DOWN, names.SCOPE_KV_CACHE,
                  names.SCOPE_MLA_ABSORB):
        assert inside(names.SCOPE_ATTENTION, scope), scope
    for scope in (names.SCOPE_MOE_ROUTER, names.SCOPE_MOE_EXPERTS,
                  names.SCOPE_MOE_SHARED):
        assert inside(names.SCOPE_MLP, scope), scope
    assert rf.pangu_ultra_moe_ragged_step.step_counts == (
        names.COUNT_EXPERT_COPIES, names.COUNT_EXPERT_ACTIVE)


def test_names_reach_the_compiled_program_of_the_tiny_model():
    """The scope path of the loss ops and of the attention kernel, as the
    compiled program's text has them (the device trace carries the same)."""
    cfg = llama.llama_tiny(dtype="float32", remat=True)
    model = llama.LlamaModel(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    def loss(p):
        return model.apply({"params": p}, ids, ids)

    import re
    text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))

    def has(component, backward=False, remat=False):
        return any(f"/{component}/" in p
                   and (names.MARK_TRANSPOSE in p) == backward
                   and (names.MARK_REMAT in p) == remat for p in paths)

    for scope in (names.SCOPE_LM_HEAD_LOSS, names.SCOPE_EMBED):
        assert has(scope) and has(scope, backward=True), scope
    # the flax module names give attention and MLP; remat marks the
    # recomputed forward (it runs inside the backward pass)
    for module in (names.MODULE_ATTENTION, names.MODULE_MLP):
        assert has(module) and has(module, backward=True), module
        assert has(module, backward=True, remat=True), module
