"""Serving-scheduler unit tests (ISSUE-11): typed request lifecycle,
admission order, KV-pressure backpressure, LIFO preemption with bit-exact
block-table restoration, and the streamed-tokens-match-one-shot-generate
CPU e2e smoke."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import llama
from deepspeed_tpu.inference.v2 import InferenceEngineV2, KVCacheExhausted
from deepspeed_tpu.serving import (AdmissionQueueFull, IllegalTransition,
                                   Request, RequestState, ServingScheduler)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.llama_tiny(dtype="float32", remat=False,
                           num_key_value_heads=2)
    model = llama.LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, cfg, params


def _engine(tiny, num_blocks=96, block_size=8, max_context=64,
            max_seqs=12, decode_burst=8):
    model, _, params = tiny
    sm = dict(max_tracked_sequences=max_seqs + 4,
              max_ragged_batch_size=64,
              max_ragged_sequence_count=max_seqs,
              max_context=max_context, block_size=block_size,
              num_blocks=num_blocks)
    return InferenceEngineV2(
        model, params=params,
        config=dict(dtype="float32", decode_burst=decode_burst,
                    state_manager=sm))


def _prompts(n, seed=0, size=8, vocab=96):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, size=size).tolist() for _ in range(n)]


# ------------------------------------------------------------------ lifecycle
def test_request_lifecycle_legal_path():
    req = Request(uid=0, prompt=[1, 2, 3])
    assert req.state is RequestState.QUEUED
    req.transition(RequestState.PREFILL)
    req.transition(RequestState.DECODE)
    req.transition(RequestState.EVICTED)
    req.transition(RequestState.QUEUED)   # requeue after preemption
    req.transition(RequestState.PREFILL)
    req.transition(RequestState.DECODE)
    req.transition(RequestState.DONE)


def test_request_lifecycle_illegal_edges():
    req = Request(uid=0, prompt=[1])
    with pytest.raises(IllegalTransition):
        req.transition(RequestState.DECODE)       # QUEUED → DECODE skips
    req.transition(RequestState.PREFILL)
    req.transition(RequestState.DECODE)
    req.transition(RequestState.DONE)
    with pytest.raises(IllegalTransition):
        req.transition(RequestState.QUEUED)       # DONE is terminal


def test_request_latency_accounting():
    req = Request(uid=0, prompt=[1], t_submit=10.0)
    req.record_token(5, 12.0, False)
    req.record_token(6, 12.5, False)
    req.record_token(7, 13.5, True)
    assert req.ttft == pytest.approx(2.0)
    assert req.token_gaps == pytest.approx([0.5, 1.0])
    assert req.produced == [5, 6, 7]


# ------------------------------------------------------------------ admission
def test_admission_is_fifo_and_caps_concurrency(tiny):
    eng = _engine(tiny)
    sched = ServingScheduler(eng, config=dict(max_concurrent=2))
    uids = [sched.submit(p) for p in _prompts(5)]
    sched.step()
    running = {u for u, r in sched._running.items()}
    assert running == set(uids[:2])          # FIFO: first two admitted
    assert sched.query(uids[2]).state is RequestState.QUEUED
    # admit order is the preemption ticket sequence
    assert (sched.query(uids[0]).admit_order
            < sched.query(uids[1]).admit_order)


def test_admission_queue_bound(tiny):
    eng = _engine(tiny)
    sched = ServingScheduler(eng, config=dict(max_queue_depth=2))
    sched.submit([1, 2])
    sched.submit([3, 4])
    with pytest.raises(AdmissionQueueFull):
        sched.submit([5, 6])


def test_duplicate_live_uid_rejected(tiny):
    eng = _engine(tiny)
    sched = ServingScheduler(eng)
    sched.submit([1, 2], uid=7)
    with pytest.raises(ValueError, match="already live"):
        sched.submit([3, 4], uid=7)


def test_non_integer_uid_accepted(tiny):
    """Explicit uids may be any hashable; auto-uids keep counting."""
    eng = _engine(tiny)
    sched = ServingScheduler(eng)
    uid = sched.submit(_prompts(1)[0], max_new_tokens=3, uid="req-42")
    auto = sched.submit(_prompts(1, seed=1)[0], max_new_tokens=3)
    assert uid == "req-42" and isinstance(auto, int)
    sched.drain()
    assert sched.query("req-42").state is RequestState.DONE
    assert len(sched.query("req-42").produced) == 3


def test_kv_backpressure_holds_admission(tiny):
    """With the pool nearly full, later requests must wait in the queue
    (not crash, not over-admit) and run after capacity frees."""
    eng = _engine(tiny, num_blocks=9, block_size=8)   # 8 usable blocks
    sched = ServingScheduler(eng)
    # each request: 1 prompt block + 1 reserve block = 2 charged blocks
    uids = [sched.submit(p, max_new_tokens=4) for p in _prompts(6)]
    sched.step()
    assert 0 < len(sched._running) < 6     # backpressure held some back
    sched.drain()
    assert sched.completed == 6
    assert all(sched.query(u).state is RequestState.DONE for u in uids)


# ----------------------------------------------------------------- preemption
def test_preemption_restores_block_table_bit_exact(tiny):
    """Force an exhaustion-driven LIFO preemption and verify the victim's
    slot releases its blocks bit-exactly (block-table row zeroed, allocator
    pool restored), then that the re-admitted victim finishes with tokens
    identical to an unpreempted run."""
    eng = _engine(tiny, num_blocks=15, block_size=8, decode_burst=0)
    ref = _engine(tiny).generate(_prompts(8), max_new_tokens=16)

    sched = ServingScheduler(eng)
    uids = [sched.submit(p, max_new_tokens=16) for p in _prompts(8)]
    table = eng.state_manager.block_table
    free0 = eng.kv_cache.num_blocks - 1
    seen_preempt = False
    for _ in range(500):
        pre_running = dict(sched._running)
        preempt_before = sched.preemptions
        sched.step()
        if sched.preemptions > preempt_before:
            seen_preempt = True
            victims = [u for u in pre_running if u not in sched._running
                       and sched.query(u).state is RequestState.QUEUED]
            assert victims
            for u in victims:
                seq = pre_running[u]
                # the engine no longer tracks the victim at all
                assert eng.state_manager.get_sequence(u) is None
        if sched.idle:
            break
    assert seen_preempt
    assert sched.completed == 8
    # every slot row back to zero, every block back in the pool — bit-exact
    assert not table.any()
    assert eng.state_manager.free_blocks == free0
    # and the produced tokens are EXACTLY the unpreempted engine's
    assert [sched.query(u).produced for u in uids] == ref
    assert sched.query(uids[-1]).preemptions >= 0


def test_preemption_gives_up_when_unrecoverable(tiny):
    """A single request that cannot fit must surface the typed exhaustion
    (nothing to preempt around), not loop forever."""
    eng = _engine(tiny, num_blocks=3, block_size=8, max_context=64,
                  decode_burst=0)   # 2 usable blocks
    sched = ServingScheduler(eng)
    sched.submit(_prompts(1, size=20)[0], max_new_tokens=8)
    with pytest.raises(KVCacheExhausted) as ei:
        for _ in range(50):
            sched.step()
    assert ei.value.free_blocks >= 0 and ei.value.wanted_blocks > 0


# ----------------------------------------------------------------- e2e smoke
def test_streams_match_one_shot_generate(tiny):
    """CPU e2e: 8 concurrent requests on a starved pool; per-token streamed
    callbacks must reproduce one-shot ``generate`` token-for-token."""
    prompts = _prompts(8, seed=3)
    ref = _engine(tiny).generate(prompts, max_new_tokens=12)

    eng = _engine(tiny, num_blocks=15, block_size=8)
    sched = ServingScheduler(eng)
    streams = {i: [] for i in range(8)}
    done_flags = {}
    for i, p in enumerate(prompts):
        sched.submit(
            p, max_new_tokens=12,
            on_token=lambda t, d, i=i: (streams[i].append(t),
                                        done_flags.__setitem__(i, d)))
    sched.drain()
    assert sched.peak_running >= 8 or sched.preemptions >= 1
    assert [streams[i] for i in range(8)] == ref
    assert all(done_flags[i] for i in range(8))   # final token flagged done


def test_eos_completion_and_immediate_flush(tiny):
    """EOS mid-stream finishes the request, flushes its blocks at once and
    truncates exactly as ``generate`` does."""
    prompts = _prompts(2, seed=5)
    probe = _engine(tiny).generate(prompts, max_new_tokens=9)
    eos = probe[0][4]
    ref = _engine(tiny).generate(prompts, max_new_tokens=9,
                                 eos_token_id=eos)
    eng = _engine(tiny)
    sched = ServingScheduler(eng)
    out = sched.serve(prompts, max_new_tokens=9, eos_token_id=eos)
    assert out == ref
    assert eng.state_manager.free_blocks == eng.kv_cache.num_blocks - 1


def test_serve_with_sampling_config(tiny):
    """Sampled serving (host RNG path) produces the requested counts and
    completes; burst stays disengaged exactly like generate's rule."""
    eng = _engine(tiny)
    sched = ServingScheduler(eng, config=dict(do_sample=True,
                                              temperature=0.8, seed=0))
    out = sched.serve(_prompts(3, seed=7), max_new_tokens=5)
    assert [len(o) for o in out] == [5, 5, 5]


# ------------------------------------------------------- the run-ahead turn
# The scheduler launches step n+1 before it fetches step n's tokens (ISSUE
# 36).  The serial spelling of the SAME loop is an engine that says its
# launches are no mere enqueues (``launches_programs``): same programs, the
# tokens collected in the turn that launched them.
class _SerialEngine(InferenceEngineV2):
    launches_programs = False


def _serial(engine):
    engine.__class__ = _SerialEngine
    return engine


def _preset(name):
    """``(model, params, vocab)`` of a tiny preset, made once."""
    if name not in _preset.made:
        if name == "llama":
            cfg = llama.llama_tiny(dtype="float32", remat=False,
                                   num_key_value_heads=2)
            model = llama.LlamaModel(cfg)
        elif name == "evabyte":         # windows of 32 close, chunks of 4
            from deepspeed_tpu.models import evabyte
            cfg = evabyte.evabyte_tiny(dtype="float32")
            model = evabyte.EvaByteModel(cfg)
        elif name == "jamba":           # recurrent state rows a slot
            from deepspeed_tpu.models import jamba
            cfg = jamba.jamba_tiny()
            model = jamba.JambaModel(cfg)
        elif name == "cohere2_moe":     # 16 experts, 8 held: device counts
            from deepspeed_tpu.models import cohere2_moe
            cfg = cohere2_moe.cohere2_moe_tiny()
            model = cohere2_moe.Cohere2MoeModel(cfg)
        else:                           # a latent cache, device counts
            from deepspeed_tpu.models import pangu_ultra_moe
            cfg = pangu_ultra_moe.pangu_ultra_moe_tiny()
            model = pangu_ultra_moe.PanguUltraMoeModel(cfg)
        params = model.init(jax.random.PRNGKey(3),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        _preset.made[name] = model, params, min(cfg.vocab_size, 96)
    return _preset.made[name]


_preset.made = {}


def _preset_engine(name, burst=4, num_blocks=96, **extra):
    model, params, _ = _preset(name)
    sm = dict(max_tracked_sequences=12, max_ragged_batch_size=24,
              max_ragged_sequence_count=6, max_context=128, block_size=8,
              num_blocks=num_blocks)
    return InferenceEngineV2(model, params=params, config=dict(
        dtype="float32", decode_burst=burst, state_manager=sm, **extra))


def _run(sched, requests, eos=None):
    """Submit ``[(prompt, max_new)]``, step to idle.  Returns the streams
    (from the callbacks), the ``last_step_counts`` of every launched step,
    and the (token, done) pairs as streamed."""
    streams = [[] for _ in requests]
    flags = [[] for _ in requests]
    for i, (prompt, new) in enumerate(requests):
        sched.submit(prompt, max_new_tokens=new, eos_token_id=eos,
                     on_token=lambda t, d, i=i: (streams[i].append(t),
                                                 flags[i].append(d)))
    steps = []
    while not sched.idle:
        sched.step()
        counts = sched.engine.last_step_counts
        assert not any(counts is c for c in steps)   # a step a turn
        steps.append(counts)
    assert sched._in_flight is None and sched.engine._uncollected == 0
    assert all(f[-1] and not any(f[:-1]) for f in flags)
    return streams, steps


def _mixed(vocab, seed=1):
    """A long prefill (three budgets and more), short prompts that decode
    beside it, and a tail where all decode together: a burst."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, vocab, size=n).tolist(), new)
            for n, new in ((70, 10), (9, 14), (17, 6), (5, 12))]


@pytest.mark.parametrize(
    "name", ["llama", "evabyte", "cohere2_moe", "pangu_ultra_moe"])
def test_run_ahead_streams_are_the_serial_spellings(name):
    """(a) Token for token: the run-ahead turn, the same loop collecting in
    the turn that launched, and ``generate()`` (``schedule_step`` = launch +
    collect in one call).  EvaByte closes windows and frees their blocks
    while a step is in flight; the routed models' device counts add up to
    the serial loop's."""
    vocab = _preset(name)[2]
    requests = _mixed(vocab)
    ahead = ServingScheduler(_preset_engine(name))
    serial = ServingScheduler(_serial(_preset_engine(name)))
    got, steps = _run(ahead, requests)
    want, serial_steps = _run(serial, requests)
    assert got == want
    assert [len(s) for s in got] == [new for _, new in requests]
    # each request decodes as generate() decodes it (run apart: generate
    # puts all prompts in at once, more rows than this engine's slots)
    eng = _preset_engine(name)
    assert got == [eng.generate([p], max_new_tokens=n)[0]
                   for p, n in requests]
    free0 = ahead.engine.kv_cache.num_blocks - 1
    assert ahead.engine.state_manager.free_blocks == free0
    assert not ahead.engine.state_manager.block_table.any()
    # every step but the first was launched on top of the one before it...
    assert ahead.steps_launched_ahead == len(steps) - 1
    assert serial.steps_launched_ahead == 0
    kinds = {c["kind"] for c in steps}
    assert kinds == {"ragged", "burst"}
    assert any(c["prefill_tokens"] and c["decode_tokens"] for c in steps)
    # ... and computed the same rows: no row past a reply's last token
    rows = sum(len(p) + new - 1 for p, new in requests)
    assert sum(c["live_tokens"] for c in steps) == rows
    assert sum(c["live_tokens"] for c in serial_steps) == rows
    for key in ahead.engine._device_counts:      # expert_copies, _active
        assert sum(c.get(key, 0) for c in steps) == \
            sum(c.get(key, 0) for c in serial_steps) > 0


@pytest.mark.parametrize("burst", [0, 4], ids=["steps", "burst"])
def test_eos_found_while_the_next_step_is_in_flight(tiny, burst):
    """(b) A request that ends by EOS has one row in the step in flight:
    its token is dropped, nothing is streamed past the EOS, the blocks
    return and the pool ends where the serial loop's does."""
    prompts = _prompts(3, seed=5)
    probe = _engine(tiny).generate(prompts, max_new_tokens=12)
    eos = probe[0][4]
    ref = _engine(tiny).generate(prompts, max_new_tokens=12,
                                 eos_token_id=eos)
    assert ref[0][-1] == eos and len(ref[0]) < 12 == len(ref[1])
    requests = [(p, 12) for p in prompts]
    ahead = ServingScheduler(_engine(tiny, decode_burst=burst))
    serial = ServingScheduler(_serial(_engine(tiny, decode_burst=burst)))
    got, steps = _run(ahead, requests, eos=eos)
    want, serial_steps = _run(serial, requests, eos=eos)
    assert got == want == ref
    assert ahead.steps_launched_ahead > 0
    for sched in (ahead, serial):
        eng = sched.engine
        assert eng.state_manager.free_blocks == eng.kv_cache.num_blocks - 1
        assert not eng.state_manager.block_table.any()
        assert not eng.state_manager.tracked_sequences
    if not burst:
        # the stale rows: one a request that ended by EOS, and no other
        n_eos = sum(s[-1] == eos for s in got)
        assert sum(c["live_tokens"] for c in steps) == \
            sum(c["live_tokens"] for c in serial_steps) + n_eos


@pytest.mark.parametrize("burst", [0, 8], ids=["steps", "burst"])
def test_an_end_by_length_gets_no_row_past_its_last_token(tiny, burst):
    """(c) Known from counts: the reply has exactly the asked length and the
    engine never ran a row for a token past it, one new token included.
    With bursts on, the replies of 2 and 3 tokens END on the one token of a
    burst of ONE iteration (ISSUE 55 (d)): no row past it, and the blocks
    come back."""
    requests = [(p, new) for p, new in zip(_prompts(4, seed=2),
                                           (1, 2, 3, 7))]
    sched = ServingScheduler(_engine(tiny, decode_burst=burst))
    got, steps = _run(sched, requests)
    assert [len(s) for s in got] == [1, 2, 3, 7]
    assert sum(c["live_tokens"] for c in steps) == \
        sum(len(p) + new - 1 for p, new in requests)
    assert got == [_engine(tiny).generate([p], max_new_tokens=n)[0]
                   for p, n in requests]
    eng = sched.engine
    assert eng.state_manager.free_blocks == eng.kv_cache.num_blocks - 1
    assert not eng.state_manager.block_table.any()
    if burst:
        # the prompts share the first step; then every turn is decode rows
        # alone: least remainders 1, 1, 4
        assert [(c["kind"], c["burst_k"]) for c in steps] == [
            ("ragged", 0), ("burst", 1), ("burst", 1), ("burst", 4)]
        assert sched.bursts_of_one == 2


def test_exhaustion_with_a_step_in_flight_collects_before_it_preempts(tiny):
    """(d) ``KVCacheExhausted`` while a step is in flight: the scheduler
    collects it, THEN preempts; a victim is never owed a token, and its
    recomputed stream equals the undisturbed one."""
    prompts = _prompts(8)
    ref = _engine(tiny).generate(prompts, max_new_tokens=16)
    eng = _engine(tiny, num_blocks=15, block_size=8, decode_burst=0)
    sched = ServingScheduler(eng)
    raised_in_flight, victims = [], []
    launch, preempt = eng.launch_step, sched._preempt_one

    def launch_step(*args, **kw):
        try:
            return launch(*args, **kw)
        except KVCacheExhausted:
            raised_in_flight.append(sched._in_flight is not None)
            raise

    def preempt_one():
        assert sched._in_flight is None and eng._uncollected == 0
        assert not any(s.owed for s in
                       eng.state_manager.tracked_sequences.values())
        victims.append(1)
        return preempt()

    eng.launch_step, sched._preempt_one = launch_step, preempt_one
    got, _ = _run(sched, [(p, 16) for p in prompts])
    assert got == ref
    assert any(raised_in_flight) and victims
    assert sched.preemptions == len(victims)
    assert eng.state_manager.free_blocks == eng.kv_cache.num_blocks - 1


@pytest.mark.parametrize("fused", [False, True], ids=["host", "burst_prng"])
def test_host_sampling_collects_before_it_launches(tiny, fused):
    """(e) Where the host draws the tokens the same loop has depth 0: no
    step is launched ahead, and the draws are today's under a seed.  With
    the bursts' device PRNG beside it, a burst runs ahead of a burst, and
    nothing is in flight while the host draws."""
    prompts = _prompts(3, seed=7)
    requests = [(p, 9) for p in prompts]
    cfg = dict(do_sample=True, temperature=0.8, top_k=20, seed=11)
    extra = dict(decode_burst_sampling=True) if fused else {}

    def engine():
        model, _, params = tiny
        sm = dict(max_tracked_sequences=16, max_ragged_batch_size=64,
                  max_ragged_sequence_count=12, max_context=64,
                  block_size=8, num_blocks=96)
        return InferenceEngineV2(model, params=params, config=dict(
            dtype="float32", decode_burst=4, state_manager=sm, **extra))

    sched = ServingScheduler(engine(), config=cfg)
    drawing = []
    sample = sched.engine._sample_row
    sched.engine._sample_row = lambda *a: (
        drawing.append(sched.engine._uncollected), sample(*a))[1]
    got, steps = _run(sched, requests)
    serial, _ = _run(ServingScheduler(_serial(engine()), config=cfg),
                     requests)
    assert got == serial and [len(s) for s in got] == [9, 9, 9]
    assert drawing and set(drawing) == {0}      # nothing else in flight
    if fused:
        assert {c["kind"] for c in steps} == {"ragged", "burst"}
        assert 0 < sched.steps_launched_ahead < len(steps) - 1
    else:
        assert sched.steps_launched_ahead == 0
        assert got == engine().generate(
            prompts, max_new_tokens=9, do_sample=True, temperature=0.8,
            top_k=20, rng=11)


def test_a_request_submitted_in_a_callback_joins_two_steps_later(tiny):
    """(f) The callback of step n's token runs in the turn that launched
    step n+1: a request submitted there joins step n+2.  And the scheduler
    drains to idle with nothing in flight."""
    eng = _engine(tiny, decode_burst=0)
    turn = [0]
    sched = ServingScheduler(eng, clock=lambda: float(turn[0]))
    first, late = _prompts(2, seed=9)
    seen = {}

    def on_token(tok, done):
        if "b" not in seen:
            seen["at"] = turn[0]
            seen["b"] = sched.submit(late, max_new_tokens=3)

    a = sched.submit(first, max_new_tokens=6, on_token=on_token)
    while not sched.idle:
        turn[0] += 1
        sched.step()
    # a's first token: launched in turn 1, streamed in turn 2 (the turn
    # that launched step 2); b is admitted into step 3
    assert seen["at"] == 2 and sched.query(seen["b"]).t_admit == 3.0
    assert sched.query(a).produced == \
        _engine(tiny).generate([first], max_new_tokens=6)[0]
    assert sched.query(seen["b"]).produced == \
        _engine(tiny).generate([late], max_new_tokens=3)[0]
    assert sched._in_flight is None and eng._uncollected == 0
    assert sched.step() == {}


def test_a_hook_in_the_step_functions_place_keeps_the_serial_order(tiny):
    """A Python callable in ``_step_fn``'s place (a test's spy, a debugging
    hook) reads "the newest call" as "the tokens just streamed": the
    scheduler does not run ahead of one."""
    eng = _engine(tiny, decode_burst=0)
    inner, calls = eng._step_fn, []
    eng._step_fn = lambda *a, **kw: (calls.append(1), inner(*a, **kw))[1]
    sched = ServingScheduler(eng)
    uid = sched.submit(_prompts(1)[0], max_new_tokens=4)
    while not sched.idle:
        n = len(calls)
        emitted = sched.step()
        assert len(calls) == n + 1 and len(emitted[uid]) == 1
    assert sched.steps_launched_ahead == 0


def test_the_engine_holds_one_step_back_and_no_more(tiny):
    """``launch_step`` on top of ONE uncollected step; a third is an error,
    not a silent wrong id."""
    eng = _engine(tiny, decode_burst=0)
    eng.put([0], [_prompts(1)[0]])
    first = eng.launch_step()
    second = eng.launch_step()          # its row's id is taken on the device
    with pytest.raises(RuntimeError, match="uncollected"):
        eng.launch_step()
    tok = eng.collect_step(first)[0]
    eng.state_manager.get_sequence(0).tokens.append(tok)
    nxt = eng.collect_step(second)[0]
    assert [tok, nxt] == _engine(tiny).generate(
        [_prompts(1)[0]], max_new_tokens=2)[0]


# ------------------------------------------- a decode-only turn is a burst
# A turn whose rows are all decode rows never runs the budget-wide ragged
# step (ISSUE 55): the least remainder, floored to a power of two, is the
# burst's length, and a least remainder of ONE is a burst of one iteration.
def _state_snapshot(eng):
    """Host copies of the cache's buffers (the device's are donated to the
    next program), one tuple an entry."""
    return [tuple(np.array(b, copy=True) for b in entry) for entry in eng._kv]


@pytest.mark.parametrize("name", ["llama", "jamba", "evabyte"])
def test_a_least_remainder_of_7_runs_as_bursts_of_4_2_and_1(name):
    """(a), (b): replies of 8 and 20 tokens whose prompts share the first
    step: the least remainder walks through 7, 3, 1 and the turns are bursts
    of 4, 2, 1, then the longer reply alone (12: 8, 4).  NO ragged step
    without a prefill row.  The streams are those of the same scheduler with
    bursts off, request for request.  A one-iteration burst writes the rows
    of its sequences and no other: a free slot's state row (Jamba), and every
    buffer's part that belongs to no running sequence, read as before it."""
    vocab = _preset(name)[2]
    rng = np.random.default_rng(4)
    requests = [(rng.integers(1, vocab, size=9).tolist(), 8),
                (rng.integers(1, vocab, size=11).tolist(), 20)]
    want, plain = _run(ServingScheduler(_preset_engine(name, burst=0)),
                       requests)
    assert {c["kind"] for c in plain} == {"ragged"}

    sched = ServingScheduler(_preset_engine(name, burst=8))
    eng, sm = sched.engine, sched.engine.state_manager
    streams = [[] for _ in requests]
    for i, (prompt, new) in enumerate(requests):
        sched.submit(prompt, max_new_tokens=new,
                     on_token=lambda t, d, i=i: streams[i].append(t))
    steps, checked = [], 0
    while not sched.idle:
        before = _state_snapshot(eng)
        sched.step()
        counts = eng.last_step_counts
        steps.append((counts["kind"], counts["burst_k"],
                      counts["prefill_tokens"]))
        if counts["burst_k"] != 1:
            continue
        # what the burst of one may write: its sequences' slots and blocks
        live = list(sm.tracked_sequences.values())
        slots = {s.slot for s in live}
        blocks = {b for s in live for b in s.blocks}
        for kind, old, new_ in zip(eng.kv_cache.kinds or
                                   ["pages"] * len(before), before,
                                   _state_snapshot(eng)):
            if kind == "state":         # slot 0, the dead rows', included
                keep = [i for i in range(sm.max_seqs) if i not in slots]
            else:                       # the garbage block is dead rows'
                keep = [i for i in range(1, old[0].shape[0])
                        if i not in blocks]
            for a, b in zip(old, new_):
                # a state entry: taps [taps, slots, ..], state [slots, ..]
                axis = int(kind == "state" and a.shape[0] != sm.max_seqs)
                assert np.array_equal(np.take(a, keep, axis),
                                      np.take(b, keep, axis))
            checked += 1
    assert streams == want and [len(s) for s in streams] == [8, 20]
    assert steps == [("ragged", 0, 20), ("burst", 4, 0), ("burst", 2, 0),
                     ("burst", 1, 0), ("burst", 8, 0), ("burst", 4, 0)]
    assert sched.bursts_of_one == 1 and checked
    assert sm.free_blocks == eng.kv_cache.num_blocks - 1
    assert not sm.block_table.any()


@pytest.mark.parametrize("second", ["step", "burst_of_one"])
def test_a_burst_of_one_on_top_of_a_step_takes_its_token_on_the_device(
        tiny, second):
    """(c) Launched while a step is in flight, a burst of one has no id for
    its row on the host: it takes the token that step chose on the device
    (``take_from``), and the host's counts (``owed``, ``seen_tokens``) read
    as after a ragged step launched in its place."""
    prompt = _prompts(1, seed=6)[0]
    eng = _engine(tiny)
    eng.put([0], [prompt])
    seq = eng.state_manager.get_sequence(0)
    first = eng.launch_step()
    if second == "step":
        nxt = eng.launch_step()
    else:
        nxt = eng.launch_burst([0], max_tokens=1)
        assert nxt.burst_k == 1 and eng.last_step_counts["burst_k"] == 1
    assert not seq.pending()        # the row's id is on the device alone
    assert (seq.owed, seq.seen_tokens, seq.n_pending) == \
        (2, len(prompt) + 1, 1)
    tok = eng.collect_step(first)[0]
    seq.tokens.append(tok)
    got = eng.collect_step(nxt)[0]
    if second == "step":
        seq.tokens.append(got)
    else:
        got, = got                  # a burst hands a list, and appends it
    assert (seq.owed, seq.seen_tokens, seq.n_pending) == \
        (0, len(prompt) + 1, 1)
    assert seq.tokens == prompt + [tok, got]
    assert [tok, got] == _engine(tiny).generate([prompt],
                                                max_new_tokens=2)[0]


def test_a_dry_pool_keeps_the_ragged_step_which_defers(tiny):
    """(e) No free block for one more position a row: no burst, not even of
    one; the ragged step runs what the pool affords and defers the rest, as
    before, and the streams are the roomy pool's."""
    # blocks of 8: a prompt of 8 fills one, its first decode row needs a
    # second; 3 requests on 4 usable blocks
    prompts = _prompts(3, seed=8)
    ref = _engine(tiny, decode_burst=0).generate(prompts, max_new_tokens=4)
    eng = _engine(tiny, num_blocks=5, block_size=8)
    sched = ServingScheduler(eng, config=dict(kv_admit_reserve_tokens=0))
    got, steps = _run(sched, [(p, 4) for p in prompts])
    assert got == ref
    # the turn after the prefill holds decode rows alone and one free
    # block for three rows that each need one
    assert (steps[1]["kind"], steps[1]["prefill_tokens"],
            steps[1]["live_tokens"]) == ("ragged", 0, 1)
    assert sched.preemptions >= 1 or any(
        c["kind"] == "ragged" and not c["prefill_tokens"] for c in steps)
    assert eng.state_manager.free_blocks == eng.kv_cache.num_blocks - 1


def test_host_drawn_tokens_keep_the_ragged_step(tiny):
    """(f) Where the host draws the tokens a decode-only turn stays a ragged
    step: no burst, of one or longer."""
    sched = ServingScheduler(_engine(tiny), config=dict(
        do_sample=True, temperature=0.8, top_k=20, seed=11))
    got, steps = _run(sched, [(p, 2) for p in _prompts(2, seed=7)])
    assert [len(s) for s in got] == [2, 2]
    assert [(c["kind"], c["prefill_tokens"]) for c in steps] == [
        ("ragged", 16), ("ragged", 0)]
    assert sched.bursts_of_one == 0


def test_more_slots_than_budget_rows_keeps_the_ragged_step(tiny):
    """The rule rests on ``max_seqs <= token budget`` (a burst iteration is
    ``max_seqs`` rows, a ragged step the budget's): an engine built the
    other way round keeps the ragged step for a least remainder of one."""
    model, _, params = tiny
    sm = dict(max_tracked_sequences=40, max_ragged_batch_size=16,
              max_ragged_sequence_count=32, max_context=64, block_size=8,
              num_blocks=96)
    eng = InferenceEngineV2(model, params=params, config=dict(
        dtype="float32", decode_burst=8, state_manager=sm))
    assert eng.min_burst == 2 and _engine(tiny).min_burst == 1
    sched = ServingScheduler(eng)
    got, steps = _run(sched, [(p, 4) for p in _prompts(2, seed=7)])
    assert [(c["kind"], c["burst_k"]) for c in steps] == [
        ("ragged", 0), ("burst", 2), ("ragged", 0)]
    assert got == _engine(tiny, decode_burst=0).generate(
        _prompts(2, seed=7), max_new_tokens=4)
