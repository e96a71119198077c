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


def test_an_end_by_length_gets_no_row_past_its_last_token(tiny):
    """(c) Known from counts: the reply has exactly the asked length and the
    engine never ran a row for a token past it, one new token included."""
    requests = [(p, new) for p, new in zip(_prompts(4, seed=2),
                                           (1, 2, 3, 7))]
    sched = ServingScheduler(_engine(tiny, decode_burst=0))
    got, steps = _run(sched, requests)
    assert [len(s) for s in got] == [1, 2, 3, 7]
    assert sum(c["live_tokens"] for c in steps) == \
        sum(len(p) + new - 1 for p, new in requests)
    assert got == [_engine(tiny).generate([p], max_new_tokens=n)[0]
                   for p, n in requests]


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
