"""EvaByte on the serving path (ISSUE 27) at small size on the CPU: the dense
model against the plain reference, prefill in slabs and decoding one token at
a time and in bursts across chunk and window ends through
``ServingScheduler`` against the reference's full forward pass (logits, not
tokens), the tie to the shared Llama code inside the first window, and the
cache manager's two kinds of state."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import evabyte, llama
from deepspeed_tpu.serving import ServingScheduler, build_serving_engine
from perfbench import harness, loader, weights

WINDOW, CHUNK, BLOCK = 32, 4, 8
REF = loader.load_part(loader.ROOT, "reference", "evabyte")


def _sizes(cfg):
    sizes = dataclasses.asdict(cfg)
    sizes["rope_theta"] = float(cfg.rope_theta)
    return sizes


def _seeded(cfg, seed, **changes):
    cfg = dataclasses.replace(cfg, **changes)
    model = evabyte.EvaByteModel(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    return model, weights.seeded_weights(shapes, harness.fold_seed(seed),
                                         dtype=jnp.float32)


# ``_record_logits`` wraps the engine's step (tests/conftest.py)
pytestmark = pytest.mark.usefixtures("no_disk_cache")


@pytest.fixture(scope="module")
def tiny():
    cfg = evabyte.evabyte_tiny(dtype="float32")
    assert (cfg.window_size, cfg.chunk_size) == (WINDOW, CHUNK)
    model, params = _seeded(cfg, 7)
    return model, cfg, params


def _engine(tiny, budget=22, num_blocks=64, burst=0, max_seqs=5,
            max_context=160):
    model, _, params = tiny
    sm = dict(max_tracked_sequences=2 * max_seqs, max_ragged_batch_size=budget,
              max_ragged_sequence_count=max_seqs, max_context=max_context,
              block_size=BLOCK, num_blocks=num_blocks)
    return InferenceEngineV2(model, params=params, config=dict(
        dtype="float32", decode_burst=burst, state_manager=sm))


def _record_logits(engine):
    """Every ragged step's logits ``[max_seqs, V]``, as the step returns
    them; a burst inlines the step and records nothing."""
    inner, sink = engine._step_fn, []

    def step(*args, **kw):
        logits, kv = inner(*args, **kw)
        sink.append(np.asarray(logits))
        return logits, kv

    step.__wrapped__ = inner.__wrapped__
    engine._step_fn = step
    return sink


# --------------------------------------------------- (a) model == reference
@pytest.mark.parametrize("windows", [0.5, 1, 2.3, 4])
def test_dense_forward_equals_the_plain_reference_on_all_heads(windows):
    cfg = evabyte.evabyte_tiny(dtype="float32", num_pred_heads=8)
    model, params = _seeded(cfg, 11)
    n = int(round(windows * WINDOW))
    ids = np.random.default_rng(n).integers(0, cfg.vocab_size, size=n)
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, jnp.asarray(ids[None]))[0]
    got = REF.all_head_logits_at(params, ids, np.arange(n), _sizes(cfg))
    assert got.shape == (n, 8, cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_array_equal(
        REF.logits_at(params, ids, np.arange(n), _sizes(cfg)), got[:, 0])
    if windows > 1:
        # past the first window the summaries matter: full causal attention
        # over every exact key is another model
        full = REF.logits_at(params, ids, np.arange(n),
                             dict(_sizes(cfg), window_size=WINDOW * 8))
        assert float(jnp.max(jnp.abs(full - got[:, 0]))) > 1e-2


def test_the_multi_byte_loss_trains_head_i_on_byte_t_plus_1_plus_i(tiny):
    model, cfg, params = tiny
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 40)))
    logits = model.apply({"params": params}, ids)
    loss = model.apply({"params": params}, ids, ids)
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = np.mean([-np.mean(np.take_along_axis(
        np.asarray(logp[:, :39 - i, i]), np.asarray(ids[:, 1 + i:, None]),
        axis=-1)) for i in range(cfg.num_pred_heads)])
    assert float(loss) == pytest.approx(want, rel=1e-5)
    grads = jax.grad(lambda p: model.apply({"params": p}, ids, ids))(params)
    assert float(jnp.abs(grads["layers_0"]["self_attn"]["eva_phi"]).max()) > 0


# ------------------------------- (b) slabs, single tokens, bursts: logits
def _serve_and_compare(tiny, burst, prompt_len=70, new=40):
    """One request through the scheduler; every ragged step that emitted a
    token has its logits compared with the reference's full forward pass
    over the same tokens.  Returns the stream and the step kinds."""
    model, cfg, params = tiny
    engine = _engine(tiny, burst=burst)
    sink = _record_logits(engine)
    sched = ServingScheduler(engine, {"max_concurrent": 4})
    prompt = np.random.default_rng(5).integers(
        0, cfg.vocab_size, size=prompt_len).tolist()
    free0 = engine.state_manager.free_blocks
    uid = sched.submit(prompt, max_new_tokens=new)
    produced, kinds, compared = [], [], 0
    while not sched.idle:
        seq = engine.state_manager.get_sequence(uid)
        slot, steps = (seq.slot if seq else None), len(sink)
        emitted = sched.step().get(uid, [])
        kinds.append((engine.last_step_counts["kind"], len(emitted)))
        produced += emitted
        if len(sink) > steps and emitted:
            ids = np.asarray(prompt + produced[:-1])
            want = REF.logits_at(params, ids, [len(ids) - 1], _sizes(cfg))[0]
            np.testing.assert_allclose(sink[-1][slot], want, atol=3e-4,
                                       rtol=3e-4)
            assert int(np.argmax(want)) == produced[-1]
            compared += 1
        seq = engine.state_manager.get_sequence(uid)
        if seq is not None:
            assert len(seq.blocks) == engine.kv_cache.blocks_for(
                seq.seen_tokens)
    assert engine.state_manager.free_blocks == free0
    return produced, kinds, compared


def test_slabs_then_single_tokens_across_chunk_and_window_ends(tiny):
    produced, kinds, compared = _serve_and_compare(tiny, burst=0)
    assert len(produced) == 40 and compared == 40
    # the prompt's 70 tokens came in slabs of at most 22 that stop at a
    # window's end: 22, 10 | 22, 10 | 6, and the 40 new tokens crossed the
    # window end at 96 one at a time
    assert [n for _, n in kinds[:4]] == [0, 0, 0, 0] and len(kinds) == 44


def test_bursts_across_chunk_and_window_ends_equal_single_tokens(tiny):
    single, _, _ = _serve_and_compare(tiny, burst=0)
    produced, kinds, compared = _serve_and_compare(tiny, burst=8)
    assert produced == single
    bursts = [n for kind, n in kinds if kind == "burst"]
    # 8, 8, 8 up to position 94, a burst of 2 to the window's end at 96, then
    # on in the next window: 8, 4 and, since ISSUE 55, the last token from a
    # burst of ONE iteration (a decode-only turn runs no ragged step); what
    # the bursts wrote (window 2's last summaries too) is read by the bursts
    # after them, whose tokens are the single-token run's, compared above
    # step by step with the reference's logits
    assert bursts == [8, 8, 8, 2, 8, 4, 1]
    assert sum(bursts) + compared == 40 and compared == 1
    assert kinds[-1] == ("burst", 1)


# ------------------------------------- (c) the tie to the shared Llama code
def test_inside_the_first_window_it_is_the_llama_step(tiny):
    model, cfg, params = tiny
    lcfg = llama.LlamaConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        max_position_embeddings=cfg.max_position_embeddings,
        rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
        dtype="float32", remat=False)
    fold = lambda p: {"weight": 1.0 + p["weight"][:, 0]}
    lparams = {"embed_tokens": params["embed_tokens"],
               "norm": fold(params["norm"]),
               "lm_head": {"kernel": params["lm_head"]["kernel"][
                   :, :cfg.vocab_size]}}
    for i in range(cfg.num_hidden_layers):
        lp = params[f"layers_{i}"]
        lparams[f"layers_{i}"] = {
            "input_layernorm": fold(lp["input_layernorm"]),
            "post_attention_layernorm": fold(lp["post_attention_layernorm"]),
            "mlp": lp["mlp"],
            "self_attn": {k: v for k, v in lp["self_attn"].items()
                          if not k.startswith("eva_")}}
    prompt = np.random.default_rng(9).integers(0, cfg.vocab_size,
                                               size=19).tolist()
    streams = []
    for m, p in ((model, params), (llama.LlamaModel(lcfg), lparams)):
        engine = _engine((m, None, p), budget=12, max_context=WINDOW)
        sink = _record_logits(engine)
        out = ServingScheduler(engine, {"max_concurrent": 2}).serve(
            [prompt], max_new_tokens=WINDOW - 19)
        streams.append((out, [s[1] for s in sink]))
    assert streams[0][0] == streams[1][0]
    assert len(streams[0][1]) == 2 + (WINDOW - 19) - 1
    np.testing.assert_allclose(np.stack(streams[0][1]),
                               np.stack(streams[1][1]), atol=2e-5, rtol=2e-5)


# ------------------------------------------------ (d) two kinds of state
def test_blocks_for_is_the_stated_function_of_the_context(tiny):
    kv = _engine(tiny).kv_cache
    per_window, summary = WINDOW // BLOCK, WINDOW // CHUNK // BLOCK
    assert summary == 1 and kv.summary_blocks == 1
    for n in range(1, 5 * WINDOW + 1):
        closed, inside = (n - 1) // WINDOW, (n - 1) % WINDOW + 1
        assert kv.blocks_for(n) == (closed + 1) * summary \
            + -(-inside // BLOCK)
        assert kv.peak_blocks_for(n) == max(kv.blocks_for(m)
                                            for m in range(1, n + 1))
        assert kv.peak_blocks_for(n, start=n - 3) == max(
            kv.blocks_for(m) for m in range(max(n - 3, 1), n + 1))
    # a context of five windows holds 4 + 1 + 4 blocks, not 20
    assert kv.blocks_for(5 * WINDOW) == 9
    assert kv.row_width(160) == 5 * summary + per_window
    assert kv.run_room(70) == 26 and kv.run_room(64) == 32
    # every other architecture: what it returned before
    cfg = llama.llama_tiny(dtype="float32", remat=False)
    other = InferenceEngineV2(llama.LlamaModel(cfg), params=llama.LlamaModel(
        cfg).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
            "params"], config=dict(dtype="float32", state_manager=dict(
                max_context=64, block_size=8, num_blocks=20,
                max_ragged_sequence_count=4, max_ragged_batch_size=16)))
    assert [other.kv_cache.blocks_for(n) for n in (0, 1, 8, 9, 64)] == \
        [0, 1, 1, 2, 8]
    assert other.kv_cache.peak_blocks_for(33, start=5) == 5
    assert other.kv_cache.run_room(7) is None
    assert other.state_manager.max_blocks_per_seq == 8


def test_window_blocks_come_back_and_the_row_is_summaries_then_window(tiny):
    engine = _engine(tiny, budget=40)
    sm = engine.state_manager
    free0 = sm.free_blocks
    tokens = np.random.default_rng(1).integers(0, 64, size=75).tolist()
    engine.put([0], [tokens])
    held = []
    while sm.get_sequence(0).pending():
        engine.schedule_step()
        seq = sm.get_sequence(0)
        held.append((seq.seen_tokens, len(seq.blocks)))
        assert len(seq.blocks) == engine.kv_cache.blocks_for(seq.seen_tokens)
        assert free0 - sm.free_blocks == len(seq.blocks)
        row = sm.block_table[seq.slot]
        visible = seq.summary_blocks + seq.window_blocks
        assert list(row[:len(visible)]) == visible
        assert list(row[-1:]) == seq.making_blocks
        assert not row[len(visible):-1].any()
        assert len(set(seq.blocks)) == len(seq.blocks)
    # slabs stop at a window's end; a full window holds 1 + 4 (+ 1 a closed
    # window), and the first token past it gives four of them back
    assert held == [(32, 5), (64, 6), (75, 2 + 1 + 2)]
    counts = engine.last_step_counts
    assert counts["context_tokens"] == 75 and counts["held_blocks"] == 5
    assert counts["chunks_closed"] == 2 and counts["windows_closed"] == 0
    assert counts["block_size"] == BLOCK
    engine.flush([0])
    assert sm.free_blocks == free0 and not sm.block_table.any()


def test_a_step_may_not_straddle_a_windows_end(tiny):
    engine = _engine(tiny)
    sm = engine.state_manager
    seq = sm.get_or_create_sequence(0)
    assert sm.schedulable_tokens(seq, 50) == WINDOW
    with pytest.raises(RuntimeError, match="straddle"):
        sm.ensure_capacity(seq, 50)
    with pytest.raises(RuntimeError, match="max_context"):
        sm.schedulable_tokens(seq, 161)


def _assert_greedy(tiny, prompt, toks):
    """Every streamed token is the argmax of the reference's logits over the
    tokens before it; a tie inside float32 rounding may go either way (the
    engines' shapes differ, and so does the order of their sums)."""
    _, cfg, params = tiny
    ids = np.asarray(prompt + toks[:-1])
    at = np.arange(len(prompt) - 1, len(ids))
    logits = np.asarray(REF.logits_at(params, ids, at, _sizes(cfg)))
    behind = logits.max(-1) - logits[np.arange(len(toks)), toks]
    assert float(behind.max()) < 1e-3, behind


def test_preemption_and_re_prefill_leak_nothing_and_change_no_token(tiny):
    model, cfg, params = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (70, 45, 100)]
    want = ServingScheduler(_engine(tiny, burst=8, num_blocks=64),
                            {"max_concurrent": 4}).serve(
                                prompts, max_new_tokens=30)
    # 15 usable blocks: the three cannot all be held (7 + 6 + 9 at their
    # peaks), and no claim is made for them, so the pool runs dry
    engine = _engine(tiny, burst=8, num_blocks=16)
    sched = ServingScheduler(engine, {
        "max_concurrent": 4, "kv_admit_reserve_tokens": 0})
    free0 = engine.state_manager.free_blocks
    sched._admit_blocks_needed = lambda req: 0
    sched._outstanding_claims = lambda: 0
    got = sched.serve(prompts, max_new_tokens=30)
    assert sched.preemptions >= 1
    for prompt, a, b in zip(prompts, got, want):
        assert len(a) == len(b) == 30
        # the same stream as without preemption, up to the first such tie,
        # and greedy by the reference's full forward pass all the way
        same = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), 29)
        _assert_greedy(tiny, prompt, a)
        _assert_greedy(tiny, prompt, b[:same + 1])
    assert engine.state_manager.free_blocks == free0
    assert not engine.state_manager.block_table.any()


def test_step_counts_say_what_the_summaries_cost(tiny):
    engine = _engine(tiny, budget=40, burst=8)
    sched = ServingScheduler(engine, {"max_concurrent": 4})
    rng = np.random.default_rng(4)
    sched.submit(rng.integers(0, 64, size=100).tolist(), max_new_tokens=12)
    seen = []
    while not sched.idle:
        sched.step()
        seen.append(dict(engine.last_step_counts))
    ragged = [c for c in seen if c["kind"] == "ragged"]
    # slabs of 32, 32, 32, 4: the k-th reads k summary pages (one a closed
    # window) of its 1 + ... pages, on the per-token count of this shape
    assert [c["windows_closed"] for c in ragged[:4]] == [1, 1, 1, 0]
    assert [c["chunks_closed"] for c in ragged[:4]] == [8, 8, 8, 1]
    assert ragged[3]["summary_pages"] == 4 * 3
    assert ragged[0]["summary_pages"] == 0
    assert all(c["summary_pages"] <= c["grid_pages"] for c in seen)
    burst = [c for c in seen if c["kind"] == "burst"][0]
    assert burst["burst_k"] == 8 and burst["chunks_closed"] == 2
    assert burst["summary_pages"] == 8 * 3
    assert burst["context_tokens"] == 100 + 8
