"""LongCat-Flash (``models/longcat_flash.py``) at tiny size on the CPU, against
the benchmark's plain reference (``perfbench/reference/longcat_flash.py``):
the dense forward; chunked prefill and decode through the paged latent cache
with a preemption; the router's choice bias; the identity experts; the 32
shares of a layer add up; a cache of TWO entries a layer; the counts a step
carries; the latent kernel itself under the step."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import ragged_forward as rf
from deepspeed_tpu.models import longcat_flash as lf
from deepspeed_tpu.moe import held_experts as he
from deepspeed_tpu.serving import build_serving_engine

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


@pytest.fixture(scope="module")
def ref():
    import sys
    sys.path.insert(0, ROOT)
    from perfbench import loader
    return loader.load_part(ROOT, "reference", "longcat_flash")


CFG = lf.longcat_flash_tiny()   # 4 layers; 32 real experts, 8 held; 16 identity
_made = {}


def _model(cfg=CFG):
    """The model and seeded weights with a NON-constant choice bias."""
    if cfg not in _made:
        model = lf.LongcatFlashModel(cfg)
        params = model.init(jax.random.PRNGKey(3),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        for l in range(cfg.num_layers):
            params[f"layers_{l}"]["moe"]["e_score_correction_bias"] = \
                0.02 * jax.random.normal(jax.random.PRNGKey(100 + l),
                                         (cfg.router_width, ))
        _made[cfg] = model, params
    return _made[cfg]


def _sizes(cfg=CFG):
    """What the reference reads, from the program's config."""
    keys = ("vocab_size", "hidden_size", "ffn_hidden_size",
            "expert_ffn_hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
            "zero_expert_num", "moe_topk", "routed_scaling_factor",
            "rms_norm_eps", "rope_theta", "n_routed_experts", "first_expert")
    return dict({k: getattr(cfg, k) for k in keys},
                num_hidden_layers=cfg.num_layers, experts_held=cfg.held)


def _scheduler(model, params, burst, dtype="float32", budget=16, sessions=2,
               context=64, blocks=40, **serving):
    return build_serving_engine(
        model, params=params,
        engine_config={"dtype": dtype, "decode_burst": burst,
                       "state_manager": {
                           "max_tracked_sequences": 2 * sessions,
                           "max_ragged_sequence_count": sessions + 1,
                           "max_context": context, "block_size": 8,
                           "num_blocks": blocks,
                           "max_ragged_batch_size": budget}},
        serving_config={"max_concurrent": sessions, **serving})


def _gaps(ref, params, prompts, produced):
    """``(worst gap, share of positions at the reference's argmax)`` of the
    streams against the reference's teacher-forced full forward (the serve
    job's measure: largest logit - the token's logit, in the logits' std)."""
    worst, hits, n = 0.0, 0, 0
    for prompt, toks in zip(prompts, produced):
        ids = np.asarray(prompt + toks[:-1], np.int32)
        at = np.arange(len(prompt) - 1, len(ids))
        logits = np.asarray(ref.logits_at(params, ids, at, _sizes()))
        chosen = logits[np.arange(len(toks)), toks]
        worst = max(worst, float(np.max(
            (logits.max(-1) - chosen) / logits.std(-1))))
        hits += int((logits.argmax(-1) == np.asarray(toks)).sum())
        n += len(toks)
    return worst, hits / n


# ------------------------------------------------- the model = the reference
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_dense_forward_is_the_reference(ref, dtype):
    """The flax model (expanded form, the program's ``mla_down``, ``route``
    and ``held_experts_apply``) against the plain reference on LOGITS: float32
    to thousandths of the logits' spread (two programs that sum in another
    order); with bfloat16 activations a near-tie of the router goes the other
    way at some token, so the rows are held by their typical error (a
    hundredth of the spread) and the argmax kept at most positions."""
    model, params = _model(dataclasses.replace(CFG, dtype=dtype))
    ids = np.random.default_rng(0).integers(0, CFG.vocab_size, 70)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(ids)[None])[0])
    want = np.asarray(ref.logits_at(params, ids, np.arange(70), _sizes()))
    if dtype == "float32":
        np.testing.assert_allclose(got, want, atol=2e-3 * float(want.std()))
        assert np.array_equal(got.argmax(-1), want.argmax(-1))
    else:
        assert np.median(np.abs(got - want)) < 0.02 * float(want.std())
        assert np.mean(got.argmax(-1) == want.argmax(-1)) >= 0.8


@pytest.mark.parametrize("burst", [0, 8], ids=["steps", "burst"])
def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        ref, burst):
    """Three prompts of 40, 30 and 21 tokens in chunks of 16 rows through a
    pool too small for them (the scheduler evicts, requeues and recomputes),
    then 14 decoded tokens each, a step at a time and through the burst:
    every streamed token is the argmax of the reference's FULL forward over
    the sequence at its position."""
    model, params = _model()
    rng = np.random.default_rng(burst)
    prompts = [rng.integers(0, 256, n).tolist() for n in (40, 30, 21)]
    sched = _scheduler(model, params, burst, sessions=3, blocks=15,
                       kv_admit_reserve_tokens=0)
    produced = sched.serve(prompts, max_new_tokens=14)
    assert sched.preemptions >= 1
    assert all(len(t) == 14 for t in produced)
    assert _gaps(ref, params, prompts, produced) == (0.0, 1.0)
    assert (getattr(sched.engine, "burst_steps", 0) > 0) == bool(burst)


def test_a_bfloat16_engine_stays_near_the_reference(ref):
    """bfloat16 activations and cache: the tokens are the reference's argmax
    at most positions, and where not, a near-tie of two logits."""
    model, params = _model(dataclasses.replace(CFG, dtype="bfloat16"))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).tolist() for n in (40, 21)]
    sched = _scheduler(model, params, 8, dtype="bfloat16")
    produced = sched.serve(prompts, max_new_tokens=14)
    gap, share = _gaps(ref, params, prompts, produced)
    assert gap < 0.25 and share >= 0.8, (gap, share)


# ------------------------------------------------------------- the router
@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_a_choice_bias_chooses_and_does_not_weigh(score):
    """``route(bias=)`` against a plain top-k of ``score + bias``: the chosen
    are those of the biased scores, the weights the scores WITHOUT the bias,
    not renormalised, times the scale; and the bias does move choices."""
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (50, 48))
    bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (48, ))
    p = np.asarray(jax.nn.softmax(logits, -1) if score == "softmax"
                   else jax.nn.sigmoid(logits), np.float64)
    want = np.argsort(-(p + np.asarray(bias, np.float64)), axis=-1)[:, :4]
    topi, topw = he.route(logits, 4, score, norm_topk=False, scale=6.0,
                          bias=bias)
    np.testing.assert_array_equal(np.sort(topi, -1), np.sort(want, -1))
    np.testing.assert_allclose(
        topw, 6.0 * np.take_along_axis(p, np.asarray(topi), -1), rtol=1e-5)
    plain, plain_w = he.route(logits, 4, score, norm_topk=False)
    assert (np.sort(plain, -1) != np.sort(topi, -1)).any()
    # a constant bias moves nothing
    same, w = he.route(logits, 4, score, norm_topk=False, scale=6.0,
                       bias=jnp.ones((48, )))
    np.testing.assert_array_equal(np.sort(same, -1), np.sort(plain, -1))
    np.testing.assert_allclose(np.sort(w, -1), 6.0 * np.sort(plain_w, -1),
                               rtol=1e-5)


def _branch_inputs(cfg, seed=0, tokens=40):
    _, params = _model(cfg)
    moe = params["layers_1"]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(seed), (tokens, cfg.hidden_size))
    return moe, h


def test_identity_experts_copy_and_real_experts_compute():
    """A token whose choices are ALL identity experts gets its input back
    times their summed weights and reaches no expert; one whose choices are
    all real gets the plain weighted sum of their SwiGLUs and no copy."""
    cfg = dataclasses.replace(CFG, experts_held=None)     # all 32 held
    moe, _ = _branch_inputs(cfg)
    D, E, k = cfg.hidden_size, cfg.n_routed_experts, cfg.moe_topk
    gate = np.zeros((D, cfg.router_width), np.float32)
    gate[0, E:E + k] = 4.0                 # dimension 0 -> identity experts
    gate[1, 3:3 + k] = 4.0                 # dimension 1 -> real experts 3..
    moe = {**moe, "gate": {"kernel": jnp.asarray(gate)},
           "e_score_correction_bias": jnp.zeros((cfg.router_width, ))}
    h = np.zeros((2, D), np.float32)
    h[0, 0], h[1, 1] = 3.0, 3.0
    h[:, 2:] = np.random.default_rng(0).normal(size=(2, D - 2)) * 0.5
    h = jnp.asarray(h)
    out, counts, zero = lf.moe_branch(h, moe, cfg)
    p = jax.nn.softmax(h @ gate, -1)
    np.testing.assert_allclose(
        out[0], 6.0 * float(p[0, E:E + k].sum()) * h[0], rtol=1e-5)
    want = sum(6.0 * p[1, e] * lf.swiglu(h[1], moe["w1"][e], moe["w3"][e],
                                         moe["w2"][e])
               for e in range(3, 3 + k))
    np.testing.assert_allclose(out[1], want, rtol=1e-4, atol=1e-6)
    assert int(zero) == k and int(counts.sum()) == k
    assert counts[3:3 + k].tolist() == [1] * k
    # a dead row is routed nowhere and counted nowhere
    _, counts, zero = lf.moe_branch(h, moe, cfg,
                                    live=jnp.asarray([False, True]))
    assert int(zero) == 0 and int(counts.sum()) == k


@pytest.mark.parametrize("seed", [0, 1])
def test_the_32_shares_add_up_to_the_uncut_layer(seed):
    """64 real experts as 32 shares of 2 beside 32 identity experts, 6 a
    token: the shares' held parts, with the identity part (which every chip
    computes alike) counted ONCE, are the uncut branch; the copies the shares
    count and the identity copies are every choice of every token."""
    cfg = dataclasses.replace(CFG, n_routed_experts=64, zero_expert_num=32,
                              moe_topk=6, experts_held=None)
    moe, h = _branch_inputs(cfg, seed, tokens=48)
    whole, counts, zero = lf.moe_branch(h, moe, cfg)
    identity, _, _ = lf.moe_branch(h, {**moe, "w2": moe["w2"] * 0}, cfg)

    parts, landed = [], []
    for chip in range(32):
        told = dataclasses.replace(cfg, experts_held=2, first_expert=2 * chip)
        stacks = {n: moe[n][2 * chip:2 * chip + 2] for n in ("w1", "w2", "w3")}
        out, n, z = lf.moe_branch(h, {**moe, **stacks}, told)
        parts.append(out - identity)
        landed.append(n)
        assert int(z) == int(zero)
    scale = float(jnp.max(jnp.abs(whole)))
    np.testing.assert_allclose(sum(parts) + identity, whole,
                               atol=2e-5 * scale)
    np.testing.assert_array_equal(np.concatenate(landed), counts)
    assert int(counts.sum()) + int(zero) == 48 * 6
    assert float(jnp.max(jnp.abs(identity))) > 0.1 * scale
    assert float(jnp.max(jnp.abs(parts[0] + identity - whole))) > 0.1 * scale


# --------------------------------------------------------------- the cache
def test_a_layer_holds_two_cache_entries_and_a_token_claims_eight_rows():
    """The count of cache entries is the MODEL's statement: four layers
    allocate 8 latent buffers, the engine's bytes a token are 8 rows', and a
    prompt's tokens are written into all 8 (each attention its own rows)."""
    model, params = _model()
    assert (CFG.num_hidden_layers, CFG.kv_cache_entries,
            CFG.kv_latent_dim) == (4, 8, 40)
    assert lf.LongcatFlashConfig().kv_cache_entries == 56
    sched = _scheduler(model, params, 0, budget=32, sessions=1)
    eng = sched.engine
    assert [tuple(x.shape for x in entry) for entry in eng.kv_cache.layers] \
        == [((40, 8, 128), )] * 8
    assert eng.kv_cache.page_layers == 8
    assert eng.kv_cache.bytes_by_kind() == (8 * 128 * 4, 0)   # float32
    prompt = np.random.default_rng(1).integers(0, 256, 13).tolist()
    sched.submit(prompt, max_new_tokens=1)
    sched.step()
    counts = eng.last_step_counts
    assert counts["live_tokens"] == 13
    assert counts["latent_keys"] == sum(range(1, 14)) * 8
    assert (counts["absorbed_rows"], counts["expanded_rows"]) == (13, 0)
    sched.drain()
    seq_rows = []
    for pages, in eng._kv:
        written = np.asarray(pages).reshape(-1, 128)
        rows = np.flatnonzero(np.abs(written[8:, :40]).sum(-1))  # past block 0
        seq_rows.append(len(rows))
        assert not written[:, 40:].any()
    assert seq_rows == [13] * 8
    firsts = [np.asarray(pages).reshape(-1, 128)[8:][:, :40] for pages, in
              eng._kv[:2]]
    assert np.abs(firsts[0] - firsts[1]).max() > 1e-3   # each its own rows


def test_a_step_carries_the_expert_and_identity_counts():
    model, params = _model()
    prompt = np.random.default_rng(1).integers(0, 256, 13).tolist()
    sched = _scheduler(model, params, 0, budget=32, sessions=1)
    sched.submit(prompt, max_new_tokens=1)
    sched.step()
    sched.step()
    counts = sched.engine.last_step_counts
    chosen = 13 * CFG.moe_topk * CFG.num_layers
    assert 0 < counts["zero_expert_copies"] < chosen
    assert 0 < counts["expert_copies"] <= chosen - counts["zero_expert_copies"]
    assert 0 < counts["expert_active"] <= 8 * 4
    assert rf.longcat_flash_ragged_step.step_counts == (
        "expert_copies", "expert_active", "zero_expert_copies")


def test_the_steps_scopes_name_the_new_parts():
    """``ds.dense_ffn`` holds the two dense SwiGLUs, ``ds.moe_zero`` lies
    inside ``ds.mlp`` beside the router and the experts, and both attentions
    keep the latent scopes."""
    model, params = _model()
    eng = _scheduler(model, params, 0, sessions=1).engine
    n = eng.state_manager.max_seqs
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    text = rf.longcat_flash_ragged_step.lower(
        params, eng._kv, i32(16), i32(16), i32(16),
        i32(n, eng.state_manager.block_table.shape[1]), i32(n),
        cfg=CFG, block_size=8).as_text(debug_info=True)
    for path in ("ds.dense_ffn", "ds.mlp/ds.moe_zero", "ds.mlp/ds.moe_router",
                 "ds.mlp/ds.moe_experts", "ds.attn/ds.mla_down",
                 "ds.attn/ds.mla_absorb", "ds.attn/ds.kv_cache"):
        assert path in text, path
    assert "ds.mlp/ds.dense_ffn" not in text


def test_the_latent_kernel_runs_under_the_step(monkeypatch):
    """A prompt of 50 tokens in chunks of 16 rows beside a short one through
    ``ds_paged_latent`` itself (interpret mode), both attentions of every
    layer: the streams are the gather's."""
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    inner = rf.longcat_flash_ragged_step.__wrapped__
    model, params = _model()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, n).tolist() for n in (50, 13)]

    def engine(use_kernel):
        sched = _scheduler(model, params, 0, context=128)

        def step(*a, **kw):
            return inner(*a, **{**kw, "use_kernel": use_kernel})

        sched.engine._step_fn = jax.jit(
            step, static_argnames=("cfg", "block_size", "use_kernel",
                                   "kv_dtype"), donate_argnums=(1, ))
        return sched

    want = engine(False).engine.generate(prompts, max_new_tokens=6)
    assert engine(True).serve(prompts, max_new_tokens=6) == want


def test_both_kernels_run_under_a_step_with_a_long_chunk(monkeypatch):
    """A budget of 48 rows (the tiny widths' rule: a run of 33 rows is
    expanded): a prompt of 60 tokens goes as 35 and 25 rows beside a short
    prompt's 13, through ``ds_paged_mla_chunk`` and ``ds_paged_latent``
    (interpret mode), both attentions of every layer with the model's two
    scale factors: the streams are the gather's, and the first step counts
    35 expanded rows with their pairs over all 8 cache entries."""
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    inner = rf.longcat_flash_ragged_step.__wrapped__
    model, params = _model()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, n).tolist() for n in (60, 13)]

    def engine(use_kernel):
        sched = _scheduler(model, params, 0, budget=48, context=128)

        def step(*a, **kw):
            return inner(*a, **{**kw, "use_kernel": use_kernel})

        sched.engine._step_fn = jax.jit(
            step, static_argnames=("cfg", "block_size", "use_kernel",
                                   "kv_dtype"), donate_argnums=(1, ))
        return sched

    want = engine(False).engine.generate(prompts, max_new_tokens=6)
    sched = engine(True)
    firsts, build = [], sched.engine._build_batch

    def counted(*a, **kw):
        out = build(*a, **kw)
        firsts.append(dict(sched.engine.last_step_counts))
        return out

    monkeypatch.setattr(sched.engine, "_build_batch", counted)
    assert sched.serve(prompts, max_new_tokens=6) == want
    assert (firsts[0]["absorbed_rows"], firsts[0]["expanded_rows"]) == (13,
                                                                        35)
    assert firsts[0]["expanded_keys"] == sum(range(1, 36)) * 8
    assert firsts[0]["latent_keys"] == sum(range(1, 14)) * 8
    assert firsts[1]["expanded_rows"] == 0      # the last 25 rows: absorbed


def test_the_config_states_what_it_implements():
    cfg = lf.LongcatFlashConfig()
    assert (cfg.router_width, cfg.q_scale, round(cfg.kv_scale ** 2),
            cfg.kv_latent_dim, cfg.num_key_value_heads) == (768, 2.0, 12,
                                                            576, 64)
    off = dataclasses.replace(cfg, mla_scale_q_lora=False,
                              mla_scale_kv_lora=False)
    assert (off.q_scale, off.kv_scale) == (1.0, 1.0)
    with pytest.raises(ValueError, match="identity"):
        lf.longcat_flash_tiny(zero_expert_type="copy")
    with pytest.raises(ValueError, match="outside the router"):
        lf.longcat_flash_tiny(first_expert=28)
