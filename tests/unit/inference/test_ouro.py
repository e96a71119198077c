"""Ouro (``models/ouro.py``: a LOOPED model) at tiny size on the CPU (2 layers
x 3 passes), against the benchmark's plain reference
(``perfbench/reference/ouro.py``): the flax forward on logits and gates;
chunked prefill and decode through the paged cache with a preemption; the
cache's ENTRIES (a row a (pass, layer) pair, pass-major inside a layer's
buffer); weight sharing; the exit distribution; the counts and scopes of a
step; the paged kernel under the rolled loop."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import ragged_forward as rf
from deepspeed_tpu.inference.v2.ragged import BlockedKVCache
from deepspeed_tpu.models import ouro
from deepspeed_tpu.serving import build_serving_engine
from deepspeed_tpu.telemetry import names

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


@pytest.fixture(scope="module")
def ref():
    import sys
    sys.path.insert(0, ROOT)
    from perfbench import loader
    return loader.load_part(ROOT, "reference", "ouro")


CFG = ouro.ouro_tiny(dtype="float32")       # 2 layers x 3 passes
_made = {}


def _model(cfg=CFG):
    """The model and seeded weights with NON-constant norm weights and a
    non-constant gate bias (flax inits ones and zeros: a norm that is left
    out or exchanged would then move nothing)."""
    if cfg not in _made:
        model = ouro.OuroModel(cfg)
        params = model.init(jax.random.PRNGKey(3),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))
        vary = lambda w: w + 0.2 * jax.random.normal(next(keys), w.shape)
        params["norm"]["weight"] = vary(params["norm"]["weight"])
        params["early_exit_gate"]["bias"] = jnp.asarray([0.4])
        for l in range(cfg.num_hidden_layers):
            lp = params[f"layers_{l}"]
            for name in [k for k in lp if k.endswith(("layernorm",
                                                       "layernorm_2"))]:
                lp[name]["weight"] = vary(lp[name]["weight"])
        _made[cfg] = model, params
    return _made[cfg]


def _sizes(cfg=CFG, **changes):
    """What the reference reads, from the program's config."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "total_ut_steps",
            "num_hidden_layers")
    return dict({k: getattr(cfg, k) for k in keys}, **changes)


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


def _gaps(ref, params, prompts, produced, sizes=None):
    """``(worst gap, share of positions at the reference's argmax)`` of the
    streams against the reference's teacher-forced full forward (the serve
    job's measure)."""
    worst, hits, n = 0.0, 0, 0
    for prompt, toks in zip(prompts, produced):
        ids = np.asarray(prompt + toks[:-1], np.int32)
        at = np.arange(len(prompt) - 1, len(ids))
        logits = np.asarray(ref.logits_at(params, ids, at, sizes or _sizes()))
        chosen = logits[np.arange(len(toks)), toks]
        worst = max(worst, float(np.max(
            (logits.max(-1) - chosen) / logits.std(-1))))
        hits += int((logits.argmax(-1) == np.asarray(toks)).sum())
        n += len(toks)
    return worst, hits / n


# ------------------------------------------------- the model = the reference
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_dense_forward_is_the_reference(ref, dtype):
    """The flax model against the plain reference on LOGITS and on the
    gates ``lam(t)`` of all three passes."""
    model, params = _model(dataclasses.replace(CFG, dtype=dtype))
    ids = np.random.default_rng(0).integers(0, CFG.vocab_size, 70)
    got, lam = model.apply({"params": params}, jnp.asarray(ids)[None],
                           return_gates=True)
    got, lam = np.asarray(got[0]), np.asarray(lam[:, 0])
    want = ref.forward(params, ids, np.arange(70), _sizes())
    logits, gates = np.asarray(want["logits"]), np.asarray(want["lam"])
    assert lam.shape == gates.shape == (3, 70)
    if dtype == "float32":
        np.testing.assert_allclose(got, logits,
                                   atol=2e-3 * float(logits.std()))
        np.testing.assert_allclose(lam, gates, atol=1e-4)
        assert np.array_equal(got.argmax(-1), logits.argmax(-1))
    else:
        assert np.median(np.abs(got - logits)) < 0.02 * float(logits.std())
        assert np.abs(lam - gates).max() < 0.03
        assert np.mean(got.argmax(-1) == logits.argmax(-1)) >= 0.8
    assert gates.std() > 0.01            # a gate that reads its input


@pytest.mark.parametrize("burst", [0, 8], ids=["steps", "burst"])
def test_prefill_then_decode_through_the_cache_is_the_references_forward(
        ref, burst):
    """Three prompts of 40, 30 and 21 tokens in chunks of 16 rows through a
    pool too small for them (the scheduler evicts, requeues and recomputes),
    then 14 decoded tokens each, a step at a time and through the burst:
    every streamed token is the argmax of the reference's FULL forward."""
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
    model, params = _model(dataclasses.replace(CFG, dtype="bfloat16"))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).tolist() for n in (40, 21)]
    sched = _scheduler(model, params, 8, dtype="bfloat16")
    produced = sched.serve(prompts, max_new_tokens=14)
    gap, share = _gaps(ref, params, prompts, produced)
    assert gap < 0.25 and share >= 0.8, (gap, share)


@pytest.mark.parametrize("change", [
    {"passes_run": 2}, {"norm_between_passes": False},
    {"pass_reads": "previous"}, {"pass_reads": "last"},
    {"post_sublayer_norms": False}], ids=lambda c: "-".join(map(str, *c.items())))
def test_a_reference_with_a_planted_fault_is_another_model(ref, change):
    """Each reading ``tools/serve_fault_check.py`` plants moves the logits
    by far more than rounding: the sound engine's tokens leave its argmax."""
    model, params = _model()
    prompts = [np.random.default_rng(5).integers(0, 256, 40).tolist()]
    produced = _scheduler(model, params, 8).serve(prompts, max_new_tokens=14)
    assert _gaps(ref, params, prompts, produced) == (0.0, 1.0)
    gap, share = _gaps(ref, params, prompts, produced, _sizes(**change))
    assert gap > 0.25 and share < 0.8, (change, gap, share)


# ------------------------------------------------------------- the entries
@pytest.mark.parametrize("layers,passes", [(2, 3), (3, 2)])
def test_a_token_claims_a_row_in_every_pass_and_layers_entry(ref, layers,
                                                             passes):
    """A model of ``L`` layers and ``T`` passes allocates ``T x L`` entries
    (``L`` buffers of ``T x num_blocks`` pages), a token claims ``T x L``
    rows, and after a request entry ``t * L + l`` holds what the reference's
    pass ``t`` of layer ``l`` computed as K and V: crossed, shared or
    exchanged entries fail here by their index."""
    cfg = dataclasses.replace(CFG, num_hidden_layers=layers,
                              total_ut_steps=passes)
    model, params = _model(cfg)
    assert cfg.kv_cache_entries == BlockedKVCache.entries_of(cfg) \
        == passes * layers
    sched = _scheduler(model, params, 0, budget=16, sessions=1, blocks=10)
    eng = sched.engine
    kv = eng.kv_cache
    assert [tuple(x.shape for x in e) for e in kv.layers] == \
        [((passes * 10, 8, 4, 16), ) * 2] * layers
    assert kv.page_layers == passes * layers
    row = 2 * 4 * 16 * 4                                  # K and V, float32
    assert kv.bytes_by_kind() == (passes * layers * row, 0)
    prompt = np.random.default_rng(1).integers(0, 256, 21).tolist()
    sched.submit(prompt, max_new_tokens=1)
    sched.step()
    counts = eng.last_step_counts
    assert counts["live_tokens"] == 16            # the first chunk of 16 rows
    assert counts["cache_token_bytes"] == passes * layers * row
    sched.drain()
    seq = eng.state_manager                      # the request is flushed:
    assert seq.free_blocks == 9                  # the pages keep what it wrote
    want = ref.forward(params, np.asarray(prompt), [20], _sizes(cfg),
                       keep_kv=True)["kv"]
    table = None
    for t in range(passes):
        for l in range(layers):
            k_pages, v_pages = kv.entry_pages(eng._kv, t * layers + l)
            assert k_pages.shape == (10, 8, 4, 16)
            k_flat = np.asarray(k_pages).reshape(80, 4, 16)
            # past block 0 of the entry, the dead rows' garbage block
            written = 8 + np.flatnonzero(np.abs(k_flat[8:]).sum((1, 2)))
            assert len(written) == 21, (t, l)     # a row a token, no other
            if table is None:
                table = written
            assert np.array_equal(written, table)      # the same block table
            for pages, ref_rows in zip((k_pages, v_pages), want[t, l]):
                got = np.asarray(pages).reshape(80, 4, 16)[table]
                np.testing.assert_allclose(got, np.asarray(ref_rows),
                                           atol=2e-4, err_msg=str((t, l)))
    # and no two entries hold the same rows
    firsts = [np.asarray(kv.entry_pages(eng._kv, e)[0]).reshape(80, 4, 16)[
        table] for e in range(passes * layers)]
    for a in range(len(firsts)):
        for b in range(a):
            assert np.abs(firsts[a] - firsts[b]).max() > 1e-3, (a, b)


def test_the_published_model_states_192_entries_and_a_buffer_a_layer():
    cfg = ouro.OuroConfig()
    assert (cfg.kv_cache_entries, cfg.kv_entries_a_buffer) == (192, 4)
    # K and V of a token in all of them: 1.5 MiB in bfloat16
    assert cfg.kv_cache_entries * 2 * cfg.num_key_value_heads \
        * cfg.head_dim * 2 == 1_572_864
    shapes = jax.eval_shape(lambda: BlockedKVCache(
        192, 43, 128, 16, 128, entries_a_buffer=4).layers)
    assert len(shapes) == 48 and shapes[0][0].shape == (4 * 43, 128, 16, 128)
    with pytest.raises(NotImplementedError):
        BlockedKVCache(6, 4, 8, 4, 16, entries_a_buffer=4)   # 6 % 4
    with pytest.raises(NotImplementedError):
        BlockedKVCache(8, 4, 8, 4, 16, entries_a_buffer=4, kv_dtype="int8")


# -------------------------------------------------------- weight sharing
@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_parameter_tree_has_L_layers_whatever_T_is(passes):
    cfg = dataclasses.replace(CFG, total_ut_steps=passes)
    shapes = jax.eval_shape(ouro.OuroModel(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    assert sorted(shapes) == ["early_exit_gate", "embed_tokens", "layers_0",
                              "layers_1", "lm_head", "norm"]
    assert sorted(shapes["layers_0"]) == [
        "input_layernorm", "input_layernorm_2", "mlp",
        "post_attention_layernorm", "post_attention_layernorm_2",
        "self_attn"]
    # the two post-sublayer gains in rows of 32 (models/ouro.py says why)
    assert [shapes["layers_0"][n]["weight"].shape for n in (
        "input_layernorm", "input_layernorm_2", "post_attention_layernorm",
        "post_attention_layernorm_2")] == [(64, ), (2, 32), (64, ), (2, 32)]
    assert shapes["early_exit_gate"]["kernel"].shape == (64, 1)
    assert shapes["early_exit_gate"]["bias"].shape == (1, )


def test_one_pass_is_a_plain_sandwich_norm_model(ref):
    """``T = 1``: embedding, the layers once, the final norm, the head: the
    plain forward written out here, with no loop in it."""
    cfg = dataclasses.replace(CFG, total_ut_steps=1)
    model, params = _model(cfg)
    ids = np.random.default_rng(4).integers(0, 256, 30)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(ids)[None])[0])
    x = ref.f32(params["embed_tokens"]["embedding"])[ids]
    with jax.default_matmul_precision("highest"):
        for l in range(cfg.num_hidden_layers):
            x, _, _ = ref.layer(x, params[f"layers_{l}"], _sizes(cfg))
        x = ref.rms_norm(x, params["norm"]["weight"], cfg.rms_norm_eps)
        want = np.asarray(x @ params["lm_head"]["kernel"])
    np.testing.assert_allclose(got, want, atol=2e-3 * float(want.std()))
    # and three passes are another model
    three = np.asarray(_model()[0].apply({"params": params},
                                         jnp.asarray(ids)[None])[0])
    assert np.abs(three - want).max() > 0.1 * float(want.std())


def test_a_threshold_under_one_raises():
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        ouro.ouro_tiny(early_exit_threshold=0.9)
    with pytest.raises(ValueError):
        ouro.ouro_tiny(total_ut_steps=0)
    assert ouro.ouro_tiny(early_exit_threshold=1).total_ut_steps == 3


@pytest.mark.parametrize("passes", [1, 3, 4])
def test_the_exit_distribution_sums_to_one(ref, passes):
    lam = jax.random.uniform(jax.random.PRNGKey(passes), (passes, 5, 7))
    p = np.asarray(ouro.exit_distribution(lam))
    assert p.shape == (passes, 5, 7) and (p >= 0).all()
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p[0], np.asarray(lam[0]) if passes > 1 else 1.0,
                               atol=1e-7)
    np.testing.assert_allclose(
        p, np.asarray(ref.exit_distribution(lam.reshape(passes, 35))
                      ).reshape(p.shape), atol=1e-6)


# ------------------------------------------------- what a step counts, names
def test_a_step_counts_rows_x_passes_and_the_gates_expected_exit(ref):
    model, params = _model()
    prompt = np.random.default_rng(1).integers(0, 256, 13).tolist()
    sched = _scheduler(model, params, 0, budget=32, sessions=1)
    sched.submit(prompt, max_new_tokens=1)
    sched.step()
    sched.step()
    counts = sched.engine.last_step_counts
    assert rf.ouro_ragged_step.step_counts == (
        names.COUNT_LOOP_ROW_PASSES, names.COUNT_GATE_EXIT_PASSES_Q8) == (
        "loop_row_passes", "gate_exit_passes_q8")
    assert counts["loop_row_passes"] == 13 * 3
    lam = ref.forward(params, np.asarray(prompt), [12], _sizes())["lam"]
    p = np.asarray(ref.exit_distribution(lam))               # [3, 13]
    expected = (p * np.arange(1, 4)[:, None]).sum()
    assert 13 < expected < 39
    assert abs(counts["gate_exit_passes_q8"] / 256 - expected) < 0.01
    # a model that runs its stack once carries none of the three
    from deepspeed_tpu.models import llama
    cfg = llama.llama_tiny(dtype="float32", remat=False)
    m = llama.LlamaModel(cfg)
    plain = _scheduler(m, m.init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 8), jnp.int32))["params"], 0,
                       sessions=1)
    plain.submit(prompt, max_new_tokens=1)
    plain.step()
    assert not {"loop_row_passes", "gate_exit_passes_q8",
                "cache_token_bytes"} & set(plain.engine.last_step_counts)


def test_the_steps_scopes_name_the_loop_the_norm_between_and_the_gate():
    """``ds.ut_pass`` holds the layers (attention, cache, MLP) and
    ``ds.ut_norm``; the gate is outside it; the last pass's norm is the
    head's; the program holds ``L`` layer bodies, not ``T x L``."""
    model, params = _model()
    eng = _scheduler(model, params, 0, sessions=1).engine
    n = eng.state_manager.max_seqs
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    text = rf.ouro_ragged_step.lower(
        params, eng._kv, i32(16), i32(16), i32(16),
        i32(n, eng.state_manager.block_table.shape[1]), i32(n),
        cfg=CFG, block_size=8).as_text(debug_info=True)
    for path in ("ds.ut_pass/ds.attn/ds.kv_cache", "ds.ut_pass/ds.mlp",
                 "ds.ut_pass/ds.ut_norm/ds.norm", "ds.exit_gate",
                 "ds.lm_head/ds.norm", "ds.embed"):
        assert path in text, path
    assert "ds.ut_pass/ds.exit_gate" not in text
    assert "ds.ut_pass/ds.lm_head" not in text
    # the loop is rolled: twice the passes, the same number of products
    six = dataclasses.replace(CFG, total_ut_steps=6)
    kv6 = _scheduler(ouro.OuroModel(six), params, 0, sessions=1).engine._kv
    text6 = rf.ouro_ragged_step.lower(
        params, kv6, i32(16), i32(16), i32(16),
        i32(n, eng.state_manager.block_table.shape[1]), i32(n),
        cfg=six, block_size=8).as_text()
    dots = lambda t: t.count("stablehlo.dot_general")
    assert dots(text6) == dots(text) > 7 * CFG.num_hidden_layers


def test_the_paged_kernel_runs_under_the_rolled_loop(ref, monkeypatch):
    """``ds_paged_runs`` (interpreted on the CPU) reads a pass's pages at the
    block table shifted by ``t x num_blocks``: tokens as the gather's."""
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    cfg = dataclasses.replace(CFG, head_dim=128, hidden_size=256,
                              num_attention_heads=2, num_key_value_heads=2,
                              intermediate_size=256)
    model, params = _model(cfg)
    prompts = [np.random.default_rng(6).integers(0, 256, n).tolist()
               for n in (19, 9)]
    sched = _scheduler(model, params, 4, budget=16, blocks=12)
    produced = sched.serve(prompts, max_new_tokens=6)
    text = str(jax.make_jaxpr(lambda *a: rf.ouro_ragged_step(
        *a, cfg=cfg, block_size=8))(
        params, sched.engine._kv, *(jnp.zeros(16, jnp.int32), ) * 3,
        jnp.zeros((3, 8), jnp.int32), jnp.zeros(3, jnp.int32)))
    assert "ds_paged_runs" in text
    assert _gaps(ref, params, prompts, produced, _sizes(cfg)) == (0.0, 1.0)
