"""Quantized paged-KV tests (ISSUE-11): rowwise codec invariants, cache
layout, the int8-vs-fp greedy parity gate (≥64 decode steps on the
decisive-logits probe model), and unset-dtype bit-identity."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.comm.collectives.quantized import (rowwise_codec,
                                                      rowwise_storage_dtype)
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.kv_codec import (kv_bytes_per_token,
                                                 resolve_kv_dtype)
from deepspeed_tpu.inference.v2.ragged import BlockedKVCache
from deepspeed_tpu.serving import ServingScheduler
from tests.unit.serving.probe_engine import probe_engine, probe_model


# -------------------------------------------------------------------- codec
def test_rowwise_codec_roundtrip_int8():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((5, 2, 32)), jnp.float32)
    enc, dec = rowwise_codec("int8", reduce_axes=1)
    q, s = enc(x)
    assert q.shape == x.shape and q.dtype == jnp.int8
    assert s.shape == (5, 2) and s.dtype == jnp.float32
    y = dec(q, s)
    # symmetric absmax int8: error bounded by scale/2 = absmax/254 per row
    bound = np.abs(np.asarray(x)).max(axis=-1) / 254.0 + 1e-7
    assert (np.abs(np.asarray(y - x)).max(axis=-1) <= bound).all()


def test_rowwise_codec_roundtrip_fp8():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 3, 16)), jnp.float32)
    enc, dec = rowwise_codec("fp8", reduce_axes=1)
    q, s = enc(x)
    assert q.dtype == jnp.float8_e4m3fn
    y = np.asarray(dec(q, s))
    # e4m3: ~2 mantissa-bit relative error after scaling
    np.testing.assert_allclose(y, np.asarray(x), rtol=0.08, atol=1e-4)


def test_rowwise_codec_zero_row_and_unknown_format():
    enc, dec = rowwise_codec("int8", reduce_axes=1)
    x = jnp.zeros((2, 4, 8), jnp.float32)
    q, s = enc(x)
    assert np.asarray(dec(q, s)).max() == 0.0   # scale=1 guard, no NaN
    with pytest.raises(ValueError, match="rowwise wire format"):
        rowwise_codec("int3")
    with pytest.raises(ValueError, match="rowwise wire format"):
        rowwise_storage_dtype("bf16")


def test_resolve_kv_dtype_spellings():
    assert resolve_kv_dtype(None) is None
    assert resolve_kv_dtype("INT8") == "int8"
    assert resolve_kv_dtype("q8") == "int8"
    assert resolve_kv_dtype("fp8_e4m3") == "fp8"
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        resolve_kv_dtype("int4")


def test_kv_bytes_per_token_accounting():
    fp = kv_bytes_per_token(2, 2, 16, None, fp_dtype=jnp.float32)
    q = kv_bytes_per_token(2, 2, 16, "int8")
    assert fp == 2 * 2 * 2 * 16 * 4
    assert q == 2 * 2 * 2 * 16 * 1 + 2 * 2 * 2 * 4
    assert q < fp / 2   # the capacity claim: >2× more tokens per byte


# -------------------------------------------------------------------- cache
def test_quantized_cache_layout():
    kv = BlockedKVCache(num_layers=2, num_blocks=8, block_size=4,
                        num_kv_heads=2, head_dim=16, kv_dtype="int8")
    assert kv.dtype == jnp.int8 and len(kv.layers) == 2
    for k_pages, v_pages, k_scales, v_scales in kv.layers:
        for pages, scales in ((k_pages, k_scales), (v_pages, v_scales)):
            assert pages.dtype == jnp.int8
            assert pages.shape == (8, 4, 2, 16)
            assert scales.shape == (8, 4, 2)
            assert scales.dtype == jnp.float32
    fp = BlockedKVCache(num_layers=2, num_blocks=8, block_size=4,
                        num_kv_heads=2, head_dim=16)
    assert fp.kv_dtype is None and len(fp.layers) == 2
    assert all(len(layer) == 2 for layer in fp.layers)      # no scales
    # 2 L buffers of their own: no two leaves share storage
    assert len({a.unsafe_buffer_pointer()
                for a in jax.tree.leaves(fp.layers)}) == 4


# ----------------------------------------------------------------- engine
def test_engine_rejects_unknown_kv_dtype():
    model, params = probe_model()
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        InferenceEngineV2(model, params=params,
                          config=dict(dtype="float32",
                                      kv_cache_dtype="nf4"))


def test_int8_kv_composes_with_tensor_parallel():
    """kv_cache_dtype: int8 × tp_size=2 (ISSUE-15 satellite / ROADMAP
    serving follow-on (b)): the per-token scale arrays shard WITH the
    cache over the kv-head dim instead of the former loud rejection —
    greedy output stays token-identical to the tp=1 int8 engine."""
    from deepspeed_tpu.models import llama
    cfg = llama.llama_tiny(dtype="float32", remat=False)
    model = llama.LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    sm = dict(max_tracked_sequences=8, max_ragged_batch_size=64,
              max_ragged_sequence_count=8, max_context=128,
              block_size=16, num_blocks=40)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 96, size=n).tolist() for n in (17, 7)]
    outs = {}
    for tp in (1, 2):
        eng = InferenceEngineV2(
            model, params=params,
            config=dict(dtype="float32", state_manager=dict(sm),
                        kv_cache_dtype="int8",
                        tensor_parallel=dict(tp_size=tp)))
        for k_pages, v_pages, k_scales, v_scales in eng._kv:
            for data, scales in ((k_pages, k_scales), (v_pages, v_scales)):
                assert data.dtype == jnp.int8
                if tp > 1:
                    # the cache AND its scales actually live across both
                    # ranks, split on the kv-head dim (scales' trailing dim)
                    assert len(data.sharding.device_set) == 2
                    assert len(scales.sharding.device_set) == 2
                    assert data.sharding.spec[2] == "tp", data.sharding.spec
                    assert scales.sharding.spec[-1] == "tp", \
                        scales.sharding.spec
        outs[tp] = eng.generate(prompts, max_new_tokens=6)
        eng.flush(range(len(prompts)))
    assert outs[1] == outs[2]


@pytest.mark.parametrize("through", ("generate", "scheduler"))
def test_int8_kv_parity_gate_64_steps(through):
    """THE acceptance gate: int8 paged-KV greedy decode token-identical to
    the fp cache over ≥64 decode steps (chunked prefill + decode bursts
    included), from the engine's own loop and through the scheduler."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 64, size=n).tolist() for n in (15, 6, 9)]
    out_fp = probe_engine().generate(prompts, max_new_tokens=64)
    eng_q = probe_engine(kv_dtype="int8")
    if through == "generate":
        out_q = eng_q.generate(prompts, max_new_tokens=64)
    else:
        out_q = ServingScheduler(eng_q).serve(prompts, max_new_tokens=64)
    assert min(len(o) for o in out_fp) >= 64
    assert out_q == out_fp
    assert getattr(eng_q, "burst_steps", 0) >= 1   # bursts ran quantized


def test_fp8_kv_serves_and_completes():
    """fp8 (e4m3) KV: 2 mantissa bits is NOT argmax-stable on a tiny
    model, so the gate here is structural — serves, right lengths, right
    storage dtype — while int8 carries the token-identity gate."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 64, size=7).tolist() for _ in range(2)]
    eng = probe_engine(kv_dtype="fp8")
    assert eng.kv_cache.dtype == jnp.float8_e4m3fn
    assert all(a.dtype == jnp.float8_e4m3fn
               for layer in eng._kv for a in layer[:2])
    out = eng.generate(prompts, max_new_tokens=8)
    assert [len(o) for o in out] == [8, 8]


def test_kv_dtype_unset_is_todays_engine():
    """``kv_cache_dtype`` unset must serve bit-identically to an engine
    built before this feature existed: same cache array (no scales), same
    step-function statics path, same tokens."""
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 64, size=9).tolist() for _ in range(2)]
    eng = probe_engine()
    assert eng._kv_dtype is None
    assert all(len(layer) == 2 for layer in eng._kv)    # K and V, no scales
    out = eng.generate(prompts, max_new_tokens=6)
    out2 = probe_engine().generate(prompts, max_new_tokens=6)
    assert out == out2


def test_quantized_kv_composes_with_weight_quant():
    """kv_cache_dtype + quantization_mode (weight-only int8) serve
    together — the two quantization planes are independent."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 64, size=8).tolist()]
    model, params = probe_model()
    both = InferenceEngineV2(
        model, params=params,
        config=dict(dtype="float32", kv_cache_dtype="int8",
                    quantization_mode="int8",
                    state_manager=dict(max_tracked_sequences=8,
                                       max_ragged_batch_size=64,
                                       max_ragged_sequence_count=8,
                                       max_context=256, block_size=16,
                                       num_blocks=96)))
    out = both.generate(prompts, max_new_tokens=6)
    assert [len(o) for o in out] == [6]
