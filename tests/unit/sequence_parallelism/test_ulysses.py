"""Ulysses tests — mirrors reference ``tests/unit/sequence_parallelism/
test_ulysses.py`` intent: the a2a head/sequence reshard must be numerically
identical to local attention."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.sequence.layer import DistributedAttention, _default_attention
from deepspeed_tpu.utils import groups


def _qkv(B=2, S=32, H=8, D=16, seed=0, kv_heads=None):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, kv_heads or H, D)).astype(np.float32)
    v = rng.standard_normal((B, S, kv_heads or H, D)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ulysses_matches_local(sp):
    groups.initialize_mesh(dp=8 // sp, sp=sp)
    q, k, v = _qkv()
    attn = DistributedAttention()
    out_dist = attn(q, k, v, causal=True)
    out_ref = _default_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out_dist), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-4)


def test_ulysses_noncausal():
    groups.initialize_mesh(dp=2, sp=4)
    q, k, v = _qkv(seed=1)
    out_dist = DistributedAttention()(q, k, v, causal=False)
    out_ref = _default_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out_dist), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-4)


def _gqa_ref(q, k, v, causal=True):
    rep = q.shape[2] // k.shape[2]
    return _default_attention(q, jnp.repeat(k, rep, axis=2),
                              jnp.repeat(v, rep, axis=2), causal=causal)


def test_ulysses_gqa_small_kv():
    """n_kv < sp → KV all-gather + head-select path (reference uneven-heads
    analog).  DistributedAttention aligns kv heads internally."""
    groups.initialize_mesh(dp=1, sp=8)
    q, k, v = _qkv(H=8, kv_heads=2, seed=2)
    out_dist = DistributedAttention()(q, k, v)
    out_ref = _gqa_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out_dist), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-4)


def test_ulysses_gqa_divisible_kv():
    """n_kv divisible by sp but < H → a2a + local group-repeat path."""
    groups.initialize_mesh(dp=2, sp=4)
    q, k, v = _qkv(H=8, kv_heads=4, seed=3)
    out_dist = DistributedAttention()(q, k, v)
    out_ref = _gqa_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out_dist), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-4)


def test_ulysses_uneven_heads():
    """r5 (VERDICT #4, reference ``uneven_heads_all2all`` layer.py:72):
    n_heads % sp != 0 with GQA n_kv < sp — padded-head a2a + routed kv,
    no full-KV replication.  Parity vs local attention at sp=4, heads=6,
    kv=2 (the VERDICT's done-criterion config)."""
    groups.initialize_mesh(dp=2, sp=4)
    q, k, v = _qkv(H=6, kv_heads=2, seed=4)
    out_dist = DistributedAttention()(q, k, v)
    out_ref = _gqa_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out_dist), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("H,kv,sp", [(6, 6, 4), (6, 3, 4), (10, 5, 8),
                                     (6, 2, 8), (8, 8, 8)])
def test_ulysses_uneven_heads_sweep(H, kv, sp):
    """Head/kv/sp combinations with every divisibility violation: H % sp,
    kv % sp, kv < sp, and the even baseline — all must match local GQA."""
    groups.initialize_mesh(dp=8 // min(sp, 8), sp=sp)
    q, k, v = _qkv(H=H, kv_heads=kv, seed=H * 31 + kv)
    out_dist = DistributedAttention()(q, k, v)
    out_ref = _gqa_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out_dist), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-4)


def test_ulysses_uneven_heads_grads():
    """Gradients flow through the pad/route path and match local GQA."""
    groups.initialize_mesh(dp=2, sp=4)
    q, k, v = _qkv(H=6, kv_heads=2, seed=5)
    attn = DistributedAttention()

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_gqa_ref(q, k, v) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3,
                                   rtol=1e-3)


def test_sp1_passthrough():
    groups.initialize_mesh(dp=8, sp=1)
    q, k, v = _qkv()
    out = DistributedAttention()(q, k, v)
    out_ref = _default_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref), atol=1e-6)


def test_ulysses_grads_flow():
    groups.initialize_mesh(dp=2, sp=4)
    q, k, v = _qkv()
    attn = DistributedAttention()

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v) ** 2)

    g = jax.grad(loss)(q, k, v)
    assert np.isfinite(np.asarray(g).sum())

    def loss_ref(q, k, v):
        return jnp.sum(_default_attention(q, k, v) ** 2)

    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-3,
                               rtol=1e-3)


def test_ulysses_region_manual_over_sp_only():
    """The a2a shard_map must be PARTIAL-manual (manual_axes == {sp}): a
    full-manual region with P(None, 'sp') specs replicated the batch into
    every dp group and the heads into every tp rank — correct numerics,
    dp·tp× dead compute (round-3 fix, same class as the pipeline batch
    replication)."""
    groups.reset_mesh()
    groups.initialize_mesh(dp=2, sp=2, tp=2)
    att = DistributedAttention()
    q = jnp.zeros((4, 8, 4, 16), jnp.float32)
    jx = jax.make_jaxpr(lambda t: att(t, t, t, causal=True))(q)
    from tests.unit.simple_model import collect_manual_axes
    found = collect_manual_axes(jx)
    assert found, "no shard_map in the Ulysses program"
    assert all(ax == frozenset({"sp"}) for ax in found), found
    groups.reset_mesh()


def test_engine_trains_gqa_uneven_heads_under_sp():
    """r5: a GQA model whose head counts violate every divisibility rule
    (h=6, kv=2, sp=4) trains through the full engine path — initialize()
    builds the sp mesh, the model hands NATIVE-width kv to
    DistributedAttention (no pre-repeat; the routed a2a aligns GQA on the
    wire), and loss decreases.  The jaxpr check pins that the q pad path
    and the kv routing path are actually in the program."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.models import llama

    groups.reset_mesh()
    dist.destroy_process_group()
    cfg = llama.LlamaConfig(
        vocab_size=64, hidden_size=48, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=6, num_key_value_heads=2,
        max_position_embeddings=64, dtype="float32", remat=False,
        tie_word_embeddings=False, use_ulysses=True)
    model = llama.LlamaModel(cfg)
    ids = np.zeros((2, 32), np.int32)
    params = model.init(jax.random.PRNGKey(0), ids, ids)["params"]
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=llama.LlamaModel(cfg), model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "adam", "params": {"lr": 0.01}},
                "zero_optimization": {"stage": 1},
                "sequence_parallel_size": 4})
    # the kv-routing path sees NATIVE kv width: the model's attention must
    # not repeat kv to H before the a2a (that replication is what the
    # routed reshard exists to avoid)
    from deepspeed_tpu.sequence.layer import DistributedAttention
    seen = {}
    orig = DistributedAttention.__call__

    def spy(self, query, key, value, **kw):
        seen["kv_heads"] = key.shape[self.scatter_idx]
        seen["q_heads"] = query.shape[self.scatter_idx]
        return orig(self, query, key, value, **kw)

    DistributedAttention.__call__ = spy
    try:
        jax.make_jaxpr(lambda p, x: eng._effective_apply_fn()(p, x, x))(
            params, ids)
    finally:
        DistributedAttention.__call__ = orig
    assert seen["kv_heads"] == 2, seen   # native width reached the a2a
    assert seen["q_heads"] == 6, seen
    assert eng.seq_parallel_world_size == 4
    rng = np.random.default_rng(0)
    bs = 2 * eng.dp_world_size
    losses = []
    for _ in range(4):
        x = rng.integers(0, 64, (bs, 32)).astype(np.int32)
        loss = eng(x, x)
        eng.backward(loss)
        eng.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    # clean up ONLY after training — resetting mid-test would let the next
    # forward auto-build a default sp=1 mesh and silently bypass Ulysses
    groups.reset_mesh()
    dist.destroy_process_group()


def test_invalid_gqa_head_ratio_fails_loudly():
    """6 q heads over 4 kv heads has no whole q-group per kv head; the old
    clip-mode take silently attended the surplus q heads to the LAST kv
    head (ADVICE.md) — now it raises at trace time."""
    attn = DistributedAttention(_default_attention)
    q, k, v = _qkv(H=6, kv_heads=4)
    with pytest.raises(ValueError, match="GQA"):
        attn._align_gqa_local(q, k, v)
    with pytest.raises(ValueError, match="GQA"):
        DistributedAttention._check_gqa_heads(6, 4)
    DistributedAttention._check_gqa_heads(8, 4)   # whole groups: fine
