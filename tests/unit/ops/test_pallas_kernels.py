"""Pallas kernel numerics vs XLA oracles (reference ``tests/unit/ops/``
pattern: each native kernel is tested against a framework implementation).

Kernels run in interpret mode on CPU (``_interpret()`` auto-detects)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import _xla_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.optimizers import (fused_adam_step,
                                                 fused_lamb_step,
                                                 fused_lion_step)
from deepspeed_tpu.ops.pallas.quantizer import (dequantize_blockwise,
                                                quantize_blockwise)


# (the package's attribute ``flash_attention`` is the function)
fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)


# ------------------------------------------------------------ flash attn
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [
    (2, 64, 4, 32),     # padded D, aligned S
    (1, 100, 2, 64),    # unaligned S (mask path)
])
def test_flash_attention_forward(shape, causal):
    B, S, H, D = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (_rand(ks[i], shape) for i in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = _xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_gqa():
    B, S, Hq, Hkv, D = 1, 64, 8, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (B, S, Hq, D))
    k = _rand(ks[1], (B, S, Hkv, D))
    v = _rand(ks[2], (B, S, Hkv, D))
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    rep = lambda x: jnp.repeat(x, Hq // Hkv, axis=2)
    ref = _xla_attention(q, rep(k), rep(v), causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_decode_offset():
    """Sq < Sk causal: last q row attends the whole K (decode semantics)."""
    B, Sq, Sk, H, D = 1, 32, 96, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (B, Sq, H, D))
    k = _rand(ks[1], (B, Sk, H, D))
    v = _rand(ks[2], (B, Sk, H, D))
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("gqa", [False, True])
def test_flash_attention_grads(gqa):
    B, S, Hq, D = 1, 64, 4, 32
    Hkv = 2 if gqa else Hq
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (B, S, Hq, D))
    k = _rand(ks[1], (B, S, Hkv, D))
    v = _rand(ks[2], (B, S, Hkv, D))

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        rep = lambda x: jnp.repeat(x, Hq // Hkv, axis=2) if gqa else x
        o = _xla_attention(q, rep(k), rep(v), causal=True)
        return jnp.sum(o * jnp.cos(o))

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("batch", [8, 3])
def test_attention_core_runs_the_kernel_per_device_under_a_mesh(
        batch, monkeypatch):
    """A Mosaic kernel cannot be partitioned by GSPMD (on >1 chip the
    lowering raises), so under the global mesh ``attention_core`` wraps the
    flash kernel in a shard_map: batch over the dp axes and heads over tp
    where they divide (B=8 on dp4 x tp2), replicated work where they do not
    (B=3).  Value and grads match the XLA formulation either way."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.utils import groups

    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")   # interpreted on CPU
    mesh = groups.initialize_mesh(dp=4, tp=2).mesh
    S, H, D = 32, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    sh = NamedSharding(mesh, P(groups.dp_axes() if batch % 4 == 0 else None))
    q, k, v = (jax.device_put(_rand(kk, (batch, S, H, D)), sh) for kk in ks)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, causal=True)))

    jaxpr = str(jax.make_jaxpr(attention.attention_core)(q, k, v))
    assert "shard_map" in jaxpr and "pallas_call" in jaxpr
    got = jax.jit(jax.value_and_grad(loss(attention.attention_core),
                                     argnums=(0, 1, 2)))(q, k, v)
    want = jax.value_and_grad(loss(attention._xla_attention),
                              argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], atol=1e-4, rtol=1e-4)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)
    # no mesh in force: the kernel is called directly
    groups.reset_mesh()
    assert "shard_map" not in str(jax.make_jaxpr(       # (a fresh trace)
        lambda *a: attention.attention_core(*a))(q, k, v))


def test_flash_attention_dead_rows_no_nan():
    """Causal attention with sk < sq leaves leading q rows fully masked
    (lse hits the dead-row sentinel).  Regression: the packed-lse identity
    contraction must not let -inf poison valid rows' gradients with NaN."""
    B, sq, sk, H, D = 1, 96, 32, 2, 32  # rows 0..63 are dead at block_q=32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (B, sq, H, D))
    k = _rand(ks[1], (B, sk, H, D))
    v = _rand(ks[2], (B, sk, H, D))

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        return jnp.sum(o * jnp.cos(o))

    o = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert bool(jnp.all(jnp.isfinite(o)))
    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def loss_ref(q, k, v):
        o = _xla_attention(q, k, v, causal=True)
        return jnp.sum(o * jnp.cos(o))

    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    # dead q-rows produce softmax over the masked row in the oracle (uniform
    # probs) but exact zeros in flash; compare only the live region
    live = sq - sk  # rows >= sq-sk attend to >=1 key
    np.testing.assert_allclose(g[0][:, live:], gr[0][:, live:],
                               atol=5e-5, rtol=5e-5)
    for a, b in zip(g[1:], gr[1:]):
        assert bool(jnp.all(jnp.isfinite(a)))


# bfloat16 inputs, as the training cells send them (float32 inputs: the
# 2e-5 / 5e-5 limits above).  The reference is float32 XLA attention on the
# SAME values; what differs is the rounding of the results to bfloat16 (and,
# on the chip alone, of each product's operands inside the MXU).
BF16_CASES = {
    # name: (sq, sk, Hq, Hkv, D, block, window, alibi)
    "no window": (256, 256, 2, 2, 64, 64, 0, False),
    # the cells' blocks: the window's edge and the diagonal cross blocks of
    # 512, one block a row is interior, the oldest are dead
    "window binds, crosses blocks": (1536, 1536, 1, 1, 64, 512, 640, False),
    "GQA 4:1 through the index maps": (192, 192, 4, 1, 32, 64, 0, False),
    "GQA 7:1, window": (192, 192, 7, 1, 32, 64, 100, False),
    "S not a multiple of the block": (200, 200, 2, 2, 64, 64, 0, False),
    "sk > sq": (64, 192, 2, 2, 64, 64, 0, False),
    "sk > sq, window": (64, 192, 2, 1, 64, 32, 72, False),
    "ALiBi": (192, 192, 4, 4, 32, 64, 0, True),
}


@pytest.mark.parametrize("case", list(BF16_CASES))
def test_flash_attention_bfloat16_against_float32_reference(case):
    sq, sk, Hq, Hkv, D, block, window, alibi = BF16_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = _rand(ks[0], (1, sq, Hq, D), jnp.bfloat16)
    k = _rand(ks[1], (1, sk, Hkv, D), jnp.bfloat16)
    v = _rand(ks[2], (1, sk, Hkv, D), jnp.bfloat16)
    cot = _rand(ks[3], (1, sq, Hq, D))
    slopes = None
    if alibi:
        from deepspeed_tpu.models.bloom import alibi_slopes
        slopes = alibi_slopes(Hq)

    def flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, window=window,
                            alibi_slopes=slopes, block_q=block, block_k=block)
        assert o.dtype == jnp.bfloat16
        return jnp.sum(o.astype(jnp.float32) * cot), o

    def ref(q, k, v):
        rep = lambda x: jnp.repeat(x, Hq // Hkv, axis=2)
        o = _xla_attention(q, rep(k), rep(v), causal=True, window=window,
                           alibi_slopes=slopes)
        return jnp.sum(o * cot), o

    f32 = lambda x: np.asarray(x, np.float32)
    (_, o), g = jax.value_and_grad(flash, argnums=(0, 1, 2), has_aux=True)(
        q, k, v)
    (_, o_ref), g_ref = jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True)(*(x.astype(jnp.float32)
                                                for x in (q, k, v)))
    np.testing.assert_allclose(f32(o), o_ref, atol=2e-2, rtol=2e-2)
    for a, b, name in zip(g, g_ref, ("dq", "dk", "dv")):
        assert a.dtype == jnp.bfloat16
        # a gradient entry sums up to S rounded terms: against its scale
        np.testing.assert_allclose(f32(a), b, rtol=2e-2,
                                   atol=2e-2 * float(np.abs(b).max()),
                                   err_msg=name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_interior_body_equals_edge_body_to_the_bit(dtype, monkeypatch):
    """On an interior block the mask is all true: the body without it gives
    the bits of the body with it (every live block masked is the kernel
    before the split), value and gradients.  Held to the bit in bfloat16;
    in float32 to the last bits, because the CPU's XLA orders the row sums
    of the two interpreted programs differently (its choice, not the
    bodies': they differ by selects that pick their first operand)."""
    sq = sk = 160
    where = (True, sq, sk, 32, 32, 70)
    steps, live, edge = fa.block_counts(sq, sk, *where[3:5], True, 70)
    assert 0 < edge < live < steps       # all three kinds of step are run
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q, k, v = (_rand(kk, (1, sq, 2, 32), jnp.dtype(dtype)) for kk in ks)

    def run():
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, window=70, block_q=32,
                                block_k=32)
            return jnp.sum(o.astype(jnp.float32) ** 2), o
        (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
        return [np.asarray(x, np.float32) for x in (o, *g)]

    split = run()
    monkeypatch.setattr(fa, "_block_needs_mask",
                        lambda q_start, k_start, *a: k_start >= 0)
    for a, b in zip(split, run()):
        if dtype == "bfloat16":
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-6)


@pytest.mark.parametrize("S, window, block, want", [
    # smallthinker_21b_train_8k, 3 layers of 4; its full layer;
    # mistral7b_train_4k (no dead window block): at blocks of 512, then at
    # the default's
    (8192, 4096, 512, (256, 108, 24)),
    (8192, 0, 512, (256, 136, 16)),
    (4096, 4096, 512, (64, 36, 8)),
    (8192, 4096, None, (64, 30, 12)),
    (8192, 0, None, (64, 36, 8)),
    (4096, 4096, None, (16, 10, 4)),
])
def test_block_counts_at_the_cells_shapes(S, window, block, want):
    assert (fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K) == (1024, 1024)
    block = block or fa.DEFAULT_BLOCK_Q
    assert fa.block_counts(S, S, block, block, True, window) == want


GRIDS = [
    # sq, sk, block_q, block_k, causal, window
    (8192, 8192, 512, 512, True, 4096),
    (96, 200, 16, 32, True, 40),      # sk > sq, a padded key tail
    (128, 70, 32, 16, True, 33),      # sk < sq: the first rows are dead
    (70, 70, 16, 64, True, 7),
    (100, 100, 32, 32, True, 0),
    (64, 96, 16, 32, False, 0),
]


@pytest.mark.parametrize("sq, sk, bq, bk, causal, window", GRIDS)
def test_a_mask_is_built_only_where_it_is_not_all_true(sq, sk, bq, bk,
                                                       causal, window):
    """``_block_needs_mask`` against the mask itself on every live block, and
    a dead block's mask is all false."""
    where = (causal, sq, sk, bq, bk, window)
    for i in range(-(-sq // bq)):
        for j in range(-(-sk // bk)):
            mask = np.asarray(fa._score_mask(i * bq, j * bk, *where))
            if fa._block_live(i * bq, j * bk, *where):
                assert fa._block_needs_mask(i * bq, j * bk, *where) == (
                    not mask.all()), (i, j)
            else:
                assert not mask.any(), (i, j)
            np.testing.assert_array_equal(      # the transposed form's
                fa._score_mask(i * bq, j * bk, *where, key_axis=0), mask.T)


@pytest.mark.parametrize("by_k", [False, True])
@pytest.mark.parametrize("sq, sk, bq, bk, causal, window", GRIDS)
def test_the_grid_walks_the_live_blocks_alone(sq, sk, bq, bk, causal, window,
                                              by_k):
    """The steps of a head's grid (the tables the index maps read): every
    live block once, row after row (K blocks of a q block; ``by_k``, the q
    blocks of a K block), and nothing dead, so a dead block costs neither a
    fetch nor a turn; a row with no live block keeps one step, whose mask is
    all false, so that its outputs are written."""
    where = (causal, sq, sk, bq, bk, window)
    nq, nk = -(-sq // bq), -(-sk // bk)
    live = np.array([[bool(fa._block_live(i * bq, j * bk, *where))
                      for j in range(nk)] for i in range(nq)])
    iq, ik = fa._live_steps(sq, sk, bq, bk, causal, window, by_k=by_k)
    assert iq.dtype == ik.dtype == np.int32
    outer, inner = (ik, iq) if by_k else (iq, ik)
    rows = live.T if by_k else live
    steps = list(zip(outer.tolist(), inner.tolist()))
    assert steps == sorted(set(steps))          # once each, in row order
    assert sorted(set(outer.tolist())) == list(range(len(rows)))
    for r, row in enumerate(rows):
        mine = [c for o, c in steps if o == r]
        if row.any():
            assert mine == [c for c in range(len(row)) if row[c]]
        else:
            c, = mine
            i, j = (c, r) if by_k else (r, c)
            assert not np.asarray(fa._score_mask(i * bq, j * bk,
                                                 *where)).any()
            assert fa._block_needs_mask(i * bq, j * bk, *where)
    assert fa.block_counts(sq, sk, bq, bk, causal, window)[1] == live.sum()


# ------------------------------------------------------------- optimizers
def _adam_oracle(g, p, m, v, lr, b1, b2, eps, wd, t):
    m_ = b1 * m + (1 - b1) * g
    v_ = b2 * v + (1 - b2) * g * g
    mh = m_ / (1 - b1**t)
    vh = v_ / (1 - b2**t)
    p_ = p - lr * (mh / (np.sqrt(vh) + eps) + wd * p)
    return p_, m_, v_


def test_fused_adam_kernel():
    rng = np.random.default_rng(0)
    shape = (33, 17)  # deliberately unaligned
    g = rng.standard_normal(shape).astype(np.float32)
    p = rng.standard_normal(shape).astype(np.float32)
    m = rng.standard_normal(shape).astype(np.float32) * 0.1
    v = np.abs(rng.standard_normal(shape)).astype(np.float32) * 0.01
    kw = dict(lr=1e-2, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.01)
    bf, p2, m2, v2 = fused_adam_step(jnp.asarray(g), jnp.asarray(p),
                                     jnp.asarray(m), jnp.asarray(v),
                                     count=3, **{
                                         "lr": kw["lr"], "beta1": kw["beta1"],
                                         "beta2": kw["beta2"],
                                         "eps": kw["eps"],
                                         "weight_decay": kw["weight_decay"]
                                     })
    pr, mr, vr = _adam_oracle(g, p, m, v, kw["lr"], kw["beta1"], kw["beta2"],
                              kw["eps"], kw["weight_decay"], 3)
    np.testing.assert_allclose(p2, pr, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(m2, mr, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(v2, vr, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(bf, np.float32), pr, atol=1e-2,
                               rtol=1e-2)  # bf16 cast
    assert bf.dtype == jnp.bfloat16


def test_fused_lion_kernel():
    rng = np.random.default_rng(1)
    g = rng.standard_normal(1000).astype(np.float32)
    p = rng.standard_normal(1000).astype(np.float32)
    m = rng.standard_normal(1000).astype(np.float32) * 0.1
    bf, p2, m2 = fused_lion_step(jnp.asarray(g), jnp.asarray(p),
                                 jnp.asarray(m), lr=1e-3, beta1=0.9,
                                 beta2=0.99, weight_decay=0.1)
    update = np.sign(0.9 * m + 0.1 * g)
    pr = p - 1e-3 * (update + 0.1 * p)
    mr = 0.99 * m + 0.01 * g
    np.testing.assert_allclose(p2, pr, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(m2, mr, atol=1e-6, rtol=1e-6)


def test_fused_lamb_kernel():
    rng = np.random.default_rng(2)
    g = rng.standard_normal(2000).astype(np.float32)
    p = rng.standard_normal(2000).astype(np.float32)
    m = np.zeros(2000, np.float32)
    v = np.zeros(2000, np.float32)
    bf, p2, m2, v2 = fused_lamb_step(jnp.asarray(g), jnp.asarray(p),
                                     jnp.asarray(m), jnp.asarray(v), lr=1e-2,
                                     beta1=0.9, beta2=0.999, eps=1e-6,
                                     weight_decay=0.01, count=1)
    m_ = 0.1 * g
    v_ = 0.001 * g * g
    u = (m_ / 0.1) / (np.sqrt(v_ / 0.001) + 1e-6) + 0.01 * p
    ratio = np.clip(np.linalg.norm(p) / np.linalg.norm(u), 0.01, 10.0)
    pr = p - 1e-2 * ratio * u
    np.testing.assert_allclose(p2, pr, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(m2, m_, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(v2, v_, atol=1e-6, rtol=1e-6)


# -------------------------------------------------------------- quantizer
@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_roundtrip(bits, use_pallas):
    rng = np.random.default_rng(3)
    x = rng.standard_normal((37, 129)).astype(np.float32)
    q, s, meta = quantize_blockwise(jnp.asarray(x), num_bits=bits,
                                    group_size=256, use_pallas=use_pallas)
    assert q.dtype == jnp.int8
    out = dequantize_blockwise(q, s, meta, use_pallas=use_pallas)
    assert out.shape == x.shape
    qmax = 2**(bits - 1) - 1
    # per-group error bound: scale/2 = absmax/(2*qmax)
    err = np.abs(np.asarray(out) - x)
    assert err.max() <= np.abs(x).max() / qmax  # ≤ 1 quant step


def test_quantize_pallas_matches_xla():
    rng = np.random.default_rng(4)
    x = rng.standard_normal(5000).astype(np.float32)
    q1, s1, m1 = quantize_blockwise(jnp.asarray(x), group_size=256,
                                    use_pallas=False)
    q2, s2, m2 = quantize_blockwise(jnp.asarray(x), group_size=256,
                                    use_pallas=True)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-7)


def test_quantize_bf16_dtype_restored():
    x = jnp.ones((64, 64), jnp.bfloat16) * 1.5
    q, s, meta = quantize_blockwise(x, group_size=128, use_pallas=False)
    out = dequantize_blockwise(q, s, meta, use_pallas=False)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), 1.5, rtol=1e-2)


def test_quantize_large_group_small_rows():
    """Regression: VMEM-limited row blocks must still cover every group
    (block ∤ rows previously skipped the tail groups on the pallas path)."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal(24 * 16384).astype(np.float32)
    q, s, meta = quantize_blockwise(jnp.asarray(x), group_size=16384,
                                    use_pallas=True)
    out = dequantize_blockwise(q, s, meta, use_pallas=True)
    err = np.abs(np.asarray(out) - x)
    assert err.max() <= np.abs(x).max() / 127
