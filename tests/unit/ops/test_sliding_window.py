"""Sliding-window attention (Mistral) across every attention path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import _xla_attention, attention_core
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.models.cache import decode_attention


def naive_window(q, k, v, window):
    B, S, H, D = q.shape
    logits = jnp.einsum("bshd,bthd->bhst", q, k) * D**-0.5
    qp = np.arange(S)[:, None]
    kp = np.arange(S)[None, :]
    mask = (kp <= qp) & (kp > qp - window)
    logits = jnp.where(jnp.asarray(mask)[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32))


def _qkv(B=1, S=75, H=4, Hkv=4, D=16, seed=0, dtype="float32"):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), dtype)
    return q, k, v


@pytest.mark.parametrize("window", [1, 7, 32, 1000])
def test_xla_window_matches_naive(window):
    q, k, v = _qkv()
    out = _xla_attention(q, k, v, causal=True, window=window)
    ref = naive_window(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("window", [7, 40])
def test_flash_window_matches_naive(window):
    # interpret-mode pallas on CPU; small blocks force multi-block + skips
    q, k, v = _qkv(S=70)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=16, block_k=16)
    ref = naive_window(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-5, rtol=1e-5)


def test_flash_window_gqa_and_grads():
    q, k, v = _qkv(S=48, H=4, Hkv=2)
    window = 13

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window,
                                       block_q=16, block_k=16) ** 2)

    def loss_ref(q, k, v):
        kr = jnp.repeat(k, 2, axis=2)
        vr = jnp.repeat(v, 2, axis=2)
        return jnp.sum(naive_window(q, kr, vr, window).astype(q.dtype) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("window", [20, 33, 70, 96])
def test_flash_window_grads_over_edge_interior_and_dead_blocks(window):
    """Blocks of 32 over 160 tokens: the diagonal and the window's lower edge
    cross some blocks (masked), some lie wholly inside the window (no mask
    is built), the oldest are dead (skipped, nothing fetched); with 20 no
    block is interior.  Float32 at the float32 limits."""
    q, k, v = _qkv(S=160, H=4, Hkv=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window,
                                       block_q=32, block_k=32) ** 2)

    def loss_ref(q, k, v):
        kr = jnp.repeat(k, 2, axis=2)
        vr = jnp.repeat(v, 2, axis=2)
        return jnp.sum(naive_window(q, kr, vr, window).astype(q.dtype) ** 2)

    vf, gf = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    vr, gr = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(vf, vr, rtol=1e-5)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


def test_window_requires_causal():
    q, k, v = _qkv(S=16)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=4)


def test_decode_attention_window():
    """Cached decode with window == full-sequence windowed attention."""
    q, k, v = _qkv(S=30, H=4, Hkv=2)
    window = 9
    kr = jnp.repeat(k, 2, axis=2)
    vr = jnp.repeat(v, 2, axis=2)
    full = naive_window(q, kr, vr, window)
    # decode the last token with the full KV buffer
    out = decode_attention(q[:, -1:], k, v, start_index=29, window=window)
    np.testing.assert_allclose(np.asarray(out[0, 0], np.float32),
                               np.asarray(full[0, -1], np.float32),
                               atol=1e-5, rtol=1e-5)


def test_mistral_training_forward_uses_window():
    """A LlamaModel with sliding_window must differ from the same model
    without it (i.e. the window actually reaches the training path)."""
    from deepspeed_tpu.models import llama
    cfg_w = llama.llama_tiny(dtype="float32", remat=False, sliding_window=8)
    cfg_f = llama.llama_tiny(dtype="float32", remat=False)
    model_w, model_f = llama.LlamaModel(cfg_w), llama.LlamaModel(cfg_f)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, 256, size=(1, 40)).astype(np.int32))
    params = model_f.init(jax.random.PRNGKey(0), ids)["params"]
    lw = model_w.apply({"params": params}, ids)
    lf = model_f.apply({"params": params}, ids)
    # early positions (< window) identical, late positions differ
    np.testing.assert_allclose(np.asarray(lw[:, :8]), np.asarray(lf[:, :8]),
                               atol=1e-5, rtol=1e-5)
    assert np.abs(np.asarray(lw[:, -1]) - np.asarray(lf[:, -1])).max() > 1e-4


def naive_alibi(q, k, v, slopes):
    B, S, H, D = q.shape
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) \
        * D**-0.5
    logits = logits + jnp.asarray(slopes, jnp.float32)[None, :, None, None] \
        * np.arange(S)[None, None, None, :]
    mask = np.tril(np.ones((S, S), bool))
    logits = jnp.where(jnp.asarray(mask)[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32))


def test_flash_alibi_matches_naive():
    """ALiBi in the flash kernel (fwd + grads) vs the naive biased path."""
    from deepspeed_tpu.models.bloom import alibi_slopes
    q, k, v = _qkv(S=44, H=4, Hkv=4)
    slopes = alibi_slopes(4)

    out = flash_attention(q, k, v, causal=True, alibi_slopes=slopes,
                          block_q=16, block_k=16)
    ref = naive_alibi(q, k, v, slopes)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=1e-5, rtol=1e-5)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       alibi_slopes=slopes,
                                       block_q=16, block_k=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(naive_alibi(q, k, v, slopes).astype(q.dtype) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


def test_window_requires_causal_on_dispatch():
    """attention_core validates BEFORE dispatch so the XLA fallback and the
    flash path fail identically (round-2 advisor: the XLA path silently
    computed full bidirectional attention)."""
    from deepspeed_tpu.ops.attention import attention_core
    q, k, v = _qkv(S=16)
    with pytest.raises(ValueError, match="causal"):
        attention_core(q, k, v, causal=False, window=4)
