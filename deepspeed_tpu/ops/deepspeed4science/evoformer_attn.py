"""Evoformer (MSA) attention — TPU rebuild of the DS4Sci kernel.

Reference surface: ``deepspeed/ops/deepspeed4science/evoformer_attn.py``
(``DS4Sci_EvoformerAttention``, CUTLASS fMHA fwd/bwd under
``csrc/deepspeed4science/evoformer_attn/``).  The CUDA kernel's point is
memory: attention over MSA tensors ``[B, N, L, H, D]`` with two additive
biases, without materializing the ``[B, N, H, L, L]`` probability tensor.

TPU design: chunked online attention over query blocks.  Each block computes
its scores against the full key axis in fp32, adds the (sliced) biases,
softmaxes, and contracts with V — so peak memory is ``[.., H, block_q, L]``
instead of ``[.., H, L, L]``.  The block function is wrapped in
``jax.checkpoint`` so the backward pass recomputes probabilities instead of
saving them (the flash-backward trade).  All of it is plain jittable JAX —
XLA tiles the two einsums onto the MXU; a hand-written Pallas kernel adds
nothing here because the shapes are static and the fusion is already total.

Bias semantics match the reference exactly (``evoformer_attn.py:88-106``):

* ``biases[0]`` — mask bias, shape ``[B, N, 1, 1, L]`` (broadcast over heads
  and queries; ``-inf``-style key mask).
* ``biases[1]`` — pair bias, shape ``[B, 1, H, L, L]`` (broadcast over the
  MSA row axis).

Gradient contract: the PAIR bias gradient flows on every path.  The MASK
bias gradient flows only on the chunked-XLA path — the Pallas flash route
(taken on TPU when a full pair bias is present, see ``_flash_bias_route``)
treats the mask as a -inf-style constant and returns a ZERO cotangent for
it, like the reference kernel with ``bias1.requires_grad=False``.  Set
``DS_TPU_EVOFORMER_FLASH=0`` to differentiate a trainable mask bias.
"""

import math
import os

import jax
import jax.numpy as jnp


def _split_q_axis(b, n_blocks, block_q):
    """Reshape a bias's query axis (-2) into blocks, or mark it broadcast.

    Returns ``(blocked, static)`` — exactly one is not None.  ``blocked`` has
    the block axis at the front for scanning: ``[nb, ..., block_q, Lk]``.
    """
    if b.shape[-2] == 1:
        return None, b
    *lead, lq, lk = b.shape
    pad = n_blocks * block_q - lq
    if pad:
        b = jnp.pad(b, [(0, 0)] * len(lead) + [(0, pad), (0, 0)])
    b = b.reshape(*lead, n_blocks, block_q, lk)
    return jnp.moveaxis(b, -3, 0), None


def evoformer_attention(q, k, v, biases=(), softmax_scale=None, block_q=256):
    """Gated-MSA-style attention with additive biases.

    Args:
      q, k, v: ``[*, L, H, D]`` (reference layout — heads after sequence).
      biases: tensors broadcastable against scores ``[*, H, Lq, Lk]``.
      softmax_scale: defaults to ``1/sqrt(D)``.
      block_q: query chunk; chosen so the transient score block
        ``[*, H, block_q, L]`` stays small.  ``L <= block_q`` uses the direct
        unchunked path.

    Returns ``[*, L, H, D]`` in ``q.dtype``.
    """
    *_, L, H, D = q.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    dtype = q.dtype
    qh = jnp.moveaxis(q, -2, -3)  # [*, H, L, D]
    kh = jnp.moveaxis(k, -2, -3)
    vh = jnp.moveaxis(v, -2, -3)

    def blk(qb, bias_list):
        # qb: [*, H, bq, D]; full keys. fp32 scores+softmax, dtype matmuls.
        s = jnp.einsum("...qd,...kd->...qk", qb, kh,
                       preferred_element_type=jnp.float32) * scale
        for b in bias_list:
            s = s + b.astype(jnp.float32)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - jax.lax.stop_gradient(m))
        o = jnp.einsum("...qk,...kd->...qd", p.astype(dtype), vh,
                       preferred_element_type=jnp.float32)
        return (o / jnp.sum(p, axis=-1, keepdims=True)).astype(dtype)

    if L <= block_q:
        out = blk(qh, list(biases))
        return jnp.moveaxis(out, -3, -2)

    n_blocks = -(-L // block_q)
    pad = n_blocks * block_q - L
    qp = jnp.pad(qh, [(0, 0)] * (qh.ndim - 2) + [(0, pad), (0, 0)])
    *lead, _, _ = qp.shape
    q_blocks = jnp.moveaxis(
        qp.reshape(*lead, n_blocks, block_q, D), -3, 0)

    scanned, static = [], []
    for b in biases:
        blocked, stat = _split_q_axis(b, n_blocks, block_q)
        if blocked is not None:
            scanned.append(blocked)
        else:
            static.append(stat)

    @jax.checkpoint
    def one(qb, bs):
        return blk(qb, list(bs) + static)

    out = jax.lax.map(lambda args: one(args[0], args[1]),
                      (q_blocks, tuple(scanned)))
    out = jnp.moveaxis(out, 0, -3)             # [*, H, nb, bq, D]
    out = out.reshape(*lead, n_blocks * block_q, D)[..., :L, :]
    return jnp.moveaxis(out, -3, -2)


def _flash_bias_route(Q, K, V, bs):
    """Route full pair-bias attention through the Pallas bias-operand flash
    kernel (``ops/pallas/flash_bias.py``) — the TPU answer to the
    reference's CUTLASS fMHA-with-bias (``csrc/deepspeed4science/
    evoformer_attn/``): dPair comes out of a dedicated in-kernel reduction
    instead of a materialized [B, N, H, L, L] score-grad tensor.

    Returns None when the route doesn't apply (no pair bias, unexpected
    shapes, or non-TPU backend without the env override).  NOTE: on this
    route the MASK bias gets a zero cotangent (it's a -inf-style constant);
    the chunked-XLA path differentiates it if ever needed.
    Env: DS_TPU_EVOFORMER_FLASH=1 forces on (tests, interpret mode), =0 off.
    """
    flag = os.environ.get("DS_TPU_EVOFORMER_FLASH")
    if flag == "0" or os.environ.get("DS_TPU_DISABLE_PALLAS_ATTN"):
        return None  # same fleet-wide kill switch as attention_core
    if flag != "1":
        from ..pallas._common import interpret_mode
        if interpret_mode():
            return None
    B, N, L, H, D = Q.shape
    mask_bias = pair_bias = None
    for b in bs:
        if b.shape[-2] == 1 and b.shape[-3] == 1 and b.shape[1] == N:
            mask_bias = b                      # [B, N, 1, 1, L]
        elif b.shape[1] == 1 and b.shape[-2] == L and b.shape[2] == H:
            pair_bias = b                      # [B, 1, H, L, L]
        else:
            return None
    if pair_bias is None:
        return None
    from ..pallas.flash_bias import flash_attention_bias
    out = flash_attention_bias(
        Q.reshape(B * N, L, H, D), K.reshape(B * N, L, H, D),
        V.reshape(B * N, L, H, D),
        bias=pair_bias.reshape(B, H, L, L),    # Gb = N batch group
        mask_bias=(None if mask_bias is None
                   else mask_bias.reshape(B * N, 1, 1, L)),
        causal=False)
    return out.reshape(B, N, L, H, D)


def DS4Sci_EvoformerAttention(Q, K, V, biases):
    """Reference-parity entry (``evoformer_attn.py:88 DS4Sci_EvoformerAttention``).

    ``Q/K/V``: ``[B, N, L, H, D]`` MSA tensors; ``biases`` a list of at most
    two: mask bias ``[B, N, 1, 1, L]`` then pair bias ``[B, 1, H, L, L]``
    (either may be None/absent).  With a full pair bias on TPU the call
    runs the Pallas bias-operand flash kernel (dBias in-kernel); otherwise
    the chunked-XLA path.
    """
    assert len(biases) <= 2, "at most two biases (mask, pair)"
    bs = [b for b in biases if b is not None]
    B, N, L = Q.shape[0], Q.shape[1], Q.shape[-3]
    for b in bs:
        assert b.shape[-1] == L and b.ndim == Q.ndim, (
            f"bias shape {b.shape} incompatible with Q {Q.shape}")
    out = _flash_bias_route(Q, K, V, bs)
    if out is not None:
        return out
    return evoformer_attention(Q, K, V, biases=bs)
