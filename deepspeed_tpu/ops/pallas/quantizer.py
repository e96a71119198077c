"""Blockwise integer (de)quantization kernels (Pallas TPU).

TPU answer to ``csrc/quantization/{quantize,dequantize,quant_reduce}.cu``:
symmetric per-group int8/int4 quantization used by

  * ZeRO++ qwZ — quantized weight all-gather (``runtime/zero/zeropp``);
  * ZeRO++ qgZ — quantize → all-to-all → dequant-reduce gradient path;
  * weight-only inference quantization (``inference/quantization``).

No swizzle kernel is needed: the reference's ``swizzled_quantize.cu`` exists
to reorder data for NCCL's hierarchical all-to-all; on TPU the hierarchy is
expressed as mesh-axis-factored collectives, so the layout is already right.

Groups are rows of a (num_groups, group_size) view; scales are per-group
absmax/qmax (symmetric, matching the reference's default quantization mode).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


from ._common import interpret_mode as _interpret


def _quant_kernel(x_ref, q_ref, s_ref, *, qmax):
    x = x_ref[:].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / qmax)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    q_ref[:] = q.astype(jnp.int8)
    s_ref[:] = jnp.broadcast_to(scale, s_ref.shape)


def _dequant_kernel(q_ref, s_ref, out_ref):
    out_ref[:] = (q_ref[:].astype(jnp.float32) *
                  s_ref[:, :1]).astype(out_ref.dtype)


def _pick_block(group_size):
    """Row-block sized to keep the VMEM working set ≈1 MiB (power-of-two,
    8..512)."""
    block = 512
    while block > 8 and block * group_size * 4 > (1 << 20):
        block //= 2
    return block


def _group_view(x, group_size, block):
    """Flatten → zero-pad → (groups, group_size), with the group count padded
    to a multiple of ``block`` so the pallas grid covers every row."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    groups = -(-n // group_size)
    groups_pad = groups + (-groups) % 8
    if groups_pad > block:
        groups_pad += (-groups_pad) % block
    flat = jnp.pad(flat, (0, groups_pad * group_size - n))
    return flat.reshape(groups_pad, group_size), n, groups


def quantize_blockwise(x, num_bits=8, group_size=2048, use_pallas=None):
    """Symmetric per-group quantization.

    Returns ``(q_int8, scales_f32, meta)`` where ``meta = (orig_shape,
    orig_dtype, valid_groups)``; int4 values occupy int8 storage (range ±7),
    packing is the transport layer's concern.
    """
    group_size = max(_LANES, group_size - group_size % _LANES)
    qmax = 127.0 if num_bits == 8 else float(2**(num_bits - 1) - 1)
    tiles, n, groups = _group_view(x, group_size, _pick_block(group_size))
    meta = (x.shape, x.dtype, groups)
    if use_pallas is None:
        use_pallas = not _interpret()
    if not use_pallas:
        xf = tiles.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
        scale = jnp.where(absmax == 0.0, 1.0, absmax / qmax)
        q = jnp.clip(jnp.round(xf / scale), -qmax, qmax).astype(jnp.int8)
        return q, scale[:, 0], meta

    rows = tiles.shape[0]
    block = min(_pick_block(group_size), rows)
    spec = pl.BlockSpec((block, group_size), lambda i: (i, 0))
    s_spec = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(rows // block, ),
        in_specs=[spec],
        out_specs=[spec, s_spec],
        out_shape=[
            jax.ShapeDtypeStruct(tiles.shape, jnp.int8),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        ],
        interpret=_interpret(),
        name="ds_quantize_blockwise",
    )(tiles)
    return q, s[:, 0], meta


def dequantize_blockwise(q, scales, meta, use_pallas=None):
    """Inverse of :func:`quantize_blockwise`."""
    shape, dtype, _ = meta
    n = 1
    for d in shape:
        n *= d
    if use_pallas is None:
        use_pallas = not _interpret()
    if not use_pallas:
        out = q.astype(jnp.float32) * scales[:, None]
    else:
        rows, group_size = q.shape
        block = min(_pick_block(group_size), rows)
        spec = pl.BlockSpec((block, group_size), lambda i: (i, 0))
        s_spec = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
        s_l = jnp.broadcast_to(scales[:, None], (rows, _LANES))
        out = pl.pallas_call(
            _dequant_kernel,
            grid=(rows // block, ),
            in_specs=[spec, s_spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
            interpret=_interpret(),
            name="ds_dequantize_blockwise",
        )(q, s_l)
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)
