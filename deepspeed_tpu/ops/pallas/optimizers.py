"""Fused optimizer update kernels (Pallas TPU).

TPU answer to the reference's multi-tensor-apply CUDA optimizers
(``csrc/adam/multi_tensor_adam.cu``, ``csrc/lion/multi_tensor_lion.cu``,
``csrc/lamb/fused_lamb_cuda_kernel.cu``): one elementwise kernel that reads
the fp32 master weight + moments + (bf16) gradient and writes the updated
master, moments, and the re-cast bf16 model weight in a single pass over HBM —
the "interleaved master-weight cast + update" that XLA sometimes splits into
two passes.

Each leaf is processed independently (XLA fuses across leaves at the jit
level; there is no multi-tensor launch-overhead problem on TPU).  Arrays are
flattened and tiled (rows, 128); hyperparameters ride in SMEM.

LAMB is two-phase, like the reference kernel: phase 1 computes the Adam-style
update and per-tensor ‖p‖²,‖u‖² partial sums; the trust ratio is formed on the
host XLA graph; phase 2 applies ``p -= lr·ratio·u``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_BLOCK_ROWS = 512  # 512×128 f32 = 256 KiB per buffer


from ._common import interpret_mode as _interpret


def _to_tiles(x):
    """Flatten → zero-pad → (rows, 128). Returns (tiles, orig_size).

    Rows are padded to a multiple of the grid block so ``rows // block``
    covers the whole array (zero padding is a fixed point of every update
    rule here: g=m=v=0 ⇒ step 0)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows = max(8, -(-n // _LANES))
    rows += (-rows) % 8
    if rows > _BLOCK_ROWS:
        rows += (-rows) % _BLOCK_ROWS
    flat = jnp.pad(flat, (0, rows * _LANES - n))
    return flat.reshape(rows, _LANES), n


def _from_tiles(tiles, n, shape, dtype):
    return tiles.reshape(-1)[:n].reshape(shape).astype(dtype)


def _row_spec(rows):
    block = min(_BLOCK_ROWS, rows)
    return block, pl.BlockSpec((block, _LANES), lambda i: (i, 0))


# ---------------------------------------------------------------- adam
def _adam_kernel(h_ref, g_ref, p_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref,
                 bf_ref, *, adam_w_mode):
    lr, b1, b2, eps, wd, c1, c2 = (h_ref[0, i] for i in range(7))
    g = g_ref[:].astype(jnp.float32)
    p = p_ref[:]
    if not adam_w_mode:
        g = g + wd * p
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    step = (m / c1) / (jnp.sqrt(v / c2) + eps)
    if adam_w_mode:
        step = step + wd * p
    p_new = p - lr * step
    po_ref[:] = p_new
    mo_ref[:] = m
    vo_ref[:] = v
    bf_ref[:] = p_new.astype(bf_ref.dtype)


def fused_adam_step(grad, master, m, v, *, lr, beta1, beta2, eps,
                    weight_decay, count, adam_w_mode=True,
                    bias_correction=True, out_dtype=jnp.bfloat16):
    """One fused Adam(W) update on a single leaf.

    Returns ``(param_out_dtype, master_f32, m_f32, v_f32)``.  ``count`` is the
    1-based step (traced scalar ok).
    """
    gt, n = _to_tiles(grad)
    pt, _ = _to_tiles(master.astype(jnp.float32))
    mt, _ = _to_tiles(m)
    vt, _ = _to_tiles(v)
    rows = gt.shape[0]
    cf = jnp.float32(count)
    c1 = 1.0 - jnp.float32(beta1)**cf if bias_correction else jnp.float32(1)
    c2 = 1.0 - jnp.float32(beta2)**cf if bias_correction else jnp.float32(1)
    hyper = jnp.stack([
        jnp.float32(lr), jnp.float32(beta1), jnp.float32(beta2),
        jnp.float32(eps), jnp.float32(weight_decay), c1, c2
    ]).reshape(1, 7)
    block, spec = _row_spec(rows)
    out = pl.pallas_call(
        functools.partial(_adam_kernel, adam_w_mode=adam_w_mode),
        grid=(rows // block, ),
        in_specs=[
            pl.BlockSpec((1, 7), lambda i: (0, 0), memory_space=pltpu.SMEM),
            spec, spec, spec, spec
        ],
        out_specs=[spec, spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(gt.shape, jnp.float32),
            jax.ShapeDtypeStruct(gt.shape, jnp.float32),
            jax.ShapeDtypeStruct(gt.shape, jnp.float32),
            jax.ShapeDtypeStruct(gt.shape, jnp.dtype(out_dtype)),
        ],
        input_output_aliases={2: 0, 3: 1, 4: 2},
        interpret=_interpret(),
        name="ds_fused_adam",
    )(hyper, gt, pt, mt, vt)
    p_new, m_new, v_new, bf16 = out
    shape = grad.shape
    return (_from_tiles(bf16, n, shape, out_dtype),
            _from_tiles(p_new, n, shape, jnp.float32),
            _from_tiles(m_new, n, shape, jnp.float32),
            _from_tiles(v_new, n, shape, jnp.float32))


# ---------------------------------------------------------------- lion
def _lion_kernel(h_ref, g_ref, p_ref, m_ref, po_ref, mo_ref, bf_ref):
    lr, b1, b2, wd = (h_ref[0, i] for i in range(4))
    g = g_ref[:].astype(jnp.float32)
    p = p_ref[:]
    update = jnp.sign(b1 * m_ref[:] + (1.0 - b1) * g)
    p_new = p - lr * (update + wd * p)
    po_ref[:] = p_new
    mo_ref[:] = b2 * m_ref[:] + (1.0 - b2) * g
    bf_ref[:] = p_new.astype(bf_ref.dtype)


def fused_lion_step(grad, master, m, *, lr, beta1, beta2, weight_decay,
                    out_dtype=jnp.bfloat16):
    """One fused Lion update (reference ``csrc/lion``).  Returns
    ``(param_out_dtype, master_f32, m_f32)``."""
    gt, n = _to_tiles(grad)
    pt, _ = _to_tiles(master.astype(jnp.float32))
    mt, _ = _to_tiles(m)
    rows = gt.shape[0]
    hyper = jnp.stack([
        jnp.float32(lr), jnp.float32(beta1), jnp.float32(beta2),
        jnp.float32(weight_decay)
    ]).reshape(1, 4)
    block, spec = _row_spec(rows)
    p_new, m_new, bf16 = pl.pallas_call(
        _lion_kernel,
        grid=(rows // block, ),
        in_specs=[
            pl.BlockSpec((1, 4), lambda i: (0, 0), memory_space=pltpu.SMEM),
            spec, spec, spec
        ],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(gt.shape, jnp.float32),
            jax.ShapeDtypeStruct(gt.shape, jnp.float32),
            jax.ShapeDtypeStruct(gt.shape, jnp.dtype(out_dtype)),
        ],
        input_output_aliases={2: 0, 3: 1},
        interpret=_interpret(),
        name="ds_fused_lion",
    )(hyper, gt, pt, mt)
    shape = grad.shape
    return (_from_tiles(bf16, n, shape, out_dtype),
            _from_tiles(p_new, n, shape, jnp.float32),
            _from_tiles(m_new, n, shape, jnp.float32))


# ---------------------------------------------------------------- lamb
def _lamb_phase1_kernel(h_ref, g_ref, p_ref, m_ref, v_ref, u_ref, mo_ref,
                        vo_ref, pn_ref, un_ref):
    b1, b2, eps, wd, c1, c2 = (h_ref[0, i] for i in range(6))
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        pn_ref[0, 0] = 0.0
        un_ref[0, 0] = 0.0

    g = g_ref[:].astype(jnp.float32)
    p = p_ref[:]
    m = b1 * m_ref[:] + (1.0 - b1) * g
    v = b2 * v_ref[:] + (1.0 - b2) * g * g
    u = (m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p
    u_ref[:] = u
    mo_ref[:] = m
    vo_ref[:] = v
    pn_ref[0, 0] += jnp.sum(p * p)
    un_ref[0, 0] += jnp.sum(u * u)


def _lamb_phase2_kernel(h_ref, p_ref, u_ref, po_ref, bf_ref):
    scaled_lr = h_ref[0, 0]
    p_new = p_ref[:] - scaled_lr * u_ref[:]
    po_ref[:] = p_new
    bf_ref[:] = p_new.astype(bf_ref.dtype)


def fused_lamb_step(grad, master, m, v, *, lr, beta1, beta2, eps,
                    weight_decay, count, bias_correction=True,
                    max_coeff=10.0, min_coeff=0.01, out_dtype=jnp.bfloat16):
    """One fused LAMB update with per-tensor trust ratio (reference
    ``csrc/lamb/fused_lamb_cuda_kernel.cu``; two-phase like the CUDA kernel's
    reduction + apply structure).  Returns
    ``(param_out_dtype, master_f32, m_f32, v_f32)``."""
    gt, n = _to_tiles(grad)
    pt, _ = _to_tiles(master.astype(jnp.float32))
    mt, _ = _to_tiles(m)
    vt, _ = _to_tiles(v)
    rows = gt.shape[0]
    cf = jnp.float32(count)
    c1 = 1.0 - jnp.float32(beta1)**cf if bias_correction else jnp.float32(1)
    c2 = 1.0 - jnp.float32(beta2)**cf if bias_correction else jnp.float32(1)
    hyper = jnp.stack([
        jnp.float32(beta1), jnp.float32(beta2), jnp.float32(eps),
        jnp.float32(weight_decay), c1, c2
    ]).reshape(1, 6)
    block, spec = _row_spec(rows)
    norm_spec = pl.BlockSpec((1, 1), lambda i: (0, 0),
                             memory_space=pltpu.SMEM)
    u, m_new, v_new, p_sq, u_sq = pl.pallas_call(
        _lamb_phase1_kernel,
        grid=(rows // block, ),
        in_specs=[
            pl.BlockSpec((1, 6), lambda i: (0, 0), memory_space=pltpu.SMEM),
            spec, spec, spec, spec
        ],
        out_specs=[spec, spec, spec, norm_spec, norm_spec],
        out_shape=[
            jax.ShapeDtypeStruct(gt.shape, jnp.float32),
            jax.ShapeDtypeStruct(gt.shape, jnp.float32),
            jax.ShapeDtypeStruct(gt.shape, jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        input_output_aliases={3: 1, 4: 2},
        interpret=_interpret(),
        name="ds_fused_lamb_phase1",
    )(hyper, gt, pt, mt, vt)

    p_norm = jnp.sqrt(p_sq[0, 0])
    u_norm = jnp.sqrt(u_sq[0, 0])
    ratio = jnp.where(
        (p_norm > 0.0) & (u_norm > 0.0),
        jnp.clip(p_norm / u_norm, min_coeff, max_coeff), 1.0)
    scaled = (jnp.float32(lr) * ratio).reshape(1, 1)

    p_new, bf16 = pl.pallas_call(
        _lamb_phase2_kernel,
        grid=(rows // block, ),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            spec, spec
        ],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct(gt.shape, jnp.float32),
            jax.ShapeDtypeStruct(gt.shape, jnp.dtype(out_dtype)),
        ],
        input_output_aliases={1: 0},
        interpret=_interpret(),
        name="ds_fused_lamb_phase2",
    )(scaled, pt, u)
    shape = grad.shape
    return (_from_tiles(bf16, n, shape, out_dtype),
            _from_tiles(p_new, n, shape, jnp.float32),
            _from_tiles(m_new, n, shape, jnp.float32),
            _from_tiles(v_new, n, shape, jnp.float32))
