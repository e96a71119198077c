"""Layout-skip block-sparse flash attention, Pallas TPU.

The reference implements block-sparse attention as Triton ``sdd``/``dsd``
block matmuls + a block-sparse softmax (``ops/sparse_attention/matmul.py``,
``softmax.py``).  The TPU formulation here streams, for every q block, ONLY
its layout-allowed k/v blocks through VMEM using scalar-prefetched block
indices (the same ``PrefetchScalarGridSpec`` trick as
``paged_attention.py``): the grid's inner dim walks the row's live-block
list, so both FLOPs and HBM traffic are proportional to the layout's
populated blocks — padded to the max row population, never to nk.

vs the XLA gather formulation (``sparse_attention.py``): the gather
materializes a [B, nq, maxk, block, D] copy of the gathered K/V in HBM;
this kernel reads each needed block exactly once per q-row directly from
the original tensors and keeps the online-softmax state in VMEM.

Backward: ``custom_vjp`` whose backward differentiates the (numerically
identical) gather formulation — also nnz-proportional, at the cost of the
transient gather buffers during the backward pass only.

Perf note: kernel tiles equal the LAYOUT block size; layouts built with
block ≥ 64 tile the MXU well (16-wide layouts work but underfill it).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_mode as _interpret
from .flash_attention import _NEG_INF, _pad_to, _score_mask


def _kernel(idx_ref, valid_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
            l_ref, *, scale, causal, block, sq):
    ih, iq, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    nkslots = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(valid_ref[ih, iq, j] == 1)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_start = iq * block
        k_start = idx_ref[ih, iq, j] * block
        mask = _score_mask(q_start, k_start, causal, sq, sq, block, block)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_ref[:] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == nkslots - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


def _fwd(q, k, v, idx, valid, block, causal, scale, sq):
    """q/k/v padded [B, H, S_p, D_p]; idx/valid [H, nq, maxk] int32."""
    B, H, _, D = q.shape  # S is layout-aligned already; only D is padded
    nq, maxk = idx.shape[1], idx.shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nq, maxk),
        in_specs=[
            pl.BlockSpec((1, 1, block, D),
                         lambda b, h, i, j, ix, vd: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block, D),
                         lambda b, h, i, j, ix, vd: (b, h, ix[h, i, j], 0)),
            pl.BlockSpec((1, 1, block, D),
                         lambda b, h, i, j, ix, vd: (b, h, ix[h, i, j], 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block, D),
                               lambda b, h, i, j, ix, vd: (b, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block, D), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
            pltpu.VMEM((block, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, block=block,
                          sq=sq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="ds_block_sparse_fwd",
    )(idx, valid, q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _bs_flash(q, k, v, idx, valid, block, causal, scale, sq, gather_ref):
    return _fwd(q, k, v, idx, valid, block, causal, scale, sq)


def _bs_fwd(q, k, v, idx, valid, block, causal, scale, sq, gather_ref):
    return _fwd(q, k, v, idx, valid, block, causal, scale, sq), (q, k, v)


def _bs_bwd(block, causal, scale, sq, gather_ref, res, do):
    """Backward = AD of the gather formulation (same math, differentiable,
    nnz-proportional); gather buffers exist only during this pass."""
    q, k, v = res
    _, vjp = jax.vjp(gather_ref, q, k, v)
    dq, dk, dv = vjp(do)
    return dq, dk, dv, None, None


_bs_flash.defvjp(_bs_fwd, _bs_bwd)


def block_sparse_flash_attention(q, k, v, layout, block, causal=False,
                                 scale=None):
    """[B, S, H, D] block-sparse attention streaming only the layout's live
    blocks (layout: [H or 1, nq, nk] bool).  Differentiable; numerics match
    ``sparse_attention.sparse_attention`` (the gather formulation) exactly.
    S must be a multiple of ``block`` (sparsity layouts already are)."""
    from ..sparse_attention.sparse_self_attention import (
        layout_gather_tables, sparse_attention)

    B, S, H, D = q.shape
    if S % block:
        raise ValueError(f"S={S} not a multiple of layout block {block}")
    scale_v = scale if scale is not None else D ** -0.5
    layout, idx, valid = layout_gather_tables(layout, H)
    valid = valid.astype("int32")

    qt = _pad_to(q.transpose(0, 2, 1, 3), 3, 128)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 3, 128)
    vt = _pad_to(v.transpose(0, 2, 1, 3), 3, 128)

    def gather_ref(qp, kp, vp):
        """The gather formulation on the padded operands (backward path)."""
        qs = qp.transpose(0, 2, 1, 3)[..., :D]
        ks = kp.transpose(0, 2, 1, 3)[..., :D]
        vs = vp.transpose(0, 2, 1, 3)[..., :D]
        out = sparse_attention(qs, ks, vs, layout, block, causal=causal,
                               scale=scale_v)
        return _pad_to(out.transpose(0, 2, 1, 3), 3, 128)

    o = _bs_flash(qt, kt, vt, jnp.asarray(idx), jnp.asarray(valid), block,
                  bool(causal), scale_v, S, gather_ref)
    return o[..., :D].transpose(0, 2, 1, 3)
