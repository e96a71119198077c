"""Flash attention with a trainable additive bias operand (dBias output).

Closes the reference's last kernel family: ``csrc/deepspeed4science/
evoformer_attn/`` (14.9k LoC CUTLASS fMHA) exists precisely because
attention-with-bias *and grad-of-bias* doesn't flash-fuse for free — the
bias gradient is the full score-gradient tensor, which a naive AD
materializes at [B, H, Sq, Sk].

TPU design (three-kernel flash, same recurrence as ``flash_attention.py``):

* forward: online softmax over K blocks with ``s = scale·qkᵀ + bias
  (+ mask_bias)``; bias tiles stream through VMEM like K/V — the score
  tensor never exists in HBM;
* backward dq / dkv: standard flash recomputation with the bias re-added;
* backward **dbias**: a dedicated reduction kernel.  The bias may be
  *broadcast-grouped* over batch and heads (shape ``[Bb, Hb, Sq, Sk]``
  against ``B = Bb·Gb`` kernel batches and ``H = Hb·Gh`` heads — the
  evoformer pair bias is ``[B, 1, H, L, L]`` over an ``N``-row MSA batch,
  i.e. Gb = N).  The group dims are the innermost *arbitrary* grid axes, so
  each bias tile accumulates ``Σ_g ds`` in VMEM scratch across consecutive
  grid steps and is written once — dBias comes out at the bias's own
  (reduced) shape and the [B, H, Sq, Sk] tensor is never materialized.

``mask_bias`` ([B, 1, 1, Sk], e.g. the evoformer MSA key mask) is additive
but NON-differentiable (stop-gradient semantics, like ALiBi slopes): its
cotangent is defined as zero on this path.  Mask biases are -inf-style
validity masks; train a mask through the chunked-XLA path if ever needed.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_mode as _interpret
from .flash_attention import (_DEAD_ROW_LSE, _NEG_INF, _col_to_row, _pad_to,
                              _row_to_col, _score_mask)

# bias tiles add a (block_q, block_k) f32 VMEM resident per kernel — default
# to 256 tiles (0.25 MB each) rather than the biasless kernel's 512.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256


def _load_bias(bias_ref, mask_ref, s, have_mask):
    """s + bias tile (+ mask row, broadcast over the q sublanes)."""
    s = s + bias_ref[0, 0].astype(jnp.float32)
    if have_mask:
        s = s + mask_ref[0, 0].astype(jnp.float32)  # [1, block_k] row
    return s


# --------------------------------------------------------------------- fwd
def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, mask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, sq, sk, block_q,
                block_k, have_mask):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start, k_start = iq * block_q, ik * block_k
    live = (jnp.logical_and(k_start < sk,
                            k_start <= q_start + block_q - 1 + (sk - sq))
            if causal else k_start < sk)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _load_bias(bias_ref, mask_ref, s, have_mask)
        mask = _score_mask(q_start, k_start, causal, sq, sk, block_q, block_k)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        m = m_ref[:, :1]
        lse = jnp.where(m == _NEG_INF, _DEAD_ROW_LSE, m + jnp.log(l_safe))
        lse_ref[0, 0] = _col_to_row(lse)  # packed [.., 1, S]


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    bias_ref, mask_ref, *, scale, causal, sq, sk, block_q,
                    block_k, q_start, k_start, have_mask):
    """Shared bwd recomputation: returns (p, ds_score) for one tile.
    ``ds_score`` is d(loss)/d(score) — multiply by ``scale`` for dq/dk,
    use as-is for dbias."""
    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = _row_to_col(lse_ref[0, 0])
    delta = _row_to_col(delta_ref[0, 0])
    s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = _load_bias(bias_ref, mask_ref, s, have_mask)
    mask = _score_mask(q_start, k_start, causal, sq, sk, block_q, block_k)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, do, p * (dp - delta)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
                   mask_ref, dq_ref, acc_ref, *, scale, causal, sq, sk,
                   block_q, block_k, have_mask):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start, k_start = iq * block_q, ik * block_k
    live = (jnp.logical_and(k_start < sk,
                            k_start <= q_start + block_q - 1 + (sk - sq))
            if causal else k_start < sk)

    @pl.when(live)
    def _compute():
        _, _, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
            mask_ref, scale=scale, causal=causal, sq=sq, sk=sk,
            block_q=block_q, block_k=block_k, q_start=q_start,
            k_start=k_start, have_mask=have_mask)
        k = k_ref[0, 0].astype(jnp.float32)
        acc_ref[:] += jax.lax.dot(ds * scale, k,
                                  preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    bias_ref, mask_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale, causal, sq, sk, block_q, block_k, have_mask):
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start, k_start = iq * block_q, ik * block_k
    live = (jnp.logical_and(k_start < sk,
                            k_start <= q_start + block_q - 1 + (sk - sq))
            if causal else k_start < sk)

    @pl.when(live)
    def _compute():
        p, do, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
            mask_ref, scale=scale, causal=causal, sq=sq, sk=sk,
            block_q=block_q, block_k=block_k, q_start=q_start,
            k_start=k_start, have_mask=have_mask)
        q = q_ref[0, 0].astype(jnp.float32)
        dv_acc[:] += jax.lax.dot_general(p, do, (((0, ), (0, )), ((), ())),
                                         preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(ds * scale, q,
                                         (((0, ), (0, )), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dbias_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      bias_ref, mask_ref, dbias_ref, acc_ref, *, scale,
                      causal, sq, sk, block_q, block_k, gb, gh, have_mask):
    """dBias at the bias's own (broadcast-grouped) resolution: the two
    innermost grid dims walk the (batch, head) group members and accumulate
    ``ds_score`` into VMEM scratch; one write per bias tile."""
    iq, ik = pl.program_id(2), pl.program_id(3)
    igb, igh = pl.program_id(4), pl.program_id(5)

    @pl.when(jnp.logical_and(igb == 0, igh == 0))
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start, k_start = iq * block_q, ik * block_k
    live = (jnp.logical_and(k_start < sk,
                            k_start <= q_start + block_q - 1 + (sk - sq))
            if causal else k_start < sk)

    @pl.when(live)
    def _compute():
        _, _, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
            mask_ref, scale=scale, causal=causal, sq=sq, sk=sk,
            block_q=block_q, block_k=block_k, q_start=q_start,
            k_start=k_start, have_mask=have_mask)
        acc_ref[:] += ds

    @pl.when(jnp.logical_and(igb == gb - 1, igh == gh - 1))
    def _finish():
        dbias_ref[0, 0] = acc_ref[:].astype(dbias_ref.dtype)


# ----------------------------------------------------------------- drivers
def _specs(B, Hq, bias_shape, mask_shape, block_q, block_k, D, order="qk"):
    """BlockSpecs shared by fwd/dq (grid b,h,iq,ik) or dkv (grid b,h,ik,iq).
    The bias index map folds broadcast groups: bias batch bb = b // Gb,
    bias head hb = h // Gh."""
    Bb, Hb = bias_shape[0], bias_shape[1]
    Gb, Gh = B // Bb, Hq // Hb
    if order == "qk":
        qi, ki = (lambda i, j: i), (lambda i, j: j)
    else:
        qi, ki = (lambda i, j: j), (lambda i, j: i)
    qspec = pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, qi(i, j), 0))
    kspec = pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h, ki(i, j), 0))
    bias_spec = pl.BlockSpec(
        (1, 1, block_q, block_k),
        lambda b, h, i, j: (b // Gb, h // Gh, qi(i, j), ki(i, j)))
    Gm = B // mask_shape[0]
    mask_spec = pl.BlockSpec(
        (1, 1, 1, block_k),
        lambda b, h, i, j: (b // Gm, 0, 0, ki(i, j)))
    row_spec = pl.BlockSpec((1, 1, 1, block_q),
                            lambda b, h, i, j: (b, h, 0, qi(i, j)))
    return qspec, kspec, bias_spec, mask_spec, row_spec


def _fwd(q, k, v, bias, mask_bias, causal, scale, block_q, block_k, sq, sk):
    B, Hq, sq_p, D = q.shape
    nq, nk = sq_p // block_q, k.shape[2] // block_k
    have_mask = mask_bias is not None
    mask_op = (mask_bias if have_mask
               else jnp.zeros((1, 1, 1, k.shape[2]), jnp.float32))
    qspec, kspec, bias_spec, mask_spec, row_spec = _specs(
        B, Hq, bias.shape, mask_op.shape, block_q, block_k, D)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, sq=sq,
                          sk=sk, block_q=block_q, block_k=block_k,
                          have_mask=have_mask),
        grid=(B, Hq, nq, nk),
        in_specs=[qspec, kspec, kspec, bias_spec, mask_spec],
        out_specs=[qspec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, sq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="ds_flash_bias_fwd",
    )(q, k, v, bias, mask_op)
    return o, lse


def _bwd(q, k, v, o, lse, do, bias, mask_bias, causal, scale, block_q,
         block_k, sq, sk):
    B, Hq, sq_p, D = q.shape
    sk_p = k.shape[2]
    nq, nk = sq_p // block_q, sk_p // block_k
    have_mask = mask_bias is not None
    mask_op = (mask_bias if have_mask
               else jnp.zeros((1, 1, 1, sk_p), jnp.float32))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    kw = dict(scale=scale, causal=causal, sq=sq, sk=sk, block_q=block_q,
              block_k=block_k, have_mask=have_mask)
    sem4 = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))

    qspec, kspec, bias_spec, mask_spec, row_spec = _specs(
        B, Hq, bias.shape, mask_op.shape, block_q, block_k, D)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(B, Hq, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, row_spec, row_spec, bias_spec,
                  mask_spec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=sem4, interpret=_interpret(),
        name="ds_flash_bias_bwd_dq",
    )(q, k, v, do, lse, delta, bias, mask_op)

    qspec2, kspec2, bias_spec2, mask_spec2, row_spec2 = _specs(
        B, Hq, bias.shape, mask_op.shape, block_q, block_k, D, order="kq")
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        grid=(B, Hq, nk, nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, row_spec2, row_spec2,
                  bias_spec2, mask_spec2],
        out_specs=[kspec2, kspec2],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=sem4, interpret=_interpret(),
        name="ds_flash_bias_bwd_dkv",
    )(q, k, v, do, lse, delta, bias, mask_op)

    # dbias: grid walks bias tiles; the (batch, head) broadcast-group
    # members are the innermost arbitrary dims, accumulated in scratch
    Bb, Hb = bias.shape[0], bias.shape[1]
    Gb, Gh = B // Bb, Hq // Hb
    mask_b = mask_op.shape[0]

    def full(spec_block, imap):
        return pl.BlockSpec(spec_block, imap)

    dbias = pl.pallas_call(
        functools.partial(_bwd_dbias_kernel, **kw, gb=Gb, gh=Gh),
        grid=(Bb, Hb, nq, nk, Gb, Gh),
        in_specs=[
            full((1, 1, block_q, D),
                 lambda b, h, i, j, g, e: (b * Gb + g, h * Gh + e, i, 0)),
            full((1, 1, block_k, D),
                 lambda b, h, i, j, g, e: (b * Gb + g, h * Gh + e, j, 0)),
            full((1, 1, block_k, D),
                 lambda b, h, i, j, g, e: (b * Gb + g, h * Gh + e, j, 0)),
            full((1, 1, block_q, D),
                 lambda b, h, i, j, g, e: (b * Gb + g, h * Gh + e, i, 0)),
            full((1, 1, 1, block_q),
                 lambda b, h, i, j, g, e: (b * Gb + g, h * Gh + e, 0, i)),
            full((1, 1, 1, block_q),
                 lambda b, h, i, j, g, e: (b * Gb + g, h * Gh + e, 0, i)),
            full((1, 1, block_q, block_k),
                 lambda b, h, i, j, g, e: (b, h, i, j)),
            full((1, 1, 1, block_k),
                 lambda b, h, i, j, g, e: ((b * Gb + g) // (B // mask_b),
                                           0, 0, j)),
        ],
        out_specs=full((1, 1, block_q, block_k),
                       lambda b, h, i, j, g, e: (b, h, i, j)),
        out_shape=jax.ShapeDtypeStruct(bias.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, block_k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="ds_flash_bias_bwd_dbias",
    )(q, k, v, do, lse, delta, bias, mask_op)
    return dq, dk, dv, dbias


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_bias(q, k, v, bias, mask_bias, causal, scale, block_q, block_k,
                sq, sk):
    o, _ = _fwd(q, k, v, bias, mask_bias, causal, scale, block_q, block_k,
                sq, sk)
    return o


def _flash_bias_fwd(q, k, v, bias, mask_bias, causal, scale, block_q,
                    block_k, sq, sk):
    o, lse = _fwd(q, k, v, bias, mask_bias, causal, scale, block_q, block_k,
                  sq, sk)
    return o, (q, k, v, bias, mask_bias, o, lse)


def _flash_bias_bwd(causal, scale, block_q, block_k, sq, sk, res, do):
    q, k, v, bias, mask_bias, o, lse = res
    dq, dk, dv, dbias = _bwd(q, k, v, o, lse, do, bias, mask_bias, causal,
                             scale, block_q, block_k, sq, sk)
    dmask = None if mask_bias is None else jnp.zeros_like(mask_bias)
    return dq, dk, dv, dbias.astype(bias.dtype), dmask


_flash_bias.defvjp(_flash_bias_fwd, _flash_bias_bwd)


def flash_attention_bias(q, k, v, bias, mask_bias=None, causal=False,
                         softmax_scale=None, block_q=DEFAULT_BLOCK_Q,
                         block_k=DEFAULT_BLOCK_K):
    """[B, S, H, D] flash attention with a trainable additive bias.

    ``bias``: [Bb, Hb, Sq, Sk] with Bb | B and Hb | H — broadcast groups are
    *contiguous* runs of the batch/head axes (batch index b uses bias row
    b // (B//Bb); fold e.g. an MSA [B, N] batch as B·N with Bb = B).  Its
    gradient comes back at the same [Bb, Hb, Sq, Sk] shape, reduced in-kernel.

    ``mask_bias``: optional additive [Bm, 1, 1, Sk] with Bm | B (key
    validity mask; contiguous grouping b → b // (B//Bm), consistent with
    the bias); NON-differentiable on this path (zero cotangent) — mask
    biases are -inf-style constants.

    Differentiable in q, k, v, bias (custom VJP, flash recomputation).
    """
    B, sq, H, D = q.shape
    _, sk, Hk, _ = k.shape
    if Hk != H:
        raise ValueError("flash_attention_bias: GQA is not supported "
                         f"(q heads {H} != kv heads {Hk})")
    if bias.ndim != 4 or B % bias.shape[0] or H % bias.shape[1]:
        raise ValueError(f"bias shape {bias.shape} must be [Bb, Hb, Sq, Sk] "
                         f"with Bb | {B} and Hb | {H}")
    if bias.shape[2] != sq or bias.shape[3] != sk:
        raise ValueError(f"bias [..., {bias.shape[2]}, {bias.shape[3]}] must "
                         f"carry the full [Sq={sq}, Sk={sk}] score plane")
    scale = float(softmax_scale) if softmax_scale is not None else D**-0.5
    block_q = max(16, min(block_q, sq))
    block_k = max(16, min(block_k, sk))

    qt = _pad_to(_pad_to(q.transpose(0, 2, 1, 3), 2, block_q), 3, 128)
    kt = _pad_to(_pad_to(k.transpose(0, 2, 1, 3), 2, block_k), 3, 128)
    vt = _pad_to(_pad_to(v.transpose(0, 2, 1, 3), 2, block_k), 3, 128)
    bt = _pad_to(_pad_to(bias, 2, block_q), 3, block_k)
    mt = None
    if mask_bias is not None:
        if mask_bias.ndim != 4 or mask_bias.shape[1:3] != (1, 1) or \
                B % mask_bias.shape[0]:
            raise ValueError(f"mask_bias shape {mask_bias.shape} must be "
                             f"[Bm, 1, 1, Sk] with Bm | {B}")
        mt = _pad_to(jax.lax.stop_gradient(
            mask_bias.astype(jnp.float32)), 3, block_k)
    o = _flash_bias(qt, kt, vt, bt, mt, bool(causal), scale, block_q,
                    block_k, sq, sk)
    return o[:, :, :sq, :D].transpose(0, 2, 1, 3)
