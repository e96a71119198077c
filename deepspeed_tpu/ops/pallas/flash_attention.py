"""Blockwise online-softmax (flash) attention, Pallas TPU.

TPU-native re-design of the reference's attention kernels
(``csrc/transformer/inference/csrc/softmax.cu`` + the FastGen blocked flash,
``inference/v2/kernels/ragged_ops/blocked_flash``): one fused kernel that
streams K/V blocks through VMEM, keeping the running max/sum (online softmax,
the same recurrence FPDT uses at chunk granularity —
``deepspeed/sequence/fpdt_layer.py:58 update_out_and_lse``) in VMEM scratch so
the S×S score matrix never exists in HBM.

Layout: [B, H, S, D] inside the kernel (callers use [B, S, H, D]; the public
wrapper transposes).  Q-heads may be a multiple of KV-heads (GQA/MQA): K/V
blocks are fetched per KV-head via the BlockSpec index map — no materialized
`repeat`, so HBM traffic stays proportional to the KV size.

Backward is the standard two-kernel flash recomputation (dq; dk+dv) behind a
``jax.custom_vjp``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Large tiles: fewer grid steps and better MXU occupancy for ~1.5 MB of
# VMEM at D=128.  An earlier sweep (another jax, no surviving record)
# preferred 512/512; on the installed stack the sweep is not measured.
# Override per-run with DS_TPU_FLASH_BLOCK_Q/K.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = float("-inf")
_DEAD_ROW_LSE = -1e30  # finite lse sentinel for fully-masked rows

# ``jax.ad_checkpoint.checkpoint_name`` of the custom VJP's residuals, in the
# kernel's [B, H, S, D] layout (q / k after the caller's rotary): what a
# ``save_only_these_names`` policy of a rematerialised block may keep.  The
# names are the identity outside a ``jax.checkpoint``, and exist only where
# attention ran as this kernel (docs/kernels.md has the bytes each costs).
RESIDUAL_OUT = "ds_flash_out"
RESIDUAL_LSE = "ds_flash_lse"
RESIDUAL_Q = "ds_flash_q"
RESIDUAL_K = "ds_flash_k"
RESIDUAL_V = "ds_flash_v"
RESIDUAL_NAMES = (RESIDUAL_OUT, RESIDUAL_LSE, RESIDUAL_Q, RESIDUAL_K,
                  RESIDUAL_V)


from ._common import interpret_mode as _interpret


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def _eye(n, dtype):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) ==
            jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)).astype(dtype)


def _col_to_row(col):
    """(n, 1) → (1, n) via an MXU identity contraction — a Mosaic-safe way to
    move per-row scalars from sublanes into lanes (cheap: n² MACs)."""
    return jax.lax.dot_general(col, _eye(col.shape[0], col.dtype),
                               (((0, ), (0, )), ((), ())),
                               preferred_element_type=jnp.float32)


def _row_to_col(row):
    """(1, n) → (n, 1) via an MXU identity contraction."""
    return jax.lax.dot_general(_eye(row.shape[1], row.dtype), row,
                               (((1, ), (1, )), ((), ())),
                               preferred_element_type=jnp.float32)


def _score_mask(q_start, k_start, causal, sq, sk, block_q, block_k,
                window=0):
    """Validity mask for one (block_q, block_k) score tile.  ``sq``/``sk`` are
    the *unpadded* lengths, so the zero-padded K tail is always excluded.
    ``window`` > 0 additionally limits each query to the last ``window`` keys
    (Mistral sliding window; requires causal)."""
    col = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = col < sk
    if causal:
        row = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                 (block_q, block_k), 0)
        mask = jnp.logical_and(mask, row + (sk - sq) >= col)
        if window:
            mask = jnp.logical_and(mask, col > row + (sk - sq) - window)
    return mask


def _alibi_bias(s, slopes_ref, h, k_start, alibi):
    """Softmax-invariant ALiBi: + slope_h * absolute key position.  ONE
    definition shared by the forward and both backward kernels so the
    recomputed probabilities can never diverge from the forward pass."""
    if not alibi:
        return s
    col = k_start + jax.lax.broadcasted_iota(jnp.float32, s.shape, 1)
    return s + slopes_ref[h, 0] * col


def _block_live(q_start, k_start, causal, sq, sk, block_q, block_k=None,
                window=0):
    """Whether this K block contributes at all (static-shape early-out).
    With a sliding window, K blocks entirely older than the newest query's
    window are dead — the block-skip that makes window cost O(S·W)."""
    live = k_start < sk
    if causal:
        live = jnp.logical_and(live,
                               k_start <= q_start + block_q - 1 + (sk - sq))
        if window:
            live = jnp.logical_and(
                live, k_start + block_k - 1 > q_start + (sk - sq) - window)
    return live


# --------------------------------------------------------------------- fwd
def _fwd_kernel(q_ref, k_ref, v_ref, slopes_ref, o_ref, lse_ref, acc_ref,
                m_ref, l_ref, *, scale, causal, sq, sk, block_q, block_k,
                window, alibi):
    ih = pl.program_id(1)
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start, k_start = iq * block_q, ik * block_k

    @pl.when(_block_live(q_start, k_start, causal, sq, sk, block_q,
                         block_k, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _alibi_bias(s, slopes_ref, ih, k_start, alibi)
        mask = _score_mask(q_start, k_start, causal, sq, sk, block_q, block_k,
                           window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Rows with every position masked (padded Q tail) keep m=-inf; guard
        # the exp so they stay 0 rather than nan.
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
        # Dead (fully-masked) rows get a finite -1e30 sentinel, not -inf: the
        # identity contraction below computes sum_i lse[i]·eye[i,j], and
        # (-inf)·0 = NaN would poison every row of the packed block.  The
        # backward needs no special-casing — exp(s − (−1e30)) at the dead
        # rows' masked positions is exp(−inf) = 0.
        lse = jnp.where(m == _NEG_INF, _DEAD_ROW_LSE, m + jnp.log(l_safe))
        # lse output is packed [B,H,1,S] (S in lanes, unit sublane dim so the
        # Mosaic block rule "dim -2 divisible by 8 OR equal to the array dim"
        # holds) — no 128-lane inflation
        lse_ref[0, 0] = _col_to_row(lse)


def _fwd(q, k, v, slopes, causal, scale, block_q, block_k, sq, sk,
         window, alibi):
    """Core on padded [B,H,S,D] inputs; sq/sk are the unpadded lengths."""
    B, Hq, sq_p, D = q.shape
    _, Hkv, sk_p, _ = k.shape
    nq, nk = sq_p // block_q, sk_p // block_k
    kv_head = lambda h: (h * Hkv) // Hq

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               sq=sq, sk=sk, block_q=block_q,
                               block_k=block_k, window=window,
                               alibi=alibi)
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, kv_head(h), j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, kv_head(h), j, 0)),
            pl.BlockSpec((Hq, 1), lambda b, h, i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, sq_p, D), q.dtype),
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
        name="ds_flash_fwd",
    )(q, k, v, slopes)
    return o, lse


# --------------------------------------------------------------------- bwd
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   slopes_ref, dq_ref, acc_ref, *, scale, causal, sq, sk,
                   block_q, block_k, window, alibi):
    ih = pl.program_id(1)
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start, k_start = iq * block_q, ik * block_k

    @pl.when(_block_live(q_start, k_start, causal, sq, sk, block_q,
                         block_k, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = _row_to_col(lse_ref[0, 0])   # packed [1,bq] lanes → [bq,1]
        delta = _row_to_col(delta_ref[0, 0])
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _alibi_bias(s, slopes_ref, ih, k_start, alibi)
        mask = _score_mask(q_start, k_start, causal, sq, sk, block_q, block_k,
                           window)
        # dead rows carry the finite _DEAD_ROW_LSE sentinel; their positions
        # are all masked, so the select discards whatever exp produced
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        acc_ref[:] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    slopes_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                    causal, sq, sk, block_q, block_k, window, alibi):
    ih = pl.program_id(1)
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start, k_start = iq * block_q, ik * block_k

    @pl.when(_block_live(q_start, k_start, causal, sq, sk, block_q,
                         block_k, window))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = _row_to_col(lse_ref[0, 0])   # packed [1,bq] lanes → [bq,1]
        delta = _row_to_col(delta_ref[0, 0])
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _alibi_bias(s, slopes_ref, ih, k_start, alibi)
        mask = _score_mask(q_start, k_start, causal, sq, sk, block_q, block_k,
                           window)
        # dead rows carry the finite _DEAD_ROW_LSE sentinel; their positions
        # are all masked, so the select discards whatever exp produced
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        # dv += pᵀ·do ; ds = p∘(do·vᵀ − delta) ; dk += dsᵀ·q
        dv_acc[:] += jax.lax.dot_general(p, do, (((0, ), (0, )), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_acc[:] += jax.lax.dot_general(ds, q, (((0, ), (0, )), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, slopes, causal, scale, block_q, block_k,
         sq, sk, window, alibi):
    B, Hq, sq_p, D = q.shape
    _, Hkv, sk_p, _ = k.shape
    nq, nk = sq_p // block_q, sk_p // block_k
    kv_head = lambda h: (h * Hkv) // Hq
    # Per-row scalars stay packed [B,H,1,S] (S in lanes, unit sublane) — the
    # kernels unpack a (1, block_q) row to a (block_q, 1) column with an MXU
    # identity contraction instead of hauling 128 duplicated lanes through
    # HBM.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]

    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, sq=sq,
                          sk=sk, block_q=block_q, block_k=block_k,
                          window=window, alibi=alibi),
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, kv_head(h), j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, kv_head(h), j, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i, j: (b, h, 0, i)),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i, j: (b, h, 0, i)),
            pl.BlockSpec((Hq, 1), lambda b, h, i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=semantics,
        interpret=_interpret(),
        name="ds_flash_bwd_dq",
    )(q, k, v, do, lse, delta, slopes)

    # dk/dv are produced per *query* head ([B,Hq,Sk,D]) and group-summed to
    # KV heads afterwards — the GQA head fan-in.
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, sq=sq,
                          sk=sk, block_q=block_q, block_k=block_k,
                          window=window, alibi=alibi),
        grid=(B, Hq, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, kv_head(h), i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, kv_head(h), i, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i, j: (b, h, 0, j)),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, h, i, j: (b, h, 0, j)),
            pl.BlockSpec((Hq, 1), lambda b, h, i, j: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, sk_p, D), k.dtype),
            jax.ShapeDtypeStruct((B, Hq, sk_p, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=semantics,
        interpret=_interpret(),
        name="ds_flash_bwd_dkv",
    )(q, k, v, do, lse, delta, slopes)
    if Hq != Hkv:
        g = Hq // Hkv
        dk = dk.reshape(B, Hkv, g, sk_p, D).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(B, Hkv, g, sk_p, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# ------------------------------------------------------------------ public
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, slopes, causal, scale, block_q, block_k, sq, sk, window,
           alibi):
    o, _ = _fwd(q, k, v, slopes, causal, scale, block_q, block_k, sq, sk,
                window, alibi)
    return o


def _flash_fwd(q, k, v, slopes, causal, scale, block_q, block_k, sq, sk,
               window, alibi):
    q = checkpoint_name(q, RESIDUAL_Q)
    k = checkpoint_name(k, RESIDUAL_K)
    v = checkpoint_name(v, RESIDUAL_V)
    o, lse = _fwd(q, k, v, slopes, causal, scale, block_q, block_k, sq, sk,
                  window, alibi)
    # the named ``o`` is BOTH the output and the residual: a policy that
    # keeps it then keeps the one array the caller's backward reads too
    o = checkpoint_name(o, RESIDUAL_OUT)
    lse = checkpoint_name(lse, RESIDUAL_LSE)
    return o, (q, k, v, slopes, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, sq, sk, window, alibi, res,
               do):
    q, k, v, slopes, o, lse = res
    dq, dk, dv = _bwd(q, k, v, o, lse, do, slopes, causal, scale, block_q,
                      block_k, sq, sk, window, alibi)
    return dq, dk, dv, jnp.zeros_like(slopes)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=True, softmax_scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    window=0, alibi_slopes=None):
    """[B, S, H, D] flash attention with GQA (Hkv | Hq) support.

    Differentiable (custom VJP with flash recomputation).  S and D need not be
    block-aligned; inputs are zero-padded and masked internally.  ``window``
    > 0 restricts each query to the last ``window`` keys (Mistral sliding
    window) with dead K blocks skipped — requires ``causal``.
    """
    B, sq, Hq, D = q.shape
    _, sk, Hkv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"q heads {Hq} not a multiple of kv heads {Hkv}")
    if window and not causal:
        raise ValueError("sliding window requires causal attention")
    scale = float(softmax_scale) if softmax_scale is not None else D**-0.5
    block_q = max(16, min(block_q, sq))
    block_k = max(16, min(block_k, sk))

    qt = _pad_to(_pad_to(q.transpose(0, 2, 1, 3), 2, block_q), 3, 128)
    kt = _pad_to(_pad_to(k.transpose(0, 2, 1, 3), 2, block_k), 3, 128)
    vt = _pad_to(_pad_to(v.transpose(0, 2, 1, 3), 2, block_k), 3, 128)
    alibi = alibi_slopes is not None
    # slopes are positional constants (ALiBi), not trainable parameters —
    # stop_gradient makes that explicit and keeps TPU/XLA paths consistent
    slopes = (jax.lax.stop_gradient(
        jnp.asarray(alibi_slopes, jnp.float32).reshape(Hq, 1))
        if alibi else jnp.zeros((Hq, 1), jnp.float32))
    o = _flash(qt, kt, vt, slopes, bool(causal), scale, block_q, block_k,
               sq, sk, int(window), alibi)
    return o[:, :, :sq, :D].transpose(0, 2, 1, 3)
