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

A grid step is one LIVE block: the grid is ``(batch, head, step)`` and the
step's q and K block come from two static tables (``_live_steps``, scalar
prefetch), so a block the causal mask or the window kills costs nothing; the
step builds a mask only where an edge of the mask crosses its block
(``_block_needs_mask``).  docs/kernels.md has what each of these bought.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Blocks of 1024 x 1024 for all three kernels: on a v5e they read 1.7-1.8 x
# the rate of 512 x 512 at the training cells' shapes (S 4096 and 8192, D 128,
# bfloat16), ahead of 512 x 1024 and 1024 x 512 at every one and at S 2048
# (docs/kernels.md has the sweep, tools/flash_block_bench.py makes it): a
# step's fixed costs (its turn, the accumulator's read and write, the
# statistics, a latched K tile's stream) are paid a quarter as often.  Twice
# that wide does not fit the default scoped VMEM.  ``_fit_vmem`` halves them
# beyond D 128.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
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


def _fit_vmem(block, D):
    """``block`` halved until a ``[block, D]`` operand, widened to float32 as
    the kernels widen it, is at most 512 KB: what 1024 rows of D 128 are, the
    widest for which the three kernels compile for a v5e under the default
    scoped VMEM (at D 256, blocks of 512)."""
    while block > 512 and block * D * 4 > 512 * 1024:
        block //= 2
    return block


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
    move per-row scalars from sublanes into lanes for ANY n (n² MACs, an
    n x n identity to build, and the values rounded as the MXU rounds a
    float32 operand at default precision: to bfloat16)."""
    return jax.lax.dot_general(col, _eye(col.shape[0], col.dtype),
                               (((0, ), (0, )), ((), ())),
                               preferred_element_type=jnp.float32)


def _row_to_col(row):
    """(1, n) → (n, 1) via an MXU identity contraction."""
    return jax.lax.dot_general(_eye(row.shape[1], row.dtype), row,
                               (((1, ), (1, )), ((), ())),
                               preferred_element_type=jnp.float32)


def _packed_row(col):
    """(n, 1) → (1, n), EXACT where ``n`` is whole lane tiles: the column
    broadcast over 128 lanes and transposed (the XLU's work, n / 8 vregs), of
    which row 0.  Any other ``n`` (no block a cell runs) takes the identity
    contraction."""
    n = col.shape[0]
    if n % 128:
        return _col_to_row(col)
    return jnp.broadcast_to(col, (n, 128)).T[:1]


def _column_tile(row):
    """(1, n) → (n, 128), the row's values down the sublanes and repeated
    over the lanes (what a ``[n, 128]`` scratch holds of a column): the row
    broadcast over 128 sublanes and transposed, exact; or the identity
    contraction, as above."""
    n = row.shape[1]
    if n % 128:
        return jnp.broadcast_to(_row_to_col(row), (n, 128))
    return jnp.broadcast_to(row, (128, n)).T


def _score_mask(q_start, k_start, causal, sq, sk, block_q, block_k,
                window=0, key_axis=1):
    """Validity mask for one score tile, ``(block_q, block_k)`` or, with the
    keys on axis 0 (``ds_flash_bwd_dkv``'s transposed scores), ``(block_k,
    block_q)``.  ``sq``/``sk`` are the *unpadded* lengths, so the zero-padded
    K tail is always excluded.  ``window`` > 0 additionally limits each query
    to the last ``window`` keys (Mistral sliding window; requires causal)."""
    shape = (block_q, block_k) if key_axis else (block_k, block_q)
    col = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, key_axis)
    mask = col < sk
    if causal:
        row = q_start + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                 1 - key_axis)
        mask = jnp.logical_and(mask, row + (sk - sq) >= col)
        if window:
            mask = jnp.logical_and(mask, col > row + (sk - sq) - window)
    return mask


def _alibi_bias(s, slopes_ref, h, k_start, alibi, key_axis=1):
    """Softmax-invariant ALiBi: + slope_h * absolute key position, the keys
    on ``key_axis`` of ``s``.  ONE definition shared by the forward and both
    backward kernels so the recomputed probabilities can never diverge from
    the forward pass."""
    if not alibi:
        return s
    # (an int32 iota, then the cast: Mosaic has no float32 iota)
    col = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, key_axis)
    return s + slopes_ref[h, 0] * col.astype(jnp.float32)


def _block_live(q_start, k_start, causal, sq, sk, block_q, block_k=None,
                window=0):
    """Whether this K block contributes at all (static-shape early-out).
    With a sliding window, K blocks entirely older than the newest query's
    window are dead — the block-skip that makes window cost O(S·W).
    Starts may be Python ints, numpy arrays or traced scalars."""
    live = k_start < sk
    if causal:
        live = live & (k_start <= q_start + block_q - 1 + (sk - sq))
        if window:
            live = live & (
                k_start + block_k - 1 > q_start + (sk - sq) - window)
    return live


def _block_needs_mask(q_start, k_start, causal, sq, sk, block_q, block_k,
                      window=0):
    """Whether ``_score_mask`` is false anywhere on this block: the padded
    key tail, the diagonal or the window's lower edge crosses it.  Where it
    is not (an INTERIOR block) the mask is all true and the step builds none.
    Read from the same quantities as ``_block_live``, which it refines."""
    edge = k_start + block_k > sk
    if causal:
        # the first row against the last key; the last row against the first
        edge = edge | (q_start + (sk - sq) < k_start + block_k - 1)
        if window:
            edge = edge | (
                k_start <= q_start + block_q - 1 + (sk - sq) - window)
    return edge


def _live_grid(sq, sk, block_q, block_k, causal, window=0):
    """``(live, needs_mask)`` of one head's ``nq x nk`` blocks, as numpy."""
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    where = (np.arange(nq)[:, None] * block_q,
             np.arange(nk)[None, :] * block_k, causal, sq, sk, block_q,
             block_k, window)
    return (np.array(np.broadcast_to(_block_live(*where), (nq, nk))),
            np.broadcast_to(_block_needs_mask(*where), (nq, nk)))


def _live_steps(sq, sk, block_q, block_k, causal, window=0, by_k=False):
    """The steps a head's grid walks: ``(iq, ik)``, the q and the K block
    index (int32 arrays) of every block ``_block_live`` admits, row after
    row: a row is a q block with its K blocks or, ``by_k``, a K block with
    its q blocks (``ds_flash_bwd_dkv``).  Static for a shape, so the kernels
    read them as scalar-prefetch tables through their index maps: a dead
    block is no step at all, it costs neither a fetch nor a turn.  A row with
    no live block keeps ONE step (its mask is all false), so that its outputs
    are written as the zeros they are."""
    live, _ = _live_grid(sq, sk, block_q, block_k, causal, window)
    if by_k:
        live = live.T
    live[~live.any(axis=1), 0] = True
    steps = tuple(x.astype(np.int32) for x in np.nonzero(live))
    return steps[::-1] if by_k else steps


def _row_ends(row_ref, t, n):
    """Whether step ``t`` of ``n`` is the first / the last of its row
    (``row_ref``: the table of the blocks that are the rows)."""
    row = row_ref[t]
    first = (t == 0) | (row_ref[jnp.maximum(t - 1, 0)] != row)
    last = (t == n - 1) | (row_ref[jnp.minimum(t + 1, n - 1)] != row)
    return first, last


def block_counts(sq, sk, block_q, block_k, causal, window=0):
    """``(blocks, live, needs_mask)`` of one head's ``nq x nk`` square: the
    grid walks the ``live`` ones and builds a mask on ``needs_mask`` of them.
    How often each part of a step engages is static for a shape, so it is a
    function and no run-time counter (``tools/flash_block_bench.py`` prints
    it beside each time)."""
    live, edge = _live_grid(sq, sk, block_q, block_k, causal, window)
    return live.size, int(live.sum()), int((live & edge).sum())


def _on_block(body, q_start, k_start, causal, sq, sk, block_q, block_k,
              window):
    """Run ``body(masked)`` on a step's block: with ``_score_mask`` where an
    edge crosses the block, without (no iota, no compare, no select) on an
    interior block, where the mask is all true.  ONE definition for the three
    kernels, as ``_score_mask`` is."""
    edge = _block_needs_mask(q_start, k_start, causal, sq, sk, block_q,
                             block_k, window)
    pl.when(edge)(lambda: body(True))
    pl.when(jnp.logical_not(edge))(lambda: body(False))


# --------------------------------------------------------------------- fwd
def _fwd_kernel(iq_ref, ik_ref, q_ref, k_ref, v_ref, slopes_ref, o_ref,
                lse_ref, acc_ref, m_ref, l_ref, *, scale, causal, sq, sk,
                block_q, block_k, window, alibi):
    ih, t = pl.program_id(1), pl.program_id(2)
    iq, ik = iq_ref[t], ik_ref[t]
    first, last = _row_ends(iq_ref, t, pl.num_programs(2))

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start, k_start = iq * block_q, ik * block_k

    def _compute(masked):
        # float32 operands at default precision: ONE bfloat16 pass of the
        # MXU, which rounds them itself (bfloat16 inputs lose nothing in
        # q·kᵀ; p rounds for p·v as _xla_attention's does).  Handing it
        # bfloat16 read 2 % slower on a v5e: the VPU packs p instead
        q, k, v = (r[0, 0].astype(jnp.float32) for r in (q_ref, k_ref, v_ref))
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _alibi_bias(s, slopes_ref, ih, k_start, alibi)
        if masked:
            mask = _score_mask(q_start, k_start, causal, sq, sk, block_q,
                               block_k, window)
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        if masked:
            # Rows with every position masked (padded Q tail) keep m=-inf;
            # guard the exp so they stay 0 rather than nan.
            m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
            p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
            alpha = jnp.where(m_prev == _NEG_INF, 0.0,
                              jnp.exp(m_prev - m_safe))
        else:
            # every score is finite, so m_new is, and exp(-inf - m_new) is
            # the 0 the guard gives: the same bits with no select
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    _on_block(_compute, q_start, k_start, causal, sq, sk, block_q, block_k,
              window)

    @pl.when(last)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        m = m_ref[:, :1]
        # Dead (fully-masked) rows get a finite -1e30 sentinel, not -inf:
        # where the packing below is the identity contraction it computes
        # sum_i lse[i]·eye[i,j], and (-inf)·0 = NaN would poison every row
        # of the packed block.  The backward needs no special-casing —
        # exp(s − (−1e30)) at the dead rows' masked positions is discarded
        # by the mask's select.
        lse = jnp.where(m == _NEG_INF, _DEAD_ROW_LSE, m + jnp.log(l_safe))
        # lse output is packed [B,H,1,S] (S in lanes, unit sublane dim so the
        # Mosaic block rule "dim -2 divisible by 8 OR equal to the array dim"
        # holds) — no 128-lane inflation
        lse_ref[0, 0] = _packed_row(lse)


def _specs(Hq, Hkv, block_q, block_k, D):
    """BlockSpecs of a grid ``(batch, head, step)`` whose step's q and K
    block come from the ``_live_steps`` tables (scalar prefetch, so an index
    map ends ``..., iq, ik``): q-shaped, K/V-shaped (per KV head: GQA through
    the index map), K-shaped a QUERY head (``ds_flash_bwd_dkv``'s outputs),
    packed row ``[.., 1, block_q]``, slopes."""
    kv_head = lambda h: (h * Hkv) // Hq
    q_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, t, iq, ik: (b, h, iq[t], 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, D),
                           lambda b, h, t, iq, ik: (b, kv_head(h), ik[t], 0))
    dkv_spec = pl.BlockSpec((1, 1, block_k, D),
                            lambda b, h, t, iq, ik: (b, h, ik[t], 0))
    row_spec = pl.BlockSpec((1, 1, 1, block_q),
                            lambda b, h, t, iq, ik: (b, h, 0, iq[t]))
    slopes_spec = pl.BlockSpec((Hq, 1), lambda b, h, t, iq, ik: (0, 0),
                               memory_space=pltpu.SMEM)
    return q_spec, kv_spec, dkv_spec, row_spec, slopes_spec


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd(q, k, v, slopes, causal, scale, block_q, block_k, sq, sk,
         window, alibi):
    """Core on padded [B,H,S,D] inputs; sq/sk are the unpadded lengths."""
    B, Hq, sq_p, D = q.shape
    Hkv = k.shape[1]
    steps = _live_steps(sq, sk, block_q, block_k, causal, window)
    q_spec, kv_spec, _, row_spec, slopes_spec = _specs(Hq, Hkv, block_q,
                                                       block_k, D)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               sq=sq, sk=sk, block_q=block_q,
                               block_k=block_k, window=window,
                               alibi=alibi)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hq, len(steps[0])),
            in_specs=[q_spec, kv_spec, kv_spec, slopes_spec],
            out_specs=[q_spec, row_spec],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, sq_p, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, sq_p), jnp.float32),
        ],
        compiler_params=_SEMANTICS,
        interpret=_interpret(),
        name="ds_flash_fwd",
    )(*steps, q, k, v, slopes)
    return o, lse


# --------------------------------------------------------------------- bwd
def _bwd_dq_kernel(iq_ref, ik_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, slopes_ref, dq_ref, acc_ref, lse_col, delta_col,
                   *, scale, causal, sq, sk, block_q, block_k, window, alibi):
    ih, t = pl.program_id(1), pl.program_id(2)
    iq, ik = iq_ref[t], ik_ref[t]
    first, last = _row_ends(iq_ref, t, pl.num_programs(2))

    @pl.when(first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # packed [1,bq] lanes → [bq,1], ONCE a q block (both depend on it
        # alone), held across its K steps as the forward holds m / l
        lse_col[:] = _column_tile(lse_ref[0, 0])
        delta_col[:] = _column_tile(delta_ref[0, 0])

    q_start, k_start = iq * block_q, ik * block_k

    def _compute(masked):
        q, k, v, do = (r[0, 0].astype(jnp.float32)
                       for r in (q_ref, k_ref, v_ref, do_ref))
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _alibi_bias(s, slopes_ref, ih, k_start, alibi)
        p = jnp.exp(s - lse_col[:, :1])
        if masked:
            # dead rows carry the finite _DEAD_ROW_LSE sentinel; their
            # positions are all masked, so the select discards whatever exp
            # produced
            p = jnp.where(_score_mask(q_start, k_start, causal, sq, sk,
                                      block_q, block_k, window), p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_col[:, :1]) * scale
        acc_ref[:] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    _on_block(_compute, q_start, k_start, causal, sq, sk, block_q, block_k,
              window)

    @pl.when(last)
    def _finish():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(iq_ref, ik_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, slopes_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale, causal, sq, sk, block_q, block_k, window, alibi):
    ih, t = pl.program_id(1), pl.program_id(2)
    ik, iq = ik_ref[t], iq_ref[t]
    first, last = _row_ends(ik_ref, t, pl.num_programs(2))

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start, k_start = iq * block_q, ik * block_k

    def _compute(masked):
        # TRANSPOSED scores, keys on axis 0: sᵀ = k·qᵀ of [block_k, block_q].
        # lse / delta are used as the packed [1, block_q] rows they are (a
        # row broadcasts over sublanes), and dv += pᵀ·do, dk += dsᵀ·q are
        # plain products: no identity contraction, no transposed operand
        q, k, v, do = (r[0, 0].astype(jnp.float32)
                       for r in (q_ref, k_ref, v_ref, do_ref))
        st = jax.lax.dot_general(k, q, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        st = _alibi_bias(st, slopes_ref, ih, k_start, alibi, key_axis=0)
        pt = jnp.exp(st - lse_ref[0, 0])
        if masked:
            # (dead rows: as in ds_flash_bwd_dq)
            pt = jnp.where(_score_mask(q_start, k_start, causal, sq, sk,
                                       block_q, block_k, window, key_axis=0),
                           pt, 0.0)
        dv_acc[:] += jax.lax.dot(pt, do, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, (((1, ), (1, )), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0, 0]) * scale
        dk_acc[:] += jax.lax.dot(dst, q, preferred_element_type=jnp.float32)

    _on_block(_compute, q_start, k_start, causal, sq, sk, block_q, block_k,
              window)

    @pl.when(last)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, slopes, causal, scale, block_q, block_k,
         sq, sk, window, alibi):
    B, Hq, sq_p, D = q.shape
    _, Hkv, sk_p, _ = k.shape
    # Per-row scalars stay packed [B,H,1,S] (S in lanes, unit sublane)
    # instead of hauling 128 duplicated lanes through HBM: ds_flash_bwd_dkv
    # uses the (1, block_q) rows as they lie; ds_flash_bwd_dq unpacks them to
    # (block_q, 1) columns with an MXU identity contraction once a q block.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    static = dict(scale=scale, causal=causal, sq=sq, sk=sk, block_q=block_q,
                  block_k=block_k, window=window, alibi=alibi)
    where = (sq, sk, block_q, block_k, causal, window)

    steps = _live_steps(*where)
    q_spec, kv_spec, dkv_spec, row_spec, slopes_spec = _specs(
        Hq, Hkv, block_q, block_k, D)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hq, len(steps[0])),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                      slopes_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                            pltpu.VMEM((block_q, 128), jnp.float32),
                            pltpu.VMEM((block_q, 128), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_SEMANTICS,
        interpret=_interpret(),
        name="ds_flash_bwd_dq",
    )(*steps, q, k, v, do, lse, delta, slopes)

    # dk/dv are produced per *query* head ([B,Hq,Sk,D]) and group-summed to
    # KV heads afterwards — the GQA head fan-in.  Rows are K blocks here.
    steps = _live_steps(*where, by_k=True)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Hq, len(steps[0])),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                      slopes_spec],
            out_specs=[dkv_spec, dkv_spec],
            scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                            pltpu.VMEM((block_k, D), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, sk_p, D), k.dtype),
            jax.ShapeDtypeStruct((B, Hq, sk_p, D), v.dtype),
        ],
        compiler_params=_SEMANTICS,
        interpret=_interpret(),
        name="ds_flash_bwd_dkv",
    )(*steps, q, k, v, do, lse, delta, slopes)
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
    D_p = -(-D // 128) * 128
    block_q = max(16, min(_fit_vmem(block_q, D_p), sq))
    block_k = max(16, min(_fit_vmem(block_k, D_p), sk))

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
