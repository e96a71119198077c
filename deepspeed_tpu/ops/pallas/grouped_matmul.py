"""Grouped (MoE expert) matmul — Pallas TPU kernel.

TPU answer to the reference's FastGen MoE kernel suite
(``inference/v2/kernels/cutlass_ops/grouped_gemm`` + ``moe_scatter``/
``moe_gather``): tokens sorted by expert multiply that expert's weight
matrix, one MXU-tiled pass over all experts.

Design (megablocks-style, guided by the group-padding trick):

* each group is padded up to a multiple of ``block_m`` INSIDE the call
  (vectorized scatter by destination index), so every row-tile belongs to
  exactly ONE expert — no straddling, no masked accumulation;
* the per-tile expert id is a scalar-prefetch operand: the kernel's
  ``w`` BlockSpec index_map reads ``expert_of_tile[m]`` to page the right
  expert's [block_k, block_n] weight tile into VMEM while the MXU chews the
  previous tile (the same scalar-prefetch pattern as the paged-attention
  kernel);
* grid (m, n, k) with k innermost accumulating into an f32 VMEM scratch.

XLA's native ``lax.ragged_dot`` serves the same role, and is what the expert
layer (``moe/held_experts.py`` ``grouped_matmul``) runs unless its caller asks
for this kernel.  Timed against it on a v5e at a serving step's shapes
(docs/kernels.md has the readings), the kernel with tiles of
256 x 1024 x 1024 is ahead in buffers of up to 2560 rows, and
``cohere2_moe_ragged_step`` asks for it there; it is behind in a buffer of
16 384, and it has NO gradient (no ``custom_vjp``): a forward that may be
differentiated runs neither this kernel nor, where its routing is near even,
``ragged_dot``, but batched dense products over per-expert padded blocks
(``held_experts.padded_swiglu``), and keeps ``ragged_dot`` for the worst
case.  A ``custom_vjp`` was tried for the training step
(``tools/moe_gmm_train_bench.py``: this kernel for the forward and the rows'
gradient, a transposed grouped product for the weights'); ``ragged_dot`` with
its transposes took half the time at that step's shapes and the padded blocks
a fifth, so the kernel stays forward-only.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_mode as _interpret


def _gmm_kernel(expert_ref, live_ref, x_ref, w_ref, y_ref, acc_ref, *, nk):
    """Grid ``(row tile m, column tile n, k)``.  A row tile past the live
    ones (``live_ref[0]``: the tiles that hold a row of some group) computes
    nothing, and its index maps stand still (:func:`gmm`), so it moves
    nothing either: the call's time follows the rows that are there, not the
    static bound of the buffer."""
    m, k = pl.program_id(0), pl.program_id(2)

    @pl.when(m < live_ref[0])
    def _live():
        @pl.when(k == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _flush():
            y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _pad_layout(group_sizes, T, E, block_m):
    """Vectorized group-padding layout.

    Returns (dest_idx [T], expert_of_tile [Tp_max//block_m], live tiles
    [1], Tp_max) where row i of the sorted input lands at padded row
    dest_idx[i], and tile t of the padded buffer belongs to expert
    expert_of_tile[t].  Tp_max is the STATIC bound T_pad = ceil(T/bm)*bm +
    E*bm (shapes stay static under jit; the kernel skips the tiles past the
    live ones, and the final gather drops their rows).  Rows of the input
    past ``sum(group_sizes)`` belong to no group: they land past the live
    tiles and come back as whatever the buffer held."""
    sizes = group_sizes.astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1, ), jnp.int32),
                              jnp.cumsum(sizes)[:-1]])
    padded = ((sizes + block_m - 1) // block_m) * block_m
    pstarts = jnp.concatenate([jnp.zeros((1, ), jnp.int32),
                               jnp.cumsum(padded)[:-1]])
    rows = jnp.arange(T, dtype=jnp.int32)
    g_of_row = jnp.searchsorted(jnp.cumsum(sizes), rows, side="right"
                                ).astype(jnp.int32)
    g_of_row = jnp.minimum(g_of_row, E - 1)
    live_rows = jnp.sum(padded)
    dest = jnp.where(rows < jnp.sum(sizes),
                     pstarts[g_of_row] + (rows - starts[g_of_row]),
                     live_rows + rows - jnp.sum(sizes))
    tp_max = ((T + block_m - 1) // block_m) * block_m + E * block_m
    tiles = jnp.arange(tp_max // block_m, dtype=jnp.int32)
    pends_tiles = jnp.cumsum(padded) // block_m        # [E]
    expert_of_tile = jnp.minimum(
        jnp.searchsorted(pends_tiles, tiles, side="right"),
        E - 1).astype(jnp.int32)
    return dest, expert_of_tile, (live_rows // block_m).reshape(1), tp_max


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def gmm(x, w, group_sizes, *, block_m=128, block_n=128, block_k=128,
        interpret=None):
    """Grouped matmul: ``y[i] = x[i] @ w[g(i)]``.

    x: [T, K] with rows SORTED by group (group g's rows contiguous);
    w: [E, K, N]; group_sizes: [E] summing to at most T.  Returns [T, N];
    a row past ``sum(group_sizes)`` is in no group and its result is
    undefined (mask it, do not multiply it by 0).
    """
    T, K = x.shape
    E, Kw, N = w.shape
    assert K == Kw, (K, Kw)
    if interpret is None:
        interpret = _interpret()
    if K % block_k or N % block_n:
        raise ValueError(f"K={K} / N={N} must divide block_k/{block_k} "
                         f"block_n/{block_n}")
    dest, expert_of_tile, live, tp = _pad_layout(group_sizes, T, E, block_m)
    xp = jnp.zeros((tp, K), x.dtype).at[dest].set(x)

    nk, nn = K // block_k, N // block_n
    grid = (tp // block_m, nn, nk)

    def at(m, n, k, live):
        """The step's tile indices; a step past the live row tiles keeps the
        last live step's, so that nothing is fetched or written for it."""
        on = m < live[0]
        last = jnp.maximum(live[0] - 1, 0)
        return (jnp.where(on, m, last), jnp.where(on, n, nn - 1),
                jnp.where(on, k, nk - 1))

    def x_map(m, n, k, e, live):
        m, _, k = at(m, n, k, live)
        return m, k

    def w_map(m, n, k, e, live):
        m, n, k = at(m, n, k, live)
        return e[m], k, n

    def y_map(m, n, k, e, live):
        m, n, _ = at(m, n, k, live)
        return m, n

    yp = pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[pl.BlockSpec((block_m, block_k), x_map),
                      pl.BlockSpec((1, block_k, block_n), w_map)],
            out_specs=pl.BlockSpec((block_m, block_n), y_map),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tp, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ds_grouped_matmul",
    )(expert_of_tile, live, xp, w)
    return yp[dest]
