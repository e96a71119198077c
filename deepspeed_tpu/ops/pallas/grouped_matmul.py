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

XLA's native ``lax.ragged_dot`` serves the same role (and is the default —
``moe_expert_ffn`` keeps it unless ``DS_TPU_MOE_GMM=1``); this kernel exists
so the MoE path has a hand-schedulable alternative to A/B on real hardware
(``tools/kernel_bench`` pattern), exactly how the reference ships a CUTLASS
grouped GEMM next to cuBLAS.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_mode as _interpret


def _gmm_kernel(expert_ref, x_ref, w_ref, y_ref, acc_ref, *, nk):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _pad_layout(group_sizes, T, E, block_m):
    """Vectorized group-padding layout.

    Returns (dest_idx [T], expert_of_tile [Tp_max//block_m], Tp_max) where
    row i of the sorted input lands at padded row dest_idx[i], and tile t of
    the padded buffer belongs to expert expert_of_tile[t].  Tp_max is the
    STATIC bound T_pad = ceil(T/bm)*bm + E*bm (shapes stay static under
    jit; tiles past the live data compute into padding rows that the final
    gather drops)."""
    sizes = group_sizes.astype(jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1, ), jnp.int32),
                              jnp.cumsum(sizes)[:-1]])
    padded = ((sizes + block_m - 1) // block_m) * block_m
    pstarts = jnp.concatenate([jnp.zeros((1, ), jnp.int32),
                               jnp.cumsum(padded)[:-1]])
    rows = jnp.arange(T, dtype=jnp.int32)
    g_of_row = jnp.searchsorted(jnp.cumsum(sizes), rows, side="right"
                                ).astype(jnp.int32)
    dest = pstarts[g_of_row] + (rows - starts[g_of_row])
    tp_max = ((T + block_m - 1) // block_m) * block_m + E * block_m
    tiles = jnp.arange(tp_max // block_m, dtype=jnp.int32)
    pends_tiles = jnp.cumsum(padded) // block_m        # [E]
    expert_of_tile = jnp.minimum(
        jnp.searchsorted(pends_tiles, tiles, side="right"),
        E - 1).astype(jnp.int32)
    return dest, expert_of_tile, tp_max


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def gmm(x, w, group_sizes, *, block_m=128, block_n=128, block_k=128,
        interpret=None):
    """Grouped matmul: ``y[i] = x[i] @ w[g(i)]``.

    x: [T, K] with rows SORTED by group (group g's rows contiguous);
    w: [E, K, N]; group_sizes: [E] summing to T.  Returns [T, N].
    """
    T, K = x.shape
    E, Kw, N = w.shape
    assert K == Kw, (K, Kw)
    if interpret is None:
        interpret = _interpret()
    if K % block_k or N % block_n:
        raise ValueError(f"K={K} / N={N} must divide block_k/{block_k} "
                         f"block_n/{block_n}")
    dest, expert_of_tile, tp = _pad_layout(group_sizes, T, E, block_m)
    xp = jnp.zeros((tp, K), x.dtype).at[dest].set(x)

    nk = K // block_k
    grid = (tp // block_m, N // block_n, nk)
    yp = pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_k),
                             lambda m, n, k, e: (m, k)),
                pl.BlockSpec((1, block_k, block_n),
                             lambda m, n, k, e: (e[m], k, n)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda m, n, k, e: (m, n)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tp, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ds_grouped_matmul",
    )(expert_of_tile, xp, w)
    return yp[dest]
