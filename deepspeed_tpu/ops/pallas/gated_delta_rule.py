"""``ds_gated_delta_slot``: the gated delta rule's ONE-TOKEN form over a buffer
of one row a slot, the float32 state rows of the cache in place.

    sk = e^g S^T k        sq = e^g S^T q          (both from the OLD state)
    d  = beta (v - sk)    o  = sq + (k . q) d     S <- e^g S + k d^T

(``models/qwen3_next.delta_rule_token`` is the same in plain XLA, and the
yardstick of the tests.)  The form is bound by the state's bytes: 64 KiB a
head read and 64 KiB written against a few hundred vector operations.  As XLA
fusions the state is read TWICE (once for the two sums over the key axis,
once for the update); here a grid step holds ``hb`` heads' tiles of one slot
in VMEM across the sums and the update, so HBM sees one read and one write of
a row, and ``input_output_aliases`` ties the state in to the state out: the
layer's buffer is never copied.

Layout: a head's tile lies ``[128 (key) on the sublanes, 128 (value) on the
lanes]``, so a sum over the key axis is a sum of the tile's 16 vregs and of
the 8 sublanes left, and ``d`` (a row along the lanes) meets every key's row
by a sublane broadcast.  ``k`` and ``q`` have to vary ALONG the sublanes and
be constant along the lanes: the step's ``[heads, 128]`` rows of both are
transposed once on the XLU (``[128, 2 hb]``: a key a sublane, a head a lane)
and a head's column is broadcast along the lanes.  (The two sums as one
product a head on the matrix unit at ``Precision.HIGHEST`` read the same time
at the gate and were not kept.)  float32 throughout: no bfloat16 operand, no
approximated ``exp`` (``e^g`` is XLA's, of ``[slots, Hv]`` values, outside
the kernel and handed to it in SMEM beside ``beta``).

The grid walks the LIVE slots first, in their order (``ids``, scalar
prefetch): a step past the last live slot leaves the state's index maps where
they stand, so a slot that is not ``live`` is neither read nor written (its
row stays bit for bit; its ``o`` is zeros), and a step with few live rows
moves few rows.  A ``fresh`` slot takes zeros for the old state, whatever the
buffer holds.  docs/kernels.md has the gate's table on a v5e.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_mode as _interpret

LANES = 128
#: value heads of a grid step, where they divide the layer's: the widest first
HEAD_BLOCKS = (16, 8)
_VMEM_BYTES = 32 * 1024 * 1024
#: the most (slot, head) pairs of a buffer: ``e^g`` and ``beta`` lie in SMEM
#: whole, a float32 each a pair beside two int32 a slot, and a v5e's SMEM is
#: 1 MiB: Mosaic compiles 2 048 slots of 32 heads (tools/aot_kernel_check.py)
#: and refuses 4 097
SMEM_PAIRS = 2048 * 32


def head_block(state):
    """Heads of a grid step for a state buffer ``[slots, Hv, dk, dv]``, or
    None (the shape stays on XLA): float32 tiles of 128 x 128, a head count
    that a block of :data:`HEAD_BLOCKS` divides, no more than
    :data:`SMEM_PAIRS` (slot, head) pairs."""
    if state.ndim != 4 or state.dtype != jnp.float32 \
            or state.shape[2:] != (LANES, LANES) \
            or state.shape[0] * state.shape[1] > SMEM_PAIRS:
        return None
    return next((hb for hb in HEAD_BLOCKS if state.shape[1] % hb == 0), None)


def _kernel(ids_ref, count_ref, fresh_ref, decay_ref, beta_ref, q_ref, k_ref,
            v_ref, s_ref, o_ref, out_ref, *, hb):
    i, j = pl.program_id(0), pl.program_id(1)
    slot, n_live = ids_ref[i], count_ref[0]

    @pl.when(i >= n_live)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((n_live == 0) & (i == 0) & (j == 0))
    def _no_live_slot():        # the one block the maps stand on goes back
        out_ref[...] = s_ref[...]

    @pl.when(i < n_live)
    def _live():
        fresh = fresh_ref[slot] != 0
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]       # [hb, 128]
        kq = jnp.sum(k * q, axis=1, keepdims=True)            # [hb, 1]
        # a key a sublane, a head a lane: columns 0 .. hb - 1 k's, then q's
        rows = jnp.concatenate(
            [k, q, jnp.zeros((LANES - 2 * hb, LANES), jnp.float32)], axis=0)
        cols = rows.T                                         # [128, 128]
        for h in range(hb):
            at = ((slot * pl.num_programs(1) + j) * hb + h, )
            decay, beta = decay_ref[at], beta_ref[at]
            S = jnp.where(fresh, 0.0, s_ref[0, h])            # [key, value]
            kc = cols[:, h:h + 1]                             # [128, 1]
            qc = cols[:, hb + h:hb + h + 1]
            sk = jnp.sum(S * kc, axis=0, keepdims=True)       # [1, 128]
            sq = jnp.sum(S * qc, axis=0, keepdims=True)
            d = beta * (v[h:h + 1] - decay * sk)
            o_ref[0, 0, h:h + 1, :] = decay * sq + kq[h:h + 1] * d
            out_ref[0, h] = decay * S + kc * d


@functools.partial(jax.jit, static_argnames=("hb", ), donate_argnums=(5, ))
def gated_delta_slot(q, k, v, g, beta, state, live, fresh, *, hb=None):
    """One token of the gated delta rule for every live slot of the buffer.

    q, k, v ``[slots, Hv, 128]`` and g, beta ``[slots, Hv]`` float32; state
    ``[slots, Hv, 128 (key), 128 (value)]`` float32 (donated: a row a
    sequence slot); live, fresh ``[slots]`` bool.  ``hb``: the heads of a
    grid step (:func:`head_block` of the state where None).  Returns ``(o
    [slots, Hv, 128] float32, state)``: a ``fresh`` slot starts from zeros; a
    slot that is not ``live`` keeps its row, and its ``o`` is zeros."""
    slots, heads = state.shape[:2]
    hb = hb or head_block(state)
    steps = heads // hb
    f32 = jnp.float32
    # the live slots first, in their order; the count of them
    ids = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    count = jnp.sum(live, dtype=jnp.int32).reshape(1)
    blocked = lambda a: a.astype(f32).reshape(slots, steps, hb, LANES)
    row = pl.BlockSpec((1, 1, hb, LANES),
                       lambda i, j, ids, *_: (ids[i], j, 0, 0))

    def standing(i, j, ids, count, *_):
        """A live slot's block; past the last one, where the maps stand."""
        last = jnp.maximum(count[0], 1) - 1
        return (ids[jnp.minimum(i, last)],
                jnp.where(i < count[0], j, steps - 1), 0, 0)

    tile = pl.BlockSpec((1, hb, LANES, LANES), standing)
    o, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(slots, steps),
            in_specs=[row, row, row, tile],
            out_specs=[row, tile]),
        out_shape=[jax.ShapeDtypeStruct((slots, steps, hb, LANES), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=_interpret(),
        name="ds_gated_delta_slot",
    )(ids, count, fresh.astype(jnp.int32), jnp.exp(g.astype(f32)).reshape(-1),
      beta.astype(f32).reshape(-1), blocked(q), blocked(k), blocked(v), state)
    return o.reshape(slots, heads, LANES), state
