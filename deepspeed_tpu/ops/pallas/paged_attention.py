"""Paged (blocked-KV) attention, Pallas TPU — the FastGen blocked-flash
analog (reference ``inference/v2/kernels/ragged_ops/blocked_flash`` +
``linear_blocked_kv_rotary``).

One grid row per ragged-batch token; the token's KV *pages* are streamed
through VMEM in block-table order using scalar-prefetched indices (the
``PrefetchScalarGridSpec`` pattern: the block index map reads the table, so
the pipeline DMAs exactly the pages this token owns), with the online-softmax
state in VMEM scratch.  GQA is expressed in the index math (no repeated KV).

The XLA fallback (``inference/v2/ragged_forward._paged_attention``) computes
the same math by gather; this kernel replaces it on TPU where the gather's
HBM blowup ([T, max_ctx, ...]) matters.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")


from ._common import interpret_mode as _interpret


def paged_attention(q, k_cache, v_cache, tables_t, positions,
                    block_size=None, window=0):
    """q: [T, H, Dh]; caches: [num_blocks, bs, Hkv, Dh];
    tables_t: [T, maxb] int32; positions: [T] int32 → [T, H, Dh].

    One token per grid row — exactly the atom-tiled kernel with atom=1
    (one shared online-softmax implementation; see _atom_kernel)."""
    return paged_attention_atoms(q, k_cache, v_cache, tables_t,
                                 positions, 1, window=window)


# ------------------------------------------------------- atom (prefill) path
def _atom_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref,
                 m_ref, l_ref, *, block_size, scale, groups, atom,
                 window):
    """Like :func:`_kernel` but one grid row covers ``atom`` consecutive
    buffer tokens OF THE SAME SEQUENCE (the batch builder guarantees the
    alignment; intra-atom pad rows produce discarded outputs).  The q tile
    becomes [Hkv, atom*g, Dh], so each kv-head dot has ``atom*g`` MXU rows
    instead of ``g`` — the reference's atom_builder idea
    (``inference/v2/kernels/ragged_ops/atom_builder``) expressed as tiling.
    """
    i, j = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    k_start = j * block_size
    # positions are consecutive within a run; pads carry pos 0, so the last
    # real row's position is the max → block-liveness bound for the tile
    pos_tile = jnp.asarray([pos_ref[i * atom + r] for r in range(atom)],
                           dtype=jnp.int32)            # [atom]
    max_pos = jnp.max(pos_tile)
    live = k_start <= max_pos
    if window:
        # blocks entirely older than the oldest row's window are dead;
        # pad rows carry pos 0, which only loosens the bound (correct)
        live = jnp.logical_and(
            live, k_start + block_size - 1 > jnp.min(pos_tile) - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [atom, H, Dh]
        k = k_ref[0].astype(jnp.float32)               # [bs, Hkv, Dh]
        v = v_ref[0].astype(jnp.float32)
        A, H, Dh = q.shape
        bs, Hkv, _ = k.shape
        # [A, H, Dh] → [Hkv, A*g, Dh]; row order within a kv head: (a, g)
        qg = q.reshape(A, Hkv, groups, Dh).transpose(1, 0, 2, 3) \
              .reshape(Hkv, A * groups, Dh)
        s = jnp.einsum("kmd,bkd->kmb", qg, k,
                       preferred_element_type=jnp.float32) * scale
        col = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        pos_rows = jnp.broadcast_to(pos_tile[:, None],
                                    (A, groups)).reshape(1, A * groups, 1)
        mask = col <= pos_rows
        if window:  # sliding window: only the last `window` positions
            mask = jnp.logical_and(mask, col > pos_rows - window)
        s = jnp.where(mask, s, _NEG_INF)

        M = Hkv * A * groups
        s_f = s.reshape(M, bs)
        m_prev = m_ref[:, :1]                          # [M, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s_f, axis=1, keepdims=True))
        m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.exp(s_f - m_safe)
        p = jnp.where(s_f == _NEG_INF, 0.0, p)
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.einsum("kmb,bkd->kmd", p.reshape(Hkv, A * groups, bs), v,
                        preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(M, Dh)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nb - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = acc_ref[:] / l_safe                      # [Hkv*A*g, Dh]
        _, A, H, Dh = o_ref.shape
        Hkv = H // groups
        out = out.reshape(Hkv, A, groups, Dh).transpose(1, 0, 2, 3) \
                 .reshape(A, H, Dh)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_attention_atoms(q, k_cache, v_cache, tables_t, positions,
                          atom, block_size=None, window=0):
    """Atom-tiled variant for prefill regions: q rows [T, H, Dh] where every
    aligned run of ``atom`` rows shares one sequence (pads allowed).  Page
    streaming uses the FIRST row's block table; per-row position masking
    gives each token its causal view.  T must be a multiple of ``atom``."""
    T, H, Dh = q.shape
    if T % atom:
        raise ValueError(f"token count {T} not a multiple of atom {atom}")
    nb_total, bs, Hkv, _ = k_cache.shape
    maxb = tables_t.shape[1]
    groups = H // Hkv
    scale = Dh**-0.5
    n_atoms = T // atom

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_atoms, maxb),
        in_specs=[
            pl.BlockSpec((1, atom, H, Dh), lambda i, j, tb, ps: (i, 0, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, Dh),
                         lambda i, j, tb, ps: (tb[i * atom, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, Hkv, Dh),
                         lambda i, j, tb, ps: (tb[i * atom, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, atom, H, Dh),
                               lambda i, j, tb, ps: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv * atom * groups, Dh), jnp.float32),
            pltpu.VMEM((Hkv * atom * groups, 128), jnp.float32),
            pltpu.VMEM((Hkv * atom * groups, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_atom_kernel, block_size=bs, scale=scale,
                          groups=groups, atom=atom, window=int(window)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_atoms, atom, H, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="ds_paged_decode" if atom == 1 else "ds_paged_atom",
    )(tables_t, positions, q.reshape(n_atoms, atom, H, Dh),
      k_cache, v_cache).reshape(T, H, Dh)
