"""Expert-parallel MoE engine — quantized all-to-all dispatch over the
collectives engine, plus routed-token accounting on the telemetry spine.

``moe/sharded_moe.py`` keeps the reference-faithful gating math and the
GSPMD constraint dispatch (tokens sharded over ("dp","ep"), the [E, C, D]
dispatch buffer constrained to P("ep") — XLA lowers the reshard to the
dispatch/return all-to-all pair).  This module is the *production* layer on
top of it:

* **one dispatch point** (:func:`dispatch_combine`) the :class:`~deepspeed_tpu
  .moe.layer.MoE` layer routes through.  With the ``moe`` config block absent
  or ``quantized_dispatch: false`` it delegates verbatim to the GSPMD path —
  bit-identical program, the same contract as ``comm_optimizations``;
* **manual-SPMD quantized dispatch** (``moe.quantized_dispatch: true``): the
  dispatch reduce and the return gather run inside ``shard_map`` regions that
  reuse :mod:`deepspeed_tpu.comm.collectives.quantized`'s blockwise codecs —
  int8/int4/fp8/fp6/fp12 payload + f32 scales on the wire instead of the fp
  activations (ZeRO++ qgZ/qwZ applied to expert exchange, arxiv 2306.10209;
  the scalable-collectives recipe of arxiv 2504.18658).  The
  ``comm_optimizations.wire_dtype_by_size`` ladder is honored: the payload
  size picks the rung, ``"fp32"`` rungs keep that band on the identical
  unquantized schedule;
* **hierarchical (ICI-intra / DCN-inter) variants** picked by
  ``topology.factor_group`` like the other collectives: full-precision
  psum-scatter over the intra-node ``ep`` factor, quantized all-to-all over
  the inter-node factor only — one quantization error on the slow hop;
* **manual-context operation**: inside the qgZ manual micro
  (``zeropp.build_manual_dp_micro``) the whole step already runs under
  ``shard_map`` — the dispatcher detects the axis context and issues the
  collectives directly (the GSPMD constraint path would emit an invalid
  nested ``with_sharding_constraint`` there);
* **routed-token accounting**: per-layer drop-fraction, overflow tokens,
  expert-load imbalance (max/mean tokens per expert) and aux loss land on
  the telemetry spine as ``moe/*`` metric families and a ``moe`` section of
  the per-step trace record (:func:`record_routing`; zero overhead while
  telemetry is off).

Gradients: the quantized exchanges are **straight-through** — forward moves
the quantized payload, backward is the exact VJP of the flat (unquantized)
linear exchange, same rule as ``qdq_all_gather_st``.  The expert compute
itself stays outside the manual regions, so expert parameters keep their
``P("ep")`` sharding and ZeRO's ``("dp","ep")`` factorization untouched.
"""

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry as _telemetry
from ..comm.collectives import quantized as Q
from ..comm.collectives.engine import (LADDER_FP, build_wire_ladder,
                                       resolve_in_ladder)
from ..utils import groups
from ..utils.logging import logger

#: wire formats the dispatch accepts: the quantized family plus the flat rung
DISPATCH_WIRES = (LADDER_FP, ) + Q.WIRE_FORMATS


@dataclass
class MoeOptions:
    """Runtime-independent mirror of the ``moe`` config block
    (``runtime/config.py:MoeConfig``) for standalone consumers — benchmarks,
    tools, tests.  The dispatcher is duck-typed: either object works."""
    enabled: bool = False
    # route the dispatch/return exchange through the manual quantized path;
    # False (default) = the GSPMD constraint path, bit-identical to pre-MoE
    quantized_dispatch: bool = False
    # wire format of the quantized exchange ("fp32" = the manual schedule
    # with the raw fp payload — schedule-identical, no codec)
    wire_dtype: str = "int8"
    quantization_group_size: int = Q.DEFAULT_GROUP_SIZE
    # 2-hop dispatch (fp intra-node, quantized inter-node) when
    # topology.factor_group sees a hierarchy on the ep axis
    hierarchical_dispatch: bool = True
    # devices-per-node override for the ep-axis hierarchy split (0 = device
    # metadata / DS_TPU_INTRA_NODE_SIZE, like the collectives engine)
    intra_node_size: int = 0
    # base seed folded (per step, per layer) into the noisy-gate rngs the
    # runtime engine threads through flax apply; None = the config "seed"
    gating_seed: int = None


# --------------------------------------------------------------- module state
_active = None       # MoeOptions / MoeConfig duck-typed, or None (disabled)
_comm_opts = None    # comm_optimizations view (wire ladder + intra override)
_ladder = None       # normalized wire_dtype_by_size rungs
_meta_emitted = set()


def configure(moe_opts, comm_opts=None):
    """Install the active ``moe`` options (the runtime engine calls this at
    bring-up; ``None``/disabled resets to the flat GSPMD path).  The
    ``comm_optimizations`` view supplies the ``wire_dtype_by_size`` ladder
    and the ``intra_node_size`` fallback."""
    global _active, _comm_opts, _ladder
    active = moe_opts if (moe_opts is not None
                          and getattr(moe_opts, "enabled", False)) else None
    # validate BEFORE mutating the module state: a rejected configure must
    # leave the previously-installed dispatcher untouched (callers restore
    # in a finally that never runs if this raises)
    ladder = None
    if active is not None:
        wire = getattr(active, "wire_dtype", "int8")
        if wire not in DISPATCH_WIRES:
            raise ValueError(
                f"moe.wire_dtype {wire!r} unknown "
                f"(have {', '.join(DISPATCH_WIRES)})")
        if comm_opts is not None and getattr(comm_opts, "enabled", False):
            ladder = build_wire_ladder(
                getattr(comm_opts, "wire_dtype_by_size", None))
    _active = active
    _comm_opts = comm_opts
    _ladder = ladder
    _meta_emitted.clear()
    return _active


def reset():
    configure(None)


def active_options():
    return _active


def snapshot():
    """The full dispatcher state as an opaque pair — hand it back to
    :func:`restore` to reinstall options AND the comm view (a bare
    ``configure(active_options())`` would drop the wire ladder)."""
    return (_active, _comm_opts)


def restore(state):
    opts, comm_opts = state
    return configure(opts, comm_opts=comm_opts)


def dispatch_wire(nbytes, opts=None):
    """Wire format for an expert-dispatch payload of ``nbytes`` logical
    bytes: the ``comm_optimizations.wire_dtype_by_size`` ladder rung when a
    ladder is installed (the autotuner's per-size choice applies to the
    hardest collective too), else ``moe.wire_dtype``.  ``"fp32"`` = the
    manual schedule with the raw fp payload."""
    opts = opts if opts is not None else _active
    default = getattr(opts, "wire_dtype", "int8") if opts is not None \
        else LADDER_FP
    return resolve_in_ladder(_ladder, nbytes, default)


def _intra_override(opts):
    if opts is not None and getattr(opts, "intra_node_size", 0):
        return int(opts.intra_node_size)
    if _comm_opts is not None:
        return int(getattr(_comm_opts, "intra_node_size", 0) or 0)
    return 0


def ep_hierarchy(mesh, opts=None, ep_axis=groups.EP_AXIS):
    """The (inter, intra) factorization of the expert-parallel axis, or
    None — the same ``topology.factor_group`` pick the other collectives
    dispatch on."""
    opts = opts if opts is not None else _active
    if opts is not None and not getattr(opts, "hierarchical_dispatch", True):
        return None
    if mesh.shape.get(ep_axis, 1) <= 1:
        return None
    from ..comm.backend import ProcessGroup
    from ..comm.collectives.topology import factor_group
    return factor_group(ProcessGroup(mesh, (ep_axis, )),
                        intra_node_size=_intra_override(opts))


def expert_dispatch_wire_bytes(n_elements, wire, group_size, n_inner=1):
    """Transported bytes of one dispatch (or return) exchange on the
    bottleneck (inter-node) link: quantized payload + scales on 1/n_inner
    of the data under the hierarchical variant; the logical fp bytes for
    the flat rung."""
    n = int(n_elements) // max(1, int(n_inner))
    if wire == LADDER_FP:
        return n * 4
    return Q.quantized_wire_bytes(n, wire, group_size)


# --------------------------------------------------- straight-through comms
# The quantized exchanges are linear maps in the flat limit; backward is the
# EXACT VJP of that flat map (all_gather ↔ sum-scatter), so quantization
# rounding never zeroes the gradient — the qdq_all_gather_st rule applied to
# expert dispatch.

@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _exchange_st(pdisp, sum_axes, ep_axes, n_ep, wire, gs):
    """Inside-shard_map dispatch reduce: fp psum over the non-expert token
    axes, then (quantized) all-to-all reduce over the ep axes — rank e ends
    with expert chunk e of the globally-summed [E, C, D] buffer."""
    r = pdisp
    if sum_axes:
        r = jax.lax.psum(r, sum_axes)
    if n_ep > 1:
        r = Q.all_to_all_quant_reduce(r, ep_axes, 0, n_ep, wire_format=wire,
                                      group_size=gs, mean=False)
    # the reduce primitive accumulates in f32; hand the expert compute its
    # own dtype back (bf16 models must not silently widen the [E, C, D]
    # buffer — 2x memory and a different numeric path than the flat einsum)
    return r.astype(pdisp.dtype)


def _exchange_st_fwd(pdisp, sum_axes, ep_axes, n_ep, wire, gs):
    return _exchange_st(pdisp, sum_axes, ep_axes, n_ep, wire, gs), None


def _exchange_st_bwd(sum_axes, ep_axes, n_ep, wire, gs, _, dy):
    g = dy
    if n_ep > 1:
        g = jax.lax.all_gather(g, ep_axes, axis=0, tiled=True)
    if sum_axes:
        g = jax.lax.psum(g, sum_axes)
    return (g, )


_exchange_st.defvjp(_exchange_st_fwd, _exchange_st_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _collect_st(local, ep_axes, n_ep, wire, gs):
    """Inside-shard_map return gather: (quantized) all-gather of the local
    expert outputs back to the full [E, C, D] buffer on every rank."""
    if n_ep <= 1:
        return local
    return Q.quantized_all_gather(local, ep_axes, 0, wire,
                                  gs).astype(local.dtype)


def _collect_st_fwd(local, ep_axes, n_ep, wire, gs):
    return _collect_st(local, ep_axes, n_ep, wire, gs), None


def _collect_st_bwd(ep_axes, n_ep, wire, gs, _, dy):
    if n_ep <= 1:
        return (dy, )
    return (jax.lax.psum_scatter(dy, ep_axes, scatter_dimension=0,
                                 tiled=True), )


_collect_st.defvjp(_collect_st_fwd, _collect_st_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _dispatch_a2a_st(pdisp, ep_axes, n_ep, wire, gs):
    """Manual-context dispatch exchange (reference ``_AllToAll``): split
    the expert dim across the ep group, concatenate each peer's capacity
    block along the slot dim — [E, C, D] → [E/ep, ep·C, D].  A permutation,
    never a sum: per-rank capacity blocks survive verbatim."""
    return Q.quantized_all_to_all(pdisp, ep_axes, 0, 1, n_ep,
                                  wire_format=wire, group_size=gs)


def _dispatch_a2a_st_fwd(pdisp, ep_axes, n_ep, wire, gs):
    return _dispatch_a2a_st(pdisp, ep_axes, n_ep, wire, gs), None


def _dispatch_a2a_st_bwd(ep_axes, n_ep, wire, gs, _, dy):
    # the exchange is a cross-rank permutation; its exact transpose is the
    # inverse all-to-all in full precision (straight-through)
    return (jax.lax.all_to_all(dy, ep_axes, split_axis=1, concat_axis=0,
                               tiled=True), )


_dispatch_a2a_st.defvjp(_dispatch_a2a_st_fwd, _dispatch_a2a_st_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _return_a2a_st(out, ep_axes, n_ep, wire, gs):
    """Manual-context return exchange: the inverse of
    :func:`_dispatch_a2a_st` — [E/ep, ep·C, D] → [E, C, D]."""
    return Q.quantized_all_to_all(out, ep_axes, 1, 0, n_ep,
                                  wire_format=wire, group_size=gs)


def _return_a2a_st_fwd(out, ep_axes, n_ep, wire, gs):
    return _return_a2a_st(out, ep_axes, n_ep, wire, gs), None


def _return_a2a_st_bwd(ep_axes, n_ep, wire, gs, _, dy):
    return (jax.lax.all_to_all(dy, ep_axes, split_axis=0, concat_axis=1,
                               tiled=True), )


_return_a2a_st.defvjp(_return_a2a_st_fwd, _return_a2a_st_bwd)


# ------------------------------------------------------ hierarchical helpers
def _hier_permute(x, n_out, n_in):
    """Pre-permute the E dim so the inner-major tiling the 2-hop
    reduce-scatter produces lands each expert chunk on its outer-major
    ``P("ep")`` rank: viewed as [n_out, n_in, eloc], swap the factors.
    Pure local reshape — no communication."""
    E = x.shape[0]
    eloc = E // (n_out * n_in)
    return x.reshape((n_out, n_in, eloc) + x.shape[1:]).swapaxes(0, 1) \
        .reshape(x.shape)


def _hier_unpermute_gathered(full, n_out, n_in):
    """Reassemble the 2-hop gather (inner gather outermost) into the
    canonical outer-major E order.  Pure local reshape."""
    E = full.shape[0]
    eloc = E // (n_out * n_in)
    return full.reshape((n_in, n_out, eloc) + full.shape[1:]) \
        .swapaxes(0, 1).reshape(full.shape)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6, 7))
def _hier_exchange_st(pdisp, sum_axes, out_ax, in_ax, n_out, n_in, wire, gs):
    """2-hop dispatch reduce: fp psum over the token axes, fp psum-scatter
    over the intra-node ep factor (ICI, full data), quantized all-to-all
    over the inter-node factor (DCN, 1/n_in of the data).  The pre-permute
    makes the result tile outer-major, i.e. exactly ``P((out, in))`` on the
    split mesh = ``P("ep")`` placement on the original device order."""
    r = pdisp
    if sum_axes:
        r = jax.lax.psum(r, sum_axes)
    r = _hier_permute(r, n_out, n_in)
    r = Q.hierarchical_quant_reduce_scatter(
        r, (in_ax, ), (out_ax, ), 0, n_in, n_out, wire_format=wire,
        group_size=gs, mean=False)
    return r.astype(pdisp.dtype)  # see _exchange_st: no silent widening


def _hier_exchange_st_fwd(pdisp, sum_axes, out_ax, in_ax, n_out, n_in, wire,
                          gs):
    return _hier_exchange_st(pdisp, sum_axes, out_ax, in_ax, n_out, n_in,
                             wire, gs), None


def _hier_exchange_st_bwd(sum_axes, out_ax, in_ax, n_out, n_in, wire, gs, _,
                          dy):
    # exact flat VJP: reassemble the full cotangent on every rank.  The
    # gather over (out, in) in axis-index order is outer-major = the
    # canonical chunk order, so no unpermute is needed.
    g = jax.lax.all_gather(dy, (out_ax, in_ax), axis=0, tiled=True)
    if sum_axes:
        g = jax.lax.psum(g, sum_axes)
    return (g, )


_hier_exchange_st.defvjp(_hier_exchange_st_fwd, _hier_exchange_st_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _hier_collect_st(local, out_ax, in_ax, n_out, n_in, wire, gs):
    """2-hop return gather: quantized all-gather over the inter-node factor
    (DCN, the small local block), fp all-gather over the intra-node factor
    (ICI), then a local reorder back to canonical expert order."""
    inter = Q.quantized_all_gather(local, (out_ax, ), 0, wire, gs)
    full = jax.lax.all_gather(inter, in_ax, axis=0, tiled=True)
    return _hier_unpermute_gathered(full, n_out, n_in).astype(local.dtype)


def _hier_collect_st_fwd(local, out_ax, in_ax, n_out, n_in, wire, gs):
    return _hier_collect_st(local, out_ax, in_ax, n_out, n_in, wire, gs), None


def _hier_collect_st_bwd(out_ax, in_ax, n_out, n_in, wire, gs, _, dy):
    # exact flat VJP of "gather my chunk to everyone": each rank keeps the
    # sum of all ranks' cotangent slices of its own (outer-major) chunk
    return (jax.lax.psum_scatter(dy, (out_ax, in_ax), scatter_dimension=0,
                                 tiled=True), )


_hier_collect_st.defvjp(_hier_collect_st_fwd, _hier_collect_st_bwd)


# ----------------------------------------------------------- manual regions
def _token_axes(mesh):
    """Mesh axes sharding the token dim of engine batches (dp_axes order,
    restricted to axes the mesh actually has — a guard for non-groups
    meshes, whose specs would otherwise name unknown axes)."""
    return tuple(a for a in groups.dp_axes() if a in mesh.shape)


def resolve_exchange(mesh, opts, ep_axis, payload_elems):
    """(wire, group_size, hierarchy-or-None, wire_bytes) for one dispatch
    exchange of ``payload_elems`` fp32 elements — what the dispatcher
    will put on the wire."""
    gs = int(getattr(opts, "quantization_group_size", Q.DEFAULT_GROUP_SIZE))
    wire = dispatch_wire(payload_elems * 4, opts)
    h = None
    if wire != LADDER_FP:
        h = ep_hierarchy(mesh, opts, ep_axis)
        if h is not None and (len(h.outer_axes) != 1
                              or len(h.inner_axes) != 1):
            h = None  # only the single-axis split shape is implemented
        if h is not None and payload_elems % (h.outer_size * h.inner_size):
            h = None
    n_inner = h.inner_size if h is not None else 1
    return wire, gs, h, expert_dispatch_wire_bytes(payload_elems, wire, gs,
                                                   n_inner)


def _emit_dispatch_meta(variant, wire, wire_bytes, E, C, D, ep):
    if not _telemetry.enabled:
        return
    key = (variant, wire, E, C, D, ep)
    if key in _meta_emitted:
        return
    _meta_emitted.add(key)
    _telemetry.metadata("moe_dispatch", {
        "variant": variant, "wire_dtype": wire,
        "wire_bytes_per_exchange": int(wire_bytes),
        "experts": int(E), "capacity": int(C), "hidden": int(D),
        "ep": int(ep)})


def _manual_dispatch_combine(x, combine, dispatch, expert_fn, opts, mesh,
                             ep_axis):
    """Expert dispatch inside an ALREADY-manual region (the qgZ micro's
    shard_map body): tokens/masks are local shards, expert params are local
    ``P("ep")`` shards — issue the collectives directly (the GSPMD
    constraint path cannot run here: a nested ``with_sharding_constraint``
    inside a manual region is invalid).

    Reference semantics (``MOELayer.forward`` + ``_AllToAll``): gating and
    capacity are PER-RANK, the a2a exchanges each rank's capacity block —
    the expert buffer becomes [E/ep, ep·C, D], a concatenation, never a
    sum (summing distinct ranks' buffers would collide their slots).
    Tokens never cross the expert-data-parallel ("dp") rows: those rows
    run the same experts on different data, and the per-leaf ZeRO
    reduction (``reduce_leaf``) averages their expert grads."""
    st = groups.get_mesh_state()
    ep = st.ep
    dmask = jax.lax.stop_gradient(dispatch.astype(x.dtype))
    pdisp = jnp.einsum("tec,td->ecd", dmask, x)
    E = pdisp.shape[0]
    if ep > 1 and E % ep:
        raise ValueError(
            f"num_experts={E} must be divisible by ep={ep} "
            "(expert stacks shard their leading dim over the ep axis)")
    if opts is not None and getattr(opts, "quantized_dispatch", False):
        # ladder rung from the LOGICAL payload: pdisp here is a per-shard
        # [E, C_local, D] buffer, but the ladder (and the autotuner probes
        # that emitted it) key on the global message size — the same
        # convention as zeropp's per-leaf ladder resolution.  The global
        # capacity scales linearly with the token-group degree.
        n_tok = int(np.prod([mesh.shape.get(a, 1)
                             for a in _token_axes(mesh)]))
        wire = dispatch_wire(pdisp.size * n_tok * 4, opts)
    else:
        wire = LADDER_FP  # flat payload, same exchange schedule
    gs = int(getattr(opts, "quantization_group_size", Q.DEFAULT_GROUP_SIZE)
             if opts is not None else Q.DEFAULT_GROUP_SIZE)
    # hierarchy needs a reshaped mesh — not expressible inside an
    # already-manual region, so the manual-context path is always 1-hop
    if ep > 1:
        local = _dispatch_a2a_st(pdisp, (ep_axis, ), ep, wire, gs)
    else:
        local = pdisp
    out = expert_fn(local)
    if ep > 1:
        full = _return_a2a_st(out, (ep_axis, ), ep, wire, gs)
    else:
        full = out
    return jnp.einsum("tec,ecd->td", combine.astype(x.dtype), full)


def _quantized_dispatch_combine(x, combine, dispatch, expert_fn, opts, mesh,
                                ep_axis):
    """The manual-SPMD expert-dispatch path under a GSPMD program: two
    ``shard_map`` regions (dispatch reduce / return gather) around the
    untouched expert compute, each wrapped in a straight-through
    ``custom_vjp`` whose backward is the exact flat VJP expressed as plain
    GSPMD einsums (XLA inserts the fp backward collectives — the same
    wire the flat path's AD uses)."""
    ep = mesh.shape[ep_axis]
    E = combine.shape[1]
    if E % ep:
        raise ValueError(
            f"num_experts={E} must be divisible by ep={ep} "
            "(expert stacks shard their leading dim over the ep axis)")
    T = x.shape[0]
    C, D = combine.shape[2], x.shape[1]
    token_axes = _token_axes(mesh)
    n_tok = int(np.prod([mesh.shape[a] for a in token_axes]))
    if T % n_tok:
        logger.warning(
            "moe.quantized_dispatch: token count %d not divisible by the "
            "token mesh degree %d — falling back to the GSPMD constraint "
            "path for this call", T, n_tok)
        from .sharded_moe import dispatch_combine as _flat
        return _flat(x, combine, dispatch, expert_fn, ep_axis=ep_axis,
                     mesh=mesh)
    payload = E * C * D
    wire, gs, h, wire_bytes = resolve_exchange(mesh, opts, ep_axis, payload)
    sum_axes = tuple(a for a in token_axes if a != ep_axis
                     and mesh.shape.get(a, 1) > 1)
    dmask = jax.lax.stop_gradient(dispatch.astype(x.dtype))
    cmask = combine.astype(x.dtype)

    if h is not None:
        smesh = h.mesh
        out_ax, in_ax = h.outer_axes[0], h.inner_axes[0]
        n_out, n_in = h.outer_size, h.inner_size
        ep_entry = (out_ax, in_ax)
        # the split mesh spells the ep factor (ep_out, ep_in); same device
        # order, so the token tiling is unchanged
        token_entry = tuple(a for a in token_axes if a != ep_axis) \
            + (out_ax, in_ax)
        variant = f"hier_q_{wire}"

        def _disp_body(tok, dm):
            pdisp = jnp.einsum("tec,td->ecd", dm, tok)
            return _hier_exchange_st(pdisp, sum_axes, out_ax, in_ax, n_out,
                                     n_in, wire, gs)

        def _ret_body(loc, cm):
            full = _hier_collect_st(loc, out_ax, in_ax, n_out, n_in, wire,
                                    gs)
            return jnp.einsum("tec,ecd->td", cm, full)
    else:
        smesh = mesh
        ep_entry = ep_axis
        token_entry = tuple(token_axes)
        variant = f"q_{wire}" if wire != LADDER_FP else "manual_fp"

        def _disp_body(tok, dm):
            pdisp = jnp.einsum("tec,td->ecd", dm, tok)
            return _exchange_st(pdisp, sum_axes, (ep_axis, ), ep, wire, gs)

        def _ret_body(loc, cm):
            full = _collect_st(loc, (ep_axis, ), ep, wire, gs)
            return jnp.einsum("tec,ecd->td", cm, full)

    ecd_spec = P(ep_entry, None, None)
    tok_entry = token_entry if len(token_entry) > 1 else token_entry[0]
    tok_spec = P(tok_entry, None)
    tok3_spec = P(tok_entry, None, None)

    def _sm(body, in_specs, out_specs):
        return jax.shard_map(body, mesh=smesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    @jax.custom_vjp
    def _dispatch_region(tok, dm):
        return _sm(_disp_body, (tok_spec, tok3_spec), ecd_spec)(tok, dm)

    def _dispatch_fwd(tok, dm):
        return _dispatch_region(tok, dm), dm

    def _dispatch_bwd(dm, dy):
        # exact flat VJP under GSPMD: XLA gathers dy over ep in fp for the
        # token-side contraction; the mask is a stop_gradient input
        return jnp.einsum("tec,ecd->td", dm, dy), jnp.zeros_like(dm)

    _dispatch_region.defvjp(_dispatch_fwd, _dispatch_bwd)

    @jax.custom_vjp
    def _combine_region(loc, cm):
        return _sm(_ret_body, (ecd_spec, tok3_spec), tok_spec)(loc, cm)

    def _combine_fwd(loc, cm):
        return _combine_region(loc, cm), (loc, cm)

    def _combine_bwd(res, dy):
        loc, cm = res
        dloc = jnp.einsum("tec,td->ecd", cm, dy)
        dloc = jax.lax.with_sharding_constraint(
            dloc, NamedSharding(mesh, P(ep_axis, None, None)))
        dcm = jnp.einsum("td,ecd->tec", dy, loc)
        return dloc, dcm

    _combine_region.defvjp(_combine_fwd, _combine_bwd)

    _emit_dispatch_meta(variant, wire, wire_bytes, E, C, D, ep)
    local = _dispatch_region(x, dmask)
    out = expert_fn(local)
    return _combine_region(out, cmask)



def _inside_axis_context():
    """True when traced inside a manual ``shard_map`` region (the values in
    hand are then per-shard blocks, not global arrays)."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def dispatch_combine(x, combine, dispatch, expert_fn,
                     ep_axis=groups.EP_AXIS, mesh=None):
    """THE expert-dispatch point ``moe/layer.py`` routes through.

    ``x`` [T, D] tokens; ``combine``/``dispatch`` [T, E, C] gate outputs;
    ``expert_fn`` [E, C, D] → [E, C, D].  Path selection:

    * inside a manual region (the qgZ micro) → direct collectives
      (:func:`_manual_dispatch_combine`);
    * ``moe.quantized_dispatch`` on an ep>1 mesh → the manual-SPMD
      (optionally hierarchical) quantized exchange;
    * otherwise → ``sharded_moe.dispatch_combine`` verbatim (bit-identical
      to the pre-engine program).
    """
    opts = _active
    if mesh is None:
        try:
            mesh = groups.get_global_mesh()
        except Exception:
            mesh = None
    if mesh is not None and _inside_axis_context():
        n_tok = int(np.prod([mesh.shape.get(a, 1)
                             for a in groups.dp_axes()]))
        if n_tok > 1:
            return _manual_dispatch_combine(x, combine, dispatch, expert_fn,
                                            opts, mesh, ep_axis)
        # single-rank token group: nothing to exchange, run locally
        from .sharded_moe import dispatch_combine as _flat
        return _flat(x, combine, dispatch, expert_fn, ep_axis=ep_axis,
                     mesh=None)
    if (opts is None or not getattr(opts, "quantized_dispatch", False)
            or mesh is None or mesh.shape.get(ep_axis, 1) <= 1):
        from .sharded_moe import dispatch_combine as _flat
        return _flat(x, combine, dispatch, expert_fn, ep_axis=ep_axis,
                     mesh=mesh)
    if mesh.shape.get("sp", 1) > 1 or mesh.shape.get("pp", 1) > 1:
        if "sp_pp_warned" not in _meta_emitted:
            _meta_emitted.add("sp_pp_warned")
            logger.warning(
                "moe.quantized_dispatch is ignored on sp/pp meshes (the "
                "manual dispatch regions assume tokens shard over "
                "(dp, ep) only); using the GSPMD constraint path")
        from .sharded_moe import dispatch_combine as _flat
        return _flat(x, combine, dispatch, expert_fn, ep_axis=ep_axis,
                     mesh=mesh)
    return _quantized_dispatch_combine(x, combine, dispatch, expert_fn,
                                       opts, mesh, ep_axis)


# --------------------------------------------------- routed-token accounting
def _stats_sink(layer, k, drop_fraction, overflow_tokens, load_imbalance,
                aux_loss, expert_util):
    """Host-side sink for the traced routing stats (jax.debug.callback
    target): per-layer ``moe/*`` metric families + the step record's
    ``moe`` section."""
    layer = str(layer)
    util = [float(u) for u in np.asarray(expert_util).reshape(-1)]
    stats = {
        "k": int(k),
        "drop_fraction": float(drop_fraction),
        "overflow_tokens": float(overflow_tokens),
        "load_imbalance": float(load_imbalance),
        "aux_loss": float(aux_loss),
        # per-expert capacity utilization (post-drop tokens / capacity C):
        # the raw signal a capacity-factor autotuner dimension needs —
        # a uniformly low vector says "shrink cf", a saturated one with
        # drops says "grow it" (ISSUE-15 satellite / ROADMAP MoE (c))
        "expert_util": util,
    }
    _telemetry.record_moe_stats(layer, stats)
    g = _telemetry.gauge(f"moe/{layer}/drop_fraction",
                         help="fraction of routed assignments dropped at "
                         "capacity")
    if g is not None:
        g.set(stats["drop_fraction"])
        _telemetry.gauge(f"moe/{layer}/load_imbalance",
                         help="max/mean tokens per expert").set(
                             stats["load_imbalance"])
        _telemetry.gauge(f"moe/{layer}/aux_loss",
                         help="load-balance aux loss").set(stats["aux_loss"])
        if util:
            _telemetry.gauge(
                f"moe/{layer}/expert_util",
                help="mean per-expert capacity utilization "
                "(post-drop tokens / capacity)").set(
                    sum(util) / len(util))
            _telemetry.gauge(
                f"moe/{layer}/expert_util_max",
                help="max per-expert capacity utilization").set(max(util))
        c = _telemetry.counter(f"moe/{layer}/overflow_tokens",
                               help="token assignments dropped at capacity")
        if stats["overflow_tokens"] > 0:
            c.inc(stats["overflow_tokens"])


def record_routing(layer, k, combine, dispatch, exp_counts, l_aux):
    """Emit one MoE layer's routed-token accounting onto the telemetry
    spine: drop-fraction (dropped assignments / T·k), overflow token count,
    expert-load imbalance (max/mean tokens per expert, post-drop) and the
    aux loss.  Zero overhead while telemetry is off (one attribute read);
    inside manual regions the values would be per-shard, so recording is
    skipped there."""
    if not _telemetry.enabled:
        return
    if _inside_axis_context():
        return  # per-shard values; the GSPMD path records the global view
    T = dispatch.shape[0]
    kept = jnp.sum(dispatch.astype(jnp.float32))
    total = jnp.float32(max(1, T * k))
    drop = 1.0 - kept / total
    overflow = total - kept
    counts = exp_counts.astype(jnp.float32)
    mean = jnp.maximum(jnp.mean(counts), 1e-9)
    imbalance = jnp.max(counts) / mean
    # per-expert capacity utilization: the POST-DROP slot occupancy of
    # each expert's [C] buffer (dispatch sums per expert / C) — counts may
    # exceed C pre-drop, occupancy cannot
    C = max(1, dispatch.shape[-1])
    occupancy = jnp.sum(dispatch.astype(jnp.float32), axis=(0, 2)) / C
    jax.debug.callback(_stats_sink, layer, k, drop, overflow, imbalance,
                       jnp.asarray(l_aux, jnp.float32), occupancy)

