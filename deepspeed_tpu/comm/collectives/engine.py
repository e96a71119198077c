"""The pluggable collectives engine behind the ``dist.*`` facade.

``comm/comm.py`` owns ONE dispatch point (``_dispatch``); when an engine is
installed and enabled, every eager ``all_reduce`` / ``all_gather`` /
``reduce_scatter`` (and the ``allgather_fn`` / ``reduce_scatter_fn`` /
``*_coalesced`` helpers riding them) is offered to :meth:`CollectivesEngine.
dispatch` first.  The engine picks a *variant*:

    ==================  =============================================
    variant             meaning
    ==================  =============================================
    (None — fallback)   today's flat single-hop collective, bit-exact
    ``hier``            hierarchical all-reduce (fp payload)
    ``q_<fmt>``         quantized payload (all-gather / reduce-scatter)
    ``hier_q_<fmt>``    2-hop: fp intra-node, quantized inter-node
    ==================  =============================================

and returns ``(result, variant, wire_bytes)`` — or None, which means "flat
path, unchanged".  ``wire_bytes`` is the payload actually crossing the
*bottleneck* (inter-node) link, which is what ``utils/comms_logging`` and
``ds_bench`` report; for flat ops it equals the logical message size.

Selection is conservative by construction: a reduce op outside SUM/AVG
(MIN/MAX/PRODUCT), a non-float dtype, an indivisible shape, a message under
``min_message_size``, or a topology with no hierarchy all fall through to
the flat path — optimized never means "sometimes wrong".
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ... import telemetry as _telemetry
from ..reduce_op import ReduceOp
from . import quantized as Q
from .config import CommOptimizations
from .topology import factor_group

_LINEAR_OPS = (ReduceOp.SUM, ReduceOp.AVG)


# ------------------------------------------------------------ jitted kernels
# Cached by (mesh, axes, ...) like comm/backend.py — jax.jit keys on function
# identity, so each signature must map to one function object.

@functools.lru_cache(maxsize=None)
def _jit_hier_all_reduce(mesh, inner_axes, outer_axes, op, total):
    """intra reduce-scatter → inter all-reduce on 1/n_inner → intra
    all-gather.  Input convention matches the flat backend: dim 0 sharded
    over the group (outer-major), output replicated."""

    def _k(blk):
        r = blk
        for a in inner_axes:
            r = jax.lax.psum_scatter(r, a, scatter_dimension=0, tiled=True)
        r = jax.lax.psum(r, outer_axes)
        for a in reversed(inner_axes):
            r = jax.lax.all_gather(r, a, axis=0, tiled=True)
        if op == ReduceOp.AVG:
            r = r / total
        return r

    return jax.jit(jax.shard_map(_k, mesh=mesh, check_vma=False,
                                 in_specs=(P(outer_axes + inner_axes), ),
                                 out_specs=P()))


@functools.lru_cache(maxsize=None)
def _jit_quant_all_gather(mesh, axes, axis, ndim, fmt, gs):
    in_spec = [None] * ndim
    in_spec[axis] = axes
    in_spec = P(*in_spec)

    def _k(blk):
        return Q.quantized_all_gather(blk, axes, axis, fmt, gs)

    return jax.jit(jax.shard_map(_k, mesh=mesh, check_vma=False,
                                 in_specs=(in_spec, ), out_specs=P()))


@functools.lru_cache(maxsize=None)
def _jit_quant_reduce_scatter(mesh, axes, op, axis, ndim, fmt, gs, n):
    out_spec = [None] * ndim
    out_spec[axis] = axes
    out_spec = P(*out_spec)

    def _k(x):
        return Q.all_to_all_quant_reduce(x, axes, axis, n, wire_format=fmt,
                                         group_size=gs,
                                         mean=(op == ReduceOp.AVG))

    return jax.jit(jax.shard_map(_k, mesh=mesh, check_vma=False,
                                 in_specs=(P(), ), out_specs=out_spec))


@functools.lru_cache(maxsize=None)
def _jit_hier_quant_reduce_scatter(mesh, inner_axes, outer_axes, op, axis,
                                   ndim, fmt, gs, n_in, n_out):
    # inner-major tiling (see hierarchical_quant_reduce_scatter docstring)
    out_spec = [None] * ndim
    out_spec[axis] = inner_axes + outer_axes
    out_spec = P(*out_spec)

    def _k(x):
        return Q.hierarchical_quant_reduce_scatter(
            x, inner_axes, outer_axes, axis, n_in, n_out, wire_format=fmt,
            group_size=gs, mean=(op == ReduceOp.AVG))

    return jax.jit(jax.shard_map(_k, mesh=mesh, check_vma=False,
                                 in_specs=(P(), ), out_specs=out_spec))


_JIT_CACHES = (_jit_hier_all_reduce, _jit_quant_all_gather,
               _jit_quant_reduce_scatter, _jit_hier_quant_reduce_scatter)


def clear_jit_caches():
    """Drop cached executables so stale Mesh objects can be collected
    (called from ``dist.destroy_process_group``)."""
    for fn in _JIT_CACHES:
        fn.cache_clear()
    from .topology import clear_topology_caches
    clear_topology_caches()


# ----------------------------------------------------------- manual islands
def straight_through_constraint(x, sharding):
    """``with_sharding_constraint`` whose transpose is the identity.

    A plain constraint's VJP re-applies the same sharding to the cotangent
    — correct for values whose gradient shares their layout, but wrong on
    a quantized-gather island's *output*: the gathered param is replicated
    over the ZeRO axes while its cotangent is the still-unreduced gradient
    contribution, and constraining that replicated would force an eager
    all-reduce the backward scheduler should own (the same hazard
    ``overlap.mark_gather_tree`` documents).  Differentiated islands
    therefore enter/exit through this straight-through flavor."""

    @jax.custom_vjp
    def _st(v):
        return jax.lax.with_sharding_constraint(v, sharding)

    _st.defvjp(lambda v: (_st(v), None), lambda _, g: (g, ))
    return _st(x)


def gspmd_region(body, *, mesh, in_specs, out_specs, axis_names=None,
                 grad_transparent=False):
    """THE enter/exit contract for shrunken manual islands inside a GSPMD
    program (ISSUE 15, docs/zero.md "GSPMD-first ZeRO").

    A ``shard_map`` call is opaque to XLA's sharding propagation: layouts
    on either side of it are re-inferred, and a mismatch materializes as a
    silent reshard right where the island meets the surrounding program.
    This wrapper owns both boundaries: every operand is constrained to the
    island's expected ``PartitionSpec`` (``with_sharding_constraint`` —
    GSPMD materializes that layout *before* manual mode begins), the body
    runs under ``shard_map`` with exactly those specs, and every result is
    constrained on the way out so propagation resumes from a declared
    layout.  XLA's latency-hiding scheduler then treats the island as one
    schedulable op and slides independent compute around it — the reason
    the qwZ/qgZ islands exist at all (the codec needs bespoke bytes on the
    wire; everything else belongs to GSPMD).

    ``grad_transparent=True`` uses :func:`straight_through_constraint` for
    the boundary constraints — required when the island is differentiated
    (the qwZ gather), see that function's docstring.  ``axis_names``
    restricts manual mode to a subset of mesh axes (partial-manual)."""
    from jax.sharding import NamedSharding

    def _is_multi(specs):
        # PartitionSpec subclasses tuple — a bare spec is ONE operand
        return isinstance(specs, (tuple, list)) and not isinstance(specs, P)

    in_t = tuple(in_specs) if _is_multi(in_specs) else (in_specs, )
    kw = dict(mesh=mesh, in_specs=in_t, out_specs=out_specs,
              check_vma=False)
    if axis_names is not None:
        kw["axis_names"] = frozenset(axis_names)
    inner = jax.shard_map(body, **kw)

    def constrain(x, spec):
        if spec is None:
            return x
        s = NamedSharding(mesh, spec)
        if grad_transparent:
            return straight_through_constraint(x, s)
        return jax.lax.with_sharding_constraint(x, s)

    def wrapped(*args):
        args = tuple(constrain(x, s) for x, s in zip(args, in_t))
        out = inner(*args)
        if _is_multi(out_specs):
            return tuple(constrain(o, s)
                         for o, s in zip(out, tuple(out_specs)))
        return constrain(out, out_specs)

    return wrapped


# ------------------------------------------------------------------- engine
#: ladder rung meaning "do not quantize this size band" — flat fp path
LADDER_FP = "fp32"


def build_wire_ladder(raw):
    """Normalize a ``wire_dtype_by_size`` value into an ascending tuple of
    ``(max_bytes, wire)`` rungs, or None when absent/empty (= global
    ``wire_dtype`` everywhere, the pre-ladder behavior).

    Accepts ``[max_bytes, wire]`` pairs or ``{"max_bytes":, "wire_dtype":}``
    dicts; ``max_bytes`` of null/None is the catch-all rung (at most one,
    necessarily last).  Rejects unknown wire formats, non-positive or
    duplicate bounds loudly — a mistyped ladder must never silently tune
    the wrong band."""
    if not raw:
        return None
    rungs = []
    for entry in raw:
        if isinstance(entry, dict):
            mb, wire = entry.get("max_bytes"), entry.get("wire_dtype")
        else:
            if len(entry) != 2:
                raise ValueError(
                    f"wire_dtype_by_size entry {entry!r} is not a "
                    "[max_bytes, wire_dtype] pair")
            mb, wire = entry
        if wire != LADDER_FP and wire not in Q.WIRE_FORMATS:
            raise ValueError(
                f"wire_dtype_by_size wire {wire!r} unknown "
                f"(have {LADDER_FP}, {', '.join(Q.WIRE_FORMATS)})")
        if mb is not None:
            mb = int(mb)
            if mb <= 0:
                raise ValueError(
                    f"wire_dtype_by_size max_bytes {mb} must be positive "
                    "(use null for the catch-all rung)")
        rungs.append((mb, str(wire)))
    bounded = [r for r in rungs if r[0] is not None]
    catchall = [r for r in rungs if r[0] is None]
    if len(catchall) > 1:
        raise ValueError("wire_dtype_by_size has multiple catch-all "
                         "(max_bytes: null) rungs")
    if len({mb for mb, _ in bounded}) != len(bounded):
        raise ValueError("wire_dtype_by_size has duplicate max_bytes bounds")
    bounded.sort(key=lambda r: r[0])
    return tuple(bounded + catchall)


def resolve_in_ladder(ladder, nbytes, default):
    """THE rung walk: first rung admitting ``nbytes`` wins (inclusive
    bounds, None = catch-all), ``default`` when the ladder is absent or
    every bounded rung is smaller.  Shared by the eager dispatch
    (:meth:`CollectivesEngine.resolve_wire_dtype`) and the ZeRO hot paths
    (``ZeroPartitionPlan.wire_for_size``) so rung semantics can never
    diverge between them."""
    if ladder is None:
        return default
    for bound, wire in ladder:
        if bound is None or nbytes <= bound:
            return wire
    return default


class CollectivesEngine:
    """Per-op variant selection over a duck-typed ``comm_optimizations``
    options object (the pydantic config model or
    :class:`~deepspeed_tpu.comm.collectives.config.CommOptimizations`)."""

    def __init__(self, opts=None):
        self.opts = opts if opts is not None else CommOptimizations()
        fmt = getattr(self.opts, "wire_dtype", "int8")
        if fmt not in Q.WIRE_FORMATS:
            raise ValueError(
                f"comm_optimizations.wire_dtype {fmt!r} unknown "
                f"(have {', '.join(Q.WIRE_FORMATS)})")
        self._ladder = build_wire_ladder(
            getattr(self.opts, "wire_dtype_by_size", None))

    def resolve_wire_dtype(self, nbytes):
        """Wire format for a payload of ``nbytes`` logical bytes: the first
        ladder rung that admits it, the global ``wire_dtype`` when the
        ladder is absent or every bounded rung is smaller.  May return
        ``"fp32"`` — the caller must fall through to the flat path."""
        return resolve_in_ladder(self._ladder, nbytes, self.opts.wire_dtype)

    @property
    def enabled(self):
        return bool(getattr(self.opts, "enabled", False))

    # ------------------------------------------------------------- helpers
    def _eligible(self, x):
        o = self.opts
        if not hasattr(x, "shape") or getattr(x, "ndim", 0) == 0:
            return False
        nbytes = x.size * x.dtype.itemsize
        return nbytes >= getattr(o, "min_message_size", 0)

    def _hierarchy(self, group):
        if not getattr(self.opts, "hierarchical_allreduce", False):
            return None
        return factor_group(group,
                            getattr(self.opts, "intra_node_size", 0))

    @staticmethod
    def _is_float(x):
        return jnp.issubdtype(x.dtype, jnp.floating)

    # ------------------------------------------------------------ dispatch
    def dispatch(self, op_name, x, group, reduce_op=ReduceOp.SUM, axis=0):
        """Offer ``x`` to the optimized variants.  Returns ``(result,
        variant, wire_bytes)`` or None (→ caller runs the flat path)."""
        if not self.enabled or group is None or not self._eligible(x):
            return None
        if op_name == "all_reduce":
            hit = self._all_reduce(x, group, reduce_op)
        elif op_name == "all_gather":
            hit = self._all_gather(x, group, axis)
        elif op_name == "reduce_scatter":
            hit = self._reduce_scatter(x, group, reduce_op, axis)
        else:
            hit = None
        if _telemetry.enabled:
            # per-variant pick counters: the autotuner's view of how often
            # each optimized path actually engages vs falls back flat
            variant = hit[1] if hit is not None else "flat_fallback"
            c = _telemetry.counter(f"comm/dispatch/{op_name}/{variant}",
                                   help="collectives-engine variant picks")
            if c is not None:
                c.inc()
        return hit

    def _all_reduce(self, x, group, op):
        if op not in _LINEAR_OPS:
            return None  # MIN/MAX/PRODUCT: flat passthrough, stays correct
        h = self._hierarchy(group)
        if h is None:
            return None
        # psum_scatter inside needs the per-rank block divisible by n_inner
        if x.shape[0] % (h.size * h.inner_size) != 0:
            return None
        fn = _jit_hier_all_reduce(h.mesh, h.inner_axes, h.outer_axes, op,
                                  h.size)
        # fp payload; the inter-node hop moves 1/n_inner of the data
        wire = (x.size * x.dtype.itemsize) // h.inner_size
        return fn(x), "hier", wire

    def _all_gather(self, x, group, axis):
        o = self.opts
        if not getattr(o, "quantized_weights", False) or \
                not self._is_float(x):
            return None
        n = group.size()
        if n <= 1 or x.shape[axis] % n != 0:
            return None
        fmt = self.resolve_wire_dtype(x.size * x.dtype.itemsize)
        if fmt == LADDER_FP:
            return None  # ladder says: this size band rides the flat path
        gs = getattr(o, "quantization_group_size", Q.DEFAULT_GROUP_SIZE)
        fn = _jit_quant_all_gather(group.mesh, group.axis_names, axis,
                                   x.ndim, fmt, gs)
        return fn(x), f"q_{fmt}", Q.quantized_wire_bytes(x.size, fmt, gs)

    def _reduce_scatter(self, x, group, op, axis):
        o = self.opts
        if not getattr(o, "quantized_gradients", False) or \
                op not in _LINEAR_OPS or not self._is_float(x):
            return None
        n = group.size()
        if n <= 1 or x.shape[axis] % n != 0:
            return None
        fmt = self.resolve_wire_dtype(x.size * x.dtype.itemsize)
        if fmt == LADDER_FP:
            return None  # ladder says: this size band rides the flat path
        gs = getattr(o, "quantization_group_size", Q.DEFAULT_GROUP_SIZE)
        h = self._hierarchy(group)
        if h is not None:
            fn = _jit_hier_quant_reduce_scatter(
                h.mesh, h.inner_axes, h.outer_axes, op, axis, x.ndim, fmt,
                gs, h.inner_size, h.outer_size)
            # quantized payload crosses DCN on 1/n_inner of the data
            wire = Q.quantized_wire_bytes(x.size // h.inner_size, fmt, gs)
            return fn(x), f"hier_q_{fmt}", wire
        fn = _jit_quant_reduce_scatter(group.mesh, group.axis_names, op,
                                       axis, x.ndim, fmt, gs, n)
        return fn(x), f"q_{fmt}", Q.quantized_wire_bytes(x.size, fmt, gs)
