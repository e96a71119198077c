"""ZeRO++ — quantized ZeRO communication (qwZ / qgZ / hpZ).

TPU-native re-design of the reference's ZeRO++ stack (wiring at
``runtime/zero/stage3.py:123`` + ``runtime/engine.py:906-913``, kernels in
``csrc/quantization``, collectives in
``runtime/comm/coalesced_collectives.py:31 all_to_all_quant_reduce``):

* **qwZ** (quantized weight all-gather): the stage-3 forward/backward param
  all-gather moves int8 + per-group scales instead of bf16 — ~2× gather
  traffic reduction.  Implemented as a ``shard_map`` wrapper around each
  dp-sharded leaf: quantize local shard → ``lax.all_gather`` the int8 payload
  → dequantize → reassemble.  Composes with TP sharding (only the ZeRO axes
  are gathered).
* **qgZ** (quantized gradient reduce): gradients are reduced with a single
  quantized all-to-all + local sum (int8 payload, fp32 accumulation).  The
  reference needs a *hierarchical* 2-hop (intra-node all-to-all, dequant-
  reduce, inter-node all-to-all with ``swizzled_quantize``) because NCCL
  all-to-all crosses nodes at full fan-out; on a TPU torus the single
  mesh-axis all-to-all already rides ICI neighbor links, so the 1-hop scheme
  gets the same 4× volume reduction with ONE quantization error instead of
  two.  When the ZeRO group spans a genuine hierarchy (dp×ep, hpZ's
  zp_outer×zp) and ``comm_optimizations.hierarchical_allreduce`` is on, the
  reduction upgrades to the true 2-hop scheme from
  ``comm/collectives/quantized.py``: full-precision reduce-scatter on the
  intra axes, quantized all-to-all across the inter axes on 1/n of the data.
* **hpZ** (secondary partition) is a *sharding policy*, not a collective:
  ``ZeroPartitionPlan(hpz_mesh=...)`` shards params over the intra-host "zp"
  mesh factor only (see ``partition.py``).

The quantized collective primitives themselves live in
``comm/collectives/quantized.py`` (shared with the eager ``dist.*`` engine
and ``ds_bench``); this module owns the ZeRO-side orchestration.

qgZ requires taking over the gradient reduction from GSPMD.  Since
ISSUE 15 the DEFAULT vehicle for that is the GSPMD-first micro
(``runtime/zero/gspmd.py``): one jit with per-leaf codec+collective
islands, XLA scheduling everything around them.  The full-manual
(``shard_map``-everything) micro below — :func:`build_manual_dp_micro` —
remains for the compositions the islands cannot express yet (tp>1 via
PARTIAL-manual shard_map, hpZ/MiCS reshaped meshes, MoE's manual-context
dispatch, dp×ep hierarchies) and for ``comm_optimizations.zero_mode:
"flat_manual"`` (the baseline the islands micro is tested against); sp/pp are
rejected loudly (their collectives interleave with the reduction being
replaced).

With ``comm_optimizations.overlap`` enabled the manual reduction runs the
bucketed two-stage pipeline from ``runtime/zero/overlap.py`` — intra-node
psum_scatter of bucket *k* overlapping the quantized inter-node
all-to-all of bucket *k−1* (docs/overlap.md).  With
``comm_optimizations.overlap.prefetch`` enabled the forward param
all-gather is the mirror image: ``pipelined_gather`` issues bucket *k+1*'s
(quantized, when qwZ) gather while bucket *k*'s layers compute, with a
``max_inflight`` window clamped by ``stage3_max_live_parameters``.
"""

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

# canonical quantized-collective primitives (also the back-compat import
# surface: tests and user code import these names from here)
from ...comm.collectives.quantized import (DEFAULT_GROUP_SIZE,
                                           all_to_all_quant_reduce,
                                           hierarchical_quant_reduce_scatter,
                                           qdq_all_gather_st,
                                           quantized_all_gather)
from .partition import (gathered_spec as _gathered_spec,
                        zero_dim as _zero_dim)


def _entry_names(entry):
    """Spec entry → tuple of axis names (shared normalize for the spec
    rewriters below)."""
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry, )


def _collapse(names):
    """Axis-name tuple → spec entry (len-collapse inverse of _entry_names)."""
    return names if len(names) > 1 else (names[0] if names else None)


def quantized_weight_gather(params, plan, wire_format="int8",
                            group_size=DEFAULT_GROUP_SIZE, prefetch=None):
    """qwZ in GSPMD mode: explicitly gather every ZeRO-sharded param with a
    quantized payload; XLA sees already-replicated (over dp) values and
    inserts no further gather.  Differentiable (straight-through; backward is
    the standard reduce-scatter).  Usable both outside and inside
    ``jax.jit``.

    ``prefetch`` (a dict from ``overlap.resolve_prefetch``) pipelines the
    per-leaf gathers bucket by bucket in forward-layer order with a bounded
    in-flight window (``overlap.pipelined_gather``) — the stage-3 prefetch
    coordinator over the quantized wire.  Persistent leaves are excluded
    from the pipeline (the gather below is the identity for them anyway).
    """
    from .partition import path_str
    mesh = plan.param_mesh

    def gather_one(path, x):
        spec = plan.param_spec(x.shape, path)
        # per-leaf axes: a rule-claimed axis (the expert "ep" dim, tp) is
        # model parallelism — never gathered here
        leaf_axes = plan.leaf_zero_axes(path, plan.param_axes)
        dim, axes = _zero_dim(spec, leaf_axes)
        if dim is None:
            return x
        out_spec = _gathered_spec(spec, leaf_axes)
        # per-leaf wire through the autotuned size ladder — x is the
        # GLOBAL array in GSPMD mode, so x.size is the logical (gathered)
        # message size the probes/dispatch key on; "fp32" rungs take the
        # plain gather inside the same straight-through wrapper
        fmt = plan.wire_for_size(wire_format,
                                 x.size * x.dtype.itemsize)
        # positional call: custom_vjp rejects kwargs for nondiff argnums.
        # The island is a gspmd_region (ISSUE 15): entered/exited through
        # straight-through sharding constraints so GSPMD resumes
        # propagation from the declared layout WITHOUT the constraint's
        # transpose forcing the gather's cotangent replicated.
        from ...comm.collectives.engine import gspmd_region
        fn = gspmd_region(
            lambda t: qdq_all_gather_st(t, axes, dim, fmt, group_size),
            mesh=mesh, in_specs=(spec, ), out_specs=out_spec,
            grad_transparent=True)
        return fn(x)

    if prefetch is not None:
        from .overlap import pipelined_gather, prefetch_buckets_for
        buckets, window, _ = prefetch_buckets_for(params, plan, prefetch)
        if buckets:
            return pipelined_gather(params, buckets, gather_one, window)
    return jax.tree_util.tree_map_with_path(
        lambda kp, x: gather_one(path_str(kp), x), params)


def build_manual_dp_micro(engine):
    """Manual-SPMD micro-step for the qgZ path.

    The GSPMD micro-step lets XLA insert the DP gradient reduction (bf16/f32);
    to quantize that traffic we compute grads per-shard under ``shard_map``
    and reduce them ourselves:

        per device:  local loss/grad on the local batch shard
        qwZ (opt.):  int8 param all-gather for stage-3 sharded params
        qgZ:         int8 all-to-all reduce-scatter into the master partition
                     (2-hop hierarchical when the group spans dp×ep / hpZ
                     axes and comm_optimizations asks for hierarchy)

    Returns ``micro(params, scale, inputs) -> (loss, grads)`` with grads in
    the master (ZeRO) sharding — drop-in for the engine's compiled micro fn.
    """
    plan = engine.plan
    zc = engine._config.zero_config
    co = engine._config.comm_optimizations_config
    gas = engine.gradient_accumulation_steps()
    apply_fn = engine._effective_apply_fn()
    grad_dtype = engine.grad_accum_dtype
    if engine.seq_parallel_world_size > 1 or engine.pp_world_size > 1:
        raise ValueError(
            "zero_quantized_gradients supports dp/ep (+tp) meshes only — "
            "sp/pp interleave their own collectives with the DP gradient "
            "reduction this path replaces; disable "
            "zero_quantized_gradients or drop the sp/pp axes")
    # tp > 1 runs in PARTIAL-manual mode: shard_map is manual over the dp
    # axes (where the quantized collectives live) while "tp" stays an auto
    # axis — GSPMD keeps inserting the tensor-parallel collectives inside
    # the body exactly as in the normal micro-step.
    manual_only = engine.mp_world_size > 1
    # With hpZ/MiCS the manual step runs over the reshaped hpz mesh, whose
    # (zp_outer, zp) axes tile the same device order as (dp, ep) on the
    # global mesh — full-dp specs are translated axis-for-axis.
    hpz_active = (plan.param_mesh is not plan.mesh or
                  plan.state_mesh is not plan.mesh)
    if hpz_active:
        from ...utils.groups import ZP_AXIS, ZP_OUTER_AXIS
        mesh = plan.param_mesh
        dp_axes = (ZP_OUTER_AXIS, ZP_AXIS)

        def _translate(spec):
            out = []
            for entry in spec:
                names = _entry_names(entry)
                if any(a in ("dp", "ep") for a in names):
                    names = tuple(a for a in names
                                  if a not in ("dp", "ep")) + dp_axes
                out.append(_collapse(names))
            return P(*out)
    else:
        mesh = plan.mesh
        dp_axes = plan.zero_axes
        _translate = lambda spec: spec
    qw = zc.zero_quantized_weights or (
        getattr(co, "enabled", False) and getattr(co, "quantized_weights",
                                                  False))
    qw_fmt, qw_gs = plan.param_wire(zc.zero_quantized_weights_format)
    qg_fmt, qg_gs = plan.grad_wire()

    def _grad_leaf_fmt(g):
        # per-leaf wire through the autotuned size ladder; inside the
        # manual body g carries the FULL gradient shape (each rank reduces
        # its whole-gradient copy), so g.size is the logical message size
        # — the same quantity the eager dispatch and the probes key on
        return plan.wire_for_size(qg_fmt, g.size * g.dtype.itemsize)
    hier = plan.hierarchical_reduce()
    # bucketed overlap scheduler: pipeline the quantized inter-node hop of
    # bucket k with the intra-node work of bucket k+1 (docs/overlap.md)
    from .overlap import overlap_opts, prefetch_opts, resolve_prefetch
    ov = overlap_opts(co)
    overlap_on = ov is not None
    # forward-direction prefetch: pipeline the stage-3 param all-gather
    # bucket by bucket under the early layers' compute (docs/overlap.md
    # forward-prefetch section); a no-op below stage 3 where every leaf is
    # persistent and the bucket list comes back empty
    pf = prefetch_opts(co)
    pf_resolved = resolve_prefetch(pf, zc) if pf is not None else None

    from .partition import path_str
    from ..utils import make_scaled_loss_fn
    loss_fn = make_scaled_loss_fn(apply_fn, gas)

    manual_axes = frozenset(
        a for a in (dp_axes if isinstance(dp_axes, tuple) else (dp_axes, )))

    def _manual_spec(spec):
        """Project a spec onto the manual axes (partial-manual shard_map
        in/out specs may reference ONLY the manual axis names; auto-axis
        sharding rides on the operands themselves)."""
        return P(*[_collapse(tuple(a for a in _entry_names(e)
                                   if a in manual_axes)) for e in spec])

    def _leaf_hier(spec, leaf_axes=None):
        """(dim, outer_axes, inner_axes) when this leaf's reduction should
        run the 2-hop scheme, else None.  Mesh axis order is major→minor, so
        the FIRST effective axis crosses the slower fabric.  ``leaf_axes``
        restricts the search to the leaf's OWN reducible axes (expert
        leaves exclude their claimed "ep" dim)."""
        if not hier:
            return None
        dim, axes = _zero_dim(spec, dp_axes if leaf_axes is None
                              else leaf_axes)
        if dim is None:
            return None
        eff = tuple(a for a in axes if mesh.shape[a] > 1)
        if len(eff) < 2:
            return None
        return dim, eff[:1], eff[1:]

    def _hier_spec(spec, leaf_axes=None):
        """Reorder a hier leaf's zero-dim axes to the inner-major tiling the
        2-hop reduce-scatter produces (see
        ``hierarchical_quant_reduce_scatter``); the apply step reshards to
        the canonical master layout at the gas boundary."""
        info = _leaf_hier(spec, leaf_axes)
        if info is None:
            return spec
        dim, outer, inner = info
        entry = _entry_names(spec[dim])
        z = set(outer + inner)
        new_z = iter(inner + outer)
        new_entry = tuple(next(new_z) if a in z else a for a in entry)
        out = list(spec)
        out[dim] = _collapse(new_entry)
        return P(*out)

    def _claimed_divisor(leaf_axes):
        n = 1
        for a in dp_axes:
            if a not in leaf_axes:
                n *= mesh.shape[a]
        return n

    def _finish_reduce(out, reduced_axes, leaf_axes):
        """Close a leaf's reduction: mean over the leaf's remaining
        reducible axes, then the extra divisor for claimed (model-parallel)
        axes — those ranks' loss terms already arrived through the forward
        collectives' transposes (the expert dispatch), but the global-mean
        loss normalization still counts them."""
        rest = tuple(a for a in leaf_axes if a not in reduced_axes)
        if rest:
            out = jax.lax.pmean(out, rest)
        extra = _claimed_divisor(leaf_axes)
        if extra > 1:
            out = out / extra
        return out

    def _unsharded_reduce(g, leaf_axes):
        """Reduction of a leaf with no reducible sharded dim.  The common
        (no claimed axes) case keeps the exact historical pmean; claimed
        leaves sum over their own group only and divide by the full loss
        normalization."""
        if tuple(leaf_axes) == tuple(dp_axes):
            return jax.lax.pmean(g, dp_axes)
        out = jax.lax.pmean(g, leaf_axes) if leaf_axes else g
        extra = _claimed_divisor(leaf_axes)
        if extra > 1:
            out = out / extra
        return out

    def micro(params, scale, inputs):
        # specs must come from the GLOBAL shapes, captured here where params
        # are still global arrays — inside the shard_map body the leaves are
        # local shards (params) and spec inference from their shapes picks
        # the wrong dim (e.g. a (16,16) param sharded to (2,16) looks
        # dim-1-shardable); grads keep global shapes today (they come from
        # the gathered full params) but get the same treatment so the body
        # never depends on in-body shapes.
        gather_specs = {}
        reduce_specs = {}
        # per-leaf reducible/gatherable axes: rule-claimed model axes (the
        # expert stack's "ep", tp dims) are NOT ZeRO shards — expert params
        # must stay local to their ep rank through the gather, and expert
        # grads reduce over the expert-DP ("dp") group only (reference
        # engine.py:2510 _reduce_expert_gradients)
        gather_axes = {}
        reduce_axes = {}

        def _record(kp, x):
            p = path_str(kp)
            claimed = plan.rule_claimed_axes(p)
            if hpz_active and any(a in ("dp", "ep") for a in claimed):
                raise ValueError(
                    f"hpZ/MiCS shard groups cannot compose with a tp rule "
                    f"claiming the dp/ep axes (leaf {p!r} claims "
                    f"{claimed}): the zp translation would fold the expert "
                    "axis into the shard group; drop "
                    "zero_hpz_partition_size/mics_shard_size or the rule")
            gather_specs[p] = plan.param_spec(x.shape, p)
            gather_axes[p] = plan.leaf_zero_axes(p, plan.param_axes)
            spec = _translate(plan.master_spec(x.shape, p))
            if manual_only:
                spec = _manual_spec(spec)
            reduce_specs[p] = spec
            reduce_axes[p] = plan.leaf_zero_axes(p, dp_axes)

        jax.tree_util.tree_map_with_path(_record, params)
        param_specs = jax.tree_util.tree_map(_translate,
                                             plan.param_specs(params),
                                             is_leaf=lambda x: isinstance(
                                                 x, P))
        master_specs = jax.tree_util.tree_map(_translate,
                                              plan.master_specs(params),
                                              is_leaf=lambda x: isinstance(
                                                  x, P))
        if manual_only:
            param_specs = jax.tree_util.tree_map(
                _manual_spec, param_specs,
                is_leaf=lambda x: isinstance(x, P))
            master_specs = jax.tree_util.tree_map(
                _manual_spec, master_specs,
                is_leaf=lambda x: isinstance(x, P))
        # hier leaves come out of the 2-hop reduce tiled inner-major
        grad_out_specs = jax.tree_util.tree_map_with_path(
            lambda kp, s: _hier_spec(s, reduce_axes.get(path_str(kp))),
            master_specs, is_leaf=lambda x: isinstance(x, P))
        from ..utils import batch_input_specs
        batch_specs = batch_input_specs(inputs, dp_axes,
                                        engine._n_replicated_batch_tail)
        # prefetch buckets from GLOBAL shapes (same reason as the specs
        # above: inside the shard_map body the leaves are local shards and
        # both sizes and spec inference would be wrong)
        pf_buckets, pf_window = (), 1
        if pf_resolved is not None:
            from .overlap import prefetch_buckets_for
            pf_buckets, pf_window, _ = prefetch_buckets_for(
                params, plan, pf_resolved)

        def _overlapped_reduce(grads):
            """Per-bucket two-stage reduction, same math as reduce_leaf:
            stage1 = full-precision intra-node psum_scatter (hier leaves
            only), stage2 = quantized inter-node all-to-all reduce +
            trailing pmean/cast.  The pipeline fences bucket k's stage2
            behind bucket k−max_inflight's output so the DCN hop of one
            bucket overlaps the ICI hop of the next."""
            from .overlap import (bucket_bytes_of, pipelined_bucket_reduce,
                                  tree_buckets)
            buckets, _, _ = tree_buckets(grads, bucket_bytes_of(ov))
            # ladder formats key on the FULL leaf size stage1 sees, not the
            # intra-scattered piece stage2 receives for hier leaves
            from .partition import path_str as _ps
            fmt_by_path = {
                _ps(kp): _grad_leaf_fmt(g)
                for kp, g in
                jax.tree_util.tree_flatten_with_path(grads)[0]}

            def stage1(path, g):
                info = _leaf_hier(reduce_specs[path], reduce_axes[path])
                if info is None:
                    return g
                dim, _, inner = info
                part = g
                for a in inner:
                    part = jax.lax.psum_scatter(part, a,
                                                scatter_dimension=dim,
                                                tiled=True)
                return part

            def stage2(path, h):
                spec = reduce_specs[path]
                leaf_axes = reduce_axes[path]
                dim, axes = _zero_dim(spec, leaf_axes)
                if dim is None:
                    return _unsharded_reduce(h, leaf_axes).astype(grad_dtype)
                fmt = fmt_by_path[path]
                info = _leaf_hier(spec, leaf_axes)
                if info is not None:
                    _, outer, inner = info
                    n_out = 1
                    for a in outer:
                        n_out *= mesh.shape[a]
                    n_in = 1
                    for a in inner:
                        n_in *= mesh.shape[a]
                    out = all_to_all_quant_reduce(h, outer, dim, n_out,
                                                  wire_format=fmt,
                                                  group_size=qg_gs,
                                                  mean=False)
                    out = out / (n_in * n_out)
                else:
                    n = 1
                    for a in axes:
                        n *= mesh.shape[a]
                    out = all_to_all_quant_reduce(h, axes, dim, n,
                                                  wire_format=fmt,
                                                  group_size=qg_gs)
                return _finish_reduce(out, axes, leaf_axes).astype(
                    grad_dtype)

            return pipelined_bucket_reduce(
                grads, buckets, stage1, stage2,
                max_inflight=getattr(ov, "max_inflight", 2))

        def body(params, inputs):
            # stage-3: reassemble full params from local shards (int8 when qwZ)
            def gather_one(path, x):
                spec = gather_specs[path]
                # per-leaf axes: rule-claimed model axes (the expert "ep"
                # dim) are NOT ZeRO shards — expert params stay local to
                # their ep rank and the dispatch a2a moves tokens instead
                dim, axes = _zero_dim(spec, gather_axes[path])
                if dim is None:
                    return x
                if qw:
                    # per-leaf ladder keys on the GATHERED (logical) size —
                    # x here is this rank's 1/n shard
                    n_g = 1
                    for a in axes:
                        n_g *= mesh.shape[a]
                    fmt = plan.wire_for_size(
                        qw_fmt, x.size * n_g * x.dtype.itemsize)
                    return quantized_all_gather(x, axes, dim, fmt, qw_gs)
                return jax.lax.all_gather(x, axes, axis=dim, tiled=True)

            if pf_buckets:
                # forward prefetch: per-bucket gathers with a bounded
                # in-flight window instead of one up-front tree gather
                from .overlap import pipelined_gather
                full = pipelined_gather(params, pf_buckets, gather_one,
                                        pf_window)
            else:
                full = jax.tree_util.tree_map_with_path(
                    lambda kp, x: gather_one(path_str(kp), x), params)
            (_, loss), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(full, scale, inputs)
            loss = jax.lax.pmean(loss, dp_axes)

            def reduce_leaf(kp, g):
                # translated spec lives in manual-mode axis space (dp_axes ∪
                # zp), so searching dp_axes covers plain/hpZ/MiCS alike;
                # per-leaf axes keep expert ("ep"-claimed) leaves on their
                # expert-DP reduction group
                p = path_str(kp)
                spec = reduce_specs[p]
                leaf_axes = reduce_axes[p]
                dim, axes = _zero_dim(spec, leaf_axes)
                if dim is None:
                    return _unsharded_reduce(g, leaf_axes).astype(grad_dtype)
                fmt = _grad_leaf_fmt(g)
                info = _leaf_hier(spec, leaf_axes)
                if info is not None:
                    _, outer, inner = info
                    n_out = 1
                    for a in outer:
                        n_out *= mesh.shape[a]
                    n_in = 1
                    for a in inner:
                        n_in *= mesh.shape[a]
                    out = hierarchical_quant_reduce_scatter(
                        g, inner, outer, dim, n_in, n_out,
                        wire_format=fmt, group_size=qg_gs)
                else:
                    n = 1
                    for a in axes:
                        n *= mesh.shape[a]
                    out = all_to_all_quant_reduce(g, axes, dim, n,
                                                  wire_format=fmt,
                                                  group_size=qg_gs)
                return _finish_reduce(out, axes, leaf_axes).astype(
                    grad_dtype)

            if overlap_on:
                grads = _overlapped_reduce(grads)
            else:
                grads = jax.tree_util.tree_map_with_path(reduce_leaf, grads)
            return loss, grads

        kw = dict(mesh=mesh, in_specs=(param_specs, batch_specs),
                  out_specs=(P(), grad_out_specs), check_vma=False)
        if manual_only:
            kw["axis_names"] = manual_axes  # tp stays auto (GSPMD)
        fn = shard_map(body, **kw)
        return fn(params, inputs)

    return micro
