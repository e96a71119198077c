"""GSPMD-first ZeRO micro-step with quantized manual islands (ISSUE 15).

The flat-manual qgZ micro (:func:`~deepspeed_tpu.runtime.zero.zeropp.
build_manual_dp_micro`) wraps the ENTIRE forward/backward in one
``shard_map``: correct, but opaque — XLA's latency-hiding scheduler cannot
move the quantized collectives against the surrounding compute, every
sharding decision inside the region is hand-rolled, and the region is what
forced the jax-0.4.37 compat shims and CHECK-fail guards of PR 5.  This
module is the replacement default (docs/zero.md "GSPMD-first ZeRO"):

* the forward/backward runs as ONE ``jit`` over ``NamedSharding``-annotated
  params/grads (``ZeroPartitionPlan.micro_shardings`` emits the full in/out
  set) — XLA inserts *and schedules* the tensor-parallel and stage-3 gather
  collectives exactly as in the unquantized micro;
* per-rank (unreduced) gradients are exposed to the program as a *leading
  dp axis*: the batch reshapes ``[B, …] → [n, B/n, …]`` sharded
  ``P(dp, …)`` and ``jax.vmap(value_and_grad, in_axes=(None, None, 0))``
  yields each rank's full gradient contribution stacked on that axis —
  the same local values the manual micro's in-body ``value_and_grad``
  produced, without the manual region (bitwise-equal on the test meshes);
* ``shard_map`` survives ONLY where a quantized wire format requires
  bespoke bytes on the wire: the per-leaf qgZ reduce island below (codec +
  ``all_to_all_quant_reduce``, entered/exited through
  :func:`~deepspeed_tpu.comm.collectives.engine.gspmd_region`) and the qwZ
  gather island ``zeropp.quantized_weight_gather`` already runs in GSPMD
  mode.  Everything around the islands is XLA's to schedule — the EQuARX
  observation (arXiv 2506.17615) applied from user space;
* overlap composes through the PR 8/9 machinery: the reduce islands ride
  ``overlap.pipelined_bucket_reduce`` (bucket *k* fenced behind bucket
  *k−max_inflight* with ``optimization_barrier``) and the stage-3 gather
  rides the qwZ pipeline / ``mark_gather_tree`` prefetch markers — the
  bucket markers are the only manual-free overlap mechanism on this path.

Compositions whose correctness depends on the full-manual region keep it:
:func:`manual_micro_reasons` names them (tp partial-manual, hpZ/MiCS
reshaped meshes, MoE's manual-context dispatch, sp/pp rejection, dp×ep
hierarchies) and the engine routes those to ``build_manual_dp_micro``
unchanged.  ``comm_optimizations.zero_mode: "flat_manual"`` forces the
legacy micro everywhere — ``tests/unit/runtime/test_zero_gspmd.py`` holds
the islands micro loss-bitwise-equal to it.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...comm.collectives.engine import gspmd_region

#: accepted ``comm_optimizations.zero_mode`` values — "gspmd" (default) is
#: the GSPMD-first micro with quantized islands where the composition
#: allows it; "flat_manual" forces the legacy full-manual micro.
ZERO_MODES = ("gspmd", "flat_manual")


def resolve_zero_mode(comm_opts):
    """The configured ``zero_mode``, validated.  Absent block/field (and
    the legacy ``zero_quantized_gradients`` knob alone) mean "gspmd"."""
    mode = getattr(comm_opts, "zero_mode", None) if comm_opts is not None \
        else None
    mode = mode or "gspmd"
    if mode not in ZERO_MODES:
        raise ValueError(
            f"comm_optimizations.zero_mode {mode!r} unknown "
            f"(have {', '.join(ZERO_MODES)})")
    return mode


def manual_micro_reasons(engine):
    """Why this config still needs the flat-manual micro (empty tuple =
    the GSPMD-first micro applies).  Each entry is a composition whose
    correctness lives inside the full-manual region today — documented in
    docs/zero.md so the list shrinks deliberately, not silently."""
    plan = engine.plan
    reasons = []
    if engine.seq_parallel_world_size > 1 or engine.pp_world_size > 1:
        # the manual builder owns the loud sp/pp rejection text
        reasons.append("sp/pp axes (rejected by the manual builder)")
    if engine.mp_world_size > 1:
        reasons.append("tp > 1 (partial-manual micro)")
    if plan.param_mesh is not plan.mesh or plan.state_mesh is not plan.mesh:
        reasons.append("hpZ/MiCS shard groups (reshaped zp mesh)")
    moe_cfg = getattr(engine._config, "moe_config", None)
    if moe_cfg is not None and getattr(moe_cfg, "enabled", False):
        reasons.append("MoE manual-context expert dispatch")
    mesh = plan.mesh
    eff = [a for a in plan.zero_axes if mesh.shape.get(a, 1) > 1]
    if len(eff) > 1:
        reasons.append("multi-axis ZeRO group (dp×ep / hierarchical "
                       "in-body reduce)")
    return tuple(reasons)


def _lead_spec(entry, ndim):
    """P(entry, None, …) for a leading-dp-axis value of rank ``ndim``."""
    return P(*((entry, ) + (None, ) * (ndim - 1)))


def build_gspmd_quantized_micro(engine):
    """The GSPMD-first qgZ micro: ``micro(params, scale, inputs) ->
    (loss, grads)`` with grads in the master (ZeRO) layout — drop-in for
    the engine's compiled micro fn, loss/grad-bitwise-equal to
    ``build_manual_dp_micro`` on pure-dp meshes (unit-gated)."""
    from ...utils.logging import logger  # noqa: F401  (parity with zeropp)
    from ..utils import make_scaled_loss_fn
    from . import zeropp
    from .overlap import overlap_opts, prefetch_opts, resolve_prefetch
    from .partition import path_str, zero_dim

    plan = engine.plan
    zc = engine._config.zero_config
    co = engine._config.comm_optimizations_config
    co_on = getattr(co, "enabled", False)
    gas = engine.gradient_accumulation_steps()
    apply_fn = engine._effective_apply_fn()
    grad_dtype = engine.grad_accum_dtype
    mesh = plan.mesh
    dp_axes = tuple(a for a in plan.zero_axes if mesh.shape.get(a, 1) > 1)
    n = int(np.prod([mesh.shape[a] for a in dp_axes], dtype=np.int64)) \
        if dp_axes else 1
    lead = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)

    qw = (zc.zero_quantized_weights or
          (co_on and getattr(co, "quantized_weights", False))) \
        and engine.zero_stage >= 3
    qw_fmt, qw_gs = plan.param_wire(zc.zero_quantized_weights_format)
    qg_fmt, qg_gs = plan.grad_wire()

    ov = overlap_opts(co)
    pf = prefetch_opts(co)
    if pf is not None and engine.zero_stage < 3:
        pf = None  # the engine already warned once (same rule as GSPMD)
    pf_resolved = resolve_prefetch(pf, zc) if pf is not None else None

    loss_fn = make_scaled_loss_fn(apply_fn, gas)

    def reduce_island(path, g):
        """One leaf's quantized gradient reduce as a shrunken manual
        island: ``g`` is the leading-axis ``[n, *shape]`` per-rank grad;
        the body (this rank's full contribution) runs EXACTLY the manual
        micro's ``reduce_leaf`` collective — ``all_to_all_quant_reduce``
        at the ladder-resolved wire — and the region re-enters GSPMD in
        the master layout."""
        spec = plan.master_spec(g.shape[1:], path)
        leaf_axes = plan.leaf_zero_axes(path, dp_axes)
        dim, axes = zero_dim(spec, leaf_axes)
        if n <= 1:
            # single-rank group: the lone lane IS the reduced gradient
            return jnp.squeeze(g, axis=0).astype(grad_dtype)
        # ladder keys on the LOGICAL (full-leaf) message size, the same
        # quantity the manual micro's in-body g.size reports
        fmt = plan.wire_for_size(qg_fmt,
                                 (g.size // n) * g.dtype.itemsize)

        def body(gl):
            g0 = jnp.squeeze(gl, axis=0)
            if dim is None:
                return jax.lax.pmean(g0, dp_axes).astype(grad_dtype)
            # route via the zeropp module attribute so test spies (and
            # future codec swaps) see one canonical call site
            out = zeropp.all_to_all_quant_reduce(
                g0, axes, dim, n, wire_format=fmt, group_size=qg_gs)
            rest = tuple(a for a in leaf_axes if a not in axes)
            if rest:
                out = jax.lax.pmean(out, rest)
            return out.astype(grad_dtype)

        return gspmd_region(
            body, mesh=mesh, in_specs=_lead_spec(lead, g.ndim),
            out_specs=spec)(g)

    def micro(params, scale, inputs):
        n_tail = engine._n_replicated_batch_tail
        k = len(inputs) - n_tail
        batch, tail = inputs[:k], inputs[k:]
        resh = []
        for x in batch:
            xr = x.reshape((n, x.shape[0] // n) + x.shape[1:])
            if lead is not None:
                xr = jax.lax.with_sharding_constraint(
                    xr, NamedSharding(mesh, _lead_spec(lead, xr.ndim)))
            resh.append(xr)

        full = params
        if qw:
            # qwZ: the per-leaf quantized gather island (already GSPMD-
            # native); with prefetch armed it pipelines its own buckets
            full = zeropp.quantized_weight_gather(
                params, plan, wire_format=qw_fmt, group_size=qw_gs,
                prefetch=pf_resolved)
        elif pf_resolved is not None:
            # flat-wire stage-3 prefetch: the PR 9 gather markers emit
            # each bucket's all-gather inside the forward graph
            from .overlap import mark_gather_tree, prefetch_buckets_for
            buckets, window, _ = prefetch_buckets_for(params, plan,
                                                      pf_resolved)
            if buckets:
                full = mark_gather_tree(params,
                                        plan.gather_shardings(params),
                                        buckets, max_inflight=window)

        def slice_loss(p, s, sl, tl):
            return loss_fn(p, s, tuple(sl) + tuple(tl))

        vg = jax.vmap(jax.value_and_grad(slice_loss, has_aux=True),
                      in_axes=(None, None, 0, None))
        (_, losses), grads = vg(full, scale, tuple(resh), tail)

        if n <= 1:
            loss = losses[0]
        else:
            # pmean island: the exact loss-normalization primitive the
            # manual micro runs (bitwise parity over the scalar too)
            losses = jax.lax.with_sharding_constraint(
                losses, NamedSharding(mesh, P(lead)))
            loss = gspmd_region(
                lambda l: jax.lax.pmean(l[0], dp_axes), mesh=mesh,
                in_specs=P(lead), out_specs=P())(losses)

        if lead is not None:
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, _lead_spec(lead, g.ndim))),
                grads)
        if ov is not None and n > 1:
            # bucketed pipeline over the islands: bucket k's quantized
            # exchange fenced behind bucket k−max_inflight — the PR 8
            # scheduler, with islands as stage2 (buckets are sized on the
            # LOGICAL leaf shapes, i.e. the params tree)
            from .overlap import (bucket_bytes_of, pipelined_bucket_reduce,
                                  tree_buckets)
            buckets, _, _ = tree_buckets(params, bucket_bytes_of(ov))
            grads = pipelined_bucket_reduce(
                grads, buckets, lambda p, g: g, reduce_island,
                max_inflight=getattr(ov, "max_inflight", 2))
        else:
            grads = jax.tree_util.tree_map_with_path(
                lambda kp, g: reduce_island(path_str(kp), g), grads)
        return loss, grads

    return micro
