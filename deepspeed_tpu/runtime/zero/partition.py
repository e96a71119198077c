"""ZeRO partitioning as sharding policy.

The TPU-native heart of ZeRO (SURVEY.md §7 design stance): the reference's
flatten/bucket/hook machinery (``runtime/zero/stage_1_and_2.py:97``,
``stage3.py:111``, ``partition_parameters.py``) collapses into *sharding
functions* — given the ZeRO stage, produce ``NamedSharding``s for params /
gradients / optimizer state over the ZeRO mesh axes, and let GSPMD emit the
reduce-scatter / all-gather pipeline those files hand-roll:

  stage 0: params, grads, optimizer state replicated; grads all-reduced.
  stage 1: optimizer state (incl. fp32 master) sharded over dp.
  stage 2: + gradient accumulator sharded over dp → XLA emits reduce-scatter
           for the grad psum (reference ``average_tensor`` stage_1_and_2.py:1045).
  stage 3: + parameters sharded over dp → XLA all-gathers on use, exactly the
           fetch/release coordinator's job (partitioned_param_coordinator.py:276),
           scheduled statically by the latency-hiding scheduler.

Each tensor is sharded along its **largest divisible axis** (no flattening —
keeping the logical shape lets XLA pick layouts, and sidesteps the reference's
alignment/padding bookkeeping).  Tensors too small to split stay replicated —
the analog of the reference's persistent-small-param threshold
(``parameter_offload.py:249 mark_persistent_parameters``).
"""

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...utils.logging import logger

_PINNED_HOST_OK = {}


def _pinned_host_supported(mesh):
    """Functional probe: memory_kind='pinned_host' may *construct* on any
    backend but fail at SPMD compile (CPU does exactly this) — so compile a
    one-op program once per backend and cache the verdict."""
    import jax.numpy as jnp
    backend = jax.default_backend()
    if backend not in _PINNED_HOST_OK:
        try:
            s = NamedSharding(mesh, P(), memory_kind="pinned_host")
            jax.jit(lambda: jnp.zeros((8, ), jnp.float32),
                    out_shardings=s)()
            _PINNED_HOST_OK[backend] = True
        except Exception:
            _PINNED_HOST_OK[backend] = False
    return _PINNED_HOST_OK[backend]


def shard_spec(shape, mesh: Mesh, axes, min_size=1, base_spec=None):
    """PartitionSpec sharding ``shape``'s largest divisible dim over ``axes``.

    ``axes`` is a tuple of mesh axis names treated as one factored axis
    (e.g. ("dp", "sp") for seq-data-parallel ZeRO sharding, reference
    engine.py:1651).  ``base_spec`` (e.g. a tensor-parallel spec) is preserved:
    the ZeRO axes go to the largest *unclaimed* dim; a dim already sharded by
    base_spec divides its residual size.
    """
    if not shape:
        return base_spec if base_spec is not None else P()
    base = list(base_spec) if base_spec is not None else []
    base = base + [None] * (len(shape) - len(base))
    # Axes already claimed by the base spec are excluded: e.g. expert params
    # sharded over "ep" take ZeRO sharding over "dp" only — which is exactly
    # the reference's expert-DP reduction group (engine.py:2510
    # _reduce_expert_gradients).
    used = set()
    for ax in base:
        if ax is None:
            continue
        used.update(ax if isinstance(ax, tuple) else (ax, ))
    axes = tuple(a for a in axes if a not in used)
    if not axes:
        return P(*base)
    n = int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64))
    if n <= 1 or int(np.prod(shape, dtype=np.int64)) < min_size:
        return P(*base)
    # largest unclaimed dim divisible by n; ties → first
    best = None
    for i, d in sorted(enumerate(shape), key=lambda t: -t[1]):
        if base[i] is not None:
            continue
        if d % n == 0:
            best = i
            break
    if best is not None:
        base[best] = axes if len(axes) > 1 else axes[0]
        return P(*base)
    # No unclaimed dim fits: compose onto a claimed dim whose residual size
    # (after its existing axes) still divides n — keeps ZeRO sharding alive
    # when TP claimed the only divisible dim.
    for i, d in sorted(enumerate(shape), key=lambda t: -t[1]):
        if base[i] is None:
            continue
        existing = base[i] if isinstance(base[i], tuple) else (base[i], )
        claimed = int(np.prod([mesh.shape[a] for a in existing], dtype=np.int64))
        if d % (claimed * n) == 0:
            base[i] = existing + tuple(axes)
            return P(*base)
    return P(*base)


def zero_dim(spec, zero_axes):
    """Locate the dim of a PartitionSpec carrying ZeRO axes.  Returns
    ``(dim, axes_present)`` or ``(None, ())`` — the shared primitive behind
    the qwZ/qgZ leaf walkers (``zeropp.py``) and the collectives engine's
    per-leaf variant selection."""
    for i, entry in enumerate(spec):
        if entry is None:
            continue
        names = entry if isinstance(entry, tuple) else (entry, )
        present = tuple(a for a in names if a in zero_axes)
        if present:
            return i, present
    return None, ()


def gathered_spec(spec, zero_axes):
    """``spec`` with its ZeRO axes stripped from the zero dim — the leaf's
    sharding AFTER the stage-3 all-gather (tp and other non-ZeRO axes
    survive).  Persistent / unsharded leaves come back unchanged.  Shared
    by the qwZ gather wrappers (``zeropp``) and the forward prefetch
    markers (``overlap.mark_gather_tree``)."""
    dim, axes = zero_dim(spec, zero_axes)
    if dim is None:
        return spec
    entry = spec[dim]
    names = entry if isinstance(entry, tuple) else (entry, )
    kept = tuple(a for a in names if a not in axes)
    new = list(spec)
    new[dim] = kept if len(kept) > 1 else (kept[0] if kept else None)
    return P(*new)


def path_str(kp):
    """jax key-path → 'a/b/c' string for rule matching."""
    parts = []
    for k in kp:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def match_tp_rule(rules, path):
    """Match ``path`` against rule keys.

    Two rule kinds, which COMPOSE rather than compete:

    * exact suffix keys (``'q_proj/kernel'``) — longest suffix wins; the
      suffix must start at a '/' component boundary (so ``'wo/kernel'`` does
      not match ``'moe_two/kernel'``);
    * scope wildcards (``'scope/*'`` or ``'a/b/*'``) — match any path that
      contains that component sequence before the leaf; their spec claims the
      *leading* dims (e.g. the stacked-layer dim of pipeline blocks or the
      expert dim), and a simultaneously-matching exact rule's spec is appended after
      it (so ``'blocks/*': P('pp')`` + ``'q_proj/kernel': P(None,'tp',None)``
      → ``P('pp', None, 'tp', None)`` on a stacked param).
    """
    if not rules:
        return None
    best, best_len = None, -1
    scope_spec, scope_len = None, -1
    bounded = "/" + path
    for key, spec in rules.items():
        if key.endswith("/*"):
            scope = key[:-2]
            # component-boundary containment (multi-component scopes allowed)
            if ("/" + scope + "/") in bounded and len(key) > scope_len:
                scope_spec, scope_len = spec, len(key)
            continue
        if (path == key or path.endswith("/" + key)) and len(key) > best_len:
            best, best_len = spec, len(key)
    if scope_spec is not None and best is not None:
        return P(*tuple(scope_spec) + tuple(best))
    if scope_spec is not None:
        return scope_spec
    return best


def tree_shard_specs(tree, mesh, axes, min_size=1):
    return jax.tree_util.tree_map(
        lambda x: shard_spec(getattr(x, "shape", ()), mesh, axes, min_size), tree)


def tree_shardings(tree, mesh, axes, min_size=1):
    return jax.tree_util.tree_map(
        lambda x: NamedSharding(mesh, shard_spec(getattr(x, "shape", ()), mesh,
                                                 axes, min_size)), tree)


def replicated(mesh):
    return NamedSharding(mesh, P())


def tree_replicated(tree, mesh):
    return jax.tree_util.tree_map(lambda x: NamedSharding(mesh, P()), tree)


class ZeroPartitionPlan:
    """Sharding policy for one ZeRO stage over given mesh axes.

    ``tp_rules``: optional dict {path-suffix: PartitionSpec} adding
    tensor-parallel sharding (composed with ZeRO axes; the TP analog of
    module_inject).  ``min_partition_size``: params with fewer elements stay
    replicated (persistence threshold analog).
    """

    def __init__(self, stage, mesh, zero_axes=("dp", ), min_partition_size=1,
                 offload_optimizer=False, offload_param=False, tp_rules=None,
                 hpz_mesh=None, mics=False, comm_opts=None):
        self.stage = stage
        self.mesh = mesh
        self.zero_axes = tuple(a for a in zero_axes if mesh.shape.get(a, 1) >= 1)
        self.min_partition_size = min_partition_size
        self.offload_optimizer = offload_optimizer
        self.offload_param = offload_param
        # comm_optimizations config (duck-typed; see comm/collectives/) —
        # steers the wire format of the quantized ZeRO hot paths
        self.comm_opts = comm_opts
        # TP rules: path-suffix → PartitionSpec over the "tp" axis (AutoTP
        # analog, reference module_inject/auto_tp.py:273) — composed with the
        # ZeRO axes on every state tensor.
        self.tp_rules = tp_rules or {}
        # hpZ (ZeRO++ secondary partition, reference engine.py:906 + utils/
        # groups.py:531): *params* shard over only the intra-host "zp" factor
        # of dp — forward all-gathers ride short ICI hops — while master/grads
        # stay sharded over full dp.  MiCS (reference runtime/zero/mics.py):
        # ALL state shards over the "zp" shard group and replicates across
        # groups; gradients still average over full dp (GSPMD emits the
        # hierarchical allreduce automatically from the specs).
        self.param_mesh, self.param_axes = mesh, self.zero_axes
        self.state_mesh, self.state_axes = mesh, self.zero_axes
        if hpz_mesh is not None:
            from ...utils.groups import ZP_AXIS
            # zp replaces only the dp/ep factor; other ZeRO axes (e.g. "sp"
            # under Ulysses seq-dp sharding) survive — hpz_mesh carries them.
            extra = tuple(a for a in self.zero_axes if a not in ("dp", "ep"))
            zp_axes = (ZP_AXIS, ) + extra
            if mics:
                self.param_mesh = self.state_mesh = hpz_mesh
                self.param_axes = self.state_axes = zp_axes
            elif stage >= 3:
                self.param_mesh, self.param_axes = hpz_mesh, zp_axes
        from ... import telemetry as _telemetry
        if _telemetry.enabled:
            # re-plans (elastic rescale, hpZ factoring changes) land in the
            # trace as metadata; the engine also emits this at bring-up
            _telemetry.metadata("zero_partition_plan", self.describe())

    def describe(self):
        """JSON-safe summary of the sharding policy — trace metadata and
        the autotuner's record of what configuration produced a trace."""
        from .gspmd import resolve_zero_mode
        from .overlap import overlap_opts, prefetch_opts
        co = self.comm_opts
        ov = overlap_opts(co)
        pf = prefetch_opts(co)
        return {
            "stage": self.stage,
            "zero_mode": resolve_zero_mode(co),
            "zero_axes": list(self.zero_axes),
            "param_axes": list(self.param_axes),
            "state_axes": list(self.state_axes),
            "min_partition_size": int(self.min_partition_size),
            "offload_optimizer": bool(self.offload_optimizer),
            "offload_param": bool(self.offload_param),
            "tp_rules": len(self.tp_rules),
            "hierarchical_reduce": self.hierarchical_reduce(),
            "grad_wire": list(self.grad_wire()),
            "param_wire": list(self.param_wire()),
            "comm_optimizations_enabled": bool(
                co is not None and getattr(co, "enabled", False)),
            "overlap_enabled": bool(ov is not None),
            "overlap_bucket_mb": (float(getattr(ov, "bucket_mb", 0.0))
                                  if ov is not None else 0.0),
            "overlap_max_inflight": (int(getattr(ov, "max_inflight", 0))
                                     if ov is not None else 0),
            "prefetch_enabled": bool(pf is not None),
            "prefetch_bucket_mb": (float(getattr(pf, "bucket_mb", 0.0))
                                   if pf is not None else 0.0),
            "prefetch_max_inflight": (int(getattr(pf, "max_inflight", 0))
                                      if pf is not None else 0),
        }

    # wire formats ----------------------------------------------------------
    # The quantized ZeRO hot paths (zeropp.py qwZ/qgZ) ask the plan what to
    # put on the wire; ``comm_optimizations`` wins when it enabled the
    # corresponding traffic class, else the ZeRO++ legacy knobs/defaults.
    def _co_wire(self, flag):
        co = self.comm_opts
        if co is not None and getattr(co, "enabled", False) and \
                getattr(co, flag, False):
            return co.wire_dtype, co.quantization_group_size
        return None

    def grad_wire(self):
        """(wire_format, scale_group_size) for quantized gradient reduce."""
        from ...comm.collectives.quantized import DEFAULT_GROUP_SIZE
        return self._co_wire("quantized_gradients") or \
            ("int8", DEFAULT_GROUP_SIZE)

    def param_wire(self, fallback_format="int8"):
        """(wire_format, scale_group_size) for quantized param all-gather."""
        from ...comm.collectives.quantized import DEFAULT_GROUP_SIZE
        return self._co_wire("quantized_weights") or \
            (fallback_format, DEFAULT_GROUP_SIZE)

    def wire_for_size(self, default_fmt, nbytes):
        """Per-leaf wire format through the ``wire_dtype_by_size`` ladder
        (docs/autotuning.md): the first rung admitting ``nbytes`` logical
        bytes wins — ``"fp32"`` means this leaf rides the unquantized
        schedule — and ``default_fmt`` covers no-ladder configs and sizes
        above every rung.  This is the ZeRO-hot-path twin of
        ``CollectivesEngine.resolve_wire_dtype``: the same ladder the
        eager dispatch honors steers the qgZ/qwZ micro-step leaves, so an
        autotuned per-size choice is applied where the training traffic
        actually flows."""
        co = self.comm_opts
        if co is None or not getattr(co, "enabled", False):
            return default_fmt
        from ...comm.collectives.engine import (build_wire_ladder,
                                                resolve_in_ladder)
        if not hasattr(self, "_wire_ladder"):
            self._wire_ladder = build_wire_ladder(
                getattr(co, "wire_dtype_by_size", None))
        return resolve_in_ladder(self._wire_ladder, nbytes, default_fmt)

    def hierarchical_reduce(self):
        """True when comm_optimizations asks gradient reduction to run the
        2-hop (intra fp → inter quantized) scheme where the ZeRO group spans
        a multi-axis hierarchy (dp×ep, hpZ's zp_outer×zp)."""
        co = self.comm_opts
        return bool(co is not None and getattr(co, "enabled", False)
                    and getattr(co, "hierarchical_allreduce", False))

    # per-leaf axis bookkeeping ---------------------------------------------
    def rule_claimed_axes(self, path):
        """Mesh axes the matched tp rule pins for ``path`` — the expert
        stack's "ep" dim (``expert_sharding_rules``), tensor-parallel "tp"
        dims, ….  Those axes are MODEL parallelism for that leaf, not ZeRO
        data sharding: the stage-3 gather must not reassemble experts
        across ranks, and grad reduction must not average distinct experts
        (the reference's expert-DP split, ``moe/utils.py is_moe_param``)."""
        if not self.tp_rules or path is None:
            return ()
        rule = match_tp_rule(self.tp_rules, path)
        if rule is None:
            return ()
        names = []
        for entry in rule:
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry, )):
                if a is not None and a != "zero" and a not in names:
                    names.append(a)
        return tuple(names)

    def leaf_zero_axes(self, path, axes=None):
        """The ZeRO axes that actually apply to ``path``: the plan's axes
        minus the ones its rule claims (for non-rule leaves this is exactly
        ``param_axes`` — zero behavior change).  THE per-leaf notion every
        gather/reduce walker must key on (``zeropp``, the prefetch
        partitioner, ``gather_shardings``)."""
        axes = tuple(self.param_axes if axes is None else axes)
        claimed = self.rule_claimed_axes(path)
        if not claimed:
            return axes
        return tuple(a for a in axes if a not in claimed)

    # specs -----------------------------------------------------------------
    def _expand_rule(self, spec, shape, zero_axes, mesh):
        """Expand ``"zero"`` placeholders in a rule spec and sanitize.

        Rules may pin where the ZeRO shard lands with the pseudo-axis
        ``"zero"`` (e.g. llama's ``P(None, ('tp', 'zero'), 'zero')`` for a
        ``[D, H, Dh]`` kernel: at dp=4, 32 or 8 heads take the axis,
        ``P(None, ('tp', 'dp'), None)``; 2 heads cannot, and it falls to
        the head dim, ``P(None, 'tp', 'dp')``; never to dim 0).
        Placement matters beyond memory balance: a shard of whole lane tiles
        is written in place by the product the TPU compiler windows over
        the shards, one inside a tile (or on a dim the next op splits) is
        written piece by piece (models/llama.py ``tp_rules``); and
        ZeRO-sharding a matmul's *contracting* dim (or an embedding's hidden
        dim) makes GSPMD propagate hidden-dim sharding into the activations
        and then involuntarily full-rematerialize them back to batch/seq
        sharding at every norm boundary.  ``zero_axes`` is the stage-dependent expansion
        of the placeholder (empty → dropped): params expand it only at
        stage ≥3, master at ≥1, grads at ≥2.

        Sanitization is per-axis greedy (kv-head analog of reference
        ``module_inject/tp_shard.py``): an explicit axis the dim can't divide
        is dropped; zero axes are placed one by one while divisibility holds,
        drawing from a pool that excludes axes the rule claims elsewhere
        (e.g. 'ep' on expert params) and consuming placed axes so a
        placeholder appearing on two dims can't double-place.

        Returns ``(PartitionSpec, pinned)`` — ``pinned`` is True when the
        rule contains a placeholder and its placement is settled (zero axes
        landed, or there were none to place), i.e. the caller must not add
        heuristic ZeRO sharding on top.
        """
        used = set()
        for ax in spec:
            for a in (ax if isinstance(ax, tuple) else (ax, )):
                if a is not None and a != "zero":
                    used.add(a)
        pool = [a for a in zero_axes if a not in used]
        wanted = any("zero" in (ax if isinstance(ax, tuple) else (ax, ))
                     for ax in spec if ax is not None)
        placed = False
        out = []
        for i, ax in enumerate(spec):
            if ax is None or (shape is not None and i >= len(shape)):
                out.append(None)
                continue
            names = ax if isinstance(ax, tuple) else (ax, )
            dim = None if shape is None else shape[i]
            final, prod = [], 1
            for a in names:
                if a == "zero":
                    for z in list(pool):
                        n = mesh.shape.get(z, 1)
                        if n > 1 and (dim is None or dim % (prod * n) == 0):
                            final.append(z)
                            prod *= n
                            pool.remove(z)
                            placed = True
                    continue
                if a not in mesh.shape:
                    raise ValueError(
                        f"tp_rules references axis {a!r} not in mesh axes "
                        f"{tuple(mesh.shape)}")
                n = mesh.shape[a]
                if dim is None or dim % (prod * n) == 0:
                    final.append(a)
                    prod *= n
            out.append(tuple(final) if len(final) > 1
                       else (final[0] if final else None))
        return P(*out), (wanted and (placed or not zero_axes))

    def _spec_for(self, shape, path, mesh, axes, enabled):
        rule = (match_tp_rule(self.tp_rules, path)
                if path is not None else None)
        zero_axes = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
        if rule is None:
            base, pinned = None, False
        else:
            base, pinned = self._expand_rule(
                rule, shape, zero_axes if enabled else (), mesh)
        if not enabled:
            return base if base is not None else P()
        if pinned:
            return base
        # plain TP rule, no rule at all, or the pinned dim couldn't take any
        # zero axis → heuristic (shard_spec re-excludes base-claimed axes)
        return shard_spec(shape, mesh, axes, self.min_partition_size,
                          base_spec=base)

    def param_spec(self, shape, path=None):
        return self._spec_for(shape, path, self.param_mesh, self.param_axes,
                              self.stage >= 3)

    def master_spec(self, shape, path=None):
        """fp32 master weights + optimizer moments."""
        return self._spec_for(shape, path, self.state_mesh, self.state_axes,
                              self.stage >= 1)

    def grad_spec(self, shape, path=None):
        """Gradient accumulator sharding. Stage ≥2 shards grads (the engine's
        micro-step constrains grad outputs to this, making XLA lower the DP
        psum to reduce-scatter)."""
        return self._spec_for(shape, path, self.state_mesh, self.state_axes,
                              self.stage >= 2)

    # tree versions ---------------------------------------------------------
    def _memory_kind(self, offload):
        # Host offload: params/optimizer state resident in pinned host memory,
        # streamed to device per use (reference ZeRO-Offload; SURVEY.md §7
        # "pinned-host offload → memory kinds").
        if not offload:
            return None
        if not _pinned_host_supported(self.mesh):
            # LOUD fallback (round-1 review): an "offload enabled" config
            # silently running fully in HBM is an OOM trap at real scale
            if not getattr(self, "_offload_fallback_warned", False):
                self._offload_fallback_warned = True
                logger.warning(
                    "offload requested but memory_kind='pinned_host' does "
                    "not compile on this platform — STATE STAYS IN DEVICE "
                    "MEMORY; expect the HBM footprint of a non-offload run "
                    "(use offload device 'nvme' for managed disk residency)")
            return None
        return "pinned_host"

    def _sharding(self, spec, offload=False, mesh=None):
        mesh = mesh if mesh is not None else self.mesh
        kind = self._memory_kind(offload)
        if kind is not None:
            return NamedSharding(mesh, spec, memory_kind=kind)
        return NamedSharding(mesh, spec)

    def param_shardings(self, params):
        return jax.tree_util.tree_map_with_path(
            lambda kp, x: self._sharding(
                self.param_spec(x.shape, path_str(kp)),
                offload=self.offload_param and self.stage >= 3,
                mesh=self.param_mesh), params)

    def master_shardings(self, params):
        return jax.tree_util.tree_map_with_path(
            lambda kp, x: self._sharding(self.master_spec(x.shape, path_str(kp)),
                                         offload=self.offload_optimizer,
                                         mesh=self.state_mesh), params)

    def grad_shardings(self, params):
        return jax.tree_util.tree_map_with_path(
            lambda kp, x: self._sharding(self.grad_spec(x.shape, path_str(kp)),
                                         mesh=self.state_mesh),
            params)

    def gather_shardings(self, params):
        """``NamedSharding``s of the POST-gather layout — each leaf's param
        sharding minus the ZeRO axes (tp survives; persistent leaves keep
        their spec).  The forward-prefetch markers constrain to these, so
        XLA emits the stage-3 all-gather at the marker instead of at first
        use."""
        def one(kp, x):
            p = path_str(kp)
            # per-leaf axes: rule-claimed axes (expert "ep", tp) survive the
            # gather — only the leaf's own ZeRO axes are stripped
            return NamedSharding(
                self.param_mesh,
                gathered_spec(self.param_spec(x.shape, p),
                              self.leaf_zero_axes(p)))

        return jax.tree_util.tree_map_with_path(one, params)

    def micro_shardings(self, params, inputs=(), n_replicated_tail=0,
                        grads="grad"):
        """The FULL in/out ``NamedSharding`` set of ONE jitted micro-step
        — the GSPMD-first contract (ISSUE 15, docs/zero.md "GSPMD-first
        ZeRO"): params in their stage layout, the loss scale and
        engine-appended input tails replicated, batch inputs sharded over
        the ZeRO axes on their leading dim; out, the loss replicated and
        the gradients in the accumulator layout (``grads="grad"``, the
        GSPMD micro's constraint target) or the master partition
        (``grads="master"``, what the qgZ reduce islands and the manual
        micro emit).  Returned as ``((params, scale, inputs), (loss,
        grads))`` — exactly the ``jit(in_shardings=…, out_shardings=…)``
        pytrees for ``micro(params, scale, inputs) -> (loss, grads)``.

        Only meaningful on the plan's own mesh (hpZ/MiCS micros translate
        their own specs); the engine cross-checks the emitted set against
        the live arrays before arming it."""
        if grads not in ("grad", "master"):
            raise ValueError(f"micro_shardings grads={grads!r} must be "
                             "'grad' or 'master'")
        from ..utils import batch_input_specs
        mesh = self.mesh
        axes = tuple(a for a in self.zero_axes
                     if mesh.shape.get(a, 1) > 1) or self.zero_axes
        rep = NamedSharding(mesh, P())
        batch = tuple(NamedSharding(mesh, s)
                      for s in batch_input_specs(inputs, axes,
                                                 n_replicated_tail))
        grad_sh = (self.grad_shardings(params) if grads == "grad"
                   else self.master_shardings(params))
        return ((self.param_shardings(params), rep, batch), (rep, grad_sh))

    def param_specs(self, params):
        return jax.tree_util.tree_map_with_path(
            lambda kp, x: self.param_spec(x.shape, path_str(kp)), params)

    def master_specs(self, params):
        return jax.tree_util.tree_map_with_path(
            lambda kp, x: self.master_spec(x.shape, path_str(kp)), params)

    def grad_specs(self, params):
        return jax.tree_util.tree_map_with_path(
            lambda kp, x: self.grad_spec(x.shape, path_str(kp)), params)
