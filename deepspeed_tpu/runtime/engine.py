"""DeepSpeedEngine — the central training wrapper (L4).

TPU-native re-design of reference ``runtime/engine.py:183``.  The reference
wraps a torch module and intercepts autograd (``forward`` :1848, ``backward``
:2007, ``step`` :2204) with per-param hooks feeding bucketed collectives.  Here
the engine owns a **jitted SPMD train step** over the global mesh:

* ``forward(*inputs)``  — runs the compiled value_and_grad micro-step, stashes
  gradients on device, returns the loss;
* ``backward(loss)``    — folds the stashed grads into the (ZeRO-sharded)
  accumulator: stage ≥2 constrains the accumulator sharding so XLA lowers the
  DP gradient reduction to reduce-scatter (the ``average_tensor`` path,
  reference stage_1_and_2.py:1045);
* ``step()``            — at the grad-accum boundary (reference
  ``is_gradient_accumulation_boundary`` engine.py:2088) runs the compiled
  update: unscale → overflow check → clip → optimizer on the sharded fp32
  master partition → re-materialize compute params (all-gather for stage ≤2,
  still-sharded for stage 3) → dynamic loss-scale update.

ZeRO stages are *sharding policies* (``zero/partition.py``), not optimizer
subclasses; the optimizer is an optax-style transform from ``deepspeed_tpu.ops``.
"""

import collections
import os
import re
import tempfile
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm as dist
from .. import telemetry as _telemetry
from ..telemetry import names as _names
from ..accelerator import get_accelerator
from ..utils import groups
from ..utils.logging import log_dist, logger
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER,
                           STEP_GLOBAL_TIMER, NoopTimer,
                           SynchronizedWallClockTimer, ThroughputTimer)
from .config import (ADAGRAD_OPTIMIZER, ADAM_OPTIMIZER, ADAMW_OPTIMIZER,
                     DeepSpeedConfig, FUSED_ADAM_OPTIMIZER,
                     FUSED_LAMB_OPTIMIZER, LAMB_OPTIMIZER, LION_OPTIMIZER,
                     SGD_OPTIMIZER)
from .dataloader import DeepSpeedDataLoader
from .loss_scaler import create_loss_scaler, has_overflow
from .lr_schedules import get_lr_scheduler
from .utils import clip_grads_by_global_norm, count_parameters, global_grad_norm
from .zero.partition import ZeroPartitionPlan

MEMORY_OPT_ALLREDUCE_SIZE = 500000000


def _owned_host_tree(tree):
    """``jax.device_get`` that GUARANTEES owning numpy arrays.

    On the CPU backend device_get returns zero-copy views (``owndata=False``,
    dlpack capsule base) aliasing the live XLA buffer; an offload path that
    drops the device reference and later reads the "host copy" is then
    reading freed/donation-reused memory — observed as NaN losses or a
    hard interpreter abort after ``offload_states``.  Copy only when the
    result actually aliases, so real-device transfers stay single-copy."""
    def own(a):
        a = np.asarray(a)
        return a if a.flags.owndata else np.array(a, copy=True)
    return jax.tree_util.tree_map(own, jax.device_get(tree))


class _ParamGroup(dict):
    """torch-style param group whose ``["lr"] = x`` writes reach the compiled
    step: the engine routes the value into the optimizer state's runtime
    ``lr_override`` leaf (no recompile).  Reference torch schedulers mutate
    ``param_groups[0]["lr"]`` directly and FusedAdam honors it."""

    def __init__(self, engine, **kw):
        super().__init__(**kw)
        self._engine = engine

    def __setitem__(self, key, value):
        super().__setitem__(key, value)
        if key == "lr" and value is not None:
            self._engine._set_client_lr(float(value))

    # dict.update/setdefault bypass __setitem__ on subclasses — route them
    # through it, or an update({"lr": x}) would be silently inert (the
    # round-2 bug class this facade exists to fix)
    def update(self, *args, **kw):
        for k, v in dict(*args, **kw).items():
            self[k] = v

    def setdefault(self, key, default=None):
        if key not in self:
            self[key] = default
        return self[key]


class _OptimizerFacade:
    """torch-optimizer-shaped view of the engine's optimizer state, for user
    code that expects ``initialize()``'s second return value (reference returns
    the wrapped torch optimizer).  ``param_groups`` exposes lr for schedulers
    written against the torch API; writes take effect (see ``_ParamGroup``)."""

    def __init__(self, engine):
        self._engine = engine
        self.param_groups = [_ParamGroup(engine, lr=None)]

    def state_dict(self):
        return {"opt_state": self._engine.opt_state}

    def load_state_dict(self, sd):
        self._engine.opt_state = sd["opt_state"]

    def zero_grad(self, set_to_none=True):
        pass  # accumulator zeroing happens inside the compiled step

    def step(self):
        self._engine.step()

    @property
    def loss_scale(self):
        return self._engine.cur_scale


def _is_flax_module(model):
    try:
        import flax.linen as nn
        return isinstance(model, nn.Module)
    except ImportError:
        return False


def _named_program(fn, name):
    """``fn`` under a stable ``__name__``: jit names the compiled program
    after it (``jit_ds_micro_flat``), which is how a profiler's module line
    tells the micro-step from the optimizer step (telemetry/names.py)."""
    def program(*args):
        return fn(*args)

    program.__name__ = program.__qualname__ = re.sub(r"\W", "_", name)
    return program


class DeepSpeedEngine:

    def __init__(self,
                 args=None,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 collate_fn=None,
                 config=None,
                 mpu=None,
                 dont_change_device=False,
                 tp_rules=None):
        if not isinstance(config, DeepSpeedConfig):
            config = DeepSpeedConfig(config)
        self._config = config
        self.client_model = model
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training = True
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self._stashed_grads = None
        self._flops_profiled = False
        self.flops_profiler = None
        self._compiled_micro = {}
        self._compiled_apply = None
        self._compiled_eval = {}
        self._micro_cost = {}     # shape key → cost-model entry (MFU feed)
        self._apply_cost = None
        # compression / user hooks
        self._param_transforms = []   # differentiable params→params, in fwd
        self._post_step_hooks = []    # called after each optimizer step

        # ---------------------------------------------------------- bring-up
        # (reference initialize() :143-146 → init_distributed; :153-162 mesh)
        mc = config.mesh_config
        zc = config.zero_config
        # hpZ secondary partition and MiCS shard groups both factor dp into
        # (outer, inner) — one reshaped mesh serves either.
        if zc.mics_shard_size and zc.mics_shard_size > 1 and \
                zc.zero_hpz_partition_size > 1 and \
                zc.zero_hpz_partition_size != zc.mics_shard_size:
            raise ValueError(
                f"mics_shard_size={zc.mics_shard_size} and "
                f"zero_hpz_partition_size={zc.zero_hpz_partition_size} are "
                "mutually exclusive shard-group factorings")
        zp_size = (zc.mics_shard_size if zc.mics_shard_size and
                   zc.mics_shard_size > 1 else zc.zero_hpz_partition_size)
        # multi-process rendezvous FIRST — the mesh below must see the
        # federated device view (reference order: init_distributed :143
        # before mesh :153)
        dist.ensure_runtime_initialized()
        rebuild = None
        if groups.mesh_is_initialized():
            # An earlier model.init / eager op may have auto-built the
            # default dp-only mesh.  If the config EXPLICITLY requests a
            # different factorization, silently keeping the stale mesh
            # would train with sp/tp/pp = 1 while the user asked otherwise
            # — rebuild instead (arrays re-placed by the engine's own
            # device_puts).  Config dims left at their defaults MERGE from
            # the current mesh (a deliberately pre-built tp=2 survives a
            # config that only names sp), and dims the config and mesh
            # agree on never force a rebuild.
            want = {"pp": mc.pp, "sp": mc.sp, "tp": mc.tp, "ep": mc.ep}
            if mc.dp not in (-1, None):
                want["dp"] = mc.dp
            # compare against MeshState TOTALS, not Mesh.shape — the grid's
            # dp axis is dp_total/ep, so shape-based comparison would flag
            # a spurious dp mismatch on every ep>1 mesh
            ms = groups.get_mesh_state()
            cur = {"pp": ms.pp, "dp": ms.dp, "sp": ms.sp, "tp": ms.tp,
                   "ep": ms.ep}
            mismatch = {k: v for k, v in want.items()
                        if v and v > 1 and cur.get(k, 1) != v}
            if mismatch:
                rebuild = {k: (want[k] if want.get(k, 1) and
                               want.get(k, 1) > 1 else cur.get(k, 1))
                           for k in ("pp", "sp", "tp", "ep")}
                rebuild["dp"] = want.get("dp")  # None → re-derive remaining
                logger.warning(
                    f"mesh already initialized as {cur} but the config "
                    f"explicitly requests {mismatch}; rebuilding as "
                    f"{ {k: v for k, v in rebuild.items() if v} } "
                    "(config dims merged over the existing mesh)")
                groups.reset_mesh()
                dist.destroy_process_group()
        if not groups.mesh_is_initialized():
            m = rebuild or {
                "pp": mc.pp, "sp": mc.sp, "tp": mc.tp, "ep": mc.ep,
                "dp": None if mc.dp in (-1, None) else mc.dp}
            groups.initialize_mesh(
                pp=m["pp"], dp=m["dp"], sp=m["sp"], tp=m["tp"], ep=m["ep"],
                zero_partition_size=zp_size)
        elif zp_size and zp_size > 1 and \
                groups.get_mesh_state().zero_partition_size != zp_size:
            # a pre-initialized mesh without the matching dp factoring would
            # silently drop hpZ/MiCS — fail loudly instead
            raise ValueError(
                f"config requests zero partition groups of {zp_size} but the "
                f"mesh was pre-initialized with zero_partition_size="
                f"{groups.get_mesh_state().zero_partition_size}; pass "
                "zero_partition_size to groups.initialize_mesh()")
        dist.init_distributed(config=config)
        self.mesh = groups.get_global_mesh()
        self.dp_world_size = groups._get_data_parallel_world_size()
        self.seq_parallel_world_size = groups._get_sequence_parallel_world_size()
        self.mp_world_size = groups._get_model_parallel_world_size()
        self.pp_world_size = groups._get_pipe_parallel_world_size()

        config.resolve_batch_sizes(self.dp_world_size)

        # ------------------------------------------------------- precision
        if config.bfloat16_enabled:
            self.compute_dtype = jnp.bfloat16
        elif config.fp16_enabled:
            self.compute_dtype = jnp.float16
        else:
            self.compute_dtype = jnp.float32
        self.loss_scaler = create_loss_scaler(
            config.fp16_enabled, config.loss_scale,
            config.dynamic_loss_scale_args)
        self.grad_accum_dtype = {
            None: jnp.float32, "fp32": jnp.float32,
            "fp16": jnp.float16, "bf16": jnp.bfloat16,
        }[config.gradient_accumulation_dtype]

        # ---------------------------------------------------------- model fn
        # (reference _configure_distributed_model engine.py:1145: dtype cast +
        # device move; here: build apply_fn + cast/shard params)
        self.module = model
        # counts a model makes on the device in a training micro-step
        # (models/smallthinker.py): their names, and the arrays of the
        # micro-steps not yet booked on a span (_ready_device_counts)
        self._device_count_names = tuple(
            getattr(model, "device_counts", None) or ())
        self._pending_counts = collections.deque()
        if _is_flax_module(model):
            def apply_fn(params, *inputs, rngs=None, **kw):
                variables = {"params": params}
                return model.apply(variables, *inputs, rngs=rngs, **kw)
            self._apply_fn = apply_fn
            self._flax = True
        elif callable(model):
            self._apply_fn = model
            self._flax = False
        else:
            raise TypeError(
                "model must be a flax Module or a callable f(params, *inputs)")

        # ZeRO partition plan (stage → sharding policy)
        zero_axes = groups.zero_sharding_axes(
            sequence_parallel=self.seq_parallel_world_size > 1)
        self.zero_stage = zc.stage
        if tp_rules is None:
            tp_rules = getattr(model, "tp_sharding_rules", None)
        # ------------------------------------------------------------- MoE
        # (docs/moe.md) — install the expert-parallel dispatch options and
        # make expert stacks shard over "ep" without hand-plumbed rules.
        # moe.enabled: false resets the dispatcher to the flat GSPMD path
        # (bit-identical program).
        from ..moe import engine as moe_engine
        moe_cfg = config.moe_config
        moe_engine.configure(moe_cfg if moe_cfg.enabled else None,
                             comm_opts=config.comm_optimizations_config)
        if moe_cfg.enabled:
            from ..moe.experts import expert_sharding_rules
            tp_rules = {**expert_sharding_rules(), **(tp_rules or {})}
        # per-step noisy-gate rng threaded through flax apply (the RSample/
        # Jitter policies were a silent no-op unless callers hand-plumbed an
        # rng); the key rides the input tail like PLD's, folded per layer by
        # flax's scope-path mixing in make_rng
        self._moe_gating_tail = bool(moe_cfg.enabled and _is_flax_module(
            model))
        self._moe_gating_key = jax.random.PRNGKey(
            moe_cfg.gating_seed if moe_cfg.gating_seed is not None
            else config._param_dict.get("seed", 1234)) \
            if self._moe_gating_tail else None
        self.plan = ZeroPartitionPlan(
            stage=zc.stage, mesh=self.mesh, zero_axes=zero_axes,
            tp_rules=tp_rules,
            min_partition_size=max(1, zc.param_persistence_threshold // 8),
            # NVMe residency is managed by the step-wired swapper, not by
            # memory-kind annotations (those are for host-RAM offload)
            offload_optimizer=(zc.offload_optimizer is not None
                               and str(zc.offload_optimizer.device) == "cpu"),
            offload_param=(zc.offload_param is not None
                           and zc.offload_param.device != "none"),
            # only when the config asked for it — a pre-initialized mesh may
            # carry an hpz factoring this engine did not request
            hpz_mesh=(groups.get_mesh_state().hpz_mesh
                      if zp_size and zp_size > 1 else None),
            mics=bool(zc.mics_shard_size and zc.mics_shard_size > 1),
            comm_opts=config.comm_optimizations_config)

        # legacy curriculum learning (reference engine exposes a
        # CurriculumScheduler when "curriculum_learning" is configured)
        self.curriculum_scheduler = None
        if self._config.curriculum_enabled_legacy:
            from .data_pipeline.curriculum_scheduler import CurriculumScheduler
            params = {k: v for k, v in
                      self._config.curriculum_params_legacy.items()
                      if k != "enabled"}
            self.curriculum_scheduler = CurriculumScheduler(params)

        ac = self._config.activation_checkpointing_config
        if ac.partition_activations or ac.cpu_checkpointing or \
                ac.contiguous_memory_optimization or ac.number_checkpoints:
            from .activation_checkpointing import configure as ac_configure
            ac_configure(deepspeed_config=self._config)

        # ------------------------------------------------------- parameters
        self.params = None
        self.master = None
        self.opt_state = None
        self.grad_acc = None
        self.scale_state = None
        self._pending_client_lr = None  # torch-API param_groups lr write
        self._last_loss = None          # reported loss for monitor events
        self._micro_losses = []         # gas-window losses (device scalars)
        self._configure_nvme_swapper(zc)
        if model_parameters is not None:
            self._install_parameters(model_parameters)

        # -------------------------------------------------------- optimizer
        self.optimizer = None
        self._grad_transform = None
        self._configure_optimizer(optimizer)

        # ------------------------------------------------------- scheduler
        self.lr_scheduler = self._configure_lr_scheduler(lr_scheduler)

        # ------------------------------------------------------- dataloader
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(
                training_data, collate_fn=collate_fn)

        # ---------------------------------------------------------- timers
        self.wall_clock_breakdown_enabled = config.wall_clock_breakdown
        self.timers = (SynchronizedWallClockTimer()
                       if config.wall_clock_breakdown else NoopTimer())
        self.tput_timer = ThroughputTimer(
            config=type("C", (), {"enabled": True})(),
            batch_size=config.train_batch_size,
            steps_per_output=config.steps_per_print)

        # ---------------------------------------------------------- monitor
        from ..monitor.monitor import MonitorMaster
        self.monitor = MonitorMaster(config.monitor_config)

        # --------------------------------------------------------- telemetry
        # (docs/observability.md) — enabling it wires the structured-event
        # spine: step spans + JSONL records, comm attribution, metrics
        # registry with the monitor as a sink.  Reading loss/grad-norm for
        # the step record costs one device sync per boundary, same as the
        # finite-grad guard; disabled (default) every emit site below is a
        # single module-attribute check.
        self._tel_step_tokens = 0
        self._tel_step_flops = 0.0       # Σ compiled flops this boundary
        self._tel_flops_incomplete = False
        self._mem_planner_emitted = False
        # "sequence_length" (top-level config key, docs/observability.md):
        # tokens per sample for the step records' token accounting.  Unset,
        # the engine ASSUMES axis 1 of inputs[0] is the sequence — loudly,
        # once (see _count_batch_tokens); token-rate metrics are omitted
        # (None, not garbage) when no defensible count exists.
        self.sequence_length = config.sequence_length
        self._seq_len_warned = False
        tc = config.telemetry_config
        if tc.enabled:
            _telemetry.configure(tc, monitor=self.monitor,
                                 rank=jax.process_index())
            _telemetry.metadata("mesh", {k: int(v) for k, v in
                                         dict(self.mesh.shape).items()})
            _telemetry.metadata("zero_partition_plan", self.plan.describe())
            _telemetry.metadata("config_hash", config.config_hash())
            _telemetry.gauge(
                "train/zero_stage",
                help="configured ZeRO stage").set(self.zero_stage)

        # -------------------------------------------------------- resilience
        rs = config.resilience_config
        self._finite_guard = rs.check_finite_grads
        self._consecutive_skips = 0
        self._gnorm_ema = None   # host-side running mean for spike detection
        if self._finite_guard.enabled and self._onebit_opt is not None:
            raise ValueError(
                "resilience.check_finite_grads is not supported with 1-bit "
                "optimizers (their apply path manages its own skip logic); "
                "disable one of them")
        self._heartbeat = None
        from ..elasticity.watchdog import HEARTBEAT_DIR_ENV
        hb_dir = rs.watchdog.heartbeat_dir or os.environ.get(
            HEARTBEAT_DIR_ENV, "")
        if (rs.watchdog.enabled or HEARTBEAT_DIR_ENV in os.environ) \
                and hb_dir:
            from ..elasticity.watchdog import HeartbeatWriter
            self._heartbeat = HeartbeatWriter(hb_dir,
                                              rank=jax.process_index())
        elif rs.watchdog.enabled:
            logger.warning(
                "resilience.watchdog enabled but no heartbeat_dir "
                "configured and DS_TPU_HEARTBEAT_DIR is unset — no "
                "heartbeats will be written (run under the elastic agent "
                "or set resilience.watchdog.heartbeat_dir)")

        # ------------------------------------------- progressive layer drop
        pld_cfg = getattr(config, "pld_config", None)
        if pld_cfg is not None and pld_cfg.enabled:
            import inspect
            target = model.__call__ if self._flax else model
            # non-flax models additionally receive the rng key explicitly
            # (flax models get it via the "pld" rng collection)
            needed = (("pld_theta", ) if self._flax
                      else ("pld_theta", "pld_rng"))
            try:
                sig_params = inspect.signature(target).parameters
                has_var_kw = any(p.kind == inspect.Parameter.VAR_KEYWORD
                                 for p in sig_params.values())
                accepts = has_var_kw or all(n in sig_params for n in needed)
            except (TypeError, ValueError):
                accepts = True  # unintrospectable callables get benefit of doubt
            if not accepts:
                raise ValueError(
                    "progressive_layer_drop is enabled but the model does "
                    f"not accept {' and '.join(needed)} keyword(s) — use "
                    "PLD-aware layers (e.g. DeepSpeedTransformerLayer) or "
                    "disable it")
            from .progressive_layer_drop import ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=pld_cfg.theta, gamma=pld_cfg.gamma)
        else:
            self.progressive_layer_drop = None
        # the PLD theta scalar + rng key (and the MoE gating key before
        # them) ride the END of the micro's input tuple and are replicated
        # (not dp-sharded) by the manual-SPMD micros (qgZ / 1-bit) —
        # reference composes PLD with comm compression the same way
        # (engine-level curriculum, orthogonal)
        self._n_replicated_batch_tail = (
            2 if self.progressive_layer_drop is not None else 0)
        if self._moe_gating_tail:
            self._n_replicated_batch_tail += 1

        # ----------------------------------------------- eigenvalue (compression)
        eig_cfg = getattr(config, "eigenvalue_config", None)
        if eig_cfg is not None and eig_cfg.enabled:
            from .eigenvalue import Eigenvalue
            self.eigenvalue = Eigenvalue(
                verbose=eig_cfg.verbose, max_iter=eig_cfg.max_iter,
                tol=eig_cfg.tol, stability=eig_cfg.stability,
                gas_boundary_resolution=eig_cfg.gas_boundary_resolution,
                layer_name=eig_cfg.layer_name, layer_num=eig_cfg.layer_num)
        else:
            self.eigenvalue = None
        self.block_eigenvalue = None

        if model_parameters is not None:
            log_dist(
                f"DeepSpeedEngine ready: zero_stage={self.zero_stage} "
                f"dtype={self.compute_dtype.__name__} mesh={dict(self.mesh.shape)} "
                f"params={count_parameters(self.params):,}", ranks=[0])

    # ------------------------------------------------------------------ setup
    def _install_parameters(self, model_parameters):
        """Cast + shard the parameter pytree per the ZeRO plan (the analog of
        zero.Init partitioning, reference partition_parameters.py:816 — params
        are 'born partitioned' via device_put with sharded layouts)."""
        mixed = self.compute_dtype != jnp.float32
        param_shardings = self.plan.param_shardings(model_parameters)

        def owned_copy(tree, dtype, shardings):
            # a compiled copy, NOT device_put: device_put may alias the
            # caller's buffers, which the donated apply-step later deletes —
            # the engine must own its state outright
            cast = jax.tree_util.tree_map(
                lambda p: jnp.asarray(p, dtype=dtype), tree)
            return jax.jit(
                lambda t: jax.tree_util.tree_map(jnp.copy, t),
                out_shardings=shardings)(cast)

        self.params = owned_copy(model_parameters, self.compute_dtype,
                                 param_shardings)
        if mixed or self.zero_stage >= 1:
            master_shardings = self.plan.master_shardings(model_parameters)
            self.master = owned_copy(model_parameters, jnp.float32,
                                     master_shardings)
        else:
            self.master = None  # pure fp32 stage-0: params are the master
        # Gradient accumulator is allocated lazily: the first backward()'s
        # stashed grads (already cast + sharded by the micro-step) become the
        # accumulator, so gas=1 never materializes a second grad buffer.
        self.grad_acc = None
        # Replicated commit avoids the 2nd-call full micro-step recompile
        # (observed as two 33MB jit_micro executables / 2× the compile
        # time) — see commit_scale_state.
        from .loss_scaler import commit_scale_state
        self.scale_state = commit_scale_state(self.mesh,
                                              self.loss_scaler.init())

    def initialize_parameters(self, rng_or_seed, *sample_inputs, **kw):
        """Flax path: init params on the engine's mesh (zero.Init analog —
        with stage 3 the fp32 master is created directly into its shards)."""
        if not self._flax:
            raise RuntimeError("initialize_parameters requires a flax Module")
        rng = (jax.random.PRNGKey(rng_or_seed)
               if isinstance(rng_or_seed, int) else rng_or_seed)
        variables = jax.eval_shape(self.module.init, rng, *sample_inputs, **kw)
        params_shape = variables["params"]
        if self.mp_world_size > 1 and not self.plan.tp_rules:
            # tp>1 with no hand-written rules: derive them from the model's
            # dataflow (reference auto_tp.py:273 tp_parser analog)
            from ..module_inject.tp_parser import derive_tp_rules_from_dataflow
            self.plan.tp_rules = derive_tp_rules_from_dataflow(
                lambda p, *i: self.module.apply({"params": p}, *i, **kw),
                params_shape, *sample_inputs)
            log_dist(f"AutoTP derived {len(self.plan.tp_rules)} sharding "
                     f"rules from dataflow", ranks=[0])
        shardings = self.plan.master_shardings(params_shape)

        def init_fn(rng):
            return self.module.init(rng, *sample_inputs, **kw)["params"]

        params = jax.jit(init_fn, out_shardings=shardings)(rng)
        self._install_parameters(params)
        if self.optimizer is None or self.opt_state is None:
            self._configure_optimizer(self.client_optimizer)
        return self.params

    def _configure_optimizer(self, client_optimizer):
        """Reference ``_configure_optimizer`` engine.py:1280 +
        ``_configure_basic_optimizer`` :1330 (config name → optimizer)."""
        from ..ops.adam import fused_adam
        from ..ops.lamb import fused_lamb
        from ..ops.lion import fused_lion, sgd
        from ..ops.muon import muon
        self._host_opt_desc = None   # set for host-steppable optimizers
        from .config import (MUON_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER,
                             ONEBIT_LAMB_OPTIMIZER, ZERO_ONE_ADAM_OPTIMIZER)

        cfg = self._config
        lr_fn = None
        if cfg.scheduler_name is not None:
            sched = get_lr_scheduler(cfg.scheduler_name, cfg.scheduler_params)
            lr_fn = sched.get_lr
            self._sched_for_lr = sched

        self._onebit_opt = None
        onebit_map = {}
        try:
            from .fp16.onebit import OnebitAdam, OnebitLamb, ZeroOneAdam
            onebit_map = {ONEBIT_ADAM_OPTIMIZER: OnebitAdam,
                          ONEBIT_LAMB_OPTIMIZER: OnebitLamb,
                          ZERO_ONE_ADAM_OPTIMIZER: ZeroOneAdam}
        except ImportError:
            pass
        if cfg.optimizer_name in onebit_map and client_optimizer is None:
            p = dict(cfg.optimizer_params or {})
            self._onebit_opt = onebit_map[cfg.optimizer_name](lr_fn=lr_fn, **p)
            self._grad_transform = None
            self.optimizer = _OptimizerFacade(self)
            if self.params is not None:
                self._init_onebit_state()
            return

        if client_optimizer is not None:
            self._grad_transform = client_optimizer
        elif cfg.optimizer_name is not None:
            p = dict(cfg.optimizer_params or {})
            name = cfg.optimizer_name
            lr = p.pop("lr", 1e-3)
            if name in (ADAM_OPTIMIZER, FUSED_ADAM_OPTIMIZER, ADAMW_OPTIMIZER):
                adam_w = p.pop("adam_w_mode", name == ADAMW_OPTIMIZER or
                               name == FUSED_ADAM_OPTIMIZER)
                betas = tuple(p.pop("betas", (0.9, 0.999)))
                eps = p.pop("eps", 1e-8)
                wd = p.pop("weight_decay", 0.0)
                bc = p.pop("bias_correction", True)
                self._grad_transform = fused_adam(
                    lr=lr, betas=betas, eps=eps, weight_decay=wd,
                    adam_w_mode=adam_w, bias_correction=bc, lr_fn=lr_fn)
                if bc:
                    # host-steppable: the native CPU kernel implements
                    # exactly this bias-corrected update
                    self._host_opt_desc = ("adam", dict(
                        lr=lr, betas=betas, eps=eps, weight_decay=wd,
                        adamw_mode=adam_w))
            elif name in (LAMB_OPTIMIZER, FUSED_LAMB_OPTIMIZER):
                self._grad_transform = fused_lamb(
                    lr=lr, betas=tuple(p.pop("betas", (0.9, 0.999))),
                    eps=p.pop("eps", 1e-8),
                    weight_decay=p.pop("weight_decay", 0.0),
                    max_coeff=p.pop("max_coeff", 10.0),
                    min_coeff=p.pop("min_coeff", 0.01), lr_fn=lr_fn)
            elif name == LION_OPTIMIZER:
                betas = tuple(p.pop("betas", (0.9, 0.99)))
                wd = p.pop("weight_decay", 0.0)
                self._grad_transform = fused_lion(
                    lr=lr, betas=betas, weight_decay=wd, lr_fn=lr_fn)
                self._host_opt_desc = ("lion", dict(
                    lr=lr, betas=betas, weight_decay=wd))
            elif name == SGD_OPTIMIZER:
                self._grad_transform = sgd(
                    lr=lr, momentum=p.pop("momentum", 0.0),
                    weight_decay=p.pop("weight_decay", 0.0), lr_fn=lr_fn)
            elif name == ADAGRAD_OPTIMIZER:
                from ..ops.adagrad import fused_adagrad
                eps = p.pop("eps", 1e-10)
                wd = p.pop("weight_decay", 0.0)
                self._grad_transform = fused_adagrad(
                    lr=lr, eps=eps, weight_decay=wd, lr_fn=lr_fn)
                self._host_opt_desc = ("adagrad", dict(
                    lr=lr, eps=eps, weight_decay=wd))
            elif name == MUON_OPTIMIZER:
                self._grad_transform = muon(
                    lr=lr, momentum=p.pop("momentum", 0.95),
                    nesterov=p.pop("nesterov", True),
                    ns_steps=p.pop("ns_steps", 5),
                    weight_decay=p.pop("weight_decay", 0.0), lr_fn=lr_fn)
            else:
                raise ValueError(f"unsupported optimizer {name!r} (have: adam, "
                                 "adamw, fusedadam, lamb, fusedlamb, lion, "
                                 "sgd, muon, adagrad)")
        else:
            self._grad_transform = fused_adam(lr=1e-3, lr_fn=lr_fn)

        self.optimizer = _OptimizerFacade(self)
        if self.params is not None:
            target = self.master if self.master is not None else self.params
            opt_shardings = jax.tree_util.tree_map(
                lambda _: None, target)  # let jit place it like its param
            self.opt_state = jax.jit(
                self._grad_transform.init,
                out_shardings=self._opt_state_shardings(target))(target)
            if self._pending_client_lr is not None:
                self._set_client_lr(self._pending_client_lr)
            if self._nvme_swapper is not None:
                # NVMe offload: state leaves HBM right away (reference
                # stage3.py swaps states out at init, not lazily)
                self._nvme_swap_out()

    # ----------------------------------------------------- NVMe state offload
    def _configure_nvme_swapper(self, zc):
        """Optimizer-state NVMe offload (reference ``stage3.py:1926``
        ``_optimizer_states_and_gradient_swap_in`` + ``swap_tensor/
        partitioned_optimizer_swapper.py``): fp32 master + moments live on
        disk between steps; ``step()`` swaps them in (async reads launched at
        the last ``backward()`` so disk latency overlaps the bwd compute
        tail) and swaps them back out after the update (async writes overlap
        the next forward)."""
        self._nvme_swapper = None
        self._nvme_prefetch = None
        self._state_on_nvme = False
        oo = zc.offload_optimizer
        if oo is not None and str(oo.device) == "nvme":
            from .swap_tensor import PartitionedOptimizerSwapper
            base = oo.nvme_path or os.path.join(
                tempfile.gettempdir(), "ds_tpu_nvme")
            swap_dir = os.path.join(
                str(base), f"zero_stage_{zc.stage}",
                f"rank{jax.process_index()}")
            self._nvme_swapper = PartitionedOptimizerSwapper(swap_dir)
            log_dist(f"NVMe optimizer-state offload → {swap_dir}", ranks=[0])

    def _nvme_swap_out(self):
        """Move (master, opt_state) HBM → disk; async writes, device buffers
        released immediately (this is what shrinks the HBM footprint)."""
        tree = {"master": self.master, "opt_state": self.opt_state}
        host = _owned_host_tree(tree)
        self.master = None
        self.opt_state = None
        self._state_on_nvme = True
        self._nvme_swapper.swap_out_tree(host)

    def _nvme_start_swap_in(self):
        if self._nvme_prefetch is None:
            self._nvme_prefetch = self._nvme_swapper.swap_in_tree_async()

    def _ensure_state_resident(self):
        """Bring offloaded state (host via offload_states, or NVMe) back to
        device refs.  Used by step(), checkpointing, and fragment APIs."""
        if getattr(self, "_host_offloaded", None):
            self.reload_states()
        if self._nvme_swapper is None or not self._state_on_nvme:
            return
        self._nvme_start_swap_in()
        tree = self._nvme_swapper.finish_swap_in(self._nvme_prefetch)
        self._nvme_prefetch = None
        self.master = tree["master"]
        self.opt_state = tree["opt_state"]
        self._state_on_nvme = False

    def _try_host_offload_step(self):
        """Host-side optimizer step for the NVMe/host optimizer-state offload
        path (reference ``csrc/adam/cpu_adam_impl.cpp`` +
        ``stage_1_and_2.py:1186``): when master + moments are host-resident,
        run the native SIMD kernels against the host fp32 state and upload
        ONLY the re-cast compute params — the fp32 state never round-trips
        through HBM.  Per-step device traffic drops
        from ~24 bytes/param (master+moments down *and* up) to
        grad-down + param-up (≈4-8 bytes/param).

        Returns the host grad-norm when it ran, else None (caller falls back
        to the compiled device apply)."""
        if self._nvme_swapper is None or not self._state_on_nvme or \
                self.grad_acc is None:
            return None
        if os.environ.get("DS_TPU_HOST_OFFLOAD_STEP", "1") == "0":
            return None   # A/B escape hatch: force the device apply path
        desc = getattr(self, "_host_opt_desc", None)
        if desc is None or self._config.fp16_enabled or \
                self._param_transforms or \
                getattr(self, "_host_offloaded", None) or \
                self._finite_guard.enabled or \
                jax.process_count() > 1:
            # dynamic loss scaling / QAT transforms / finite-grad guard /
            # multi-host keep the compiled device path (each would need its
            # own host pass — the guard's skip-select in particular)
            return None
        name, p = desc
        from ..ops import cpu_optimizers as K
        # grads → host (the ONLY device→host bytes on this path)
        grads = jax.device_get(self.grad_acc)
        param_shardings = self.plan.param_shardings(self.grad_acc)
        self.grad_acc = None
        self._nvme_start_swap_in()
        tree = self._nvme_swapper.finish_swap_in(self._nvme_prefetch)
        self._nvme_prefetch = None
        master, opt = tree["master"], tree["opt_state"]
        inv = 1.0 / float(np.asarray(self.scale_state.scale))

        def writable_f32(a):
            a = np.ascontiguousarray(a, dtype=np.float32)
            # device_get may hand back read-only views; the kernels (and the
            # clip/unscale passes) mutate in place
            return a if a.flags.writeable else a.copy()

        g_leaves = [writable_f32(g).ravel()
                    for g in jax.tree_util.tree_leaves(grads)]
        if inv != 1.0:
            for g in g_leaves:
                g *= np.float32(inv)
        gn = float(np.sqrt(sum(K.cpu_sq_norm(g) for g in g_leaves)))
        clip = self._config.gradient_clipping
        if clip and clip > 0 and gn > clip:
            coef = np.float32(clip / gn)
            for g in g_leaves:
                g *= coef

        m_leaves = [writable_f32(m)
                    for m in jax.tree_util.tree_leaves(master)]
        count_leaf = np.asarray(opt.count)
        count = int(count_leaf.ravel()[0]) + 1
        # mirror the device transform's lr exactly: lr_fn(count+1) with the
        # lr_override state leaf winning (resolve_lr semantics) — get_lr()
        # keys off global_steps, which lags count by one at the boundary
        ov_leaf = np.asarray(getattr(opt, "lr_override", np.nan))
        ov = float(ov_leaf.ravel()[0]) if ov_leaf.size else np.nan
        if not np.isnan(ov):
            lr = ov
        elif self._pending_client_lr is not None:
            lr = float(self._pending_client_lr)
        else:
            # ONLY the config-wired scheduler — the device transform's lr_fn
            # comes from cfg.scheduler_name, never from a client scheduler
            sched = getattr(self, "_sched_for_lr", None)
            lr = (float(np.asarray(sched.get_lr(np.int32(count))).ravel()[0])
                  if sched is not None else None)
        # first moment / accumulator tree: adam+lion call it mu, adagrad sum
        mu_attr = "mu" if hasattr(opt, "mu") else "sum"
        mu_tree = getattr(opt, mu_attr)
        mu_leaves = [writable_f32(x).ravel()
                     for x in jax.tree_util.tree_leaves(mu_tree)]
        bf16 = self.compute_dtype == jnp.bfloat16
        import ml_dtypes
        new_params = []
        if name == "adam":
            kern = K.DeepSpeedCPUAdam(lr=p["lr"], betas=p["betas"],
                                      eps=p["eps"],
                                      weight_decay=p["weight_decay"],
                                      adamw_mode=p["adamw_mode"])
            nu_leaves = [writable_f32(x).ravel()
                         for x in jax.tree_util.tree_leaves(opt.nu)]
            for m, g, mu, nu in zip(m_leaves, g_leaves, mu_leaves, nu_leaves):
                out = np.empty(m.size, np.uint16) if bf16 else None
                kern.step_count = count - 1
                kern.step(m.ravel(), g, mu, nu, bf16_out=out, lr=lr)
                new_params.append(
                    out.view(ml_dtypes.bfloat16).reshape(m.shape)
                    if bf16 else m)
        elif name == "adagrad":
            kern = K.DeepSpeedCPUAdagrad(lr=p["lr"], eps=p["eps"],
                                         weight_decay=p["weight_decay"])
            for m, g, s in zip(m_leaves, g_leaves, mu_leaves):
                out = np.empty(m.size, np.uint16) if bf16 else None
                kern.step(m.ravel(), g, s, bf16_out=out, lr=lr)
                new_params.append(
                    out.view(ml_dtypes.bfloat16).reshape(m.shape)
                    if bf16 else m)
        else:   # lion
            kern = K.DeepSpeedCPULion(lr=p["lr"], betas=p["betas"],
                                      weight_decay=p["weight_decay"])
            for m, g, mu in zip(m_leaves, g_leaves, mu_leaves):
                out = np.empty(m.size, np.uint16) if bf16 else None
                kern.step(m.ravel(), g, mu, bf16_out=out, lr=lr)
                new_params.append(
                    out.view(ml_dtypes.bfloat16).reshape(m.shape)
                    if bf16 else m)

        # upload ONLY the compute params, sharded per the plan
        treedef = jax.tree_util.tree_structure(master)
        params_tree = jax.tree_util.tree_unflatten(treedef, new_params)
        self.params = jax.tree_util.tree_map(
            lambda v, s: jax.device_put(v, s), params_tree, param_shardings)
        # moments/master were updated in place; persist + bump the count
        # (same leaf shape it arrived with — a later device-apply fallback
        # must see the tree layout it expects)
        new_opt = opt._replace(
            count=np.full_like(count_leaf, count),
            **{mu_attr: jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(mu_tree),
                [m.reshape(o.shape) for m, o in
                 zip(mu_leaves, jax.tree_util.tree_leaves(mu_tree))])})
        if name == "adam":
            new_opt = new_opt._replace(nu=jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(opt.nu),
                [n.reshape(o.shape) for n, o in
                 zip(nu_leaves, jax.tree_util.tree_leaves(opt.nu))]))
        master_tree = jax.tree_util.tree_unflatten(treedef, m_leaves)
        self.master = None
        self.opt_state = None
        self._state_on_nvme = True
        self._nvme_swapper.swap_out_tree({"master": master_tree,
                                          "opt_state": new_opt})
        self.host_offload_steps = getattr(self, "host_offload_steps", 0) + 1
        return gn

    def _init_onebit_state(self):
        """Place the 1-bit optimizer state: moments replicated, per-worker
        error buffers sharded over dp (fp16/onebit/common.py layout)."""
        from .fp16.onebit.common import _dp_axes
        axes, mesh = _dp_axes(self)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        target = self.master if self.master is not None else self.params
        state = self._onebit_opt.init(target, max(1, n))
        rep = NamedSharding(mesh, P())
        err = NamedSharding(mesh, P(axes if axes else None, None))
        place = lambda t, s: jax.tree_util.tree_map(
            lambda x: jax.device_put(x, s), t)
        self.opt_state = state._replace(
            mu=place(state.mu, rep), nu=place(state.nu, rep),
            worker_error=place(state.worker_error, err),
            server_error=place(state.server_error, err),
            extra=place(state.extra, rep))

    def _opt_state_shardings(self, target):
        """Optimizer moments shard like the master weights; scalars replicated."""
        state_shape = jax.eval_shape(self._grad_transform.init, target)
        # Build by structure: state trees contain `mu`/`nu` shaped like the
        # target params; suffix path-matching applies the same TP rules.
        from .zero.partition import path_str

        def map_state(s):
            return jax.tree_util.tree_map_with_path(
                lambda kp, x: NamedSharding(
                    self.plan.state_mesh,
                    self.plan.master_spec(x.shape, path_str(kp))), s)
        return map_state(state_shape)

    def _configure_lr_scheduler(self, client_scheduler):
        cfg = self._config
        if client_scheduler is not None:
            return client_scheduler
        if cfg.scheduler_name is not None:
            return getattr(self, "_sched_for_lr", None) or get_lr_scheduler(
                cfg.scheduler_name, cfg.scheduler_params)
        return None

    # -------------------------------------------------------------- properties
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def zero_optimization_stage(self):
        return self.zero_stage

    def zero_optimization(self):
        return self.zero_stage > 0

    def fp16_enabled(self):
        return self._config.fp16_enabled

    def bfloat16_enabled(self):
        return self._config.bfloat16_enabled

    def gradient_clipping(self):
        return self._config.gradient_clipping

    def get_lr(self):
        if self._pending_client_lr is not None:
            return [self._pending_client_lr]
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "get_lr"):
            return [float(self.lr_scheduler.get_lr(
                jnp.asarray(max(1, self.global_steps))))]
        return [None]

    def _scheduler_reclaims_lr(self):
        """Reference semantics: an engine-managed lr scheduler rewrites
        ``param_groups`` every step, so a one-off client lr write lasts only
        until the scheduler's next step.  Mirror that by clearing the
        override whenever the managed scheduler steps."""
        if self._pending_client_lr is None:
            return
        self._pending_client_lr = None
        if self.opt_state is not None and hasattr(self.opt_state,
                                                  "lr_override"):
            from ..ops.adam import no_lr_override
            self.opt_state = self.opt_state._replace(
                lr_override=no_lr_override())

    def _set_client_lr(self, value):
        """Route a torch-API ``param_groups[0]["lr"]`` write into the
        optimizer state's runtime ``lr_override`` leaf so the already-compiled
        step picks it up without recompilation."""
        self._pending_client_lr = value
        if self.opt_state is None:
            return  # applied when the state is created
        if not hasattr(self.opt_state, "lr_override"):
            raise NotImplementedError(
                "this optimizer does not support torch-style lr writes via "
                "param_groups (client/1-bit optimizers manage their own lr); "
                "use an lr scheduler in the config instead")
        self.opt_state = self.opt_state._replace(
            lr_override=jnp.full((), value, jnp.float32))

    @property
    def cur_scale(self):
        return float(self.scale_state.scale) if self.scale_state is not None else 1.0

    @property
    def skipped_steps(self):
        """fp16 overflow-skipped step count.  The per-boundary overflow flag
        stays on device (no host sync in ``step()``); reading this property
        drains the device accumulator."""
        acc = getattr(self, "_overflow_acc", None)
        if acc is not None:
            self._overflow_acc = None
            self._skipped_base += int(jax.device_get(acc))
        return self._skipped_base

    @skipped_steps.setter
    def skipped_steps(self, value):
        self._skipped_base = int(value)
        self._overflow_acc = None

    def is_gradient_accumulation_boundary(self):
        """Reference engine.py:2088."""
        return (self.micro_steps + 1) % self.gradient_accumulation_steps() == 0

    def train(self, mode=True):
        self.training = mode
        return self

    def eval(self):
        return self.train(False)

    # ------------------------------------------------------------- data path
    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None,
                     route=None, data_sampler=None, num_local_io_workers=None):
        """Reference ``deepspeed_io`` engine.py:1753: global-batch loader.
        ``num_local_io_workers`` > 0 overlaps batch IO/collation with the
        device step (threaded sliding window, see ``DeepSpeedDataLoader``)."""
        if batch_size is None:
            batch_size = (self.train_micro_batch_size_per_gpu() *
                          self.dp_world_size)
        if data_sampler is None:
            data_sampler = self._config_curriculum_sampler(dataset,
                                                           batch_size)
        return DeepSpeedDataLoader(dataset, batch_size=batch_size,
                                   collate_fn=collate_fn,
                                   num_local_io_workers=num_local_io_workers,
                                   data_sampler=data_sampler)

    def _config_curriculum_sampler(self, dataset, batch_size):
        """Config-driven curriculum sampler (reference ``deepspeed_io``
        builds a ``DeepSpeedDataSampler`` when
        ``data_efficiency.data_sampling.curriculum_learning`` is enabled,
        engine.py:1753): metric values come from a ``DataAnalyzer`` output
        directory (``{metric}_values.npy``) or inline ``metric_values``."""
        cl = (self._config.train_data_config.get("data_sampling", {})
              .get("curriculum_learning", {}))
        if not cl.get("enabled"):
            return None
        metrics = cl.get("curriculum_metrics", {})
        if not metrics:
            return None
        if len(metrics) > 1:
            logger.warning("multiple curriculum metrics configured; using "
                           "the first (difficulty composition not "
                           "implemented)")
        name, mcfg = next(iter(metrics.items()))
        if "metric_values" in mcfg:
            values = np.asarray(mcfg["metric_values"])
        else:
            from .data_pipeline.data_analyzer import DataAnalyzer
            values = DataAnalyzer.load_metric(mcfg["output_path"], name)
        sched_keys = ("min_difficulty", "max_difficulty", "schedule_type",
                      "schedule_config")
        from .data_pipeline.data_sampler import DeepSpeedDataSampler
        # global batch = micro × gas: the curriculum advances once per
        # OPTIMIZER step and the sampler yields gas micro index-lists
        gas = self.gradient_accumulation_steps()
        return DeepSpeedDataSampler(
            total_samples=len(dataset),
            global_batch_size=batch_size * gas,
            metric_values=values,
            curriculum_config={k: mcfg[k] for k in sched_keys
                               if k in mcfg},
            gradient_accumulation_steps=gas)

    def _batch_sharding(self, x):
        """Shard batch dim 0 over dp (and sequence dim 1 over sp if enabled)."""
        ndim = getattr(x, "ndim", 0)
        spec = [None] * ndim
        if ndim >= 1:
            spec[0] = groups.dp_axes()
        if ndim >= 2 and self.seq_parallel_world_size > 1:
            spec[1] = groups.SP_AXIS
        return NamedSharding(self.mesh, P(*spec))

    def shard_batch(self, *inputs):
        """Place host batch arrays onto the mesh.

        Single-process: ``device_put`` of the full global batch.
        Multi-process (pods): each process passes its LOCAL shard of the
        global batch — per-process data feeding, the reference's per-rank
        ``DistributedSampler`` contract (rank = ``groups.
        _get_data_parallel_rank()``) — and the global array is assembled
        without any cross-host data movement via
        ``jax.make_array_from_process_local_data``.
        """
        if jax.process_count() > 1:
            arrays = [np.asarray(x) for x in inputs]
            return tuple(
                jax.make_array_from_process_local_data(
                    self._batch_sharding(x), x)
                for x in arrays)
        for x in inputs:
            shape = np.shape(x)  # no copy/D2H — device arrays stay put
            if len(shape) >= 1 and shape[0] % max(1, self.dp_world_size):
                # fail HERE with config vocabulary, not deep inside
                # device_put with a raw sharding-divisibility error
                raise ValueError(
                    f"batch dim {shape[0]} is not divisible by the "
                    f"data-parallel degree {self.dp_world_size} — feed "
                    f"train_micro_batch_size_per_gpu × dp = "
                    f"{self.train_micro_batch_size_per_gpu()} × "
                    f"{self.dp_world_size} rows per micro-step (shape "
                    f"{shape})")
            if len(shape) >= 2 and self.seq_parallel_world_size > 1 and \
                    shape[1] % self.seq_parallel_world_size:
                raise ValueError(
                    f"sequence dim {shape[1]} is not divisible by the "
                    f"sequence-parallel degree "
                    f"{self.seq_parallel_world_size} (mesh sp) — pad the "
                    f"sequence (shape {shape})")
        return tuple(
            jax.device_put(jnp.asarray(x), self._batch_sharding(jnp.asarray(x)))
            for x in inputs)

    # -------------------------------------------------------------- hooks
    def register_param_transform(self, fn):
        """Register a differentiable params→params transform composed into
        the forward (QAT fake-quant, LoRA merge, …); invalidates compiles."""
        self._param_transforms.append(fn)
        self.invalidate_compiled()

    def register_post_step_hook(self, fn):
        self._post_step_hooks.append(fn)

    def invalidate_compiled(self):
        self._compiled_micro = {}
        self._compiled_apply = None
        self._compiled_eval = {}
        self._micro_cost = {}
        self._apply_cost = None

    def _effective_apply_fn(self, with_pld=True):
        """apply_fn with registered param transforms composed in — the single
        model-fn entry for every micro-step variant (GSPMD / qgZ / 1-bit)
        and the flops profiler.  In training mode with PLD enabled, the two
        trailing inputs forward() appends (theta, rng key) are stripped and
        delivered as kwargs here — so every consumer stays consistent with
        the augmented input convention."""
        fn = self._apply_fn
        for t in self._param_transforms:
            fn = (lambda inner, t: lambda params, *i, **k: inner(
                t(params), *i, **k))(fn, t)
        if self._moe_gating_tail and self.training and with_pld:
            # the per-step MoE gating key rides the input tail (before the
            # PLD pair); deliver it as the flax "gating" rng collection so
            # make_rng folds in each layer's scope path — per-step,
            # per-layer seeding without hand-plumbing.  Gated on the same
            # flag as the PLD strip: with_pld=False callers (eigenvalue
            # probe) pass RAW inputs with no appended tails, and popping
            # i[-1] there would eat a real model input
            inner_g = fn

            def fn(params, *i, rngs=None, **k):
                r = dict(rngs or {})
                r["gating"] = i[-1]
                return inner_g(params, *i[:-1], rngs=r, **k)
        if with_pld and self.progressive_layer_drop is not None \
                and self.training:
            inner = fn
            if self._flax:
                fn = lambda params, *i, **k: inner(
                    params, *i[:-2], pld_theta=i[-2],
                    rngs={"pld": i[-1]}, **k)
            else:
                # non-flax models receive the key explicitly — they have no
                # rng collection to draw the drop decision from
                fn = lambda params, *i, **k: inner(
                    params, *i[:-2], pld_theta=i[-2], pld_rng=i[-1], **k)
        return fn

    # ---------------------------------------------------------- compiled fns
    def _micro_step_fn(self):
        """Build (loss, grads) = value_and_grad over compute params."""
        if self._onebit_opt is not None:
            from .zero.overlap import overlap_opts, prefetch_opts
            if overlap_opts(self._config.comm_optimizations_config) \
                    is not None or \
                    prefetch_opts(self._config.comm_optimizations_config) \
                    is not None:
                # LOUD: the 1-bit micro manages its own gradient exchange
                # (error-compensated compressed all-reduce) — a user who
                # armed overlap (or overlap_comm / prefetch) must not
                # believe the bucket schedulers are hiding anything here
                logger.warning(
                    "comm_optimizations.overlap (and overlap.prefetch) is "
                    "ignored with 1-bit optimizers: their micro-step "
                    "consumes unreduced per-worker grads and runs its own "
                    "compressed exchange (docs/overlap.md limits)")
            # 1-bit optimizers consume *unreduced* per-worker grads
            return self._onebit_opt.build_micro(self)
        apply_fn = self._effective_apply_fn()
        gas = self.gradient_accumulation_steps()
        zc = self._config.zero_config
        co = self._config.comm_optimizations_config
        co_on = getattr(co, "enabled", False)
        if zc.zero_quantized_gradients or (co_on and co.quantized_gradients):
            # qgZ — the path selection collapses to gspmd / gspmd+islands
            # (ISSUE 15): the default is the GSPMD-first micro whose only
            # manual regions are the shrunken codec+collective islands
            # (runtime/zero/gspmd.py), so XLA schedules everything around
            # them; compositions whose correctness still lives inside the
            # full-manual region — and zero_mode: "flat_manual" — keep the
            # legacy micro (docs/zero.md "GSPMD-first ZeRO").
            from .zero.gspmd import build_gspmd_quantized_micro
            if self._qgz_uses_manual_micro():
                from .zero.zeropp import build_manual_dp_micro
                return build_manual_dp_micro(self)
            return build_gspmd_quantized_micro(self)
        from .zero.overlap import prefetch_opts, resolve_prefetch
        pf = prefetch_opts(co)
        if pf is not None and self.zero_stage < 3:
            if not getattr(self, "_prefetch_stage_warned", False):
                self._prefetch_stage_warned = True
                # LOUD: below stage 3 params are not sharded — there is no
                # forward all-gather for the prefetch pipeline to hide
                logger.warning(
                    "comm_optimizations.overlap.prefetch is ignored at "
                    "ZeRO stage %d: the stage-3 param all-gather it "
                    "pipelines does not exist (params replicated)",
                    self.zero_stage)
            pf = None
        pf_resolved = resolve_prefetch(pf, zc) if pf is not None else None
        qw = (zc.zero_quantized_weights or
              (co_on and co.quantized_weights)) and self.zero_stage >= 3
        if qw:
            # qwZ: int8 param all-gather (straight-through bwd); with
            # prefetch armed the gather itself runs the bucket pipeline,
            # so the GSPMD marker path below is skipped
            from .zero.zeropp import quantized_weight_gather
            inner = apply_fn
            qw_fmt, qw_gs = self.plan.param_wire(
                zc.zero_quantized_weights_format)
            apply_fn = lambda params, *inputs: inner(
                quantized_weight_gather(params, self.plan,
                                        wire_format=qw_fmt,
                                        group_size=qw_gs,
                                        prefetch=pf_resolved), *inputs)
        dc = self._config.domino_config
        if dc.enabled:
            if self.progressive_layer_drop is not None:
                raise ValueError(
                    "domino µ-streams cannot compose with "
                    "progressive_layer_drop (the PLD rng/theta tail would be "
                    "batch-split); disable one of them")
            # Domino µ-streams: independent half-batch subgraphs give the
            # latency-hiding scheduler filler compute for TP collectives
            from .domino.transformer import split_microstreams
            apply_fn = split_microstreams(apply_fn, dc.n_streams)
        from .utils import make_scaled_loss_fn
        loss_fn = make_scaled_loss_fn(apply_fn, gas,
                                      bool(self._device_count_names))

        from .zero.overlap import overlap_opts
        ov = overlap_opts(co)
        if ov is not None:
            # bucketed overlap scheduler (GSPMD flavor): per-bucket
            # custom_vjp markers emit the gradient sharding constraints —
            # and thus XLA's reduce-scatters — inside the backward graph,
            # where the latency-hiding scheduler can slide them under the
            # remaining backward compute (docs/overlap.md)
            from .zero.overlap import (bucket_bytes_of, describe_buckets,
                                       mark_tree, tree_buckets)
            bucket_bytes = bucket_bytes_of(ov)
            inner_loss_fn = loss_fn

            def loss_fn(params, scale, inputs):
                buckets, _, _ = tree_buckets(params, bucket_bytes)
                if _telemetry.enabled and \
                        not getattr(self, "_overlap_meta_emitted", False):
                    self._overlap_meta_emitted = True
                    _telemetry.metadata("overlap_buckets",
                                        describe_buckets(buckets))
                marked = mark_tree(params, self.plan.grad_shardings(params),
                                   buckets)
                return inner_loss_fn(marked, scale, inputs)

        if pf_resolved is not None and not qw:
            # forward-direction prefetch (GSPMD flavor): per-bucket
            # custom_vjp markers apply the *gathered* sharding constraints
            # — and thus XLA's all-gathers — inside the forward graph, in
            # forward-layer order with a max_live-bounded in-flight window,
            # so bucket k+1's gather is issued while bucket k's layers
            # compute (docs/overlap.md forward-prefetch section).  The qwZ
            # path pipelines its own quantized gather above instead.
            from .zero.overlap import (describe_buckets, mark_gather_tree,
                                       prefetch_buckets_for)
            inner_pf_fn = loss_fn

            def loss_fn(params, scale, inputs):
                buckets, window, _ = prefetch_buckets_for(
                    params, self.plan, pf_resolved)
                if not buckets:
                    # every leaf persistent (or tp-claimed): nothing to
                    # gather, keep the program untouched
                    return inner_pf_fn(params, scale, inputs)
                if _telemetry.enabled and \
                        not getattr(self, "_prefetch_meta_emitted", False):
                    self._prefetch_meta_emitted = True
                    _telemetry.metadata(
                        "prefetch_buckets",
                        {"window": window,
                         "buckets": describe_buckets(buckets)})
                marked = mark_gather_tree(
                    params, self.plan.gather_shardings(params), buckets,
                    max_inflight=window)
                return inner_pf_fn(marked, scale, inputs)

        def micro(params, scale, inputs):
            (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, scale, inputs)
            # the cast to the accumulator's dtype under a name of its own:
            # its ops have no module in their path (1.2-1.4 ms a step on the
            # embedding's and the head's gradients, PR 56)
            with jax.named_scope(_names.SCOPE_GRAD_CAST):
                grads = jax.tree_util.tree_map(
                    lambda g, s: jax.lax.with_sharding_constraint(
                        g.astype(self.grad_accum_dtype), s),
                    grads, self.plan.grad_shardings(params))
            return loss, grads

        return micro

    def _qgz_uses_manual_micro(self):
        """THE routing gate between the two qgZ micros — one predicate
        shared by ``_micro_step_fn`` (which micro is built) and
        ``_micro_variant`` (what the compiled program is named), so the
        tag can never drift from the program it labels.  True = the
        legacy full-manual micro: forced by ``zero_mode: "flat_manual"``
        or required by a composition ``manual_micro_reasons`` names
        (logged once when it's the reasons, not the knob)."""
        from .zero.gspmd import manual_micro_reasons, resolve_zero_mode
        co = self._config.comm_optimizations_config
        mode = resolve_zero_mode(co)
        reasons = manual_micro_reasons(self)
        if reasons and mode != "flat_manual" and \
                not getattr(self, "_manual_micro_logged", False):
            self._manual_micro_logged = True
            logger.info(
                "ZeRO quantized gradients: GSPMD-first micro not "
                "available for this config (%s) — running the "
                "flat-manual micro (docs/zero.md \"GSPMD-first "
                "ZeRO\")", "; ".join(reasons))
        return mode == "flat_manual" or bool(reasons)

    def _micro_variant(self):
        """Short tag of which micro-step flavor is compiled — the cost
        model's program names distinguish the overlap/prefetch/qgZ
        variants the ISSUE-14 observability tracks."""
        if self._onebit_opt is not None:
            return "1bit"
        zc = self._config.zero_config
        co = self._config.comm_optimizations_config
        co_on = getattr(co, "enabled", False)
        if zc.zero_quantized_gradients or (co_on and co.quantized_gradients):
            if self._qgz_uses_manual_micro():
                return "qgZ_manual"
            qv = "qgZ_islands"
            if (zc.zero_quantized_weights or
                    (co_on and co.quantized_weights)) and \
                    self.zero_stage >= 3:
                qv += "+qwZ"
            return qv
        from .zero.overlap import overlap_opts, prefetch_opts
        parts = []
        if overlap_opts(co) is not None:
            parts.append("overlap")
        if prefetch_opts(co) is not None and self.zero_stage >= 3:
            parts.append("prefetch")
        if (zc.zero_quantized_weights or (co_on and co.quantized_weights)) \
                and self.zero_stage >= 3:
            parts.append("qwZ")
        return "+".join(parts) if parts else "flat"

    def _micro_jit_shardings(self, inputs):
        """The explicit ``jit`` in/out ``NamedSharding`` set for the GSPMD
        micro variants (``plan.micro_shardings`` — ISSUE 15's "one jit over
        NamedSharding-annotated params/grads").  None when a variant owns
        its own layout (1-bit, the flat-manual micro, hpZ/MiCS reshaped
        meshes, offloaded state) or when the live arrays disagree with the
        plan's emitted set (e.g. sp batch sharding) — the compile must
        describe what actually runs, so disagreement falls back to
        inference rather than forcing a reshard."""
        if self._onebit_opt is not None:
            return None
        plan = self.plan
        if plan.param_mesh is not plan.mesh or \
                plan.state_mesh is not plan.mesh or \
                plan.offload_param or plan.offload_optimizer:
            return None
        variant = self._micro_variant()
        if variant in ("1bit", "qgZ_manual"):
            return None
        in_sh, out_sh = plan.micro_shardings(
            self.params, inputs, self._n_replicated_batch_tail,
            grads=("master" if variant.startswith("qgZ_islands")
                   else "grad"))

        def agree(x, s):
            sh = getattr(x, "sharding", None)
            if sh is None:
                return False
            try:
                return sh.is_equivalent_to(s, getattr(x, "ndim", 0))
            except (AttributeError, TypeError):
                return sh == s
        live = list(jax.tree_util.tree_leaves(self.params)) + list(inputs)
        want = list(jax.tree_util.tree_leaves(in_sh[0])) + list(in_sh[2])
        if len(live) != len(want) or \
                not all(agree(x, s) for x, s in zip(live, want)):
            return None
        return in_sh, out_sh

    def _get_compiled_micro(self, inputs):
        key = tuple((tuple(x.shape), str(x.dtype)) for x in inputs)
        if key not in self._compiled_micro:
            micro = _named_program(
                self._micro_step_fn(),
                _names.PROGRAM_MICRO + self._micro_variant())
            # compile ahead-of-time (the same single compile jit would do
            # lazily) so XLA's cost/memory analysis of the EXACT training
            # executable lands in the cost-model registry — MFU/HBM
            # observability and the once-per-compile OOM-margin warning
            # (docs/observability.md "MFU & HBM")
            from ..profiling import cost_model
            args = (self.params, self.scale_state.scale, inputs)
            sh = self._micro_jit_shardings(inputs)
            jitted = (jax.jit(micro, in_shardings=sh[0],
                              out_shardings=sh[1])
                      if sh is not None else jax.jit(micro))
            fn, entry = cost_model.capture_jit(
                f"train/micro_step[{self._micro_variant()}]"
                + (f"#{len(self._compiled_micro)}"
                   if self._compiled_micro else ""),
                jitted, args,
                meta={"zero_stage": self.zero_stage,
                      "gas": self.gradient_accumulation_steps()})
            self._compiled_micro[key] = fn
            self._micro_cost[key] = entry
        return self._compiled_micro[key]

    def _accumulate_fn(self):
        def acc(grad_acc, grads):
            return jax.tree_util.tree_map(
                lambda a, g: a + g.astype(a.dtype), grad_acc, grads)
        return jax.jit(_named_program(acc, _names.PROGRAM_ACCUMULATE),
                       donate_argnums=(0, ))

    def _apply_update_fn(self):
        """The boundary step: unscale, overflow, clip, optimizer, recast."""
        if self._onebit_opt is not None:
            inner = self._onebit_opt.build_apply(self)
            # 1-bit applies manage their own skip logic; accept (and drop)
            # the guard's spike-limit operand so step() calls uniformly
            return (lambda params, master, opt_state, grad_acc, scale_state,
                    spike_limit: inner(params, master, opt_state, grad_acc,
                                       scale_state))
        plan = self.plan
        cfg = self._config
        grad_clip = cfg.gradient_clipping
        transform = self._grad_transform
        scaler = self.loss_scaler
        fp16 = cfg.fp16_enabled
        guard = self._finite_guard.enabled
        compute_dtype = self.compute_dtype
        has_master = self.master is not None

        def apply(params, master, opt_state, grad_acc, scale_state,
                  spike_limit):
            inv = 1.0 / scale_state.scale
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32) * inv, grad_acc)
            del grad_acc
            # reshard grads to master layout (stage 1: scatter; free slice)
            grads = jax.tree_util.tree_map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s),
                grads, plan.master_shardings(grads))
            overflow = (has_overflow(grads) if fp16 or guard
                        else jnp.zeros((), jnp.bool_))
            gnorm = global_grad_norm(grads)
            # the poisoned/spiking step rides the fp16 skip path for every
            # precision: the update is computed but never committed
            skip = overflow
            if guard:
                skip = jnp.logical_or(skip, gnorm > spike_limit)
            if grad_clip and grad_clip > 0:
                grads, _ = clip_grads_by_global_norm(grads, grad_clip, norm=gnorm)

            target = master if has_master else params
            updates, new_opt = transform.update(grads, opt_state, target)
            new_target = jax.tree_util.tree_map(
                lambda p, u: (p.astype(jnp.float32) + u.astype(jnp.float32)
                              ).astype(p.dtype), target, updates)

            # skip on overflow (reference fp16 optimizer step semantics)
            def sel(new, old):
                return jax.tree_util.tree_map(
                    lambda n, o: jnp.where(skip, o, n), new, old)
            new_target = sel(new_target, target)
            new_opt = sel(new_opt, opt_state)

            # Pin the OUTPUT layouts to the plan: without these constraints
            # XLA picks the master/optimizer output shardings freely and
            # (observed on the pinned jaxlib) returns them REPLICATED — the
            # ZeRO-1/2 state partition silently evaporated after the first
            # boundary, inflating steady-state HBM by ~Nx and forcing a
            # second apply-step compile on the de-sharded inputs.  Found by
            # the PR-14 compiled-cost capture (the AOT executable rejected
            # its own second call).
            from .zero.partition import path_str as _path_str
            new_target = jax.tree_util.tree_map(
                lambda t, s: jax.lax.with_sharding_constraint(t, s),
                new_target, plan.master_shardings(new_target))
            new_opt = jax.tree_util.tree_map_with_path(
                lambda kp, x: jax.lax.with_sharding_constraint(
                    x, NamedSharding(
                        plan.state_mesh,
                        plan.master_spec(x.shape, _path_str(kp)))),
                new_opt)

            if has_master:
                new_master = new_target
                new_params = jax.tree_util.tree_map(
                    lambda m, s: jax.lax.with_sharding_constraint(
                        m.astype(compute_dtype), s),
                    new_master, plan.param_shardings(new_master))
            else:
                new_master = None
                new_params = new_target

            # loss-scale dynamics key off true fp16 overflow only — a
            # grad-norm spike must not shrink the scale
            new_scale = scaler.update(scale_state, overflow)
            return new_params, new_master, new_opt, new_scale, skip, gnorm

        return apply

    def _get_compiled_apply(self, args=None):
        if self._compiled_apply is None:
            jitted = jax.jit(
                _named_program(self._apply_update_fn(),
                               _names.PROGRAM_APPLY),
                donate_argnums=(0, 1, 2, 3, 4))
            if args is not None:
                # AOT capture like the micro-step: the boundary update's
                # executable is where ALL model states are live at once —
                # its memory_analysis is the static figure the mem-
                # estimator planner is checked against (donation aliasing
                # is subtracted by the analysis)
                from ..profiling import cost_model
                fn, entry = cost_model.capture_jit(
                    "train/apply_update", jitted, args,
                    meta={"zero_stage": self.zero_stage})
                self._compiled_apply = fn
                self._apply_cost = entry
            else:
                self._compiled_apply = jitted
        return self._compiled_apply

    def _spike_limit(self):
        """Grad-norm ceiling for the current step (replicated f32 scalar):
        ``spike_factor ×`` the running mean of recent healthy grad norms,
        +inf while disabled / warming up."""
        g = self._finite_guard
        if (not g.enabled or g.grad_norm_spike_factor <= 0
                or self._gnorm_ema is None
                or self.global_steps < g.spike_warmup_steps):
            return jnp.asarray(jnp.inf, jnp.float32)
        return jnp.asarray(g.grad_norm_spike_factor * self._gnorm_ema,
                           jnp.float32)

    def _account_guarded_step(self, skip, gnorm):
        """Host-side consecutive-skip bookkeeping for the finite-grad guard
        (one device sync per boundary — the documented cost of enabling
        it).  Aborts loudly when skips persist: silently skipping forever
        turns a poisoned data pipeline into a training run that 'finishes'
        without having trained."""
        g = self._finite_guard
        tripped = bool(jax.device_get(skip))
        gn = float(jax.device_get(gnorm))
        if not tripped:
            self._consecutive_skips = 0
            if np.isfinite(gn):
                self._gnorm_ema = (gn if self._gnorm_ema is None
                                   else 0.9 * self._gnorm_ema + 0.1 * gn)
            return
        self._consecutive_skips += 1
        logger.warning(
            "finite-grad guard: skipped poisoned step %d (grad norm %s, "
            "%d consecutive skip(s), abort at %d)", self.global_steps + 1,
            gn, self._consecutive_skips, g.max_consecutive_skips)
        if self.monitor.enabled:
            self.monitor.write_resilience_events(
                [("consecutive_skips", float(self._consecutive_skips))],
                step=self.global_samples)
        if self._consecutive_skips >= g.max_consecutive_skips:
            raise RuntimeError(
                f"finite-grad guard: {self._consecutive_skips} consecutive "
                f"steps produced non-finite or spiking gradients (last "
                f"grad norm {gn}, step {self.global_steps + 1}) — the "
                "input pipeline or numerics are poisoned, not transient; "
                "aborting so the supervisor can restart from the last "
                "valid checkpoint. Raise resilience.check_finite_grads."
                "max_consecutive_skips if this is expected.")

    # ------------------------------------------------------------- public API
    def forward(self, *inputs, **kwargs):
        """Reference engine.py:1848.  In training mode, runs the fused
        loss+grad micro-step and stashes grads for ``backward``."""
        self._check_params()
        if not self.training:
            return self._eval_forward(self.shard_batch(*inputs), kwargs)
        if _telemetry.enabled:
            _telemetry.begin_step(self.global_steps)
        ids = {"step": self.global_steps, "micro_step": self.micro_steps}
        with _telemetry.scope(_names.TRAIN_SHARD_BATCH, **ids):
            inputs = self.shard_batch(*inputs)
        self.timers(FORWARD_GLOBAL_TIMER).start()
        if _telemetry.enabled:
            self._tel_step_tokens += self._count_batch_tokens(inputs)
        if self._moe_gating_tail:
            # per-step fold-in: same compiled program, fresh key each
            # micro-step; flax make_rng folds in the layer path per layer
            inputs = (*inputs, jax.random.fold_in(self._moe_gating_key,
                                                  self.micro_steps))
        if self.progressive_layer_drop is not None:
            inputs = (*inputs,
                      np.float32(self.progressive_layer_drop.get_theta()),
                      jax.random.PRNGKey(self.micro_steps))
        micro = self._get_compiled_micro(inputs)
        if _telemetry.enabled:
            key = tuple((tuple(x.shape), str(x.dtype)) for x in inputs)
            entry = self._micro_cost.get(key)
            if entry is not None:
                # the call COUNT is execution truth — it ticks even when
                # the backend gave this program no flop figure
                entry.calls += 1
            if entry is not None and entry.flops is not None:
                self._tel_step_flops += entry.flops
            else:
                # no flop count for this program: MFU must refuse (None),
                # not report garbage from a partial sum
                self._tel_flops_incomplete = True
        # the call of the compiled loss+grad program: it returns once the
        # program is enqueued, or — on a full chip — once the device has
        # freed the buffers it needs (the host then waits HERE for the
        # previous step).  The recorder's "forward" phase is this span.
        with _telemetry.scope(_names.TRAIN_MICRO,
                              phase=_telemetry.SPAN_FORWARD, **ids,
                              **self._ready_device_counts()):
            loss, grads = micro(self.params, self.scale_state.scale, inputs)
        if isinstance(loss, tuple):
            # what the model counted on the device: booked on the span of
            # the first later call that finds the array ready
            loss, counts = loss
            self._pending_counts.append(counts)
        from ..utils.fault_injection import fault_point
        if fault_point("engine.poison", step=self.micro_steps):
            # injected data poisoning: NaN loss + grads, exactly what a bad
            # batch / numerics blow-up produces — drives the finite-grad
            # guard tests
            loss = jnp.full_like(loss, jnp.nan)
            grads = jax.tree_util.tree_map(
                lambda g: jnp.full_like(g, jnp.nan), grads)
        self._stashed_grads = grads
        self._micro_losses.append(loss)  # device scalar; synced only on report
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        self._maybe_profile_flops(inputs)
        return loss

    def _ready_device_counts(self):
        """The counts of the earlier micro-steps whose arrays the device has
        finished, summed under the model's ``device_counts`` names, with how
        many micro-steps they cover; ``{}`` where none is ready.  Never a
        wait for the device: an array that is not ready stays for a later
        call, and one that is costs the copy of a few integers."""
        pending, total, n = self._pending_counts, None, 0
        while pending and pending[0].is_ready():
            c = np.asarray(pending.popleft())
            total = c if total is None else total + c
            n += 1
        if not n:
            return {}
        return {**{k: int(v) for k, v in zip(self._device_count_names,
                                             total)},
                _names.COUNT_MICROS_COVERED: n}

    def _eval_forward(self, inputs, kwargs):
        """Compiled eval/validation forward, shape-keyed like the train
        micro-step (reference ``engine.py:3696`` compile wrapper role) —
        transforms (QAT fake-quant, …) apply in eval too, otherwise
        validation measures a different model than is being optimized.
        kwargs are baked into the compiled closure only when they are mode
        flags (bool/str/None — the flax ``train=False``/``deterministic=True``
        style); anything else (arrays, rngs dicts, per-call-varying scalars)
        falls back to op-by-op dispatch so the cache cannot grow one
        executable per distinct kwarg value."""
        if not all(isinstance(v, (bool, str, type(None)))
                   for v in kwargs.values()):
            return self._effective_apply_fn()(self.params, *inputs, **kwargs)
        kw_key = tuple(sorted(kwargs.items()))
        key = (tuple((tuple(x.shape), str(x.dtype)) for x in inputs), kw_key)
        fn = self._compiled_eval.get(key)
        if fn is None:
            apply_fn = self._effective_apply_fn()
            fn = jax.jit(lambda params, *i: apply_fn(params, *i, **kwargs))
            self._compiled_eval[key] = fn
        return fn(self.params, *inputs)

    def _maybe_profile_flops(self, inputs):
        """Flops profiler hook (reference engine wires FlopsProfiler at
        ``flops_profiler.profile_step``, profiler.py:30)."""
        fp = self._config.flops_profiler_config
        if not fp.enabled or self._flops_profiled or \
                self.micro_steps + 1 < fp.profile_step:
            return
        self._flops_profiled = True
        from ..profiling.flops_profiler import FlopsProfiler, jaxpr_flops
        prof = FlopsProfiler(self)
        apply_fn = self._effective_apply_fn()

        def fwd(params, inputs):
            out = apply_fn(params, *inputs)
            return out[0] if isinstance(out, (tuple, list)) else out

        # analytic only (trace, no compile — the train step is already
        # compiled in _compiled_micro; recompiling here would double the
        # XLA compile time/memory for large models)
        prof.profile(fwd, self.params, inputs, compile_xla=False)
        prof.step_flops = jaxpr_flops(self._micro_step_fn(), self.params,
                                      self.scale_state.scale, inputs)[0]
        if dist.get_rank() == 0:
            prof.print_model_profile(profile_step=self.micro_steps + 1,
                                     top_modules=fp.top_modules,
                                     detailed=fp.detailed,
                                     output_file=fp.output_file)
        self.flops_profiler = prof

    def start_device_trace(self, trace_dir):
        """Capture a jax.profiler (xplane) trace of subsequent steps — the
        per-module latency view (flax scope names survive into XLA metadata;
        round-1 review: profiler depth beyond the analytic flops walk)."""
        from ..profiling.flops_profiler import FlopsProfiler
        self._trace_profiler = FlopsProfiler(self)
        return self._trace_profiler.start_trace(trace_dir)

    def stop_device_trace(self):
        return self._trace_profiler.stop_trace()

    def __call__(self, *inputs, **kwargs):
        return self.forward(*inputs, **kwargs)

    def backward(self, loss=None, **kwargs):
        """Reference engine.py:2007: fold stashed grads into the accumulator."""
        if self._stashed_grads is None:
            raise RuntimeError("backward() called without a prior forward() "
                               "in training mode")
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        ids = {"step": self.global_steps, "micro_step": self.micro_steps}
        # "backward" is a host-side fold: the gradients were computed by
        # the micro-step program that forward() launched
        with _telemetry.scope(_names.TRAIN_BACKWARD,
                              phase=_telemetry.SPAN_BACKWARD, **ids):
            offloaded = getattr(self, "_host_offloaded", None)
            if offloaded and "grad_acc" in offloaded:
                # grads offloaded mid-accumulation: restore BEFORE the None
                # check or the prior micro-batches' gradients are silently
                # lost
                host, shardings = offloaded["grad_acc"]
                self.grad_acc = jax.tree_util.tree_map(jax.device_put, host,
                                                       shardings)
                del offloaded["grad_acc"]
            # the fold that triggers the (GSPMD-lowered) DP grad reduction —
            # device-side reduce time lands inside this span under fence mode
            with _telemetry.scope(_names.TRAIN_ACCUMULATE,
                                  phase=_telemetry.SPAN_GRAD_REDUCE, **ids):
                if self.grad_acc is None:
                    self.grad_acc = self._stashed_grads
                else:
                    if not hasattr(self, "_acc_fn"):
                        self._acc_fn = self._accumulate_fn()
                    self.grad_acc = self._acc_fn(self.grad_acc,
                                                 self._stashed_grads)
            self._stashed_grads = None
            if (self._nvme_swapper is not None and self._state_on_nvme
                    and self.is_gradient_accumulation_boundary()):
                # last microbatch: start the async disk reads now so they
                # overlap the backward compute tail (reference swap-in
                # overlap, stage3.py:1926)
                self._nvme_start_swap_in()
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss

    def step(self):
        """Reference engine.py:2204 — apply at the grad-accum boundary."""
        self._check_params()
        self.timers(STEP_GLOBAL_TIMER).start()
        if self.is_gradient_accumulation_boundary():
            if self.grad_acc is None and \
                    not getattr(self, "_host_offloaded", None):
                raise RuntimeError("step() at a grad-accum boundary without "
                                   "any backward() since the last boundary")
            ids = {"step": self.global_steps,
                   "micro_step": self.micro_steps}
            # the call of the optimizer program (the recorder's
            # "optimizer" phase); like the micro-step it only enqueues
            # unless the chip is full
            with _telemetry.scope(_names.TRAIN_APPLY,
                                  phase=_telemetry.SPAN_OPTIMIZER, **ids):
                host_gnorm = self._try_host_offload_step()
                if host_gnorm is not None:
                    skipped = jnp.zeros((), jnp.bool_)
                    gnorm = host_gnorm
                else:
                    # restore offloaded state FIRST — grads may live on
                    # host via offload_states(include=["lp_grads"])
                    self._ensure_state_resident()
                    if self.grad_acc is None:
                        raise RuntimeError(
                            "step() at a grad-accum boundary without any "
                            "backward() since the last boundary")
                    apply_args = (self.params, self.master, self.opt_state,
                                  self.grad_acc, self.scale_state,
                                  self._spike_limit())
                    apply = self._get_compiled_apply(apply_args)
                    (self.params, self.master, self.opt_state,
                     self.scale_state, skipped, gnorm) = apply(*apply_args)
                    if _telemetry.enabled and self._apply_cost is not None:
                        # counted HERE (where the program ran, flops known
                        # or not) — the host-offload branch above never
                        # executes this executable
                        self._apply_cost.calls += 1
                    self.grad_acc = None
                    if self._nvme_swapper is not None:
                        # updated state back to disk (async; overlaps the
                        # next forward)
                        self._nvme_swap_out()
            if self._finite_guard.enabled:
                self._account_guarded_step(skipped, gnorm)
            with _telemetry.scope(_names.TRAIN_REPORT, **ids):
                self.global_steps += 1
                self.global_samples += self.train_batch_size()
                if self.progressive_layer_drop is not None:
                    self.progressive_layer_drop.update_state(
                        self.global_steps)
                if self._config.fp16_enabled:
                    # NO host sync here: the overflow flag accumulates on
                    # device and drains at steps_per_print (or on a
                    # skipped_steps read)
                    ov = skipped.astype(jnp.int32)
                    self._overflow_acc = (ov if self._overflow_acc is None
                                          else self._overflow_acc + ov)
                if self.lr_scheduler is not None and \
                        hasattr(self.lr_scheduler, "step"):
                    self.lr_scheduler.step()
                    self._scheduler_reclaims_lr()
                if self.curriculum_scheduler is not None:
                    self.curriculum_scheduler.update_difficulty(
                        self.global_steps)
                for hook in self._post_step_hooks:
                    hook(self)
                if self._micro_losses:
                    # the step's loss = mean over the gas window (reference
                    # engine.py:2029 logs the accumulated mean, not the last
                    # microbatch)
                    self._last_loss = self._micro_losses
                    self._micro_losses = []
                self._report_step_metrics(gnorm)
            if _telemetry.enabled:
                self._telemetry_step_end(skipped, gnorm)
            if self._heartbeat is not None:
                # liveness signal for the elastic agent's watchdog: one
                # atomic file write per optimizer step
                self._heartbeat.beat(self.global_steps)
        self.micro_steps += 1
        self.timers(STEP_GLOBAL_TIMER).stop()

    def _report_step_metrics(self, gnorm):
        if self._config.fp16_enabled and self.global_steps % \
                self._config.steps_per_print == 0:
            before = self._skipped_base
            if self.skipped_steps != before:   # drains the device accumulator
                log_dist(f"{self._skipped_base - before} overflow-skipped "
                         f"step(s) since last report (step "
                         f"{self.global_steps}), scale → {self.cur_scale}",
                         ranks=[0])
        if self.monitor.enabled and self.global_steps % \
                self._config.steps_per_print == 0:
            events = [("Train/Samples/lr", self.get_lr()[0] or 0.0,
                       self.global_samples)]
            if self._last_loss is not None:
                # reference writes Train/Samples/train_loss every logged step
                # (engine.py:2029) — the loss curve is the monitor's main job
                ll = self._last_loss
                val = (float(np.mean([float(l) for l in ll]))
                       if isinstance(ll, list) else float(ll))
                events.append(("Train/Samples/train_loss", val,
                               self.global_samples))
            if self._config.fp16_enabled:
                events.append(("Train/Samples/loss_scale", self.cur_scale,
                               self.global_samples))
            self.monitor.write_events(events)
        if self.wall_clock_breakdown_enabled:
            self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                             STEP_GLOBAL_TIMER])

    def _count_batch_tokens(self, inputs):
        """Tokens in this micro-batch for the step record's token-rate
        metrics (``tokens``, ``tokens_per_sec_per_chip``).

        With the top-level config key ``"sequence_length"`` set, tokens =
        batch × sequence_length, cross-checked LOUDLY against axis 1 of
        ``inputs[0]`` when it has one.  Unset, a ≥2-D first input ASSUMES
        axis 1 is the sequence — a heuristic that silently counted feature
        dims as tokens for non-token models, so it now warns once and
        points at the config key; a 1-D input counts samples.  Returns 0
        (→ rate metrics omitted as None, never garbage) when there is
        nothing defensible to count."""
        if not inputs:
            return 0
        shape = np.shape(inputs[0])
        if not shape:
            return 0
        seq = self.sequence_length
        if seq:
            if len(shape) >= 2 and shape[1] != seq and \
                    not self._seq_len_warned:
                self._seq_len_warned = True
                logger.warning(
                    "token accounting: config sequence_length=%d but "
                    "inputs[0] has axis-1 size %d — counting batch × "
                    "sequence_length per the config; fix the config (or "
                    "the batch layout) if tokens/s looks wrong", seq,
                    shape[1])
            return int(shape[0]) * int(seq)
        if len(shape) >= 2:
            if not self._seq_len_warned:
                self._seq_len_warned = True
                logger.warning(
                    "token accounting: no \"sequence_length\" in the "
                    "config — ASSUMING inputs[0] axis 1 (=%d) is the "
                    "sequence for tokens/s; set the top-level "
                    "sequence_length key to validate this (a feature dim "
                    "here silently inflates token rates — "
                    "docs/observability.md)", shape[1])
            return int(np.prod(shape[:2]))
        return int(shape[0])

    def _telemetry_step_end(self, skipped, gnorm):
        """Close the telemetry step window with the boundary's numbers and
        refresh the live-metrics registry.  Reading loss/grad-norm/skip
        forces one device sync per boundary — the documented cost of
        telemetry ON (mirrors the finite-grad guard).  The same sync makes
        the ``memory_stats()`` snapshot (the record's ``hbm`` section) a
        true boundary figure, and the compiled-cost registry prices the
        step's executed flops for ``mfu`` (docs/observability.md
        "MFU & HBM")."""
        metrics = {}
        ll = self._last_loss
        try:
            if ll is not None:
                metrics["loss"] = (float(np.mean([float(l) for l in ll]))
                                   if isinstance(ll, list) else float(ll))
            metrics["grad_norm"] = float(jax.device_get(gnorm))
            metrics["skipped"] = float(jax.device_get(skipped))
        except Exception as e:   # telemetry must never kill a step
            logger.warning("telemetry: step metric read failed (%s)", e)
        if self._config.fp16_enabled:
            metrics["loss_scale"] = self.cur_scale
        metrics["samples"] = self.train_batch_size()
        tokens = self._tel_step_tokens
        self._tel_step_tokens = 0
        if tokens:
            metrics["tokens"] = tokens
        metrics["lr"] = self.get_lr()[0]
        # compiled-cost feed: Σ micro flops this window + the boundary
        # update; refused (absent → None) when any executed program had no
        # flop count — MFU is a measurement, not a guess
        from ..profiling import cost_model
        step_flops = None
        if not self._tel_flops_incomplete and self._tel_step_flops > 0:
            step_flops = self._tel_step_flops
            if self._apply_cost is not None:
                if self._apply_cost.flops is None:
                    # the boundary update ran but has no flop figure: a
                    # micro-only sum would be a silent partial — refuse
                    step_flops = None
                else:
                    step_flops += self._apply_cost.flops
        if step_flops is not None:
            metrics["step_flops_per_chip"] = step_flops
            # the recorder derives mfu = step_flops / wall / peak at
            # end_step (it owns the wall clock); peak rides along so the
            # spine stays generic
            metrics["peak_flops_per_chip"] = \
                cost_model.peak_flops_per_chip()
        self._tel_step_flops = 0.0
        self._tel_flops_incomplete = False
        # device-memory snapshot on the boundary sync telemetry already
        # pays for → the step record's "hbm" section + live gauges
        hbm = None
        try:
            from .utils import memory_usage_snapshot
            snap = memory_usage_snapshot()
            hbm = {k: snap[k] for k in ("live_bytes", "peak_bytes",
                                        "limit_bytes")}
            _telemetry.record_hbm(hbm)
        except Exception as e:   # telemetry must never kill a step
            logger.warning("telemetry: memory_stats read failed (%s)", e)
        # refresh the compiled-programs table in the trace metadata every
        # boundary: entries mutate between captures too (call counts), and
        # a version-gated snapshot shipped stale calls=1 tables.  A handful
        # of dict writes per boundary, dwarfed by the device sync above.
        _telemetry.metadata("compiled_programs",
                            cost_model.registry().describe())
        if not self._mem_planner_emitted and self.params is not None:
            # static HBM planner figure for the trace's planner-vs-measured
            # delta (trace_report) — once, from the live partition plan
            self._mem_planner_emitted = True
            try:
                from ..profiling import mem_estimator
                est = mem_estimator.estimate_from_plan(
                    self.params, self.plan,
                    compute_dtype_bytes=jnp.dtype(
                        self.compute_dtype).itemsize,
                    grad_bytes=jnp.dtype(self.grad_accum_dtype).itemsize,
                    include_master=self.master is not None)
                _telemetry.metadata("mem_planner", est)
            except Exception as e:
                logger.warning("telemetry: mem planner estimate failed "
                               "(%s)", e)
        # MoE routed-token stats arrive via jax.debug.callback whenever
        # telemetry is on and the model contains MoE layers (record_routing
        # gates on telemetry, not the moe block) — drain the effect queue
        # so this step's stats land in THIS step's record, not the next
        # one's.  No-op (and cheap) when nothing is pending.
        try:
            jax.effects_barrier()
        except Exception:
            pass
        record = _telemetry.end_step(metrics=metrics)
        reg = _telemetry.get_registry()
        if reg is not None:
            reg.counter("train/steps",
                        help="optimizer steps completed").inc()
            if metrics.get("skipped"):
                reg.counter("train/skipped_steps",
                            help="boundary updates skipped (overflow/"
                            "finite-grad guard)").inc()
            if "loss" in metrics:
                reg.gauge("train/loss").set(metrics["loss"])
            if "grad_norm" in metrics:
                reg.gauge("train/grad_norm").set(metrics["grad_norm"])
            if record is not None:
                wall_s = record["wall_ms"] / 1e3
                reg.histogram("train/step_seconds",
                              help="optimizer-step wall time").observe(
                                  wall_s)
                reg.gauge("train/exposed_comm_fraction",
                          help="host-exposed comm time / step wall time"
                          ).set(record["comm"]["exposed_comm_fraction"])
                if tokens and wall_s > 0:
                    reg.gauge(
                        "train/tokens_per_sec_per_chip",
                        help="tokens/s/chip over the last step").set(
                            tokens / wall_s / max(1, jax.device_count()))
                rmfu = record.get("metrics", {}).get("mfu")
                if rmfu is not None:
                    reg.gauge(
                        "train/mfu",
                        help="model-FLOPs utilization: compiled per-chip "
                        "flops/s ÷ per-chip peak").set(rmfu)
            if hbm is not None:
                reg.gauge("hbm/live_bytes",
                          help="device bytes_in_use at the boundary"
                          ).set(hbm["live_bytes"])
                reg.gauge("hbm/peak_bytes",
                          help="device peak_bytes_in_use").set(
                              hbm["peak_bytes"])
                if hbm["limit_bytes"]:
                    reg.gauge("hbm/limit_bytes",
                              help="device bytes_limit").set(
                                  hbm["limit_bytes"])
        if self.global_steps % self._config.steps_per_print == 0:
            _telemetry.export_metrics(step=self.global_samples)

    def train_batch(self, data_iter=None):
        """Convenience full-batch step (forward+backward+step × GAS)."""
        if data_iter is None:
            data_iter = iter(self.training_dataloader)
        losses = []
        self.tput_timer.start()
        for _ in range(self.gradient_accumulation_steps()):
            batch = next(data_iter)
            if not isinstance(batch, (tuple, list)):
                batch = (batch, )
            loss = self.forward(*batch)
            self.backward(loss)
            self.step()
            losses.append(loss)
        self.tput_timer.stop(global_step=True)
        # mean over the gas window as a DEVICE scalar (reference train_batch
        # returns the aggregated loss tensor, engine.py:2029) — converting to
        # float here would block async dispatch on every micro-batch window
        if len(losses) == 1:
            return losses[0].astype(jnp.float32)
        return jnp.mean(jnp.stack([l.astype(jnp.float32) for l in losses]))

    def _check_params(self):
        offloaded = getattr(self, "_host_offloaded", None)
        if offloaded and "params" in offloaded:
            # forward needs ONLY the params back; master/opt_state stay on
            # host until step()/checkpointing asks (the point of offloading
            # optimizer state is running generation forwards without it)
            host, shardings = offloaded["params"]
            self.params = jax.tree_util.tree_map(jax.device_put, host,
                                                 shardings)
            del offloaded["params"]  # only after the puts succeeded
        if self.params is None:
            raise RuntimeError(
                "engine has no parameters — pass model_parameters to "
                "initialize() or call engine.initialize_parameters(seed, "
                "*sample_inputs) first")

    def compute_block_eigenvalues(self, *sample_inputs):
        """Per-block Hessian max-eigenvalues of the loss (reference engine
        eigenvalue hook, consumed by compression's quantization-offset
        scheduling).  Caches the result on ``self.block_eigenvalue``."""
        if self.eigenvalue is None:
            raise RuntimeError("eigenvalue is not enabled in the config "
                               '("eigenvalue": {"enabled": true})')
        self._check_params()
        inputs = self.shard_batch(*sample_inputs)
        apply_fn = self._effective_apply_fn(with_pld=False)
        self.block_eigenvalue = self.eigenvalue.compute_eigenvalue(
            lambda p, *i: apply_fn(p, *i), self.params, *inputs)
        return self.block_eigenvalue

    def compile(self, backend=None, compile_kwargs=None) -> None:
        """Reference ``engine.py:3696`` (torch.compile wrapper).  Every
        train/eval step here is already traced+compiled by XLA under jit, so
        this only records the request for API parity."""
        self._is_compiled = True

    @property
    def is_compiled(self) -> bool:
        return getattr(self, "_is_compiled", False)

    # ------------------------------------------------- state offload on demand
    _OFFLOAD_STATE_ATTRS = {"optim_states": "opt_state",
                            "hp_params": "master",
                            "lp_params": "params",
                            "lp_grads": "grad_acc"}

    def offload_states(self, include=None, device="cpu", pin_memory=True,
                       non_blocking=False):
        """Move engine states to host memory on demand (reference
        ``engine.py:3720``; used by RLHF-style flows to free HBM between
        phases).  ``include``: subset of {"optim_states", "hp_params",
        "lp_params", "lp_grads"}; default all.  States return via
        :meth:`reload_states` (or automatically on the next
        forward/backward/step)."""
        if str(device) not in ("cpu", "OffloadDeviceEnum.cpu"):
            raise ValueError(f"only host offload is supported, got {device}")
        if getattr(self, "_state_on_nvme", False):
            raise RuntimeError("states already offloaded to NVMe")
        names = (set(include) if include is not None
                 else set(self._OFFLOAD_STATE_ATTRS))
        self._host_offloaded = getattr(self, "_host_offloaded", None) or {}
        for name in names:
            # accept both "optim_states" and OffloadStateTypeEnum.optim_states
            attr = self._OFFLOAD_STATE_ATTRS.get(str(name).split(".")[-1])
            if attr is None:
                raise ValueError(
                    f"unknown state {name!r} "
                    f"(have: {sorted(self._OFFLOAD_STATE_ATTRS)})")
            tree = getattr(self, attr)
            if tree is None or attr in self._host_offloaded:
                continue
            shardings = jax.tree_util.tree_map(lambda x: x.sharding, tree)
            host = _owned_host_tree(tree)  # OWNING host copy — a device_get
            # view would alias the buffer released on the next line
            setattr(self, attr, None)     # release the HBM buffers
            self._host_offloaded[attr] = (host, shardings)

    def reload_states(self, non_blocking=False):
        """Reload offloaded states to their original device shardings
        (reference ``engine.py:3747``)."""
        for attr, (host, shardings) in (getattr(self, "_host_offloaded",
                                                None) or {}).items():
            setattr(self, attr, jax.tree_util.tree_map(
                jax.device_put, host, shardings))
        self._host_offloaded = {}

    # ----------------------------------------------------------- checkpointing
    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True, exclude_frozen_parameters=False,
                        async_save=False):
        """``async_save=True`` stages the write and returns immediately
        (the reference's Nebula async engine role); the `latest` tag
        commits at :meth:`wait_for_checkpoint` (also called automatically
        before the next save)."""
        from .checkpoint_engine import save_engine_checkpoint
        self._ensure_state_resident()
        self.wait_for_checkpoint()   # one pending async save at a time
        out = save_engine_checkpoint(self, save_dir, tag=tag,
                                     client_state=client_state,
                                     save_latest=save_latest,
                                     async_save=async_save)
        if async_save:
            self._pending_ckpt = out
            if not getattr(self, "_ckpt_atexit", False):
                # a script whose LAST act is an async save would otherwise
                # exit without ever committing the `latest` tag
                import atexit
                import weakref
                ref = weakref.ref(self)
                atexit.register(
                    lambda: ref() is not None and ref().wait_for_checkpoint())
                self._ckpt_atexit = True
        return out

    def wait_for_checkpoint(self):
        """Block until a pending ``async_save`` checkpoint is durable."""
        pending = getattr(self, "_pending_ckpt", None)
        if pending is not None:
            self._pending_ckpt = None  # even a failed commit must not wedge
            pending.wait()

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False, custom_load_fn=None):
        # a pending async save must commit first: `latest` isn't written
        # until then, and the target dir may still be mid-write
        self.wait_for_checkpoint()
        try:
            return self._load_checkpoint_impl(
                load_dir, tag, load_optimizer_states,
                load_lr_scheduler_states, load_module_only)
        finally:
            if self.progressive_layer_drop is not None:
                # resume at the annealed theta, not a fresh 1.0
                self.progressive_layer_drop.update_state(self.global_steps)

    def _load_checkpoint_impl(self, load_dir, tag, load_optimizer_states,
                              load_lr_scheduler_states, load_module_only):
        if self._config.checkpoint_config.load_universal:
            from ..checkpoint.universal_checkpoint import load_universal_checkpoint
            return load_universal_checkpoint(
                self, load_dir, tag=tag,
                load_optimizer_states=load_optimizer_states,
                load_lr_scheduler_states=load_lr_scheduler_states,
                load_module_only=load_module_only)
        from .checkpoint_engine import load_engine_checkpoint
        return load_engine_checkpoint(
            self, load_dir, tag=tag,
            load_optimizer_states=load_optimizer_states,
            load_lr_scheduler_states=load_lr_scheduler_states,
            load_module_only=load_module_only)

    def _export_16bit_tree(self):
        """Source tree for :meth:`save_16bit_model` — overridden by engines
        whose parameters do not live on device (InfinityEngine)."""
        return self.params

    def save_16bit_model(self, save_dir, save_filename="pytorch_model.bin",
                         exclude_frozen_parameters=False):
        """Consolidated compute-dtype export (reference engine.py:3638 +
        _zero3_consolidated_16bit_state_dict :3569 — here a device_get of the
        global arrays *is* the consolidation).

        Written as ``.npz``; bf16 leaves are stored as uint16 raw views with
        their names recorded under ``__bf16__`` (numpy cannot serialize the
        ml_dtypes dtype) — reload with
        :func:`deepspeed_tpu.runtime.utils.load_16bit_npz`."""
        import ml_dtypes
        import numpy as onp
        from .utils import ensure_directory_exists
        name = save_filename
        if name.endswith(".bin"):
            name = name[:-4] + ".npz"
        elif not name.endswith(".npz"):
            name += ".npz"   # np.savez appends it anyway; keep path honest
        path = os.path.join(save_dir, name)
        ensure_directory_exists(path)
        from .zero.partition import path_str
        flat, bf16_names = {}, []
        for kp, leaf in jax.tree_util.tree_leaves_with_path(
                self._export_16bit_tree()):
            arr = onp.asarray(leaf)
            if self.compute_dtype == jnp.bfloat16 and \
                    arr.dtype != ml_dtypes.bfloat16:
                arr = arr.astype(ml_dtypes.bfloat16)
            key = path_str(kp)
            if arr.dtype == ml_dtypes.bfloat16:
                bf16_names.append(key)
                arr = arr.view(onp.uint16)
            flat[key] = arr
        flat["__bf16__"] = onp.asarray(bf16_names)
        onp.savez(path, **flat)
        return path

    # -------------------------------------------------------------- zero APIs
    def get_fp32_param(self, path=None):
        """Tensor-fragment API analog (reference utils/tensor_fragment.py):
        full fp32 weights as a host pytree."""
        self._ensure_state_resident()
        src = self.master if self.master is not None else self.params
        return jax.tree_util.tree_map(lambda x: np.asarray(x, dtype=np.float32), src)

    def empty_partition_cache(self):
        pass  # XLA owns buffers; parity no-op (reference engine.py:3747 area)

    def parameter_names(self):
        """path_str names of every parameter, for the tensor-fragment API."""
        from ..utils.tensor_fragment import parameter_names
        return parameter_names(self)
