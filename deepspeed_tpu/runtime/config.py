"""DeepSpeed-compatible JSON config system.

Analog of reference ``runtime/config.py:706`` (``DeepSpeedConfig``) with the
same JSON schema — the batch-size trinity, optimizer/scheduler sections,
fp16/bf16, zero_optimization, gradient clipping, monitoring, comms logging,
flops profiler, activation checkpointing, pipeline and mesh topology.

TPU-specific addition: a ``"mesh"`` section (``{"pp":1,"dp":-1,"sp":1,"tp":1,
"ep":1}``) declaring the device-grid factorization; absent, it is derived from
``pipeline``/``tensor_parallel``/``sequence_parallel_size`` keys the reference
spreads across subsystems.
"""

import json
import os
from typing import Any, Dict, Optional, Union

from pydantic import Field, model_validator

from .config_utils import DeepSpeedConfigModel, dict_raise_error_on_duplicate_keys
from .zero.config import DeepSpeedZeroConfig
from ..utils.logging import logger

ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
FUSED_ADAM_OPTIMIZER = "fusedadam"
LAMB_OPTIMIZER = "lamb"
FUSED_LAMB_OPTIMIZER = "fusedlamb"
LION_OPTIMIZER = "lion"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
ZERO_ONE_ADAM_OPTIMIZER = "zerooneadam"
ONEBIT_LAMB_OPTIMIZER = "onebitlamb"
SGD_OPTIMIZER = "sgd"
MUON_OPTIMIZER = "muon"
ADAGRAD_OPTIMIZER = "adagrad"

DEEPSPEED_OPTIMIZERS = [
    ADAM_OPTIMIZER, ADAMW_OPTIMIZER, FUSED_ADAM_OPTIMIZER, LAMB_OPTIMIZER,
    FUSED_LAMB_OPTIMIZER, LION_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER,
    ZERO_ONE_ADAM_OPTIMIZER, ONEBIT_LAMB_OPTIMIZER, SGD_OPTIMIZER,
    MUON_OPTIMIZER, ADAGRAD_OPTIMIZER,
]


class FP16Config(DeepSpeedConfigModel):
    """Reference fp16 section (``runtime/fp16/loss_scaler.py`` consumers)."""
    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = Field(0.0, ge=0.0)  # 0 = dynamic
    initial_scale_power: int = Field(16, ge=0)
    loss_scale_window: int = Field(1000, ge=1)
    hysteresis: int = Field(2, ge=1)
    consecutive_hysteresis: bool = False
    min_loss_scale: float = Field(1.0, ge=0.0)
    fp16_master_weights_and_grads: bool = False


class BF16Config(DeepSpeedConfigModel):
    enabled: bool = False
    immediate_grad_update: bool = True


class CommsLoggerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: list = []
    # True → block_until_ready around each logged collective (precise
    # latency, but serializes the async pipeline — measurement changes the
    # program).  False (default) → dispatch-side timing only.
    sync_timing: bool = False


class CommsConfig(DeepSpeedConfigModel):
    comms_logger: CommsLoggerConfig = CommsLoggerConfig()

    @property
    def comms_logger_enabled(self):
        return self.comms_logger.enabled


class PrefetchConfig(DeepSpeedConfigModel):
    """``"comm_optimizations.overlap.prefetch"`` — the forward-direction
    ZeRO-3 param-gather prefetch (``runtime/zero/overlap.py``,
    docs/overlap.md).  Own gate, independent of ``overlap.enabled``: the
    two directions (backward grad reduce, forward param gather) compose
    but arm separately.  Reference configs arm it via an explicit
    ``zero_optimization.stage3_prefetch_bucket_size`` instead (0 there
    keeps it off); an explicit block here wins — loudly."""
    enabled: bool = False
    # bucket payload bound in MiB; 0 (default) = the 32 MiB overlap
    # default.  Configs armed via an explicit
    # zero_optimization.stage3_prefetch_bucket_size get bucket_mb stamped
    # from that ELEMENT count × the compute dtype itemsize.
    bucket_mb: float = Field(0.0, ge=0)
    # max buckets with their all-gather outstanding; further clamped per
    # model by stage3_max_live_parameters (overlap.live_window)
    max_inflight: int = Field(2, ge=1)


class OverlapConfig(DeepSpeedConfigModel):
    """``"comm_optimizations.overlap"`` — the bucketed backward-pass
    gradient-reduction scheduler (``runtime/zero/overlap.py``,
    docs/overlap.md).  Disabled (default) is bit-identical: the micro-step
    compiles to exactly the unbucketed program.  Enabled, the gradient
    reduce is split into ``bucket_mb``-bounded buckets dispatched inside
    the backward graph as each layer's gradients materialize, so XLA (or
    the manual qgZ pipeline) can hide the reduce under remaining backward
    compute.  The nested ``prefetch`` block is the forward mirror: the
    stage-3 param all-gather issued bucket by bucket under the forward
    compute."""
    enabled: bool = False
    # bucket size bound in MiB of gradient payload; fractional values are
    # allowed (tiny models need sub-MiB bounds to form >1 bucket)
    bucket_mb: float = Field(32.0, gt=0)
    # manual (qgZ) path only: how many buckets may have their inter-node
    # hop outstanding at once; the GSPMD path leaves scheduling to XLA
    max_inflight: int = Field(2, ge=1)
    # forward-direction stage-3 param-gather prefetch (own enable gate)
    prefetch: PrefetchConfig = PrefetchConfig()


class CommOptimizationsConfig(DeepSpeedConfigModel):
    """``"comm_optimizations"`` section — the topology-aware quantized
    collectives engine (``comm/collectives/``, docs/collectives.md).

    Disabled (default) is bit-identical to the flat collectives.  Enabled,
    the facade's eager collectives dispatch to hierarchical/quantized
    variants, and the ZeRO gradient/param paths switch to quantized wire
    traffic (qgZ/qwZ semantics) per the flags below.  The nested
    ``overlap`` block has its own ``enabled`` gate (the scheduler changes
    *when* reduces run, not what they carry, so it composes with either
    the flat or the quantized path)."""
    enabled: bool = False
    # intra-node reduce-scatter → inter-node op on 1/N → intra-node
    # all-gather; engages only when the group spans a topology hierarchy
    hierarchical_allreduce: bool = True
    # quantize param all-gather payloads (ZeRO++ qwZ analog)
    quantized_weights: bool = False
    # quantize gradient reduce-scatter payloads (ZeRO++ qgZ analog)
    quantized_gradients: bool = False
    # wire format: int8 | int4 | fp8 | fp6 | fp12
    wire_dtype: str = "int8"
    # per-message-size wire-format ladder: ascending [max_bytes, wire]
    # rungs ([null, wire] = catch-all, "fp32" = keep that band flat); sizes
    # above every rung use the global wire_dtype.  None (default) = global
    # wire_dtype everywhere, bit-identical to the pre-ladder engine.
    # Typically emitted by the autotuner (docs/autotuning.md) from measured
    # per-size probes — the EQuARX lesson that optimal quantization varies
    # by message size.
    wire_dtype_by_size: Optional[list] = None
    # elements per quantization scale group (lane-aligned down, min 128)
    quantization_group_size: int = Field(2048, ge=128)
    # devices per node for the hierarchy split; 0 = auto-detect from device
    # metadata (TPU slice / process boundaries) or DS_TPU_INTRA_NODE_SIZE
    intra_node_size: int = Field(0, ge=0)
    # messages under this many bytes always take the flat path
    min_message_size: int = Field(0, ge=0)
    # which micro-step architecture carries the quantized-gradient (qgZ)
    # training path (ISSUE 15, docs/zero.md "GSPMD-first ZeRO"):
    #   "gspmd" (default) — ONE jit over NamedSharding-annotated state with
    #     shard_map islands only around the codec+collective exchanges, so
    #     XLA's latency-hiding scheduler owns the program; compositions the
    #     islands cannot express yet (tp>1, hpZ/MiCS, MoE, dp×ep) keep the
    #     manual micro automatically;
    #   "flat_manual" — force the legacy full-manual shard_map micro
    #     (the baseline the islands micro is held bitwise-equal to).
    zero_mode: str = "gspmd"
    # bucketed backward-pass gradient-reduction scheduler (own enable gate)
    overlap: OverlapConfig = OverlapConfig()

    @model_validator(mode="after")
    def _check_zero_mode(self):
        from .zero.gspmd import ZERO_MODES
        if self.zero_mode not in ZERO_MODES:
            raise ValueError(
                f"comm_optimizations.zero_mode {self.zero_mode!r} unknown "
                f"(have {', '.join(ZERO_MODES)})")
        return self


class MoeConfig(DeepSpeedConfigModel):
    """``"moe"`` section — the expert-parallel MoE engine
    (``moe/engine.py``, docs/moe.md).

    ``enabled: false`` (default) and ``quantized_dispatch: false`` are both
    bit-identical to the plain GSPMD constraint dispatch (normalized-jaxpr
    contract, same as ``comm_optimizations``).  Enabled, the engine threads
    the noisy-gate rngs (per step, per layer) through flax apply and books
    routed-token accounting on the telemetry spine; ``quantized_dispatch``
    additionally routes the expert dispatch/return all-to-all through the
    manual-SPMD quantized exchange (blockwise codecs from
    ``comm/collectives/quantized.py``; hierarchical ICI/DCN variants picked
    by ``topology.factor_group``)."""
    enabled: bool = False
    # manual-SPMD quantized expert exchange (dispatch reduce + return
    # gather); False = the GSPMD constraint path, program-identical
    quantized_dispatch: bool = False
    # wire format of the quantized exchange: int8 | int4 | fp8 | fp6 |
    # fp12 | fp32 ("fp32" = the manual schedule with the raw fp payload).
    # A comm_optimizations.wire_dtype_by_size ladder, when present,
    # overrides this per payload size (the autotuner's per-size choice
    # applies to expert dispatch too).
    wire_dtype: str = "int8"
    # elements per quantization scale group (lane-aligned down, min 128)
    quantization_group_size: int = Field(2048, ge=128)
    # 2-hop dispatch (fp intra-node psum-scatter, quantized inter-node
    # all-to-all) when the ep axis spans a topology hierarchy
    hierarchical_dispatch: bool = True
    # devices per node for the ep-axis hierarchy split; 0 = auto-detect
    # (device metadata / DS_TPU_INTRA_NODE_SIZE), like the other collectives
    intra_node_size: int = Field(0, ge=0)
    # base seed for the per-step, per-layer noisy-gate rng fold-in
    # (RSample/Jitter policies); None = the config-level "seed"
    gating_seed: Optional[int] = None


class MonitorConfig(DeepSpeedConfigModel):
    """Reference ``monitor/config.py``: tensorboard/wandb/comet/csv."""

    class TensorBoardConfig(DeepSpeedConfigModel):
        enabled: bool = False
        output_path: str = ""
        job_name: str = "DeepSpeedJobName"

    class WandbConfig(DeepSpeedConfigModel):
        enabled: bool = False
        group: Optional[str] = None
        team: Optional[str] = None
        project: str = "deepspeed"

    class CSVConfig(DeepSpeedConfigModel):
        enabled: bool = False
        output_path: str = ""
        job_name: str = "DeepSpeedJobName"

    class CometConfig(DeepSpeedConfigModel):
        enabled: bool = False
        api_key: Optional[str] = None
        project: Optional[str] = None
        workspace: Optional[str] = None
        experiment_name: Optional[str] = None

    tensorboard: TensorBoardConfig = TensorBoardConfig()
    wandb: WandbConfig = WandbConfig()
    csv_monitor: CSVConfig = CSVConfig()
    comet: CometConfig = CometConfig()


class FlopsProfilerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    recompute_fwd_factor: float = 0.0
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


class PldConfig(DeepSpeedConfigModel):
    """``progressive_layer_drop`` section (reference
    ``runtime/progressive_layer_drop.py`` + PLD paper schedule)."""
    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001


class EigenvalueConfig(DeepSpeedConfigModel):
    """``eigenvalue`` section (reference ``runtime/eigenvalue.py`` — layer
    Hessian eigenvalues for compression's quantization-offset schedule)."""
    enabled: bool = False
    verbose: bool = False
    max_iter: int = 100
    tol: float = 1e-2
    stability: float = 1e-6
    gas_boundary_resolution: int = 1
    layer_name: str = ""
    layer_num: int = 0


class HybridEngineConfig(DeepSpeedConfigModel):
    """Reference ``deepspeed/runtime/config.py`` hybrid_engine section
    (RLHF train↔generate flip-flop, ``runtime/hybrid_engine.py:30``)."""
    enabled: bool = False
    max_out_tokens: int = 512
    inference_tp_size: int = 1
    release_inference_cache: bool = False
    pin_parameters: bool = True
    tp_gather_partition_size: int = 8


class DominoConfig(DeepSpeedConfigModel):
    """Domino µ-stream TP overlap (reference ``runtime/domino/transformer.py``
    — here ``runtime/domino/transformer.split_microstreams``): opt-in batch
    split into independent streams so the scheduler can hide TP collectives
    that GSPMD compilation leaves exposed.  A/B first (``domino_ab``) — on
    most TP meshes XLA already hides them and plain wins."""
    enabled: bool = False
    n_streams: int = 2


class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """Reference ``runtime/activation_checkpointing/config.py`` schema; on TPU
    this steers ``jax.checkpoint`` policies (SURVEY.md §7)."""
    partition_activations: bool = False
    contiguous_memory_optimization: bool = False
    cpu_checkpointing: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False


class PipelineConfig(DeepSpeedConfigModel):
    stages: Union[int, str] = "auto"
    partition: str = "best"
    seed_layers: bool = False
    activation_checkpoint_interval: int = 0
    pipe_partitioned: bool = True
    grad_partitioned: bool = True
    # TPU addition: microbatch schedule executed inside one jitted program
    schedule: str = "1f1b"  # or "gpipe"


class MeshConfig(DeepSpeedConfigModel):
    """TPU device-grid factorization (dp=-1 → all remaining devices)."""
    pp: int = Field(1, ge=1)
    dp: int = -1
    sp: int = Field(1, ge=1)
    tp: int = Field(1, ge=1)
    ep: int = Field(1, ge=1)


class GradientClippingConfig(DeepSpeedConfigModel):
    enabled: bool = False


class CheckpointConfig(DeepSpeedConfigModel):
    tag_validation: str = "Warn"
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write: dict = {}


class DataTypesConfig(DeepSpeedConfigModel):
    grad_accum_dtype: Optional[str] = None


class AioConfig(DeepSpeedConfigModel):
    """Reference ``csrc/aio`` tuning knobs (``deepspeed/runtime/swap_tensor``)."""
    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True
    use_gds: bool = False


class CheckpointIntegrityConfig(DeepSpeedConfigModel):
    """Per-tag ``manifest.json`` (file list + sizes + checksums + config
    hash) committed after all tree writes; ``load_checkpoint`` verifies it
    and falls back to the newest *valid* tag on mismatch/partial tags."""
    enabled: bool = True
    keep_n: int = Field(0, ge=0)  # valid tags retained; 0 = unlimited
    save_retries: int = Field(3, ge=0)      # transient-FS retry attempts
    retry_backoff: float = Field(0.25, ge=0.0)  # seconds, doubles per retry


class FiniteGradsConfig(DeepSpeedConfigModel):
    """Opt-in NaN/Inf + grad-norm-spike step guard: a poisoned step is
    skipped via the fp16 loss-scaler skip path (also for bf16/fp32) and
    consecutive skips past ``max_consecutive_skips`` abort loudly.  Enabling
    it syncs the skip flag to host each boundary."""
    enabled: bool = False
    max_consecutive_skips: int = Field(5, ge=1)
    # skip when gnorm > factor × running mean of recent gnorms; 0 disables
    grad_norm_spike_factor: float = Field(0.0, ge=0.0)
    spike_warmup_steps: int = Field(10, ge=0)  # steps before spikes arm


class WatchdogConfig(DeepSpeedConfigModel):
    """Worker-side heartbeat files monitored by ``DSElasticAgent`` so a
    *hung* worker (stuck collective) is killed and relaunched, not just a
    dead one.  ``heartbeat_dir`` defaults to ``$DS_TPU_HEARTBEAT_DIR`` (the
    elastic agent exports a per-agent tempdir) and must be NODE-LOCAL per
    agent — see ``elasticity/watchdog.py``."""
    enabled: bool = False
    heartbeat_dir: str = ""
    stall_timeout: float = Field(300.0, gt=0.0)


class ResilienceConfig(DeepSpeedConfigModel):
    """``"resilience"`` JSON section — see docs/resilience.md."""
    checkpoint_integrity: CheckpointIntegrityConfig = \
        CheckpointIntegrityConfig()
    check_finite_grads: FiniteGradsConfig = FiniteGradsConfig()
    watchdog: WatchdogConfig = WatchdogConfig()


class TelemetryMetricsConfig(DeepSpeedConfigModel):
    """Live-metrics half of the telemetry block: registry + sinks."""
    enabled: bool = True
    # 0 = no HTTP endpoint (telemetry.prometheus_text() still renders)
    prometheus_port: int = Field(0, ge=0)
    # export/serve only on process 0 (the aggregation rank); False = every
    # rank exports its own series
    rank0_only: bool = True


class TelemetryConfig(DeepSpeedConfigModel):
    """``"telemetry"`` JSON section — see docs/observability.md.  Off by
    default: the recorder, its files, the registry and the boundary sync
    all sit behind the module-level ``deepspeed_tpu.telemetry.enabled``
    flag, and losses are bit-identical to a build without the subsystem.
    The ``ds:`` profiler annotations (``telemetry.scope``) are written
    either way and need no key here."""
    enabled: bool = False
    trace_dir: str = "telemetry"   # chrome trace + per-step JSONL land here
    trace_steps: int = Field(0, ge=0)  # stop step records after N; 0 = all
    # block on the accelerator at phase boundaries: CPU-accurate phase
    # attribution at the cost of serializing async dispatch
    fence: bool = False
    metrics: TelemetryMetricsConfig = TelemetryMetricsConfig()


class ElasticityConfig(DeepSpeedConfigModel):
    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: list = [2, 4, 6]
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    version: float = 0.2
    ignore_non_elastic_batch_info: bool = False
    prefer_larger_batch_size: bool = True


class DeepSpeedConfigError(Exception):
    pass


class DeepSpeedConfig:
    """Parsed + validated master config (reference ``runtime/config.py:706``)."""

    def __init__(self, config: Union[str, Dict, None], mpu=None, mesh_param=None):
        if config is None:
            config = {}
        if isinstance(config, str):
            if not os.path.exists(config):
                raise DeepSpeedConfigError(
                    f"DeepSpeed config path does not exist: {config}")
            with open(config) as f:
                self._param_dict = json.load(
                    f, object_pairs_hook=dict_raise_error_on_duplicate_keys)
        elif isinstance(config, dict):
            self._param_dict = dict(config)
        elif isinstance(config, DeepSpeedConfig):
            self._param_dict = dict(config._param_dict)
        else:
            raise DeepSpeedConfigError(
                f"Expected a string path or dict, got {type(config)}")

        self.mesh_param = mesh_param
        self._initialize_params(self._param_dict)
        self._configure_train_batch_size()
        self._do_sanity_check()

    # ------------------------------------------------------------------ parse
    def _initialize_params(self, pd):
        """Reference ``runtime/config.py:801 _initialize_params``."""
        self.train_batch_size = pd.get("train_batch_size", None)
        self.train_micro_batch_size_per_gpu = pd.get(
            "train_micro_batch_size_per_gpu", None)
        self.gradient_accumulation_steps = pd.get("gradient_accumulation_steps", None)
        self.steps_per_print = pd.get("steps_per_print", 10)
        # tokens per sample, for the telemetry step records' token-rate
        # metrics (docs/observability.md "MFU & HBM").  Unset, the engine
        # assumes axis 1 of the first input is the sequence — loudly.
        self.sequence_length = pd.get("sequence_length", None)
        if self.sequence_length is not None:
            if not isinstance(self.sequence_length, int) or \
                    self.sequence_length <= 0:
                raise DeepSpeedConfigError(
                    f"sequence_length must be a positive int, got "
                    f"{self.sequence_length!r}")
        self.dump_state = pd.get("dump_state", False)
        self.disable_allgather = pd.get("disable_allgather", False)
        self.communication_data_type = pd.get("communication_data_type", None)
        self.seq_parallel_communication_data_type = pd.get(
            "seq_parallel_comm_data_type", "fp32")
        self.prescale_gradients = pd.get("prescale_gradients", False)
        self.gradient_predivide_factor = pd.get("gradient_predivide_factor", 1.0)
        self.sparse_gradients_enabled = pd.get("sparse_gradients", False)
        if self.sparse_gradients_enabled:
            # reference runtime/sparse_tensor.py compresses torch sparse
            # embedding grads for the allreduce; XLA keeps embedding grads
            # dense (scatter-add fused into the backward) and there is no
            # sparse collective to route them through — reject rather than
            # silently ignore the knob
            raise ValueError(
                "sparse_gradients is a torch sparse-embedding optimization "
                "with no XLA analog (embedding grads are dense and the "
                "scatter-add fuses into the backward); remove the key")

        self.zero_config = DeepSpeedZeroConfig(**pd.get("zero_optimization", {}) or {})
        self.zero_optimization_stage = self.zero_config.stage
        self.zero_enabled = self.zero_optimization_stage > 0

        self.fp16_config = FP16Config(**pd.get("fp16", {}) or {})
        self.bf16_config = BF16Config(**pd.get("bfloat16", pd.get("bf16", {})) or {})
        self.fp16_enabled = self.fp16_config.enabled
        self.bfloat16_enabled = self.bf16_config.enabled
        if self.fp16_enabled and self.bfloat16_enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")
        self.fp16_auto_cast = self.fp16_config.auto_cast
        self.loss_scale = self.fp16_config.loss_scale
        self.initial_dynamic_scale = 2**self.fp16_config.initial_scale_power
        self.dynamic_loss_scale_args = {
            "init_scale": 2**self.fp16_config.initial_scale_power,
            "scale_window": self.fp16_config.loss_scale_window,
            "min_scale": self.fp16_config.min_loss_scale,
            "delayed_shift": self.fp16_config.hysteresis,
        }

        grad_clip = pd.get("gradient_clipping", 0.0)
        self.gradient_clipping = float(grad_clip) if grad_clip else 0.0

        self.optimizer_name = None
        self.optimizer_params = None
        self.optimizer_legacy_fusion = False
        opt = pd.get("optimizer")
        if opt:
            self.optimizer_name = str(opt.get("type", "")).lower()
            self.optimizer_params = opt.get("params", {})
            self.optimizer_legacy_fusion = opt.get("legacy_fusion", False)

        self.scheduler_name = None
        self.scheduler_params = None
        sched = pd.get("scheduler")
        if sched:
            self.scheduler_name = sched.get("type")
            self.scheduler_params = sched.get("params", {})

        self.wall_clock_breakdown = pd.get("wall_clock_breakdown", False)
        self.memory_breakdown = pd.get("memory_breakdown", False)
        self.monitor_config = MonitorConfig(**{
            k: v
            for k, v in pd.items()
            if k in ("tensorboard", "wandb", "csv_monitor", "comet")
        })
        self.comms_config = CommsConfig(**pd.get("comms_logger", {})
                                        and {"comms_logger": pd.get("comms_logger")})
        self.comm_optimizations_config = CommOptimizationsConfig(
            **pd.get("comm_optimizations", {}) or {})
        from ..comm.collectives import WIRE_FORMATS, build_wire_ladder
        if self.comm_optimizations_config.wire_dtype not in WIRE_FORMATS:
            raise DeepSpeedConfigError(
                f"comm_optimizations.wire_dtype "
                f"{self.comm_optimizations_config.wire_dtype!r} unknown "
                f"(have {', '.join(WIRE_FORMATS)})")
        try:
            # normalize/validate the per-size ladder at config load, not at
            # first dispatch — a mistyped rung must fail bring-up loudly
            build_wire_ladder(
                self.comm_optimizations_config.wire_dtype_by_size)
        except ValueError as e:
            raise DeepSpeedConfigError(
                f"comm_optimizations.wire_dtype_by_size invalid: {e}") \
                from e
        # reference-compat: ``zero_optimization.overlap_comm: true`` (the
        # DeepSpeed knob for overlapping gradient reduction with backward)
        # arms the bucketed overlap scheduler unless the user pinned the
        # overlap block explicitly
        _ov_user = ((pd.get("comm_optimizations") or {}).get("overlap")
                    or {})
        if self.zero_config.overlap_comm and "enabled" not in _ov_user:
            self.comm_optimizations_config.overlap.enabled = True
        # reference-compat: an EXPLICIT ``stage3_prefetch_bucket_size``
        # arms the forward param-gather prefetch (the knob was previously
        # parsed but silently ignored); 0 keeps prefetch off (reference
        # semantics).  An explicit overlap.prefetch block wins — loudly,
        # so a config carrying both knows which knob is steering.
        _pf_user = (_ov_user.get("prefetch") or {}) \
            if isinstance(_ov_user, dict) else {}
        _zo_user = pd.get("zero_optimization") or {}
        _pf_knob = ("stage3_prefetch_bucket_size" in _zo_user
                    or "prefetch_bucket_size" in _zo_user)
        if _pf_knob and self.zero_config.stage >= 3:
            if "enabled" in _pf_user:
                logger.warning(
                    "zero_optimization.stage3_prefetch_bucket_size is "
                    "overridden by the explicit "
                    "comm_optimizations.overlap.prefetch block (prefetch "
                    "stays %s); the stage3 knob only arms the prefetch "
                    "when no explicit block is present",
                    "enabled" if self.comm_optimizations_config.overlap
                    .prefetch.enabled else "disabled")
            else:
                _pf = self.comm_optimizations_config.overlap.prefetch
                _pf.enabled = self.zero_config.prefetch_bucket_size > 0
                if _pf.enabled and "bucket_mb" not in _pf_user:
                    # the knob is an ELEMENT count (reference units) —
                    # stamp the byte bound here, where we know the knob
                    # was explicit (the field's 5e7 default must not
                    # silently size buckets)
                    _itemsize = 2 if (self.fp16_enabled
                                      or self.bfloat16_enabled) else 4
                    _pf.bucket_mb = (self.zero_config.prefetch_bucket_size
                                     * _itemsize / float(1 << 20))
        # "moe" block: the expert-parallel MoE engine (docs/moe.md).  Wire
        # format validated at config load like comm_optimizations — a
        # mistyped dispatch wire must fail bring-up, not first dispatch.
        self.moe_config = MoeConfig(**pd.get("moe", {}) or {})
        # "fp32" = manual schedule with the raw fp payload (the ladder's
        # flat rung).  Deliberately NOT imported from
        # moe.engine.DISPATCH_WIRES: importing the moe package here would
        # pull flax into every config parse; a sync test guards the
        # duplication instead
        _dispatch_wires = ("fp32", ) + tuple(WIRE_FORMATS)
        if self.moe_config.wire_dtype not in _dispatch_wires:
            raise DeepSpeedConfigError(
                f"moe.wire_dtype {self.moe_config.wire_dtype!r} unknown "
                f"(have {', '.join(_dispatch_wires)})")
        self.flops_profiler_config = FlopsProfilerConfig(
            **pd.get("flops_profiler", {}) or {})
        self.hybrid_engine = HybridEngineConfig(
            **pd.get("hybrid_engine", {}) or {})
        self.domino_config = DominoConfig(**pd.get("domino", {}) or {})
        self.activation_checkpointing_config = ActivationCheckpointingConfig(
            **pd.get("activation_checkpointing", {}) or {})
        self.pipeline_config = PipelineConfig(**pd.get("pipeline", {}) or {})
        self.pld_config = PldConfig(
            **pd.get("progressive_layer_drop", {}) or {})
        self.eigenvalue_config = EigenvalueConfig(
            **pd.get("eigenvalue", {}) or {})
        self.checkpoint_config = CheckpointConfig(**pd.get("checkpoint", {}) or {})
        self.data_types_config = DataTypesConfig(**pd.get("data_types", {}) or {})
        self.aio_config = AioConfig(**pd.get("aio", {}) or {})
        self.elasticity_config = ElasticityConfig(**pd.get("elasticity", {}) or {})
        self.resilience_config = ResilienceConfig(
            **pd.get("resilience", {}) or {})
        self.telemetry_config = TelemetryConfig(
            **pd.get("telemetry", {}) or {})
        # "autotuning" block: validated strictly here (unknown keys fail
        # bring-up loudly — autotuning/config.py forbids extras) so a
        # mistyped search knob never silently tunes the default space.
        # enabled: false (default) changes nothing; enabled: true is a
        # declaration consumed by ``autotuning.run_autotuning`` — the
        # engine itself never starts a search mid-initialize.
        from ..autotuning.config import AutotuningConfig
        try:
            self.autotuning_config = AutotuningConfig(
                **pd.get("autotuning", {}) or {})
        except Exception as e:
            raise DeepSpeedConfigError(f"autotuning config invalid: {e}") \
                from e
        if self.autotuning_config.enabled:
            logger.info(
                "autotuning.enabled: run the search via "
                "deepspeed_tpu.autotuning.run_autotuning(...) (or "
                "tools/autotune_smoke.py); initialize() itself does not "
                "start trials")

        self.gradient_accumulation_dtype = self.data_types_config.grad_accum_dtype

        # Mesh factorization (TPU addition): explicit "mesh" block wins, else
        # derive from reference-style keys.
        mesh_dict = dict(pd.get("mesh", {}) or {})
        if "tensor_parallel" in pd:
            mesh_dict.setdefault("tp", pd["tensor_parallel"].get("tp_size", 1))
        if "sequence_parallel_size" in pd:
            mesh_dict.setdefault("sp", pd["sequence_parallel_size"])
        if self.mesh_param is not None:
            # mesh_param: tuple (dp, sp) like reference initialize() :153-162
            mesh_dict.setdefault("dp", self.mesh_param[0])
            if len(self.mesh_param) > 1:
                mesh_dict.setdefault("sp", self.mesh_param[1])
        self.mesh_config = MeshConfig(**mesh_dict)

        self.load_universal_checkpoint = self.checkpoint_config.load_universal
        self.use_node_local_storage = self.checkpoint_config.use_node_local_storage

        self.seed = pd.get("seed", 1234)
        self.compile_config = pd.get("compile", {})
        self.graph_harvesting = pd.get("graph_harvesting", False)
        self.train_data_config = pd.get("data_efficiency", {})
        self.curriculum_enabled_legacy = bool(
            pd.get("curriculum_learning", {}).get("enabled", False))
        self.curriculum_params_legacy = pd.get("curriculum_learning", {})

    # ----------------------------------------------------- batch size trinity
    def _configure_train_batch_size(self):
        """Resolve train_batch = micro_batch * grad_accum * dp_world
        (reference ``runtime/config.py`` ``_set_batch_related_parameters``)."""
        self._dp_degree = None  # resolved lazily once mesh exists

        tb = self.train_batch_size
        mb = self.train_micro_batch_size_per_gpu
        gas = self.gradient_accumulation_steps
        # Defer full resolution to resolve_batch_sizes(dp) — record raw here.
        self._raw_batch = (tb, mb, gas)

    def resolve_batch_sizes(self, dp_world_size):
        """Complete the trinity given the DP degree (called by the engine once
        the mesh is built).  Mirrors reference assertions (~config.py:837+).

        Under elastic training the agent exports the re-solved schedule as
        DS_ELASTIC_* env (reference: torchelastic rendezvous feeds the
        elastic batch math into ``_configure_train_batch_size``); those
        override the static JSON numbers so a rescaled restart picks up the
        new world's batch sizes without editing the config file."""
        import os as _os
        tb, mb, gas = self._raw_batch
        if (self.elasticity_config is not None
                and getattr(self.elasticity_config, "enabled", False)
                and "DS_ELASTIC_TRAIN_BATCH_SIZE" in _os.environ):
            tb = int(_os.environ["DS_ELASTIC_TRAIN_BATCH_SIZE"])
            mb = int(_os.environ.get("DS_ELASTIC_MICRO_BATCH_SIZE", mb or 1))
            gas = None  # derived from tb/(mb·dp) below
        if tb is not None and mb is not None and gas is not None:
            if tb != mb * gas * dp_world_size:
                raise DeepSpeedConfigError(
                    f"train_batch_size ({tb}) != micro_batch ({mb}) * "
                    f"grad_accum ({gas}) * dp_world ({dp_world_size})")
        elif tb is not None and mb is not None:
            gas = tb // (mb * dp_world_size)
            if gas == 0 or tb % (mb * dp_world_size) != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size ({tb}) not divisible by micro_batch*dp "
                    f"({mb}*{dp_world_size})")
        elif tb is not None and gas is not None:
            if tb % (gas * dp_world_size) != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size ({tb}) not divisible by gas*dp")
            mb = tb // (gas * dp_world_size)
        elif tb is not None:
            gas = 1
            if tb % dp_world_size != 0:
                raise DeepSpeedConfigError(
                    f"train_batch_size ({tb}) not divisible by dp ({dp_world_size})")
            mb = tb // dp_world_size
        elif mb is not None:
            gas = gas or 1
            tb = mb * gas * dp_world_size
        else:
            raise DeepSpeedConfigError(
                "At least train_batch_size or train_micro_batch_size_per_gpu "
                "must be set in the config")
        self.train_batch_size = tb
        self.train_micro_batch_size_per_gpu = mb
        self.gradient_accumulation_steps = gas
        self._dp_degree = dp_world_size
        return tb, mb, gas

    # ------------------------------------------------------------------ checks
    def _do_sanity_check(self):
        if self.optimizer_name is not None and self.fp16_enabled:
            pass  # fp16 + any optimizer is allowed; dynamic scale handles it
        if self.zero_optimization_stage > 0 and not (self.fp16_enabled
                                                     or self.bfloat16_enabled):
            logger.debug("ZeRO enabled with fp32 — allowed, but bf16 is the "
                         "TPU-recommended precision")

    def config_hash(self):
        """Stable content hash of the user config — recorded in each
        checkpoint manifest so a resume under a *different* config is
        flagged (warning, not error: elastic rescales legitimately resume
        with a re-solved batch schedule)."""
        import hashlib
        blob = json.dumps(self._param_dict, sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def print_user_config(self):
        logger.info(json.dumps(self._param_dict, sort_keys=True, indent=4))
