"""Autotuning config — same JSON keys as reference
``autotuning/constants.py`` / ``autotuning/config.py`` for the surviving
surface, plus the comm-surface closed loop (ISSUE 12 / docs/autotuning.md).

Unlike every other config block, this one REJECTS unknown keys
(``extra="forbid"``): a mistyped search knob (``bucket_mb_candiates``)
would otherwise silently tune the default space and burn the whole trial
budget measuring nothing the user asked for.  Stale reference-only fields
(``arg_mappings``, ``mp_size``, ``model_info``, ``overwrite``,
``max/min_train_batch_size``) that were parsed-but-ignored are gone for
the same reason — configs carrying them now fail loudly instead of
pretending the knob did something.
"""

from typing import Dict, List, Optional

from pydantic import ConfigDict, model_validator

from ..runtime.config_utils import DeepSpeedConfigModel

METRICS = ("throughput", "latency", "flops", "step_time")
TUNER_TYPES = ("gridsearch", "random", "model_based")
#: metrics where smaller is better (the tuner runs in min mode)
MIN_METRICS = ("latency", "step_time")


class AutotuningConfig(DeepSpeedConfigModel):
    # pydantic v2 merges this with DeepSpeedConfigModel's ConfigDict, so
    # only the one divergence is stated: unknown keys fail loudly (see
    # module doc) instead of the base's extra="allow"
    model_config = ConfigDict(extra="forbid")

    enabled: bool = False
    fast: bool = True
    results_dir: str = "autotuning_results"
    exps_dir: str = "autotuning_exps"
    start_profile_step: int = 3
    end_profile_step: int = 5
    # throughput | latency | flops | step_time (step_time/latency = min mode)
    metric: str = "throughput"
    tuner_type: str = "gridsearch"      # gridsearch | random | model_based
    tuner_early_stopping: int = 5
    tuner_num_trials: int = 50
    max_train_micro_batch_size_per_gpu: int = 1024
    min_train_micro_batch_size_per_gpu: int = 1
    num_tuning_micro_batch_sizes: int = 3
    zero_stages: Optional[List[int]] = None  # TPU addition: restrict space
    # TPU addition: also explore mesh factorizations (the launcher-level
    # knob the reference cannot tune in-process).  Candidates are dicts for
    # the config's "mesh" key, e.g. [{"dp": -1}, {"dp": -1, "tp": 2}];
    # None + tune_mesh=True → derived from the device count.
    tune_mesh: bool = False
    mesh_candidates: Optional[List[Dict]] = None

    # ------------------------------------------------ comm-surface loop
    # tune_comm: walk the comm_optimizations/ZeRO surface instead of the
    # legacy stage × micro-batch grid — topology probe first, then the
    # search stage over per-size wire dtype / hierarchy / min_message_size
    # / overlap bucketing, scored by measured step time with
    # exposed_comm_frac as the tie-breaker (docs/autotuning.md).
    tune_comm: bool = False
    # mesh axis the comm trials/probes sweep
    comm_axis: str = "dp"
    # micro-probe surface: log2 payload bytes per size bucket, quantized
    # wire formats to race against the flat fp32 op, and the warmup +
    # repeat-block protocol (median + IQR, see ds_bench --repeat)
    probe_sizes: List[int] = [14, 18, 22]
    probe_wires: List[str] = ["int8", "fp8"]
    probe_iters: int = 4
    probe_warmup: int = 1
    probe_repeat: int = 3
    # search-space candidate lists
    bucket_mb_candidates: List[float] = [1.0, 4.0, 32.0]
    max_inflight_candidates: List[int] = [2]
    min_message_sizes: List[int] = [0]
    hierarchical_candidates: List[bool] = [True]
    # quantization_group_size candidates composed onto the quantized
    # (qgZ/qwZ) wire bases; empty (default) keeps the block default —
    # the space is unchanged unless the user opts into the sweep
    group_size_candidates: List[int] = []
    # the zero-mode search dimension: when
    # "flat_manual" is listed, every quantized-gradient wire base gets a
    # legacy full-manual-micro sibling so the measured trial decides which
    # micro architecture carries qgZ on THIS model/mesh (docs/zero.md)
    zero_mode_candidates: List[str] = ["gspmd", "flat_manual"]
    # candidates within this relative step-time margin count as a tie and
    # are broken by the lower exposed_comm_frac
    tie_rtol: float = 0.02

    @model_validator(mode="after")
    def _check_enums(self):
        if self.metric not in METRICS:
            raise ValueError(f"autotuning.metric {self.metric!r} unknown "
                             f"(have {', '.join(METRICS)})")
        if self.tuner_type not in TUNER_TYPES:
            raise ValueError(
                f"autotuning.tuner_type {self.tuner_type!r} unknown "
                f"(have {', '.join(TUNER_TYPES)})")
        from ..comm.collectives import WIRE_FORMATS
        for w in self.probe_wires:
            if w not in WIRE_FORMATS:
                raise ValueError(
                    f"autotuning.probe_wires entry {w!r} unknown "
                    f"(have {', '.join(WIRE_FORMATS)})")
        from ..runtime.zero.gspmd import ZERO_MODES
        for zm in self.zero_mode_candidates:
            if zm not in ZERO_MODES:
                raise ValueError(
                    f"autotuning.zero_mode_candidates entry {zm!r} unknown "
                    f"(have {', '.join(ZERO_MODES)})")
        for gs in self.group_size_candidates:
            if int(gs) < 128:
                raise ValueError(
                    "autotuning.group_size_candidates entries must be "
                    f">= 128 (got {gs}) — the codecs lane-align scale "
                    "groups down to 128")
        if self.start_profile_step < 1:
            raise ValueError("autotuning.start_profile_step must be >= 1")
        return self
