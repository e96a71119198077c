"""Runtime-independent view of the ``comm_optimizations`` config block.

The JSON-schema'd pydantic model lives with the rest of the config system
(``runtime/config.py:CommOptimizationsConfig``); this dataclass carries the
same fields with the same defaults for standalone consumers (benchmarks,
tests, tools) that must not drag the full runtime config machinery in.  The
engine itself is duck-typed — either object works.
"""

from dataclasses import dataclass, field

from .quantized import DEFAULT_GROUP_SIZE


@dataclass
class Prefetch:
    """Forward-direction ZeRO-3 param-gather prefetch knobs (see
    ``runtime/zero/overlap.py`` / docs/overlap.md forward-prefetch
    section).  Own enable gate, independent of ``Overlap.enabled``."""
    enabled: bool = False
    # bucket payload bound in MiB; 0 = the 32 MiB overlap default (the
    # config layer stamps this from stage3_prefetch_bucket_size when that
    # reference knob armed the prefetch)
    bucket_mb: float = 0.0
    # max buckets with their all-gather outstanding; clamped per model by
    # stage3_max_live_parameters
    max_inflight: int = 2


@dataclass
class Overlap:
    """Bucketed backward-pass gradient-reduction scheduler knobs (see
    ``runtime/zero/overlap.py`` / docs/overlap.md).  Own enable gate:
    bucketing changes when reduces run, not what they carry."""
    enabled: bool = False
    # bucket size bound in MiB of gradient payload (fractional ok)
    bucket_mb: float = 32.0
    # manual qgZ path: max buckets with the inter-node hop outstanding
    max_inflight: int = 2
    # forward-direction stage-3 param-gather prefetch
    prefetch: Prefetch = field(default_factory=Prefetch)


@dataclass
class CommOptimizations:
    """See docs/collectives.md for the knob-by-knob story."""
    enabled: bool = False
    # hierarchical (intra-node → inter-node → intra-node) all-reduce and the
    # 2-hop quantized reduce-scatter; engages only when a topology hierarchy
    # exists (multi-axis group, TPU slice boundary, or intra_node_size)
    hierarchical_allreduce: bool = True
    # quantize all-gather payloads (ZeRO++ qwZ-style wire compression)
    quantized_weights: bool = False
    # quantize reduce-scatter payloads (ZeRO++ qgZ-style)
    quantized_gradients: bool = False
    # wire format for quantized payloads: int8 | int4 | fp8 | fp6 | fp12
    wire_dtype: str = "int8"
    # per-message-size wire-format ladder (EQuARX: the optimal quantization
    # varies by message size).  List of [max_bytes, wire] rungs, ascending;
    # a payload of n logical bytes takes the first rung with n <= max_bytes
    # (null/None max_bytes = catch-all), sizes above every rung fall back to
    # the global ``wire_dtype``.  "fp32" as a rung wire means "do not
    # quantize this size band" (flat path).  None/absent (default) keeps
    # the global ``wire_dtype`` for every size — bit-identical to the
    # pre-ladder engine.  Emitted by the autotuner (docs/autotuning.md).
    wire_dtype_by_size: list = None
    # elements per quantization scale group (lane-aligned down to ≥128)
    quantization_group_size: int = DEFAULT_GROUP_SIZE
    # devices per node for the hierarchy split; 0 = auto-detect from device
    # metadata (slice/process boundaries) or DS_TPU_INTRA_NODE_SIZE
    intra_node_size: int = 0
    # tensors smaller than this many bytes always take the flat path
    # (latency-bound regime — quantize/hierarchy overhead beats the savings)
    min_message_size: int = 0
    # micro-step architecture for the qgZ training path: "gspmd" (default,
    # the GSPMD-first micro with quantized islands — docs/zero.md) or
    # "flat_manual" (force the legacy full-manual shard_map micro)
    zero_mode: str = "gspmd"
    # bucketed backward-pass gradient-reduction scheduler
    overlap: Overlap = field(default_factory=Overlap)
