"""Inference v2 config (reference ``inference/v2/config_v2.py``:
``RaggedInferenceEngineConfig``, ``DeepSpeedTPConfig``,
``DSStateManagerConfig`` — same key names, TPU-sized defaults)."""

from typing import Optional

from ...runtime.config_utils import DeepSpeedConfigModel


class DeepSpeedTPConfig(DeepSpeedConfigModel):
    tp_size: int = 1


class DSStateManagerConfig(DeepSpeedConfigModel):
    max_tracked_sequences: int = 2048
    max_ragged_batch_size: int = 768          # token budget per engine step
    max_ragged_sequence_count: int = 512      # seqs per step
    max_context: int = 8192
    memory_config: Optional[dict] = None
    offload: bool = False

    # blocked-KV geometry (reference AllocationMode/KVCacheConfig)
    block_size: int = 128
    num_blocks: Optional[int] = None          # None → derived


class RaggedInferenceEngineConfig(DeepSpeedConfigModel):
    tensor_parallel: DeepSpeedTPConfig = DeepSpeedTPConfig()
    state_manager: DSStateManagerConfig = DSStateManagerConfig()
    dtype: str = "bfloat16"
    quantization_mode: Optional[str] = None
    # Quantized paged-KV serving (``kv_codec.py``): store the blocked KV
    # cache as int8/fp8 rows + per-token f32 scales (dequant-on-read ragged
    # forward) so one chip holds ~2-4× more concurrent sequences.  None
    # (default) keeps the full-precision cache — bit-identical programs.
    kv_cache_dtype: Optional[str] = None
    # Max greedy decode steps fused into one device program when every
    # running sequence is in pure decode (``ragged_forward.decode_burst``) —
    # one host round-trip per ``decode_burst`` tokens instead of per token.
    # 0/1 disables (exact per-step reference loop).
    decode_burst: int = 16
    # Opt-in: fuse SAMPLED decode too (device-side temperature/top-k/top-p
    # categorical with the jax PRNG).  Off by default because the draws are
    # a different (seed-deterministic) stream than the host loop's numpy
    # Generator; requires ``rng`` passed as a seed, not a Generator.
    decode_burst_sampling: bool = False
