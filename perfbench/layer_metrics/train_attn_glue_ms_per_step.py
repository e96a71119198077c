"""Per-layer metric ``train_attn_glue_ms_per_step``."""


def read(record):
    """The first chip's time in the leaf ops under ``ds.attn_rotary``,
    ``ds.attn_kv_repeat`` and ``ds.attn_core`` that are NOT ``ds_flash_*``
    kernels (nor collectives): rotary, the K/V repeat, layout changes and
    ``shard_map`` edges, per whole step (``perfbench/train_step_trace.py``)."""
    from perfbench import train_step_trace
    t = train_step_trace.traced(record)
    return t and train_step_trace.per_step(t, "attn_glue_ms")
