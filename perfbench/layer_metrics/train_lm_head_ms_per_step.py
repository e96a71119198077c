"""Per-layer metric ``train_lm_head_ms_per_step``."""


def read(record):
    """Time of the first chip's ops under the ``ds.lm_head_loss`` scope
    (the head's matmul and the loss, forward and backward) per traced
    step."""
    from perfbench import program_trace
    return program_trace.per_train_step(
        record, lambda s: s["device_ms_by_class"].get("lm_head", 0.0))
