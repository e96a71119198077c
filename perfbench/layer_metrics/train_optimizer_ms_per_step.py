"""Per-layer metric ``train_optimizer_ms_per_step``."""


def read(record):
    """Time of the first chip's ops that belong to the ``ds_apply_update``
    program (the optimizer step, its collectives included) per traced step
    (``ds:train.apply`` spans)."""
    from perfbench import program_trace
    return program_trace.per_train_step(
        record, lambda s: s["optimizer_program_ms"])
