"""Per-layer metric ``train_flash_kernel_ms_per_step``."""


def read(record):
    """Time of the first chip inside the ``ds_flash_*`` kernels (forward,
    recomputed forward, both backward kernels) per traced step."""
    from perfbench import program_trace
    return program_trace.per_train_step(
        record, lambda s: s["device_ms_by_class"].get("flash_kernel", 0.0))
