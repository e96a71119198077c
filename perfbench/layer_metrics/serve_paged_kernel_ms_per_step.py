"""Per-layer metric ``serve_paged_kernel_ms_per_step``."""


def read(record):
    """Time of the first chip inside the ``ds_paged_*`` kernels per traced
    ``ds:serve.step``."""
    from perfbench import program_trace
    s = program_trace.summary(record)
    if not s or not s["serve"]["steps"]:
        return None
    return s["device_ms_by_class"].get("paged_kernel", 0.0) \
        / s["serve"]["steps"]
