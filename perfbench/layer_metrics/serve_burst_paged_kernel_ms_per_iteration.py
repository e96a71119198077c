"""Per-layer metric ``serve_burst_paged_kernel_ms_per_iteration``."""


def read(record):
    """Time of the first chip inside the ``ds_paged_*`` kernels within the
    executions of the whole decode bursts, an iteration (the sum of their
    ``burst_k``); None where the stretch holds no whole burst
    (``perfbench/step_trace.py``)."""
    from perfbench import step_trace
    t = step_trace.traced(record)
    return t and step_trace.per_iteration(t, "paged_kernel_ms")
