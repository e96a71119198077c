"""Per-layer metric ``serve_ragged_paged_kernel_ms``."""


def read(record):
    """Time of the first chip inside the ``ds_paged_*`` kernels within the
    execution of a ragged engine step, a step: over the steps whose launch,
    execution and fetch lie inside the traced stretch
    (``perfbench/step_trace.py``)."""
    from perfbench import step_trace
    t = step_trace.traced(record)
    return t and step_trace.ragged_mean(t, "paged_kernel_ms")
