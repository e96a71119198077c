"""Per-layer metric ``serve_burst_iteration_device_ms``."""


def read(record):
    """Device time of one iteration of a decode burst (token generation):
    the whole bursts' execution time over the sum of their ``burst_k``;
    None where the stretch holds no whole burst
    (``perfbench/step_trace.py``)."""
    from perfbench import step_trace
    t = step_trace.traced(record)
    return t and step_trace.per_iteration(t, "device_ms")
