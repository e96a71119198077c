"""Per-layer metric ``serve_ragged_step_device_ms``."""


def read(record):
    """Mean device time of a ragged engine step (prompt processing and mixed
    steps): the duration of its program's execution on the first chip's
    ``XLA Modules`` line, over the steps whose launch, execution and fetch
    lie inside the traced stretch (``perfbench/step_trace.py``)."""
    from perfbench import step_trace
    t = step_trace.traced(record)
    return t and step_trace.ragged_mean(t, "device_ms")
