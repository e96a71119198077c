"""Per-layer metric ``train_device_gap_ms_per_step``."""


def read(record):
    """The first chip's idle BETWEEN the executions of a whole step's
    programs (the end of one to the start of the next, other programs' ops
    taken off), per whole step (``perfbench/train_step_trace.py``)."""
    from perfbench import train_step_trace
    t = train_step_trace.traced(record)
    return t and train_step_trace.per_step(t, "gap_ms")
