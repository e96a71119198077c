"""Per-layer metric ``train_host_late_ms_per_step``."""


def read(record):
    """Of ``train_device_gap_ms_per_step``, the part in which the next
    program had not been enqueued (before the end of the runtime's
    ``DoEnqueueProgram`` for it): how long a step the chip waited for the
    HOST.  The inside twin of ``train_host_ms_per_step``; a host time beside
    a device time, so it stands on the clock check of
    ``perfbench/train_step_trace.py``."""
    from perfbench import train_step_trace
    t = train_step_trace.traced(record)
    return t and train_step_trace.per_step(t, "host_late_ms")
