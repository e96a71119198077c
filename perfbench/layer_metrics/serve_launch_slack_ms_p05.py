"""Per-layer metric ``serve_launch_slack_ms_p05``."""


def read(record):
    """Over the engine steps launched ahead: (end of the execution of the
    step before) - (end of the step's ``ds:serve.launch``), 5th percentile:
    how long the next program had been queued when the device needed it.
    At or under 0 the chip waited for the host.  A host time subtracted from
    a device time: it stands on the clock checks of
    ``perfbench/step_trace.py``."""
    from perfbench import step_trace
    from perfbench.harness import percentile
    t = step_trace.traced(record)
    return t and percentile(step_trace.launch_slacks_ms(t), 5)
