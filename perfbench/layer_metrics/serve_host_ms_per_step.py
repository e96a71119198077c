"""Per-layer metric ``serve_host_ms_per_step``."""


def read(record):
    """The scheduler's serial host work a step: mean duration of the traced
    ``ds:serve.step`` spans minus their ``ds:serve.fetch`` (the one place
    the host waits for the device)."""
    from perfbench import program_trace
    s = program_trace.summary(record)
    if not s or not s["serve"]["steps"]:
        return None
    return s["serve"]["host_ms"] / s["serve"]["steps"]
