"""Per-layer metric ``serve_step_ms_p50``."""


def read(record):
    """Median of the benchmark's span around each ``sched.step()`` that
    emitted tokens."""
    from perfbench.harness import percentile
    return percentile(record.get("step_ms") or [], 50)
