"""Per-layer metric ``serve_queue_ms_p95``."""


def read(record):
    """95th percentile of ``Request.t_admit - t_submit`` over the requests
    submitted and admitted inside the window."""
    from perfbench.harness import percentile
    return percentile(record.get("queue_ms") or [], 95)
