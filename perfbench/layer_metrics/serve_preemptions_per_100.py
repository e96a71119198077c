"""Per-layer metric ``serve_preemptions_per_100``."""


def read(record):
    """``ServingScheduler.preemptions`` in the window per 100 completed
    requests."""
    if not record.get("completed"):
        return None
    return 100.0 * record["preemptions"] / record["completed"]
