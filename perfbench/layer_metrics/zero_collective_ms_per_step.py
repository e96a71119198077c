"""Per-layer metric ``zero_collective_ms_per_step``."""


def read(record):
    """Time during which a collective op is in flight on the first chip (the
    union of their intervals, an async op from its start to its done), per
    traced step.  Most of it overlaps compute: see the exposed share."""
    tr = record.get("trace")
    if not tr or not record.get("traced_steps"):
        return None
    return 1e3 * tr["collective_s"] / record["traced_steps"]
