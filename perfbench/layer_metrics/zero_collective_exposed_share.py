"""Per-layer metric ``zero_collective_exposed_share``."""


def read(record):
    """Collective time during which no other op runs on that chip, over the
    traced stretch."""
    tr = record.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
