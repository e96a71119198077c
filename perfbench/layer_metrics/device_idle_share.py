"""Per-layer metric ``device_idle_share.<job>``."""


def read(record):
    """1 - union of op intervals / traced stretch, mean over the chips."""
    tr = record.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
