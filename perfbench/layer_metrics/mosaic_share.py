"""Per-layer metric ``mosaic_share.<job>``."""


def read(record):
    """Share of the first chip's busy time inside Mosaic custom calls."""
    tr = record.get("trace")
    if not tr or not tr["busy_s_by_device"][0]:
        return None
    return 100.0 * tr["mosaic_s"] / tr["busy_s_by_device"][0]
