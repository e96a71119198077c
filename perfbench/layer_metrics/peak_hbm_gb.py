"""Per-layer metric ``peak_hbm_gb.<job>``."""


def read(record):
    """``memory_stats()["peak_bytes_in_use"]``, max over chips, after the
    window: the peak of the whole process, the reference check included."""
    peak = (record.get("device") or {}).get("memory_peak_bytes")
    return peak / 1e9 if peak else None
