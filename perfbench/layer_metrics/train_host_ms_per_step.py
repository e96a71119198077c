"""Per-layer metric ``train_host_ms_per_step``."""


def read(record):
    """Host time the three engine calls take to return, per step: the
    benchmark's own spans around ``engine()``, ``backward()``, ``step()``."""
    spans = record.get("spans") or {}
    if not spans.get("forward"):
        return None
    calls = sum(e - s for name in ("forward", "backward", "step")
                for s, e in spans.get(name, ()))
    return 1e3 * calls / len(spans["forward"])
