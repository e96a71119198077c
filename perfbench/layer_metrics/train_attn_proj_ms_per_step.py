"""Per-layer metric ``train_attn_proj_ms_per_step``."""


def read(record):
    """The first chip's time in the leaf ops under ``ds.attn_proj`` (the q,
    k, v products and the o product: forward, backward and recomputed; a
    ZeRO gather of their weights is the collectives'), per whole step
    (``perfbench/train_step_trace.py``)."""
    from perfbench import train_step_trace
    t = train_step_trace.traced(record)
    return t and train_step_trace.per_step(t, "attn_proj_ms")
