"""Per-layer metric ``serve_tpot_ms_p95``."""


def read(record):
    """95th percentile, over every request that streamed two or more tokens
    inside the window (in flight at its close or not), of the time per output
    token there: (last token - first token) / (tokens - 1).  Per request and
    not per gap: a decode burst hands a caller k tokens at once."""
    from perfbench.harness import percentile
    return percentile(record.get("tpot_ms") or [], 95)
