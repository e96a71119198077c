"""Per-layer metric ``serve_cache_kb_per_token``."""


def read(record):
    """KB (1024 bytes) the cache keeps of ONE token over all its entries:
    the ``cache_token_bytes`` of the traced ``ds:serve.step`` spans (the
    engine's own count of its buffers; a looped model's steps carry it: an
    entry a (pass, layer) pair).  None where no step carries it."""
    from perfbench import serve_trace
    t = serve_trace.traced(record)
    rows = [int(c["cache_token_bytes"]) for c in (t["steps"] if t else ())
            if "cache_token_bytes" in c]
    return sum(rows) / len(rows) / 1024 if rows else None
