"""Per-layer metric ``serve_cache_tokens_per_row``."""


def read(record):
    """Tokens of context a row of the paged cache stands for: over the traced
    ``ds:serve.step`` spans, the running sequences' context lengths
    (``context_tokens``) over the cache rows they hold (``held_blocks`` x
    ``block_size``).  At most 1 where every token keeps its K/V; above it
    where a window's K/V are given back and 1/chunk_size summaries stay."""
    from perfbench import serve_trace
    t = serve_trace.traced(record)
    rows = sum(int(c.get("held_blocks", 0)) * int(c.get("block_size", 0))
               for c in t["steps"]) if t else 0
    if not rows:
        return None
    return sum(int(c.get("context_tokens", 0)) for c in t["steps"]) / rows
