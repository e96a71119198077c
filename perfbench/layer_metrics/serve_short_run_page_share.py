"""Per-layer metric ``serve_short_run_page_share``."""


def read(record):
    """Share of the paged kernel's K/V page loads whose item computed one
    slab of rows and not its whole tile: over the traced ``ds:serve.step``
    spans, ragged steps and bursts, sum ``short_pages`` / sum ``grid_pages``
    (both of one layer's call).  A short item is a run that lies inside one
    slab: a decode token, a burst's row.  None where no traced step carries
    the count (a program from before it existed)."""
    from perfbench import serve_trace
    t = serve_trace.traced(record)
    steps = [c for c in t["steps"] if "short_pages" in c] if t else []
    loads = sum(int(c.get("grid_pages", 0)) for c in steps)
    if not loads:
        return None
    return 100.0 * sum(int(c["short_pages"]) for c in steps) / loads
