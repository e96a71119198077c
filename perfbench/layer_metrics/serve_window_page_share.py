"""Per-layer metric ``serve_window_page_share``."""


def read(record):
    """How far the window bounds the reads: over the traced ``ds:serve.step``
    spans, the K/V page loads of the window layers' paged-attention calls
    (``grid_pages_window``) over what as many full layers' calls load
    (``grid_pages_full`` x window layers / full layers), in %.  100 where
    every context lies inside the window; the published pattern is three
    window layers to one full.  None where no traced step carries the
    counts."""
    from perfbench import serve_trace
    t = serve_trace.traced(record)
    steps = [c for c in t["steps"] if "grid_pages_full" in c] if t else []
    full = sum(int(c["grid_pages_full"]) for c in steps)
    if not full:
        return None
    return 100.0 * sum(int(c["grid_pages_window"]) for c in steps) / (3 * full)
