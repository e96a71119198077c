"""Per-layer metric ``serve_live_page_share``."""


def read(record):
    """Share of the paged kernel's grid that is useful work: over the traced
    ragged ``ds:serve.step`` spans, the pages the live rows' contexts really
    span (``live_pages``) over the (row, page) steps the grid visits
    (``grid_pages``)."""
    from perfbench import program_trace
    s = program_trace.summary(record)
    sums = s["serve"]["ragged_sums"] if s else {}
    if not sums.get("grid_pages"):
        return None
    return 100.0 * sums["live_pages"] / sums["grid_pages"]
