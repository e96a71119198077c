"""Per-layer metric ``serve_expanded_row_share``: of the live rows of the
ragged engine steps, the share that read the paged cache through the
EXPANDED form of multi-head latent attention (``ds_paged_mla_chunk``: the
per-head keys and values made from the latent pages inside the kernel, once a
run) and not through the absorbed one (``ds_paged_latent``).  A run's length
picks the form; the batch builder counts both on every ``ds:serve.step``."""


def share(steps):
    """``expanded_rows`` over the rows that took either form (a step that
    carries no ``absorbed_rows``, a model with no latent cache: its
    ``live_tokens``, none of them expanded), in %; None without a row."""
    expanded = sum(int(c.get("expanded_rows", 0)) for c in steps)
    rows = sum(int(c["absorbed_rows"]) + int(c.get("expanded_rows", 0))
               if "absorbed_rows" in c else int(c.get("live_tokens", 0))
               for c in steps)
    return 100.0 * expanded / rows if rows else None


def read(record):
    """Over the traced ``ds:serve.step`` spans of kind ``ragged`` (a burst
    has one row a sequence and never takes the expanded form).  0 where the
    program counts no expanded row: a parent of PR 51, a cache that is not a
    latent one.  None for an untraced run or a trace with no ragged step."""
    from perfbench import serve_trace
    t = serve_trace.traced(record)
    return share([c for c in t["steps"] if c.get("kind") == "ragged"]) \
        if t else None
