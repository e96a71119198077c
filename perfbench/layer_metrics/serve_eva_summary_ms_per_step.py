"""Per-layer metric ``serve_eva_summary_ms_per_step``."""


def read(record):
    """Time of the first chip's ops under the ``ds.eva_summary`` scope (the
    pooling of the chunks a step completes into their summaries, and the
    summaries' scatter into the cache) per traced ``ds:serve.step``."""
    from perfbench import program_trace, serve_trace
    names = program_trace.program_names()
    scope = getattr(names, "SCOPE_EVA_SUMMARY", None)
    t = serve_trace.traced(record) if scope else None
    if not t or not t["steps"]:
        return None
    under = [ms for parts, ms in t["ops"] if scope in parts]
    if not under:
        return None
    return sum(under) / len(t["steps"])
