"""Per-layer metric ``serve_kv_cache_ms_per_step``."""


def read(record):
    """Time of the first chip's ops under the ``ds.kv_cache`` scope (what a
    step spends to put its K/V into the paged cache: the scatter, and in a
    program that keeps the cache as one array, the copies of a layer out of
    it and back) per traced ``ds:serve.step``.  The scope is read by
    membership in the op's path: it may lie inside ``ds.attn``."""
    from perfbench import program_trace, serve_trace
    names = program_trace.program_names()
    scope = getattr(names, "SCOPE_KV_CACHE", None)
    t = serve_trace.traced(record) if scope else None
    if not t or not t["steps"]:
        return None
    under = [ms for parts, ms in t["ops"] if scope in parts]
    if not under:
        return None
    return sum(under) / len(t["steps"])
