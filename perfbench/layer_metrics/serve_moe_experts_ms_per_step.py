"""Per-layer metric ``serve_moe_experts_ms_per_step`` (and what the other
readers of the expert layer's scopes share: ``scope_ms``)."""

def scope_ms(record, scope_name):
    """``(milliseconds of the first chip's ops whose scope path holds the
    program's scope ``names.<scope_name>``, traced ds:serve.step spans)`` of
    a traced run, or None: no traced run, a program without the name, or no
    op under it.  (The ``lax.cond`` between the expert layer's two buffer
    lengths and a burst's ``while`` are events WITHOUT a scope path on a v5e:
    the ops they hold are counted once, under their own paths.)"""
    from perfbench import program_trace, serve_trace
    names = program_trace.program_names()
    scope = getattr(names, scope_name, None)
    t = serve_trace.traced(record) if scope else None
    if not t or not t["steps"]:
        return None
    under = [ms for parts, ms in t["ops"] if scope in parts]
    return (sum(under), t["steps"]) if under else None


def traced_config(record):
    """The configuration file of the cell whose trace this run left
    (``.perfbench_trace/<cell>/``), as a dictionary with ``depth`` (its
    serving depth) added; None without a trace or a manifest that names the
    cell."""
    import os
    from perfbench import loader, program_trace
    path = program_trace.find_trace() if record.get("trace") else None
    if path is None:
        return None
    cell = os.path.relpath(path, os.path.join(
        program_trace.ROOT, ".perfbench_trace")).split(os.sep)[0]
    manifest = loader.load_manifest(program_trace.ROOT)
    try:
        entry = loader.find(manifest["configs"], loader.find(
            manifest["workloads"], cell, "workload")["config"], "config")
    except KeyError:
        return None
    config = loader.load_json(os.path.join(program_trace.ROOT, entry["file"]))
    depth = config["num_hidden_layers"]
    return dict(config, depth=depth["serve"] if isinstance(depth, dict)
                else depth)


def read(record):
    """Time of the first chip's ops under the ``ds.moe_experts`` scope (the
    gather of the copies that landed on a held expert, the grouped matmuls,
    the weighted scatter-add) per traced ``ds:serve.step``."""
    got = scope_ms(record, "SCOPE_MOE_EXPERTS")
    return got and got[0] / len(got[1])
