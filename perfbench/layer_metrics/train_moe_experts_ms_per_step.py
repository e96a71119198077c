"""Per-layer metric ``train_moe_experts_ms_per_step`` (and what the readers of
a routed TRAINING step share: ``traced``, ``scope_ms``, ``counted``,
``traced_config``).

``program_trace.summary`` classes a training step's ops by module (attention,
mlp, lm_head ...) and has no line for the expert layer's own scopes or for the
counts a model makes on the device; this file walks the same trace itself."""

import json
import os

_CACHE = {}


def scope_parts(tf_op):
    """The scope path's components, JAX's wrappers taken off:
    ``transpose(jvp(ds.moe_experts))`` -> ``ds.moe_experts``, so that forward,
    backward and recomputed ops of a scope read alike."""
    out = []
    for part in tf_op.rstrip(":").split("/"):
        while "(" in part and part.endswith(")"):
            part = part[part.index("(") + 1:-1]
        out.append(part)
    return out


def reduce_planes(planes, names):
    """``{"steps", "micros", "ops"}`` of a trace's planes: the traced
    optimizer steps (``ds:train.apply`` spans), the stats of every
    ``ds:train.micro`` span, and the first chip's ops as ``(scope path
    components, milliseconds)``, all inside the ``pb:traced`` stretch; None
    with no device op."""
    from perfbench import program_trace, xplane
    device = sorted(n for n in planes if xplane.DEVICE_PLANE.match(n))
    ops = planes[device[0]].get(xplane.OP_LINE) if device else None
    if not ops:
        return None
    host = [e for evs in planes.get(program_trace.HOST_PLANE, {}).values()
            for e in evs]
    lo, hi = min(e[1] for e in ops), max(e[2] for e in ops)
    window = [e for e in host if e[0] == xplane.WINDOW_SPAN]
    if window:
        t = max(window, key=lambda e: e[2] - e[1])
        if t[1] < hi and t[2] > lo:
            lo, hi = t[1], t[2]
    inside = lambda name: [e[3] for e in host
                           if e[0] == names.SPAN_PREFIX + name
                           and lo <= e[1] <= hi]
    return {
        "steps": len(inside(names.TRAIN_APPLY)),
        "micros": inside(names.TRAIN_MICRO),
        "ops": [(scope_parts(meta.get("tf_op") or ""),
                 (min(e, hi) - max(s, lo)) / 1e6)
                for _, s, e, _, meta in ops if min(e, hi) > max(s, lo)]}


def traced(record):
    """:func:`reduce_planes` of this run's trace, or None: no traced run, no
    trace file, or a program without the names."""
    from perfbench import program_trace
    names = program_trace.program_names()
    if not record.get("trace") or names is None:
        return None
    path = program_trace.find_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = t = reduce_planes(program_trace.read_file(path), names)
        if t is not None:
            print("INFO train_trace: " + json.dumps({
                "steps": t["steps"], "micro_spans": len(t["micros"]),
                "counted": counted(t)}), flush=True)
    return _CACHE[key]


def counted(t):
    """The counts made on the device that the traced ``ds:train.micro`` spans
    carry, summed, under their own names, with ``micro_steps_covered`` (the
    micro-steps they are the counts of: a span brings those of EARLIER steps,
    one or several); None where no span carries any."""
    rows = [c for c in t["micros"] if "micro_steps_covered" in c]
    if not rows:
        return None
    return {k: sum(int(c[k]) for c in rows) for k in rows[0]
            if k not in ("step", "micro_step")}


def scope_ms(record, scope_name):
    """``(milliseconds a traced step of the first chip's ops whose scope path
    holds the program's scope ``names.<scope_name>``, forward and backward,
    the reduced trace)``, or None: no traced run, no traced step, a program
    without the name, or no op under it."""
    from perfbench import program_trace
    names = program_trace.program_names()
    scope = getattr(names, scope_name, None)
    t = traced(record) if scope else None
    if not t or not t["steps"]:
        return None
    under = [ms for parts, ms in t["ops"] if scope in parts]
    return (sum(under) / t["steps"], t) if under else None


def traced_config(record):
    """The configuration file of the cell whose trace this run left
    (``.perfbench_trace/<cell>/``), or None."""
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
    return loader.load_json(os.path.join(program_trace.ROOT, entry["file"]))


def read(record):
    """Time of the first chip's ops under the ``ds.moe_experts`` scope (the
    gather of the copies that landed on a held expert, the grouped products
    and the weighted scatter-add, forward and backward) per traced step."""
    got = scope_ms(record, "SCOPE_MOE_EXPERTS")
    return got and got[0]
