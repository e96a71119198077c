"""What the serving readers added since PR 27 read from a run's trace and
``program_trace.summary`` does not hold: every count of the traced
``ds:serve.step`` spans, and the first chip's ops with their scope paths,
both inside the ``pb:traced`` stretch.  With no traced run, no trace file or a
program without the names it returns None, and the readers built on it
return None."""

import json
import os

from . import program_trace, xplane

_CACHE = {}
#: counts of a ``ds:serve.step`` that ``program_trace``'s ``ragged_sums`` does
#: not sum (``INFO program_spans`` is that file's line, with a fixed list):
#: summed over the traced steps of every kind on the ``INFO serve_trace`` line
COUNTS = ("context_tokens", "held_blocks", "summary_pages", "grid_pages",
          "chunks_closed", "windows_closed")


def traced(record):
    """``{"steps": [counts of each ds:serve.step], "ops": [(scope path
    components, milliseconds)]}`` of this run's trace, or None."""
    names = program_trace.program_names()
    if not record.get("trace") or names is None:
        return None
    path = program_trace.find_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = t = _reduce(program_trace.read_file(path), names)
        if t is not None:
            print("INFO serve_trace: " + json.dumps({
                "steps": len(t["steps"]),
                "sums": {k: sum(int(c.get(k, 0)) for c in t["steps"])
                         for k in COUNTS}}), flush=True)
    return _CACHE[key]


def _reduce(planes, names):
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
    step = names.SPAN_PREFIX + names.SERVE_STEP
    return {
        "steps": [e[3] for e in host if e[0] == step and lo <= e[1] <= hi],
        "ops": [((meta.get("tf_op") or "").rstrip(":").split("/"),
                 (min(e, hi) - max(s, lo)) / 1e6)
                for _, s, e, _, meta in ops if min(e, hi) > max(s, lo)]}
