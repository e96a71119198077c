"""A serving step's life as one row: its launch, its execution on the chip and
its fetch, joined by the step's id.

Since PR 36 an engine step's life crosses two scheduler turns, and since
ISSUE 37 every span of it carries the step's id: ``ds:serve.launch`` (with
``kind`` and ``burst_k``), the ``ds:serve.fetch`` that waits for it (with the
counts made on the device), ``ds:serve.dispatch``, and the turn's
``ds:serve.step`` as ``launch`` (the step it launched, whose counts it holds)
and ``fetched``.  This module adds the third leg, the step's execution on chip
0's ``XLA Modules`` line, and reduces the chip's ops INSIDE each execution.

The join, as one v5e trace read by hand showed it (PR 37): a module event
carries a ``run_id`` stat, and so does the runtime's host event
``DoEnqueueProgram`` of the same program, which lies inside the
``ds:serve.launch`` that dispatched it (on whichever host thread).  So a
launch's execution is the module event of a step program
(``names.PROGRAM_RAGGED_STEP*`` / ``PROGRAM_DECODE_BURST``) whose ``run_id``
an enqueue inside the launch span names.  Launches and executions are both in
order, so ``execution index - launch`` is one number a trace: a launch whose
enqueue fell outside its span takes the execution that number gives it, and
executions whose launch predates the trace get their ids the same way.

The table checks itself: the span's ``kind`` against the program's name, the
execution's start not before its launch span's start, its end not after its
fetch's end (+0.2 ms), and the order (every joined launch at the one offset).
A row is WHOLE where launch span, execution and fetch lie inside the
``pb:traced`` stretch; the executions that straddle its edges are kept, clipped
to it, so that the table adds up to the stretch's totals.

``traced(record)`` reduces the newest trace once a process and prints one
``INFO step_trace: {...}`` line.  With no traced run, no trace file, or a
program whose spans carry no ``launch`` (any parent before PR 37) it returns
None, and every reader built on it returns None.
"""

import json
import os
import re
import statistics

from . import program_trace, xplane

#: the runtime's host event of one program given to the chip's queue; its
#: ``run_id`` stat is the ``run_id`` of the execution on the module line
ENQUEUE = "DoEnqueueProgram"
RUN_ID = "run_id"
#: an execution may end this long after its fetch returned before the clock
#: check fails: the two clocks agree to well under it (PR 24)
SLACK_NS = 0.2e6
#: a ``ds.<layer>`` scope in an op's scope path (``telemetry/names.py``)
_SCOPE = re.compile(r"\bds\.\w+")
_CACHE = {}


def read_file(path):
    """``program_trace.read_file`` with the runtime's enqueue events kept."""
    with open(path, "rb") as f:
        data = f.read()
    chips = sorted(int(m.group(1)) for m in map(
        xplane.DEVICE_PLANE.match, program_trace.plane_names(data)) if m)
    first = f"/device:TPU:{chips[0]}" if chips else None
    spans = ("ds:", xplane.SPAN_PREFIX)
    host = program_trace.HOST_PLANE
    return program_trace.read_planes(
        data, want_plane=lambda n: n in (host, first),
        want_event=lambda p, e: p != host or e.startswith(spans)
        or e == ENQUEUE)


def _kind_of(program, names):
    """The kind of step a module event's program runs, or None."""
    if names.PROGRAM_DECODE_BURST in program:
        return names.KIND_BURST
    if names.PROGRAM_RAGGED_STEP in program:
        return names.KIND_RAGGED
    return None


def _program_of(meta, programs):
    """The program an op runs in: its metadata's program id (an int64 stat)
    as the module line prints it."""
    try:
        return programs.get(int(meta.get("program_id")) % (1 << 64), "")
    except (TypeError, ValueError):
        return ""


def _leaves(ops):
    """Of the op line's events, those that hold no other: a burst's ``while``
    and the expert layer's ``conditional`` are parents (without a scope
    path), the ops they hold are counted once, under their own names."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    parents, stack = set(), []
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]][2] <= e[1]:
            stack.pop()
        if stack:
            parents.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(ops) if i not in parents]


def join(planes, names):
    """The table of one trace: ``{"rows": [...], "checks": {...}, ...}``, one
    row a launched step (see the module's docstring), or None where the
    program's spans carry no ``launch``."""
    launch_key = getattr(names, "COUNT_LAUNCH", None)
    device = sorted(n for n in planes if xplane.DEVICE_PLANE.match(n))
    chip = planes[device[0]] if device else {}
    ops, modules = chip.get(xplane.OP_LINE), chip.get(
        program_trace.MODULE_LINE)
    if launch_key is None or not ops or not modules:
        return None
    host = [e for evs in planes.get(program_trace.HOST_PLANE, {}).values()
            for e in evs]
    prefix = names.SPAN_PREFIX

    def by_launch(name):
        return {int(e[3][launch_key]): e for e in host
                if e[0] == prefix + name and launch_key in e[3]}

    launches = by_launch(names.SERVE_LAUNCH)
    if not launches:
        return None
    fetches, turns = (by_launch(n) for n in (names.SERVE_FETCH,
                                             names.SERVE_STEP))

    lo, hi = min(e[1] for e in ops), max(e[2] for e in ops)
    window = [e for e in host if e[0] == xplane.WINDOW_SPAN]
    if window:
        t = max(window, key=lambda e: e[2] - e[1])
        if t[1] < hi and t[2] > lo:
            lo, hi = t[1], t[2]

    # ---- the executions of the two step programs, in order, and the join
    kinds = (names.KIND_RAGGED, names.KIND_BURST)
    programs, execs = {}, []
    for name, s, e, stats, _ in sorted(modules, key=lambda m: m[1]):
        m = re.match(r"^(.*)\((\d+)\)$", name)
        if not m:
            continue
        programs[int(m.group(2))] = m.group(1)
        kind = _kind_of(m.group(1), names)
        if kind is not None:
            execs.append({"program": m.group(1), "kind": kind, "start": s,
                          "end": e, RUN_ID: stats.get(RUN_ID)})
    index_of = {x[RUN_ID]: i for i, x in enumerate(execs)
                if x[RUN_ID] is not None}
    enqueued = sorted((e[1], e[3].get(RUN_ID)) for e in host
                      if e[0] == ENQUEUE)
    found = {}                      # launch -> execution index, by run_id
    for n, span in launches.items():
        hits = [index_of[r] for t, r in enqueued
                if span[1] <= t <= span[2] and r in index_of]
        if len(hits) == 1:
            found[n] = hits[0]
    if not found:
        # no enqueue event names a step program: nothing to hold on to
        return {"rows": [], "edges": [], "kinds": kinds, "checks": {},
                "joined_by": {}, "busy_ms": 0.0, "uncovered_ms": 0.0,
                "uncovered_ms_by_program": {},
                "paged_kernel_ms_in_stretch": None,
                "unjoined": sum(lo <= s[1] and s[2] <= hi
                                for s in launches.values())}
    offsets = [i - n for n, i in found.items()]
    offset = statistics.mode(offsets)
    checks = {"kind": 0, "order": sum(o != offset for o in offsets),
              "launch_clock": 0, "fetch_clock": 0}

    # ---- chip-0 time inside every execution that touches the stretch
    leaves = _leaves(ops)
    classes = {}
    inside = [_Reduced() for _ in execs]
    starts = [x["start"] for x in execs]
    at = 0
    for name, s, e, _, meta in leaves:
        while at + 1 < len(execs) and starts[at + 1] <= s:
            at += 1
        x = execs[at]
        cs, ce = max(s, lo), min(e, hi)
        if ce <= cs or not x["start"] <= s < x["end"]:
            continue
        program = _program_of(meta, programs)
        if (name, program) not in classes:
            classes[name, program] = program_trace.classify(
                name, meta, program, names)
        inside[at].add(name, (ce - cs) / 1e6, classes[name, program],
                       meta.get("tf_op") or "")

    rows, edges, unjoined = [], [], 0
    joined_by = {"run_id": 0, "order": 0}
    for i, x in enumerate(execs):
        n = i - offset
        in_stretch = x["end"] > lo and x["start"] < hi
        span = launches.get(n)
        if span is None and n > min(launches):
            # the trace lost a launch span (a step launched before the trace
            # began is an edge, not a fault)
            unjoined += lo <= x["start"] and x["end"] <= hi
        if found.get(n, i) != i:
            continue                # counted in checks["order"]
        turn, fetch = turns.get(n), fetches.get(n)
        row = {
            "launch": n, "kind": x["kind"], "program": x["program"],
            "burst_k": int(span[3].get("burst_k", 0)) if span else None,
            "exec_start": x["start"], "exec_end": x["end"],
            "device_ms": (x["end"] - x["start"]) / 1e6,
            "inside_ms": max(0.0, min(x["end"], hi) - max(x["start"], lo))
            / 1e6,
            "launch_start": span and span[1], "launch_end": span and span[2],
            "fetch_start": fetch and fetch[1], "fetch_end": fetch and fetch[2],
            "counts": dict(turn[3]) if turn else {},
            "device_counts": {k: v for k, v in fetch[3].items()
                              if k != launch_key} if fetch else {},
            "before_end": execs[i - 1]["end"] if i else None,
            **inside[i].as_dict()}
        if span is not None and in_stretch:
            joined_by["run_id" if n in found else "order"] += 1
            checks["kind"] += span[3].get("kind") != x["kind"]
            checks["launch_clock"] += x["start"] < span[1]
            if fetch is not None:
                checks["fetch_clock"] += x["end"] > fetch[2] + SLACK_NS
        row["whole"] = bool(
            span is not None and lo <= span[1] and x["start"] >= lo
            and max(x["end"], fetch[2] if fetch else 0) <= hi)
        if row["whole"]:
            rows.append(row)
        elif in_stretch:
            edges.append(row)
    # a launch inside the stretch whose execution the trace does not hold
    unjoined += sum(lo <= s[1] and s[2] <= hi and not 0 <= n + offset
                    < len(execs) for n, s in launches.items())

    busy = xplane.clip(xplane.union((e[1], e[2]) for e in ops), lo, hi)
    uncovered = xplane.subtract(busy, xplane.union(
        (r["exec_start"], r["exec_end"]) for r in rows + edges))
    between = {}
    for name, s, e, _, _ in modules:
        m = re.match(r"^(.*)\((\d+)\)$", name)
        if m and _kind_of(m.group(1), names) is None:
            part = xplane.total(xplane.clip(uncovered, s, e))
            if part:
                between[m.group(1)] = between.get(m.group(1), 0.0) \
                    + part / 1e6
    return {
        "rows": rows, "edges": edges, "kinds": kinds, "unjoined": unjoined,
        "checks": checks, "joined_by": joined_by,
        "busy_ms": xplane.total(busy) / 1e6,
        "uncovered_ms": xplane.total(uncovered) / 1e6,
        "uncovered_ms_by_program": between,
        "paged_kernel_ms_in_stretch": sum(
            (min(e, hi) - max(s, lo)) / 1e6 for name, s, e, _, _ in leaves
            if min(e, hi) > max(s, lo) and xplane.op_parts(name)[0]
            .startswith(names.KERNEL_PAGED))}


class _Reduced:
    """Chip-0 time of the leaf ops inside one execution: by layer class
    (``program_trace.classify``), by kernel and by ``ds.*`` scope (an op
    counts under every scope of its path: ``ds.attn`` holds
    ``ds.kv_cache``)."""

    __slots__ = ("classes", "kernels", "scopes")

    def __init__(self):
        self.classes, self.kernels, self.scopes = {}, {}, {}

    def add(self, name, ms, cls, tf_op):
        self.classes[cls] = self.classes.get(cls, 0.0) + ms
        if cls.endswith("_kernel"):
            kernel = re.sub(r"\.\d+$", "", xplane.op_parts(name)[0])
            self.kernels[kernel] = self.kernels.get(kernel, 0.0) + ms
        for scope in set(_SCOPE.findall(tf_op)):
            self.scopes[scope] = self.scopes.get(scope, 0.0) + ms

    def as_dict(self):
        return {"class_ms": self.classes, "kernel_ms": self.kernels,
                "scope_ms": self.scopes,
                "paged_kernel_ms": self.classes.get("paged_kernel", 0.0)}


# ------------------------------------------------------- what the readers ask
def whole(t, kind):
    return [r for r in t["rows"] if r["kind"] == kind]


def ragged_mean(t, key):
    """The mean of ``row[key]`` over the WHOLE ragged steps, or None."""
    rows = whole(t, t["kinds"][0])
    return sum(r[key] for r in rows) / len(rows) if rows else None


def per_iteration(t, key):
    """``sum row[key] / sum burst_k`` over the WHOLE bursts, or None where
    the stretch holds none."""
    rows = whole(t, t["kinds"][1])
    iterations = sum(r["burst_k"] for r in rows)
    return sum(r[key] for r in rows) / iterations if iterations else None


def launch_slacks_ms(t):
    """Over the whole rows launched ahead: how long the step's program had
    been queued when the device finished the step before it."""
    return [(r["before_end"] - r["launch_end"]) / 1e6 for r in t["rows"]
            if r["counts"].get("launched_ahead") and r["before_end"]]


def summarize(t, names):
    """What the ``INFO step_trace`` line says of a table."""
    numeric = lambda d: {k: v for k, v in d.items()
                         if isinstance(v, int) and not isinstance(v, bool)}
    kinds = {}
    for kind in t["kinds"]:
        rows = whole(t, kind)
        if not rows:
            continue
        sums, kernels = {}, {}
        for r in rows:
            for k, v in {**numeric(r["counts"]),
                         **numeric(r["device_counts"])}.items():
                if k not in ("step", "block_size", *names.SERVE_STEP_IDS):
                    sums[k] = sums.get(k, 0) + v
            for k, v in r["kernel_ms"].items():
                kernels[k] = kernels.get(k, 0.0) + v
        device = [r["device_ms"] for r in rows]
        kinds[kind] = {
            "n": len(rows), "device_ms_mean": statistics.fmean(device),
            "device_ms_p50": statistics.median(device),
            "iterations": sum(r["burst_k"] or 1 for r in rows),
            "kernel_ms": kernels, "sums": sums}
    launched = [r["counts"].get("launched_ahead") for r in t["rows"]
                if "launched_ahead" in r["counts"]]
    covered = t["busy_ms"] - t["uncovered_ms"]
    parts = {"whole_" + k: sum(r["paged_kernel_ms"] for r in whole(t, k))
             for k in t["kinds"]}
    parts["edges"] = sum(r["paged_kernel_ms"] for r in t["edges"])
    return {
        "rows": len(t["rows"]), "edge_rows": len(t["edges"]),
        "unjoined": t["unjoined"], "failed_checks": t["checks"],
        "joined_by": t["joined_by"], "kinds": kinds,
        "edges": [{"launch": r["launch"], "kind": r["kind"],
                   "inside_ms": r["inside_ms"],
                   "paged_kernel_ms": r["paged_kernel_ms"]}
                  for r in t["edges"]],
        "launched_ahead_share": (sum(launched) / len(launched)
                                 if launched else None),
        "busy_ms": t["busy_ms"],
        "covered_share": 100.0 * covered / t["busy_ms"] if t["busy_ms"]
        else None,
        "uncovered_ms_by_program": t["uncovered_ms_by_program"],
        "paged_kernel_ms": {**parts,
                            "in_stretch": t["paged_kernel_ms_in_stretch"]}}


def traced(record):
    """The table of this run's trace, or None."""
    names = program_trace.program_names()
    if not record.get("trace") or names is None:
        return None
    path = program_trace.find_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = t = join(read_file(path), names)
        if t is not None:
            print("INFO step_trace: " + json.dumps(
                summarize(t, names), default=float), flush=True)
    return _CACHE[key]
