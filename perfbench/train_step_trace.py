"""A training step's life as one row a launched program: its span on the host,
its enqueue, its execution on the chip, and the gap before it put down to the
host or to the runtime.  The twin of ``step_trace.py`` (serving).

The engine's loop launches three programs (``telemetry/names.py``): the
micro-step ``ds_micro_<variant>`` inside ``ds:train.micro``, ``ds_accumulate``
inside every ``ds:train.accumulate`` but a step's first (which only keeps the
gradients), and ``ds_apply_update`` inside ``ds:train.apply``.  The host
launches them in one order and the chip runs them in that order, so spans and
executions are two sequences that differ by one offset a trace.  As in
``step_trace``, the runtime's host event ``DoEnqueueProgram`` carries the
``run_id`` of the execution on chip 0's ``XLA Modules`` line.  In training it
comes from one of the runtime's own threads a little AFTER the call that
asked for it returned (a v5e trace read by hand, PR 56), so a span takes the
first enqueue of its program at or after its start that no earlier span took;
the offset is the one most of those pairs agree on (the others fail the order
check), and a span that found no enqueue takes the execution the offset gives
it.  A trace with no such event (a runtime that renames it) is joined BY
ORDER: the least offset at which no execution starts before its span, and
``joined_by`` says so.

**The gap before a row** is chip 0's idle between the end of the execution
before it and its own start, split at the enqueue's end (by order: the
span's end): before it the program was not in the chip's queue, the chip
waited for the HOST (``host_late``); after it the program was queued and the
wait is the runtime's or the device's (``queued``).  The host's part is
given, instant by instant, to the innermost ``ds:`` span over it (to the
``pb:`` span where no ``ds:`` one is open, else ``outside_spans``), and to the
runtime's own host events of 20 us or more that were open in it, by name
(events nest, so their shares overlap).  A host time beside a device time:
``started_before_enqueued_ms`` says by how much an execution starts BEFORE
its enqueue ends, which is the least the two clocks differ by (1.3-1.5 ms on
the v5e host of PR 56: the split cannot be told finer than that).

The table checks itself (the span's program against the module's name; no
execution before its span's start; the order; ``unjoined``; no long wait for
the host under a benchmark span that holds an engine call) and adds up: gaps
between executions + idle inside executions + the stretch's two ends = the
stretch's idle on chip 0 (on a four-chip host the wait lies INSIDE the
execution, between its start and its first op: ``idle_before_first_op_ms``).
A row is WHOLE where span, execution and the end
of the execution before it lie inside the ``pb:traced`` stretch (and it is
not the line's last event, which the trace's end cuts short), a step where all
its rows are and its optimizer step is among them.  Inside an execution
every op is counted once (``step_trace._leaves``: what a ``while`` or a
``conditional`` holds belongs to the leaf).

**A scope is the EXECUTABLE's, not the tree's.**  jax leaves an op's metadata
out of the persistent compile cache's key (``cache_key.py``: debug info is
stripped unless ``jax_compilation_cache_include_metadata_in_key``), so a tree
that only renames loads the program an OLDER tree compiled, with the older
names: its trace then holds no ``ds.attn_*`` path, the two attention readers
read 0.0 and ``attention_ms.outside_the_four_scopes`` is the whole class.
Rows, gaps and classes do not depend on it.  To read the scopes of a tree,
give the run a compile cache of its own (``JAX_COMPILATION_CACHE_DIR=<new
directory>``).

``traced(record)`` reduces the newest trace once a process and prints one
``INFO train_step_trace: {...}`` line.  With no traced run, no trace file, no
device plane, no ``ds:train.micro`` in it, or a program without the attention
block's scopes (any parent before PR 56) it returns None, and every reader
built on it returns None.
"""

import bisect
import json
import os
import re
import statistics
import time

from . import program_trace, xplane
from .step_trace import (ENQUEUE, RUN_ID, _SCOPE, _Reduced, _leaves,
                         _program_of)

#: a host event that is neither a span nor the enqueue is kept from this long
HOST_EVENT_NS = 20e3
#: a wait for the host this long under a benchmark span that holds an engine
#: call is a stretch of the engine without a span of its own
LONG_GAP_NS = program_trace.LONG_GAP_NS
OUTSIDE = "outside_spans"
#: the benchmark's spans around the engine's three calls (jobs/train.py)
ENGINE_CALLS = ("pb:forward", "pb:backward", "pb:step")
_MODULE = re.compile(r"^(?:jit_)?(.*)\((\d+)\)$")
_CACHE = {}


def read_file(path):
    """The host's spans, its enqueue events and its other events of
    ``HOST_EVENT_NS`` or more, and the first chip's plane."""
    with open(path, "rb") as f:
        data = f.read()
    chips = sorted(int(m.group(1)) for m in map(
        xplane.DEVICE_PLANE.match, program_trace.plane_names(data)) if m)
    first = f"/device:TPU:{chips[0]}" if chips else None
    host = program_trace.HOST_PLANE
    planes = program_trace.read_planes(
        data, want_plane=lambda n: n in (host, first))
    spans = ("ds:", xplane.SPAN_PREFIX)
    for line, events in planes.get(host, {}).items():
        planes[host][line] = [
            e for e in events if e[0].startswith(spans) or e[0] == ENQUEUE
            or e[2] - e[1] >= HOST_EVENT_NS]
    return planes


def _kind_of(program, names):
    """Which of the loop's three programs a module event runs, or None."""
    for kind in (names.PROGRAM_MICRO, names.PROGRAM_ACCUMULATE,
                 names.PROGRAM_APPLY):
        if kind in program:
            return kind
    return None


def _innermost(spans):
    """``[(start, end, label)]``, disjoint and sorted: at every instant the
    shortest of ``spans`` (``(label, start, end)``) that is open."""
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    out = []
    for a, b in zip(edges, edges[1:]):
        open_ = [x for x in spans if x[1] <= a and b <= x[2]]
        if open_:
            label = min(open_, key=lambda x: x[2] - x[1])[0]
            if out and out[-1][2] == label and out[-1][1] == a:
                out[-1] = (out[-1][0], b, label)
            else:
                out.append((a, b, label))
    return out


def _share_out(intervals, segments, into):
    """Add the time of ``intervals`` that lies in each of ``segments``
    (``_innermost``'s) to ``into[label]``; returns what lies in none."""
    starts = [s[0] for s in segments]
    left = []
    for lo, hi in intervals:
        at = lo
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(segments) and segments[i][0] < hi:
            s, e, label = segments[i]
            i += 1
            if e <= at:
                continue
            if s > at:
                left.append((at, s))
            part = min(e, hi) - max(s, at)
            into[label] = into.get(label, 0.0) + part
            at = min(e, hi)
        if at < hi:
            left.append((at, hi))
    return left


def join(planes, names):
    """The table of one trace: ``{"rows": [...], "steps": [...], ...}``, one
    row a launched training program (the module's docstring), or None."""
    device = sorted(n for n in planes if xplane.DEVICE_PLANE.match(n))
    chip = planes[device[0]] if device else {}
    ops, modules = chip.get(xplane.OP_LINE), chip.get(
        program_trace.MODULE_LINE)
    if not ops or not modules:
        return None
    host = [e for evs in planes.get(program_trace.HOST_PLANE, {}).values()
            for e in evs]
    prefix = names.SPAN_PREFIX
    micro_span, acc_span, apply_span = (
        prefix + n for n in (names.TRAIN_MICRO, names.TRAIN_ACCUMULATE,
                             names.TRAIN_APPLY))
    if not any(e[0] == micro_span for e in host):
        return None

    lo, hi = min(e[1] for e in ops), max(e[2] for e in ops)
    window = [e for e in host if e[0] == xplane.WINDOW_SPAN]
    if window:
        t = max(window, key=lambda e: e[2] - e[1])
        if t[1] < hi and t[2] > lo:
            lo, hi = t[1], t[2]

    # ---- the spans that launch a program, in order
    kind_of_span = {micro_span: names.PROGRAM_MICRO,
                    acc_span: names.PROGRAM_ACCUMULATE,
                    apply_span: names.PROGRAM_APPLY}
    launches, kept = [], set()
    for e in sorted((e for e in host if e[0] in kind_of_span),
                    key=lambda e: e[1]):
        step = e[3].get("step")
        if e[0] == acc_span and step not in kept:
            kept.add(step)          # a step's first fold keeps, launches none
            continue
        launches.append(e)

    # ---- the executions of the three programs, in order
    programs, execs = {}, []
    for name, s, e, stats, _ in sorted(modules, key=lambda m: m[1]):
        m = _MODULE.match(name)
        if not m:
            continue
        programs[int(m.group(2))] = m.group(1)
        kind = _kind_of(m.group(1), names)
        if kind is not None:
            execs.append({"program": m.group(1), "kind": kind, "start": s,
                          "end": e, RUN_ID: stats.get(RUN_ID)})
    if not execs:
        return None
    # the trace's end cuts the event it falls in short: the line's last
    # event has no end to trust
    last_module = max(m[1] for m in modules)
    index_of = {x[RUN_ID]: i for i, x in enumerate(execs)
                if x[RUN_ID] is not None}
    # the runtime enqueues from its own threads, a little after the call
    # that asked for it returned: a span takes the first enqueue of its
    # program at or after its start that no earlier span took
    enqueues = sorted((e[1], e[2], index_of[e[3].get(RUN_ID)]) for e in host
                      if e[0] == ENQUEUE and e[3].get(RUN_ID) in index_of)
    found, enqueued_at, taken = {}, {}, set()
    for n, span in enumerate(launches):
        for j, (start, end, i) in enumerate(enqueues):
            if j not in taken and start >= span[1] \
                    and execs[i]["kind"] == kind_of_span[span[0]]:
                taken.add(j)
                found[n], enqueued_at[n] = i, end
                break
    if found:
        offsets = [i - n for n, i in found.items()]
        offset = statistics.mode(offsets)
        order_faults = sum(o != offset for o in offsets)
    else:
        # by order: the least offset at which no execution starts before
        # its span (executions launched before the trace began lead)
        offset = next((o for o in range(len(execs)) if all(
            execs[n + o]["start"] >= s[1] for n, s in enumerate(launches)
            if n + o < len(execs))), 0)
        order_faults = 0
    checks = {"program": 0, "clock": 0, "order": order_faults}

    # ---- chip-0 time of the leaf ops inside every execution
    starts = [x["start"] for x in execs]
    inside = [_Inside(names) for _ in execs]
    first_op = [x["end"] for x in execs]
    classes = {}
    for name, s, e, _, meta in _leaves(ops):
        at = bisect.bisect_right(starts, s) - 1
        cs, ce = max(s, lo), min(e, hi)
        if at < 0 or ce <= cs or s >= execs[at]["end"]:
            continue
        first_op[at] = min(first_op[at], s)
        program = _program_of(meta, programs)
        if (name, program) not in classes:
            classes[name, program] = program_trace.classify(
                name, meta, program, names)
        inside[at].add(name, (ce - cs) / 1e6, classes[name, program],
                       meta.get("tf_op") or "")

    # ---- chip-0 idle: inside an execution, in the gap before one, or at
    # the stretch's two ends
    busy = xplane.clip(xplane.union((e[1], e[2]) for e in ops), lo, hi)
    idle_inside = [0.0] * len(execs)
    gaps = [[] for _ in execs]
    ends_ns = 0.0
    for s, e in xplane.subtract([(lo, hi)], busy):
        at = bisect.bisect_right(starts, s) - 1
        while s < e:
            x = execs[at] if at >= 0 else None
            if x is not None and s < x["end"]:        # inside execution at
                cut = min(e, x["end"])
                idle_inside[at] += cut - s
            else:                   # after execution at, before the next
                nxt = starts[at + 1] if at + 1 < len(execs) else hi
                cut = min(e, max(nxt, s))
                if at < 0 or at + 1 >= len(execs):
                    ends_ns += cut - s
                else:
                    gaps[at + 1].append((s, cut))
                at += 1
            if cut <= s:
                break
            s = cut

    ds_segments = _innermost([(e[0], e[1], e[2]) for e in host
                              if e[0].startswith(prefix)])
    pb_segments = _innermost([(e[0], e[1], e[2]) for e in host
                              if e[0].startswith(xplane.SPAN_PREFIX)
                              and e[0] != xplane.WINDOW_SPAN])
    others = sorted((e for e in host if e[0] != ENQUEUE
                     and not e[0].startswith((prefix, xplane.SPAN_PREFIX))),
                    key=lambda e: e[1])
    other_starts = [e[1] for e in others]
    longest = max((e[2] - e[1] for e in others), default=0.0)

    rows, edges, unjoined = [], [], 0
    joined_by = {"run_id": 0, "order": 0}
    for i, x in enumerate(execs):
        n = i - offset
        span = launches[n] if 0 <= n < len(launches) else None
        in_stretch = x["end"] > lo and x["start"] < hi
        if found.get(n, i) != i:
            continue                # counted in checks["order"]
        if span is None:
            # an execution launched before the trace began leads the line;
            # one past the last span means the trace lost a span
            unjoined += n >= len(launches) and in_stretch
        enq = enqueued_at.get(n, span[2] if span else None)
        gap = gaps[i]
        late, queued, by_span = [], [], {}
        for s, e in gap:
            cut = min(max(enq, s), e) if enq is not None else s
            if cut > s:
                late.append((s, cut))
            if e > cut:
                queued.append((cut, e))
        left = _share_out(_share_out(late, ds_segments, by_span),
                          pb_segments, by_span)
        if left:
            by_span[OUTSIDE] = xplane.total(left)
        open_ = {}                  # events nest and repeat: a name's union
        for s, e in late:
            j = bisect.bisect_left(other_starts, s - longest)
            while j < len(others) and others[j][1] < e:
                if others[j][2] > s:
                    open_.setdefault(others[j][0], []).append(
                        (max(others[j][1], s), min(others[j][2], e)))
                j += 1
        by_event = {k: xplane.total(xplane.union(v))
                    for k, v in open_.items()}
        row = {
            "program": x["program"], "kind": x["kind"],
            "step": span[3].get("step") if span else None,
            "micro_step": span[3].get("micro_step") if span else None,
            "span_start": span and span[1], "span_end": span and span[2],
            "enqueued_at": enq,
            "exec_start": x["start"], "exec_end": x["end"],
            "device_ms": (x["end"] - x["start"]) / 1e6,
            "before_end": execs[i - 1]["end"] if i else None,
            # an execution cannot start before its enqueue ends: what it
            # does by is the least the two clocks differ by
            "started_before_enqueued_ms": (
                max(0.0, enqueued_at[n] - x["start"]) / 1e6
                if n in enqueued_at else 0.0),
            "gap_ms": xplane.total(gap) / 1e6,
            "host_late_ms": xplane.total(late) / 1e6,
            "queued_ms": xplane.total(queued) / 1e6,
            "host_late_ms_by_span": {k: v / 1e6 for k, v in by_span.items()},
            "host_late_ms_by_event": {k: v / 1e6
                                      for k, v in by_event.items()},
            "idle_inside_ms": idle_inside[i] / 1e6,
            # of it, from the execution's start to its first op: where a
            # program that was dequeued waits for its buffers or its peers
            "idle_before_first_op_ms": max(
                0.0, min(first_op[i], hi) - max(x["start"], lo)) / 1e6,
            **inside[i].as_dict()}
        if span is not None and in_stretch:
            joined_by["run_id" if n in found else "order"] += 1
            checks["program"] += kind_of_span[span[0]] != x["kind"]
            checks["clock"] += x["start"] < span[1]
        row["whole"] = bool(
            span is not None and span[1] >= lo and x["end"] <= hi
            and x["start"] < last_module
            and row["before_end"] is not None and row["before_end"] >= lo)
        if row["whole"]:
            rows.append(row)
        elif in_stretch:
            edges.append(row)
    # a launching span inside the stretch whose execution the trace lacks
    unjoined += sum(lo <= s[1] and s[2] <= hi
                    and not 0 <= n + offset < len(execs)
                    for n, s in enumerate(launches))

    # ---- a step is whole where all its rows are and its update is there
    by_step = {}
    for r in rows + edges:
        by_step.setdefault(r["step"], []).append(r)
    steps = sorted(
        s for s, rs in by_step.items() if s is not None
        and all(r["whole"] for r in rs)
        and any(r["kind"] == names.PROGRAM_APPLY for r in rs)
        and any(r["kind"] == names.PROGRAM_MICRO for r in rs))
    whole = set(steps)
    checks["unlabelled"] = sum(
        sum(v for k, v in r["host_late_ms_by_span"].items()
            if k in ENGINE_CALLS) * 1e6 > LONG_GAP_NS
        for r in rows if r["step"] in whole)

    covered = xplane.union((x["start"], x["end"]) for x in execs)
    between = {}
    for name, s, e, _, _ in modules:
        m = _MODULE.match(name)
        if m and _kind_of(m.group(1), names) is None:
            part = xplane.total(
                xplane.subtract(xplane.clip(busy, s, e), covered))
            if part:
                between[m.group(1)] = between.get(m.group(1), 0.0) \
                    + part / 1e6
    idle = {"between_ms": sum(xplane.total(g) for g in gaps) / 1e6,
            "inside_ms": sum(idle_inside) / 1e6, "ends_ms": ends_ns / 1e6,
            "stretch_ms": (hi - lo - xplane.total(busy)) / 1e6}
    checks["adds_up"] = int(abs(
        idle["between_ms"] + idle["inside_ms"] + idle["ends_ms"]
        - idle["stretch_ms"]) > 0.1 * max(1, len(steps)))
    return {"rows": rows, "edges": edges, "steps": steps, "checks": checks,
            "unjoined": unjoined, "joined_by": joined_by, "idle": idle,
            "stretch_ms": (hi - lo) / 1e6,
            "busy_ms": xplane.total(busy) / 1e6,
            "other_programs_ms": between,
            "kinds": (names.PROGRAM_MICRO, names.PROGRAM_ACCUMULATE,
                      names.PROGRAM_APPLY)}


class _Inside(_Reduced):
    """``step_trace._Reduced`` (an op counts under every scope of its path)
    and, beside it, every leaf op ONCE: under the innermost ``ds.*`` scope of
    its path, or by its own name where the path holds none; and the
    attention block's parts."""

    __slots__ = ("proj", "glue", "innermost", "unscoped", "attn")

    def __init__(self, names):
        super().__init__()
        self.proj = names.SCOPE_ATTN_PROJ
        self.glue = {names.SCOPE_ATTN_ROTARY, names.SCOPE_ATTN_KV_REPEAT,
                     names.SCOPE_ATTN_CORE}
        self.innermost, self.unscoped = {}, {}
        self.attn = {"proj": 0.0, "glue": 0.0, "collective": 0.0}

    def add(self, name, ms, cls, tf_op):
        super().add(name, ms, cls, tf_op)
        scopes = _SCOPE.findall(tf_op)
        if not scopes:
            key = (xplane.op_label(name), cls, tf_op)
            self.unscoped[key] = self.unscoped.get(key, 0.0) + ms
            return
        self.innermost[scopes[-1]] = self.innermost.get(scopes[-1], 0.0) + ms
        part = ("proj" if self.proj in scopes else
                "glue" if self.glue.intersection(scopes) else None)
        if part and cls == "collective":
            # a ZeRO gather of the block's weights is the collectives'
            self.attn["collective"] += ms
        elif part and cls != "flash_kernel":
            self.attn[part] += ms

    def as_dict(self):
        return {**super().as_dict(), "innermost_scope_ms": self.innermost,
                "unscoped_ms": sum(self.unscoped.values()),
                "unscoped_ops": self.unscoped,
                "attn_proj_ms": self.attn["proj"],
                "attn_glue_ms": self.attn["glue"],
                "attn_collective_ms": self.attn["collective"]}


# ------------------------------------------------------- what the readers ask
def whole_rows(t, kind=None):
    """The rows of the whole steps (of one of ``t["kinds"]``, or all)."""
    steps = set(t["steps"])
    return [r for r in t["rows"] if r["step"] in steps
            and (kind is None or r["kind"] == kind)]


def per_step(t, key):
    """``sum row[key]`` over the whole steps' rows / the whole steps, or
    None where the stretch holds no whole step."""
    if not t["steps"]:
        return None
    return sum(r[key] for r in whole_rows(t)) / len(t["steps"])


def micro_step_device_ms(t):
    """Mean duration of the micro-step's executions in the whole steps."""
    rows = whole_rows(t, t["kinds"][0])
    return statistics.fmean(r["device_ms"] for r in rows) if rows else None


def _sum_dicts(rows, key, over):
    out = {}
    for r in rows:
        for k, v in r[key].items():
            out[k] = out.get(k, 0.0) + v / over
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def summarize(t):
    """What the ``INFO train_step_trace`` line says of a table."""
    n = len(t["steps"])
    rows = whole_rows(t)
    over = max(n, 1)
    by_program = {}
    for r in rows:
        p = by_program.setdefault(r["program"], {"n": 0, "device_ms": 0.0})
        p["n"] += 1
        p["device_ms"] += r["device_ms"]
    # the optimizer's and the fold's ops are named by their program
    loop = [r for r in rows if r["kind"] == t["kinds"][0]]
    unscoped = _sum_dicts(loop, "unscoped_ops", over)
    by_class = {}
    for (_, cls, _), v in unscoped.items():
        by_class[cls] = by_class.get(cls, 0.0) + v
    first = sorted(r["exec_start"] for r in loop)
    late = per_step(t, "host_late_ms") or 0.0
    classes = _sum_dicts(rows, "class_ms", over)
    return {
        "whole_steps": n, "rows": len(rows),
        "edge_rows": len(t["edges"]) + len(t["rows"]) - len(rows),
        "unjoined": t["unjoined"], "failed_checks": t["checks"],
        "joined_by": t["joined_by"],
        "started_before_enqueued_ms_max": max(
            (r["started_before_enqueued_ms"] for r in rows), default=0.0),
        "programs": {k: {"n": v["n"],
                         "device_ms_mean": v["device_ms"] / v["n"]}
                     for k, v in by_program.items()},
        # a whole step from its first execution's start to the next's
        "step_ms": ((first[-1] - first[0]) / 1e6 / (len(first) - 1)
                    if len(first) > 1 else None),
        "gap_ms_per_step": {
            "between_executions": per_step(t, "gap_ms"),
            "host_late": per_step(t, "host_late_ms"),
            "queued": per_step(t, "queued_ms"),
            "host_late_by_span": _sum_dicts(
                rows, "host_late_ms_by_span", over),
            "host_late_share_by_event": {
                k: 100.0 * v / late for k, v in list(_sum_dicts(
                    rows, "host_late_ms_by_event", over).items())[:12]}
            if late else {},
            "longest": sorted(
                ({"step": r["step"], "program": r["program"],
                  "gap_ms": r["gap_ms"], "host_late_ms": r["host_late_ms"],
                  "by_span": r["host_late_ms_by_span"]}
                 for r in rows), key=lambda g: -g["gap_ms"])[:3]},
        "idle_inside_executions_ms_per_step": per_step(t, "idle_inside_ms"),
        "of_it_before_the_first_op": per_step(t, "idle_before_first_op_ms"),
        "stretch": {"ms": t["stretch_ms"], "busy_ms": t["busy_ms"],
                    "idle": t["idle"],
                    "other_programs_ms": t["other_programs_ms"]},
        "class_ms": classes,
        "scope_ms": _sum_dicts(rows, "scope_ms", over),
        "innermost_scope_ms": _sum_dicts(rows, "innermost_scope_ms", over),
        "unscoped_ms": per_step(t, "unscoped_ms"),
        "micro_step_unscoped_ms_by_class": by_class,
        "micro_step_unscoped_longest": [
            {"op": k[0], "class": k[1], "path": k[2][-90:], "ms": v}
            for k, v in list(unscoped.items())[:10]],
        "attention_ms": {
            "proj": per_step(t, "attn_proj_ms"),
            "glue": per_step(t, "attn_glue_ms"),
            "collective": per_step(t, "attn_collective_ms"),
            "flash_kernel": classes.get("flash_kernel", 0.0),
            "class_attention": classes.get("attention", 0.0),
            # the whole class where the executable came from a compile cache
            # that an older tree filled (see the module's docstring)
            "outside_the_four_scopes": classes.get("attention", 0.0)
            - (per_step(t, "attn_proj_ms") or 0.0)
            - (per_step(t, "attn_glue_ms") or 0.0)},
    }


def traced(record):
    """The table of this run's trace, or None."""
    names = program_trace.program_names()
    if not record.get("trace") or names is None \
            or not hasattr(names, "SCOPE_ATTN_PROJ"):
        return None
    path = program_trace.find_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        t0 = time.perf_counter()
        _CACHE[key] = t = join(read_file(path), names)
        if t is not None:
            print("INFO train_step_trace: " + json.dumps(
                {**summarize(t), "reader_s": time.perf_counter() - t0},
                default=float), flush=True)
    return _CACHE[key]
