"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read.  Needs nothing but JAX (``ProfileData``).

A trace is planes (one per device, one per host), each with lines (on a
device: the op line, the module line, step lines; on the host: threads), each
with events that have a name, a start and a duration in nanoseconds.

* busy: the union of the intervals in which an op runs on a device, clipped to
  the window; ``busy_s`` is its mean over the devices used;
* window: the benchmark's ``pb:traced`` annotation where the trace holds it
  (the host wrote it around the traced steps), else first op to last op;
* idle gaps: the window minus busy on the first device, each gap labelled by
  the innermost benchmark span (``pb:<name>``) that covers its middle;
* collectives: events whose own instruction name or opcode says all-gather,
  all-reduce, reduce-scatter, all-to-all or collective-permute (never the
  operand text: a compute fusion that reads ``%all-gather-done.12`` is
  compute); ``collective_s`` is the union of their intervals, an async op
  from its start to its done; exposed = the part of that union during which
  no other op runs on that device;
* Mosaic: ``custom-call`` ops on the op line whose target is
  ``tpu_custom_call`` (what Pallas kernels lower to).
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|all_gather|all_reduce|reduce_scatter|all_to_all|collective_permute",
    re.I)
MOSAIC = re.compile(r"custom-call|custom_call|mosaic|pallas", re.I)
MOSAIC_TARGET = "tpu_custom_call"
#: a device op's event name is its whole HLO instruction:
#: ``%name = result-type opcode(operands...), attributes``
_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<type>\(?[a-z0-9]+\[[^\]]*\])?"
                  r".*?\s(?P<op>[a-z][a-z0-9\-]*)\(")
SPAN_PREFIX = "pb:"
WINDOW_SPAN = SPAN_PREFIX + "traced"


def find_trace(directory):
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


# ---------------------------------------------------------------- intervals
def union(intervals):
    """Sorted, merged [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """Parts of merged ``a`` not covered by merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ------------------------------------------------------------------ reading
def read_planes(profile):
    """``{plane name: {line name: [(name, start_ns, end_ns)]}}``."""
    planes = {}
    for plane in profile.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, float(ev.start_ns),
                            float(ev.start_ns) + float(ev.duration_ns)))
    return planes


def op_parts(event_name):
    """``(instruction name, opcode)`` of a device op's event name; the opcode
    is empty where the name is no whole HLO instruction (a bare
    ``all-gather-start.5``)."""
    m = _HLO.match(event_name)
    if m:
        return m.group("name"), m.group("op")
    return event_name.split(" = ", 1)[0].lstrip("%"), ""


def is_collective(event_name):
    name, op = op_parts(event_name)
    return bool(COLLECTIVE.search(name) or COLLECTIVE.search(op))


def is_mosaic(event_name):
    name, op = op_parts(event_name)
    if not op:
        return bool(MOSAIC.search(name))
    return op == "custom-call" and (
        "custom_call_target" not in event_name
        or MOSAIC_TARGET in event_name)


def op_label(event_name):
    """A short label of a device op for the breakdown: its HLO name, opcode
    and first result shape (``fusion.72 fusion f32[4096,32000]``); a name
    that is no HLO instruction is kept (cut to 80 characters)."""
    m = _HLO.match(event_name)
    if not m:
        return event_name[:80]
    shape = (m.group("type") or "").lstrip("(")
    return f"{m.group('name')} {m.group('op')} {shape}".strip()[:80]


def reduce_planes(planes, n_devices=None):
    device_planes = sorted(
        (int(DEVICE_PLANE.match(n).group(1)), n) for n in planes
        if DEVICE_PLANE.match(n))
    if n_devices:
        device_planes = device_planes[:n_devices]
    if not device_planes:
        return None
    host_spans = []
    for pname, lines in planes.items():
        if DEVICE_PLANE.match(pname):
            continue
        for evs in lines.values():
            host_spans += [e for e in evs if e[0].startswith(SPAN_PREFIX)]

    ops_by_dev = []
    for _, pname in device_planes:
        lines = planes[pname]
        ops = lines.get(OP_LINE)
        if ops is None:
            ops = [e for ln, evs in lines.items() if "ops" in ln.lower()
                   for e in evs]
        ops_by_dev.append(ops)
    all_ops = [e for ops in ops_by_dev for e in ops]
    if not all_ops:
        return None
    lo = min(e[1] for e in all_ops)
    hi = max(e[2] for e in all_ops)
    traced = [e for e in host_spans if e[0] == WINDOW_SPAN]
    if traced:
        t = max(traced, key=lambda e: e[2] - e[1])
        if t[1] < hi and t[2] > lo:      # same clock as the device events
            lo, hi = t[1], t[2]
    window = hi - lo

    busy = []
    for ops in ops_by_dev:
        busy.append(total(clip(union((s, e) for _, s, e in ops), lo, hi)))
    ops0 = [(n, max(s, lo), min(e, hi)) for n, s, e in ops_by_dev[0]
            if min(e, hi) > max(s, lo)]
    busy0 = union((s, e) for _, s, e in ops0)

    by_name = {}
    for n, s, e in ops0:
        key = op_label(n)
        by_name[key] = by_name.get(key, 0.0) + (e - s)
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])

    # collectives: any line of the first device's plane (async ones may sit
    # on a line of their own)
    plane0 = planes[device_planes[0][1]]
    coll = [(s, e) for ln, evs in plane0.items() for n, s, e in evs
            if is_collective(n) and (ln == OP_LINE
                                         or "step" not in ln.lower())
            and "module" not in ln.lower()]
    coll_u = clip(union(coll), lo, hi)
    other = union((s, e) for n, s, e in ops0 if not is_collective(n))
    exposed = subtract(coll_u, other)
    mosaic = sum(e - s for n, s, e in ops0 if is_mosaic(n))

    gaps = subtract([(lo, hi)], busy0)
    by_label = {}
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [h for h in host_spans
                 if h[1] <= mid <= h[2] and h[0] != WINDOW_SPAN]
        label = (min(cover, key=lambda h: h[2] - h[1])[0][len(SPAN_PREFIX):]
                 if cover else "outside_spans")
        by_label[label] = by_label.get(label, 0.0) + (e - s)
    idle_gaps = sorted(by_label.items(), key=lambda kv: -kv[1])

    ns = 1e-9
    return {
        "devices": len(device_planes),
        "window_s": window * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "busy_s_by_device": [b * ns for b in busy],
        "op_events": len(ops0),
        "collective_s": total(coll_u) * ns,
        "collective_exposed_s": total(exposed) * ns,
        "mosaic_s": mosaic * ns,
        "top_ops": [[n, d * ns] for n, d in top_ops[:10]],
        "idle_gaps": [[n, d * ns] for n, d in idle_gaps[:10]],
        "longest_gap_s": max((e - s for s, e in gaps), default=0.0) * ns,
    }


def reduce_file(path, n_devices=None):
    from jax.profiler import ProfileData
    return reduce_planes(read_planes(ProfileData.from_file(path)),
                         n_devices=n_devices)


def describe_file(path, per_line=8):
    """What a trace holds, for a look by hand: planes, lines, event counts
    and each line's most expensive event names."""
    from jax.profiler import ProfileData
    rows = []
    for pname, lines in read_planes(ProfileData.from_file(path)).items():
        for lname, evs in lines.items():
            agg = {}
            for n, s, e in evs:
                agg[n] = agg.get(n, 0.0) + (e - s)
            top = sorted(agg.items(), key=lambda kv: -kv[1])[:per_line]
            rows.append({"plane": pname, "line": lname, "events": len(evs),
                         "top": [[n, d * 1e-9] for n, d in top]})
    return rows


def write_planes(planes, path):
    """Write ``{plane: {line: [(name, start_ns, end_ns)]}}`` as an
    ``.xplane.pb`` (through ProfileData's text-proto door): how the tests'
    small trace is made, and how a slice of a real one can be kept."""
    from jax.profiler import ProfileData
    out = []
    for pname, lines in planes.items():
        ids = {}
        body = []
        for lname, evs in lines.items():
            rows = []
            for name, start, end in evs:
                mid = ids.setdefault(name, len(ids) + 1)
                rows.append(
                    f"events {{ metadata_id: {mid} "
                    f"offset_ps: {int(round(start * 1000))} "
                    f"duration_ps: {int(round((end - start) * 1000))} }}")
            body.append(f'lines {{ name: "{lname}" timestamp_ns: 0 '
                        + " ".join(rows) + " }")
        quoted = lambda n: n.replace("\\", "\\\\").replace('"', '\\"')
        meta = " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} '
            f'name: "{quoted(n)}" }} }}' for n, i in ids.items())
        out.append(f'planes {{ name: "{pname}" ' + " ".join(body) + " "
                   + meta + " }")
    data = ProfileData.text_proto_to_serialized_xspace("\n".join(out))
    with open(path, "wb") as f:
        f.write(data)
