"""Reduce a run's profiler trace by the PROGRAM's own names: the ``ds:`` host
spans with their counts, the ``ds_*`` kernels, the ``ds.*`` scopes and the
``ds_*`` programs that ``deepspeed_tpu/telemetry/names.py`` lists.

What a v5e trace holds (read by hand, PR 24): a device op's scope path
(``jit(ds_micro_flat)/jvp(LlamaModel)/ds.lm_head_loss/lm_head/dot_general``)
and its program id are stats of the event's METADATA (``tf_op``,
``program_id``), which ``jax.profiler.ProfileData`` does not expose (its
``stats`` are the event's own: ``device_offset_ps`` ...).  So this module reads
the ``.xplane.pb`` wire format itself; it needs no dependency at all.

* host spans: ``ds:<name>`` events with their stats (the counts) and their
  nesting; a span's self time is its duration minus what its children cover;
* device: the op line of chip 0, clipped to the ``pb:traced`` stretch as
  ``xplane.py`` does; every op is given ONE layer class by the program's
  names (``classify``), the rest is ``unclassed``;
* idle: each gap of chip 0 is labelled by the innermost ``ds:`` span over
  its middle.

``summary(record)`` reduces the newest trace under ``<root>/.perfbench_trace``
once a process and prints one ``INFO program_spans: {...}`` line.  With a
program that lacks the names (``deepspeed_tpu.telemetry.names`` cannot be
imported), or with no trace, it returns None and every reader built on it
returns None.
"""

import glob
import json
import os
import re
import struct

from . import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OUTSIDE = "outside ds: spans"
UNCLASSED = "unclassed"
#: idle gaps longer than this must carry a label (acceptance of ISSUE 24)
LONG_GAP_NS = 100e3


def program_names():
    """The program's table of names, or None with a program that has none."""
    try:
        from deepspeed_tpu.telemetry import names
    except ImportError:
        return None
    return names


# ------------------------------------------------------------- wire format
def _varint(buf, i):
    result = shift = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf, i, end):
    """``(field number, wire type, value)`` of one message; a length-
    delimited value is its ``(start, end)`` in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, span, stat_names):
    """One XStat -> ``(name, value)``; a ref value is the referenced name."""
    name = value = None
    for no, wire, v in _fields(buf, *span):
        if no == 1:
            name = stat_names.get(v, str(v))
        elif no == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif no == 3:
            value = v
        elif no == 4:
            value = _signed(v)
        elif no in (5, 6):
            value = _text(buf, v)
        elif no == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf, span):
    key = value = None
    for no, _, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def read_planes(data, want_plane=None, want_event=None):
    """``{plane: {line: [(name, start_ns, end_ns, stats, meta_stats)]}}``
    from the bytes of an ``.xplane.pb``.  ``want_plane(name)`` and
    ``want_event(plane, name)`` leave out what is not needed (other chips'
    planes, the runtime's own host events) before it is decoded."""
    buf = memoryview(data)
    planes = {}
    for no, _, plane_span in _fields(buf, 0, len(buf)):
        if no != 1:
            continue
        name, lines, ev_meta, st_meta = "", [], [], []
        for f, _, v in _fields(buf, *plane_span):
            if f == 2:
                name = _text(buf, v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                ev_meta.append(v)
            elif f == 5:
                st_meta.append(v)
        if want_plane is not None and not want_plane(name):
            continue
        stat_names = {}
        for span in st_meta:
            key, value = _map_entry(buf, span)
            for f, _, v in _fields(buf, *value):
                if f == 2:
                    stat_names[key] = _text(buf, v)
        metas = {}
        for span in ev_meta:
            key, value = _map_entry(buf, span)
            ev_name, stats = "", []
            for f, _, v in _fields(buf, *value):
                if f == 2:
                    ev_name = _text(buf, v)
                elif f == 5:
                    stats.append(v)
            if want_event is not None and not want_event(name, ev_name):
                continue
            metas[key] = (ev_name, dict(_stat(buf, s, stat_names)
                                        for s in stats))
        out = planes.setdefault(name, {})
        for span in lines:
            line_name, t0, events = "", 0, []
            for f, _, v in _fields(buf, *span):
                if f == 2:
                    line_name = _text(buf, v)
                elif f == 3:
                    t0 = v
                elif f == 4:
                    events.append(v)
            rows = out.setdefault(line_name, [])
            for ev in events:
                meta_id = offset = duration = 0
                stats = []
                for f, _, v in _fields(buf, *ev):
                    if f == 1:
                        meta_id = v
                    elif f == 2:
                        offset = v
                    elif f == 3:
                        duration = v
                    elif f == 4:
                        stats.append(v)
                meta = metas.get(meta_id)
                if meta is None:
                    continue
                start = t0 + offset / 1000.0
                rows.append((meta[0], start, start + duration / 1000.0,
                             dict(_stat(buf, s, stat_names) for s in stats),
                             meta[1]))
    return planes


def plane_names(data):
    buf = memoryview(data)
    return [_text(buf, v) for no, _, span in _fields(buf, 0, len(buf))
            if no == 1 for f, _, v in _fields(buf, *span) if f == 2]


def read_file(path):
    """The host's ``ds:`` / ``pb:`` spans and the first chip's plane."""
    with open(path, "rb") as f:
        data = f.read()
    chips = sorted(int(m.group(1)) for m in
                   map(xplane.DEVICE_PLANE.match, plane_names(data)) if m)
    first = f"/device:TPU:{chips[0]}" if chips else None
    spans = ("ds:", xplane.SPAN_PREFIX)
    return read_planes(
        data, want_plane=lambda n: n in (HOST_PLANE, first),
        want_event=lambda p, e: p != HOST_PLANE or e.startswith(spans))


def find_trace(root=None):
    """The newest ``.xplane.pb`` under ``<root>/.perfbench_trace/``."""
    paths = glob.glob(os.path.join(root or ROOT, ".perfbench_trace", "*",
                                   "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


# ----------------------------------------------------------- classification
def _components(tf_op):
    """The scope path's components, JAX's wrappers taken off:
    ``transpose(jvp(ds.lm_head_loss))`` -> ``ds.lm_head_loss``."""
    out = []
    for part in tf_op.rstrip(":").split("/"):
        while "(" in part and part.endswith(")"):
            part = part[part.index("(") + 1:-1]
        out.append(part)
    return out


def classify(event_name, meta, program, names):
    """The ONE layer class of a device op, by the program's names: its own
    instruction name (kernels, collectives), then the program it runs in
    (the optimizer step), then its scope path."""
    op, opcode = xplane.op_parts(event_name)
    if op.startswith(names.KERNEL_FLASH):
        return "flash_kernel"
    if op.startswith(names.KERNEL_PAGED):
        return "paged_kernel"
    if op.startswith(names.KERNEL_PREFIX) and opcode == "custom-call":
        return "other_kernel"
    if xplane.is_collective(event_name):
        return "collective"
    if names.PROGRAM_APPLY in program:
        return "optimizer"
    if names.PROGRAM_ACCUMULATE in program:
        return "accumulate"
    parts = set(_components(meta.get("tf_op") or ""))
    if names.SCOPE_LM_HEAD_LOSS in parts or names.SCOPE_LM_HEAD in parts:
        return "lm_head"
    if names.SCOPE_EMBED in parts:
        return "embed"
    if names.SCOPE_KV_CACHE in parts:       # lies inside ds.attn since PR 28
        return "kv_cache"
    if names.MODULE_ATTENTION in parts or names.SCOPE_ATTENTION in parts:
        return "attention"
    if names.MODULE_MLP in parts or names.SCOPE_MLP in parts:
        return "mlp"
    if names.SCOPE_NORM in parts or any(
            p == "norm" or p.endswith("layernorm") for p in parts):
        return "norm"
    if any(re.fullmatch(r"layers_\d+", p) for p in parts):
        return "residual"
    return UNCLASSED


# ---------------------------------------------------------------- reduction
def _nest(spans):
    """``[(name, start, end, stats, children_ns)]``: every span with the
    time its direct children cover (spans sorted, properly nested)."""
    out, stack = [], []
    for name, s, e, stats in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        row = [name, s, e, stats, 0.0]
        if stack:
            stack[-1][4] += min(e, stack[-1][2]) - s
        stack.append(row)
        out.append(row)
    return out


def reduce_planes(planes, names):
    device = sorted(n for n in planes if xplane.DEVICE_PLANE.match(n))
    if not device:
        return None
    chip = planes[device[0]]
    ops = chip.get(xplane.OP_LINE) or []
    if not ops:
        return None
    host = [e for evs in planes.get(HOST_PLANE, {}).values() for e in evs]
    lo, hi = min(e[1] for e in ops), max(e[2] for e in ops)
    traced = [e for e in host if e[0] == xplane.WINDOW_SPAN]
    if traced:
        t = max(traced, key=lambda e: e[2] - e[1])
        if t[1] < hi and t[2] > lo:
            lo, hi = t[1], t[2]

    # the program an op belongs to: its metadata's program id, named by the
    # module line (``jit_ds_apply_update(<id>)``)
    programs, modules = {}, {}
    for name, s, e, _, _ in chip.get(MODULE_LINE, ()):
        m = re.match(r"^(.*)\((\d+)\)$", name)
        if not m:
            continue
        programs[int(m.group(2))] = m.group(1)
        if lo <= (s + e) / 2 <= hi:
            modules[m.group(1)] = modules.get(m.group(1), 0) + 1

    by_class, by_program, by_kernel, unclassed = {}, {}, {}, {}
    lm_head = {"forward": 0.0, "backward": 0.0}
    recompute = 0.0
    busy, classes = [], {}
    for name, s, e, _, meta in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        busy.append((s, e))
        program = programs.get(_unsigned(meta.get("program_id")), "")
        if (name, program) not in classes:      # once an instruction
            classes[name, program] = classify(name, meta, program, names)
        cls = classes[name, program]
        by_class[cls] = by_class.get(cls, 0.0) + (e - s)
        by_program[program] = by_program.get(program, 0.0) + (e - s)
        tf_op = meta.get("tf_op") or ""
        if cls.endswith("_kernel"):
            kernel = re.sub(r"\.\d+$", "", xplane.op_parts(name)[0])
            by_kernel[kernel] = by_kernel.get(kernel, 0.0) + (e - s)
        if cls == "lm_head":
            way = "backward" if names.MARK_TRANSPOSE in tf_op else "forward"
            lm_head[way] += e - s
        if names.MARK_REMAT in tf_op:
            recompute += e - s
        if cls == UNCLASSED:
            label = xplane.op_label(name)
            unclassed[label] = unclassed.get(label, 0.0) + (e - s)
    busy_u = xplane.union(busy)
    busy_ns = xplane.total(busy_u)

    spans = _nest([(n[len(names.SPAN_PREFIX):], s, e, st)
                   for n, s, e, st, _ in host
                   if n.startswith(names.SPAN_PREFIX)])
    inside = [r for r in spans if lo <= r[1] <= hi]
    host_rows = {}
    for name, s, e, _, children in inside:
        row = host_rows.setdefault(name, {"n": 0, "total_ms": 0.0,
                                          "self_ms": 0.0})
        row["n"] += 1
        row["total_ms"] += (e - s) / 1e6
        row["self_ms"] += (e - s - children) / 1e6

    idle, unlabelled_long = {}, 0
    for s, e in xplane.subtract([(lo, hi)], busy_u):
        mid = (s + e) / 2
        cover = [r for r in spans if r[1] <= mid <= r[2]]
        if cover:
            label = min(cover, key=lambda r: r[2] - r[1])[0]
        else:
            # say what the host was doing there: the benchmark's own span
            bench = [h for h in host if h[1] <= mid <= h[2]
                     and h[0].startswith(xplane.SPAN_PREFIX)
                     and h[0] != xplane.WINDOW_SPAN]
            label = OUTSIDE + (
                f" ({min(bench, key=lambda h: h[2] - h[1])[0]})"
                if bench else "")
        idle[label] = idle.get(label, 0.0) + (e - s)
        if not cover and e - s > LONG_GAP_NS:
            unlabelled_long += 1
    idle_ns = sum(idle.values())

    # serving steps: the counts of every ds:serve.step, its fetch taken off
    fetches = [r for r in inside if r[0] == names.SERVE_FETCH]
    serve = {"steps": 0, "host_ms": 0.0, "kinds": {}}
    sums = {}
    for r in inside:
        if r[0] != names.SERVE_STEP:
            continue
        serve["steps"] += 1
        waited = sum(f[2] - f[1] for f in fetches
                     if r[1] <= f[1] and f[2] <= r[2])
        serve["host_ms"] += (r[2] - r[1] - waited) / 1e6
        kind = r[3].get("kind")
        serve["kinds"][kind] = serve["kinds"].get(kind, 0) + 1
        if kind == names.KIND_RAGGED:
            for key in ("token_budget", "live_tokens", "prefill_tokens",
                        "decode_tokens", "grid_pages", "live_pages",
                        "short_pages"):
                sums[key] = sums.get(key, 0) + int(r[3].get(key, 0))
    serve["ragged_sums"] = sums

    ms = lambda d: {k: v / 1e6 for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])}
    return {
        "window_ms": (hi - lo) / 1e6,
        "busy_ms": busy_ns / 1e6,
        "device_ms_by_class": ms(by_class),
        "unclassed_share": (100.0 * by_class.get(UNCLASSED, 0.0) / busy_ns
                            if busy_ns else None),
        "unclassed_top": [[k, v] for k, v in list(ms(unclassed).items())[:5]],
        "device_ms_by_program": ms(by_program),
        "optimizer_program_ms": sum(
            v for k, v in by_program.items()
            if names.PROGRAM_APPLY in k) / 1e6,
        "device_ms_by_kernel": ms(by_kernel),
        "lm_head_ms": {k: v / 1e6 for k, v in lm_head.items()},
        "recompute_ms": recompute / 1e6,
        "modules_in_window": modules,
        "train_steps": host_rows.get(names.TRAIN_APPLY, {}).get("n", 0),
        "host_spans": host_rows,
        "idle_ms": idle_ns / 1e6,
        "idle_ms_by_span": ms(idle),
        "idle_outside_share": (
            100.0 * sum(v for k, v in idle.items() if k.startswith(OUTSIDE))
            / idle_ns if idle_ns else 0.0),
        "long_gaps_without_label": unlabelled_long,
        "serve": serve,
    }


def _unsigned(value):
    """A program id (an int64 stat) as the module line prints it."""
    try:
        value = int(value)
    except (TypeError, ValueError):
        return None
    return value + (1 << 64) if value < 0 else value


def reduce_file(path, names):
    return reduce_planes(read_file(path), names)


# ------------------------------------------------------------- the readers'
_CACHE = {}


def summary(record):
    """The reduction of this run's trace, or None: no traced run (``record``
    has no ``trace``), no trace file, or a program without the names."""
    if not record.get("trace"):
        return None
    names = program_names()
    path = find_trace()
    if names is None or path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce_file(path, names)
        if _CACHE[key] is not None:
            print("INFO program_spans: " + json.dumps(
                _CACHE[key], default=float), flush=True)
    return _CACHE[key]


def per_train_step(record, value_of):
    """``value_of(summary)`` milliseconds over the traced optimizer steps."""
    s = summary(record)
    if not s or not s["train_steps"]:
        return None
    return value_of(s) / s["train_steps"]


# ------------------------------------------------------------------ writing
def write_planes(planes, path):
    """Write ``{plane: {line: [(name, start_ns, end_ns, stats, meta_stats)]}}``
    as an ``.xplane.pb`` with stats (through ProfileData's text-proto door,
    as ``xplane.write_planes`` does without them): how the tests' small
    trace is made."""
    from jax.profiler import ProfileData
    quoted = lambda n: str(n).replace("\\", "\\\\").replace('"', '\\"')

    def stat(stat_ids, key, value):
        sid = stat_ids.setdefault(key, len(stat_ids) + 1)
        kind = ("int64_value" if isinstance(value, int) else
                "double_value" if isinstance(value, float) else "str_value")
        shown = value if kind != "str_value" else f'"{quoted(value)}"'
        return f"stats {{ metadata_id: {sid} {kind}: {shown} }}"

    out = []
    for pname, lines in planes.items():
        ids, stat_ids, body = {}, {}, []
        for lname, evs in lines.items():
            rows = []
            for name, start, end, stats, meta in evs:
                mid = ids.setdefault(name, (len(ids) + 1, meta))[0]
                rows.append(
                    f"events {{ metadata_id: {mid} "
                    f"offset_ps: {int(round(start * 1000))} "
                    f"duration_ps: {int(round((end - start) * 1000))} "
                    + " ".join(stat(stat_ids, k, v)
                               for k, v in (stats or {}).items()) + " }")
            body.append(f'lines {{ name: "{lname}" timestamp_ns: 0 '
                        + " ".join(rows) + " }")
        meta_text = " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} '
            f'name: "{quoted(n)}" '
            + " ".join(stat(stat_ids, k, v) for k, v in (m or {}).items())
            + " } }" for n, (i, m) in ids.items())
        stat_text = " ".join(
            f'stat_metadata {{ key: {i} value {{ id: {i} '
            f'name: "{quoted(k)}" }} }}' for k, i in stat_ids.items())
        out.append(f'planes {{ name: "{pname}" ' + " ".join(body) + " "
                   + meta_text + " " + stat_text + " }")
    data = ProfileData.text_proto_to_serialized_xspace("\n".join(out))
    with open(path, "wb") as f:
        f.write(data)
