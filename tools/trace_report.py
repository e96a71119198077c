#!/usr/bin/env python
"""Step-time breakdown report over telemetry output.

Ingests the per-step JSONL record stream (``steps.jsonl``) the
``telemetry`` subsystem emits — optionally cross-checking the Chrome trace
(``trace.json``) — and prints:

1. a per-step table: wall time, phase breakdown (forward / backward /
   grad_reduce / optimizer / checkpoint), host-exposed comm time and the
   **exposed-comm-fraction** (exposed comm / step wall — the number the
   backward-overlap scheduler and the comm autotuner optimize toward 0);
2. an aggregate per-``op[variant]`` collective table: count, avg latency,
   transported (wire) bytes, effective wire bandwidth — quantized/
   hierarchical variants (``q_int8``, ``hier``, ``hier_q_*``) report
   side-by-side with flat ops so a config's comm trajectory is one read.

Usage:
    python tools/trace_report.py <trace_dir | steps.jsonl> [--json] [--last N]

``--json`` emits the machine-readable summary (the autotuner's input)
instead of the tables.  Pure stdlib; no jax import — runs anywhere the
trace files land.
"""

import argparse
import json
import os
import sys

PHASE_COLUMNS = ("forward", "backward", "grad_reduce", "optimizer",
                 "checkpoint")


def load_steps(path):
    """Parse step records from a ``steps.jsonl`` file or a directory
    containing one.  Malformed lines are skipped with a note on stderr
    (a run killed mid-write leaves a torn last line)."""
    if os.path.isdir(path):
        path = os.path.join(path, "steps.jsonl")
    if not os.path.exists(path):
        print(f"# no step record stream at {path}", file=sys.stderr)
        return []
    steps, bad = [], 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                bad += 1
                continue
            if "step" in rec and "wall_ms" in rec:
                steps.append(rec)
    if bad:
        print(f"# skipped {bad} malformed line(s) in {path}",
              file=sys.stderr)
    return steps


def validate_chrome_trace(trace_path):
    """Schema check of the Chrome trace: parses + required event keys.
    Returns (ok, detail)."""
    try:
        with open(trace_path) as f:
            trace = json.load(f)
    except (OSError, ValueError) as e:
        return False, f"unreadable: {e}"
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        return False, "no traceEvents list"
    required = ("name", "ph", "ts", "pid", "tid")
    for i, ev in enumerate(events):
        missing = [k for k in required if k not in ev]
        if missing:
            return False, f"event {i} missing keys {missing}"
    return True, f"{len(events)} events"


def load_trace_metadata(trace_path):
    """``otherData`` from the Chrome trace: the compiled-programs table and
    the mem-planner estimate land there (engine metadata emits).  Returns
    {} when absent/unreadable — metadata is an enrichment, not a
    requirement."""
    try:
        with open(trace_path) as f:
            trace = json.load(f)
        other = trace.get("otherData")
        return other if isinstance(other, dict) else {}
    except (OSError, ValueError):
        return {}


def planner_vs_measured(meta):
    """Planner-vs-measured delta: the mem-estimator's static state bytes
    against the largest compiled ``memory_analysis`` peak.  None unless
    both sides exist."""
    planner = meta.get("mem_planner") or {}
    planned = planner.get("total_bytes")
    peaks = [p.get("peak_hbm_bytes")
             for p in meta.get("compiled_programs") or []
             if p.get("peak_hbm_bytes")]
    if not planned or not peaks:
        return None
    measured = max(peaks)
    return {"stage": planner.get("stage"),
            "planner_bytes": float(planned),
            "measured_bytes": float(measured),
            "ratio": measured / planned if planned else None}


def summarize(steps):
    """Aggregate a run: mean wall/phases, merged comm attribution, the
    exposed-comm-fraction series, the overlap-efficiency figure
    (hidden / total measured comm time), and the MFU/HBM series the
    compiled-cost capture feeds (docs/observability.md "MFU & HBM")."""
    n = len(steps)
    phases = {}
    comm_ops = {}
    wall_total = 0.0
    exposed_total = 0.0
    hidden_comm_total = 0.0
    fused_steps = 0
    tokens_total = 0
    mfu_vals = []
    hbm_live_max = 0
    hbm_peak_max = 0
    hbm_limit = 0
    for rec in steps:
        mfu = rec.get("metrics", {}).get("mfu")
        if mfu is not None:
            mfu_vals.append(float(mfu))
        hbm = rec.get("hbm") or {}
        hbm_live_max = max(hbm_live_max, int(hbm.get("live_bytes", 0)))
        hbm_peak_max = max(hbm_peak_max, int(hbm.get("peak_bytes", 0)))
        hbm_limit = max(hbm_limit, int(hbm.get("limit_bytes", 0)))
        wall_total += rec.get("wall_ms", 0.0)
        for name, ms in rec.get("phases", {}).items():
            phases[name] = phases.get(name, 0.0) + ms
        comm = rec.get("comm", {})
        exposed_total += comm.get("exposed_ms", 0.0)
        hidden_comm_total += comm.get("hidden_ms", 0.0)
        if not comm.get("ops") and not comm.get("total_ms", 0.0):
            # the whole step ran inside one compiled graph: no eager
            # collectives, so host-side comm attribution has nothing to
            # measure (comm is hidden by construction, not absent)
            fused_steps += 1
        for key, row in comm.get("ops", {}).items():
            agg = comm_ops.setdefault(key, {"count": 0, "total_ms": 0.0,
                                            "msg_bytes": 0, "wire_bytes": 0,
                                            "hidden_ms": 0.0})
            agg["count"] += row.get("count", 0)
            agg["total_ms"] += row.get("total_ms", 0.0)
            agg["msg_bytes"] += row.get("msg_bytes", 0)
            agg["wire_bytes"] += row.get("wire_bytes", 0)
            agg["hidden_ms"] += row.get("hidden_ms", 0.0)
        tokens_total += rec.get("metrics", {}).get("tokens", 0)
    # MoE routed-token accounting: per-layer means across steps
    moe_layers = {}
    moe_steps = 0
    for rec in steps:
        layers = rec.get("moe", {}).get("layers")
        if not layers:
            continue
        moe_steps += 1
        for name, st in layers.items():
            agg = moe_layers.setdefault(name, {
                "n": 0, "k": int(st.get("k", 1)), "drop_fraction": 0.0,
                "overflow_tokens": 0.0, "load_imbalance": 0.0,
                "aux_loss": 0.0})
            agg["n"] += 1
            for key in ("drop_fraction", "overflow_tokens",
                        "load_imbalance", "aux_loss"):
                agg[key] += float(st.get(key, 0.0))
            util = st.get("expert_util")
            if isinstance(util, list) and util:
                # per-expert capacity utilization (ISSUE-15 satellite):
                # summarize as mean/max occupancy — the capacity-factor
                # autotuner signal — keeping old archives byte-stable
                agg["util_n"] = agg.get("util_n", 0) + 1
                agg["expert_util_mean"] = (agg.get("expert_util_mean", 0.0)
                                           + sum(util) / len(util))
                agg["expert_util_max"] = max(
                    agg.get("expert_util_max", 0.0), max(util))
                agg["experts"] = len(util)
    for agg in moe_layers.values():
        n = max(1, agg.pop("n"))
        for key in ("drop_fraction", "overflow_tokens", "load_imbalance",
                    "aux_loss"):
            agg[key] /= n
        un = agg.pop("util_n", 0)
        if un:
            agg["expert_util_mean"] /= un
    for agg in comm_ops.values():
        agg["avg_ms"] = agg["total_ms"] / max(1, agg["count"])
        comm_ms = agg["total_ms"] + agg.get("hidden_ms", 0.0)
        agg["gbps"] = (agg["wire_bytes"] * 8 / (comm_ms / 1e3) / 1e9
                       if comm_ms > 0 else 0.0)
    comm_total = exposed_total + hidden_comm_total
    return {
        "steps": n,
        "wall_ms_mean": wall_total / n if n else 0.0,
        "phases_ms_mean": {k: v / n for k, v in sorted(phases.items())},
        "exposed_ms_mean": exposed_total / n if n else 0.0,
        "exposed_comm_fraction_mean": (exposed_total / wall_total
                                       if wall_total > 0 else 0.0),
        "hidden_ms_mean": max(0.0, (wall_total - exposed_total) / n)
        if n else 0.0,
        "hidden_comm_ms_mean": hidden_comm_total / n if n else 0.0,
        "overlap_efficiency": (hidden_comm_total / comm_total
                               if comm_total > 0 else 1.0),
        "fused_steps": fused_steps,
        "comm_attribution_unavailable": bool(n and fused_steps == n),
        "comm_ops": comm_ops,
        "moe_layers": moe_layers,
        "moe_steps": moe_steps,
        "mfu_mean": (sum(mfu_vals) / len(mfu_vals)) if mfu_vals else None,
        "mfu_steps": len(mfu_vals),
        "hbm": ({"live_bytes_max": hbm_live_max,
                 "peak_bytes_max": hbm_peak_max,
                 "limit_bytes": hbm_limit or None}
                if (hbm_live_max or hbm_peak_max) else None),
        "tokens_total": tokens_total,
        "tokens_per_sec": (tokens_total / (wall_total / 1e3)
                           if wall_total > 0 and tokens_total else 0.0),
    }


def _fmt_bytes(b):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if b < 1024 or unit == "GiB":
            return f"{b:.0f}{unit}" if unit == "B" else f"{b:.1f}{unit}"
        b /= 1024.0


def render_report(steps, summary, last=None, print_fn=print):
    """The human tables.  Deterministic for a given input (golden-output
    tested)."""
    shown = steps[-last:] if last else steps
    cols = [p for p in PHASE_COLUMNS
            if any(p in r.get("phases", {}) for r in shown)]
    # non-training span names (the serving scheduler emits prefill/decode/
    # mixed) get their own columns so mixed archives stay readable
    cols += sorted({p for r in shown for p in r.get("phases", {})}
                   - set(PHASE_COLUMNS))
    # MFU/HBM columns render only when some record carries them (older
    # archives and serving-only traces stay byte-stable)
    has_mfu = any(r.get("metrics", {}).get("mfu") is not None
                  for r in shown)
    has_hbm = any(r.get("hbm") for r in shown)
    header = f"{'step':>6}{'wall_ms':>10}"
    for p in cols:
        header += f"{p:>12}"
    header += f"{'comm_ms':>10}{'exposed_frac':>14}"
    if has_mfu:
        header += f"{'mfu':>8}"
    if has_hbm:
        header += f"{'hbm_MiB':>9}"
    if shown:
        print_fn("== per-step breakdown (ms) ==")
        print_fn(header)
        for rec in shown:
            comm = rec.get("comm", {})
            line = f"{rec['step']:>6}{rec['wall_ms']:>10.2f}"
            for p in cols:
                line += f"{rec.get('phases', {}).get(p, 0.0):>12.2f}"
            if not comm.get("ops") and not comm.get("total_ms", 0.0):
                # zero comm events ≠ zero comm: the step is fully jitted
                line += f"{'-':>10}{'(fused)':>14}"
            else:
                line += (f"{comm.get('exposed_ms', 0.0):>10.2f}"
                         f"{comm.get('exposed_comm_fraction', 0.0):>14.3f}")
            if has_mfu:
                mfu = rec.get("metrics", {}).get("mfu")
                line += (f"{mfu:>8.4f}" if mfu is not None else f"{'-':>8}")
            if has_hbm:
                hbm = rec.get("hbm") or {}
                live = hbm.get("live_bytes")
                line += (f"{live / 2**20:>9.1f}" if live is not None
                         else f"{'-':>9}")
            print_fn(line)
        print_fn("")
        print_fn(f"== run summary ({summary['steps']} steps) ==")
        print_fn(f"mean step wall: {summary['wall_ms_mean']:.2f} ms | "
                 f"exposed comm: {summary['exposed_ms_mean']:.2f} ms | "
                 f"exposed-comm-fraction: "
                 f"{summary['exposed_comm_fraction_mean']:.3f}")
        if summary.get("hidden_comm_ms_mean", 0.0) > 0:
            print_fn(f"hidden comm: {summary['hidden_comm_ms_mean']:.2f} ms"
                     f" | overlap-efficiency (hidden/total comm): "
                     f"{summary['overlap_efficiency']:.3f}")
        if summary.get("comm_attribution_unavailable"):
            print_fn("note: comm attribution unavailable (fully fused "
                     "step) — no eager collectives ran; communication is "
                     "scheduled inside the compiled step and the 0.000 "
                     "exposed fraction above is a lower bound, not a "
                     "measurement")
        if summary.get("mfu_mean") is not None:
            print_fn(f"MFU (mean over {summary['mfu_steps']} steps): "
                     f"{summary['mfu_mean']:.4f}")
        hbm = summary.get("hbm")
        if hbm:
            limit = hbm.get("limit_bytes")
            line = (f"HBM: live max {_fmt_bytes(hbm['live_bytes_max'])} | "
                    f"peak {_fmt_bytes(hbm['peak_bytes_max'])}")
            if limit:
                line += (f" | limit {_fmt_bytes(limit)} "
                         f"({hbm['peak_bytes_max'] / limit:.1%} used)")
            print_fn(line)
        if summary["tokens_per_sec"]:
            print_fn(f"tokens/s (all chips): {summary['tokens_per_sec']:.0f}")
        for name, ms in summary["phases_ms_mean"].items():
            frac = (ms / summary["wall_ms_mean"]
                    if summary["wall_ms_mean"] > 0 else 0.0)
            print_fn(f"  {name:<14} {ms:>10.2f} ms  ({frac:>5.1%})")
        print_fn("")
    print_fn("== collectives by op[variant] ==")
    print_fn(f"{'op[variant]':<34}{'count':>7}{'avg_ms':>10}"
             f"{'wire':>10}{'eff_Gbps':>10}")
    if not summary["comm_ops"]:
        print_fn("  (no eager collectives recorded — all comm ran inside "
                 "compiled steps, i.e. fully hidden)")
    for key, agg in sorted(summary["comm_ops"].items()):
        print_fn(f"{key:<34}{agg['count']:>7}{agg['avg_ms']:>10.3f}"
                 f"{_fmt_bytes(agg['wire_bytes']):>10}{agg['gbps']:>10.2f}")
    moe_layers = summary.get("moe_layers") or {}
    if moe_layers:
        print_fn("")
        print_fn(f"== MoE routed-token accounting "
                 f"(mean over {summary.get('moe_steps', 0)} steps) ==")
        # per-expert capacity-utilization columns only when some layer
        # recorded the vector (old archives stay byte-stable)
        has_util = any("expert_util_mean" in st
                       for st in moe_layers.values())
        header = (f"{'layer':<28}{'k':>3}{'drop_frac':>11}{'overflow':>10}"
                  f"{'imbalance':>11}{'aux_loss':>10}")
        if has_util:
            header += f"{'util_mean':>11}{'util_max':>10}"
        print_fn(header)
        for name, st in sorted(moe_layers.items()):
            line = (f"{name:<28}{st.get('k', 1):>3}"
                    f"{st['drop_fraction']:>11.3f}"
                    f"{st['overflow_tokens']:>10.1f}"
                    f"{st['load_imbalance']:>11.2f}"
                    f"{st['aux_loss']:>10.4f}")
            if has_util:
                um = st.get("expert_util_mean")
                ux = st.get("expert_util_max")
                line += (f"{um:>11.3f}" if um is not None else f"{'-':>11}")
                line += (f"{ux:>10.3f}" if ux is not None else f"{'-':>10}")
            print_fn(line)
    programs = summary.get("compiled_programs") or []
    if programs:
        print_fn("")
        print_fn("== compiled programs (XLA cost model, per chip) ==")
        print_fn(f"{'program':<40}{'calls':>7}{'GFLOPs':>9}"
                 f"{'bytes_acc':>11}{'peak_hbm':>10}{'src':>10}")
        for p in programs:
            flops = p.get("flops")
            ba = p.get("bytes_accessed")
            peak = p.get("peak_hbm_bytes")
            print_fn(
                f"{p.get('name', '?'):<40}{p.get('calls', 0):>7}"
                + (f"{flops / 1e9:>9.3f}" if flops is not None
                   else f"{'-':>9}")
                + (f"{_fmt_bytes(ba):>11}" if ba is not None
                   else f"{'-':>11}")
                + (f"{_fmt_bytes(peak):>10}" if peak else f"{'-':>10}")
                + f"{p.get('source') or '-':>10}")
    delta = summary.get("mem_planner_delta")
    if delta:
        print_fn("")
        print_fn(
            f"planner vs measured (stage {delta['stage']}): states "
            f"{_fmt_bytes(delta['planner_bytes'])} planned vs "
            f"{_fmt_bytes(delta['measured_bytes'])} compiled peak "
            f"(x{delta['ratio']:.2f} — the gap is activations/temp the "
            "states planner deliberately excludes)")


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="trace_report",
        description="step-time breakdown from telemetry steps.jsonl")
    ap.add_argument("path", help="telemetry trace dir or steps.jsonl file")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable summary instead of "
                    "tables")
    ap.add_argument("--last", type=int, default=None, metavar="N",
                    help="only show the last N steps in the per-step table")
    args = ap.parse_args(argv)

    steps = load_steps(args.path)
    if not steps:
        print("no step records found", file=sys.stderr)
        return 1
    summary = summarize(steps)

    trace_path = (os.path.join(args.path, "trace.json")
                  if os.path.isdir(args.path) else
                  os.path.join(os.path.dirname(args.path), "trace.json"))
    if os.path.exists(trace_path):
        ok, detail = validate_chrome_trace(trace_path)
        summary["chrome_trace"] = {"valid": ok, "detail": detail}
        meta = load_trace_metadata(trace_path)
        if meta.get("compiled_programs"):
            summary["compiled_programs"] = meta["compiled_programs"]
        if meta.get("mem_planner"):
            summary["mem_planner"] = meta["mem_planner"]
        delta = planner_vs_measured(meta)
        if delta:
            summary["mem_planner_delta"] = delta

    if args.json:
        print(json.dumps(summary, indent=2))
        return 0
    render_report(steps, summary, last=args.last)
    ct = summary.get("chrome_trace")
    if ct:
        state = "valid" if ct["valid"] else f"INVALID ({ct['detail']})"
        print(f"\nchrome trace: {state} — load trace.json in "
              "https://ui.perfetto.dev or chrome://tracing")
    return 0


if __name__ == "__main__":
    sys.exit(main())
