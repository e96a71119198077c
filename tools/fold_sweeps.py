#!/usr/bin/env python
"""Summarize on-chip runs: ladder legs + sweeps, ranked, with suggested
default folds.

Usage: python tools/fold_sweeps.py [--priors OUT.json]

``--priors OUT.json`` additionally exports the aggregated (direction,
bucket_mb, wire_dtype) overlap-sweep bests as an autotuner priors file —
``deepspeed_tpu.autotuning`` (``autotuning.priors_file`` config or
``tools/autotune_smoke.py --priors``) ingests it to seed the search with
measured ground truth.
"""

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import bench  # noqa: E402


def _load(path):
    try:
        with open(path) as f:
            rec = json.loads(f.read().strip().splitlines()[-1])
        return rec if isinstance(rec, dict) and "metric" in rec else None
    except (OSError, ValueError, IndexError):
        return None


def _load_ds_bench(path):
    """ds_bench --json payload (dict with a ``rows`` list), else None."""
    try:
        with open(path) as f:
            rec = json.load(f)
        return rec if isinstance(rec, dict) and isinstance(
            rec.get("rows"), list) else None
    except (OSError, ValueError):
        return None


def aggregate_overlap(paths):
    """Merge overlap-sweep rows from ds_bench --json payloads: mean
    overlap_efficiency / exposed_comm_frac per (direction, bucket_mb,
    wire_dtype) candidate, best first within each direction.  ``direction``
    is "reduce" (backward grad reduce) or "gather" (forward param-gather
    prefetch); rows predating the gather direction count as "reduce".
    Returns a list of aggregate dicts (empty when no file carries overlap
    rows) — one sweep archive feeds the autotuner BOTH bucket sizes."""
    cells = {}
    for path in paths:
        payload = _load_ds_bench(path)
        if payload is None:
            continue
        for row in payload["rows"]:
            if row.get("overlap_efficiency") is None or \
                    row.get("bucket_mb") is None:
                continue
            key = (row.get("direction") or "reduce",
                   float(row["bucket_mb"]), row.get("wire_dtype", "?"))
            c = cells.setdefault(key, {"n": 0, "eff": 0.0, "exposed": 0.0,
                                       "mfu": 0.0, "mfu_n": 0,
                                       "peak_hbm": 0})
            c["n"] += 1
            c["eff"] += float(row["overlap_efficiency"])
            c["exposed"] += float(row.get("exposed_comm_frac") or 0.0)
            if row.get("mfu") is not None:
                c["mfu"] += float(row["mfu"])
                c["mfu_n"] += 1
            if row.get("peak_hbm_bytes"):
                c["peak_hbm"] = max(c["peak_hbm"],
                                    int(row["peak_hbm_bytes"]))
    out = [{"direction": d, "bucket_mb": mb, "wire_dtype": wd,
            "runs": c["n"],
            "overlap_efficiency": c["eff"] / c["n"],
            "exposed_comm_frac": c["exposed"] / c["n"],
            "mfu": (c["mfu"] / c["mfu_n"]) if c["mfu_n"] else None,
            "peak_hbm_bytes": c["peak_hbm"] or None}
           for (d, mb, wd), c in cells.items()]
    out.sort(key=lambda r: (r["direction"], -r["overlap_efficiency"]))
    return out


def aggregate_serve(paths):
    """Merge serving-bench rows (``direction: "serve"`` — serve_bench
    --json) across runs: mean TTFT/TBT percentiles and tokens/s/chip per
    KV wire dtype, total preemptions.  Coexists with overlap/op rows in
    mixed archives (those carry ``direction`` None/reduce/gather and are
    skipped here, exactly as serve rows are skipped by
    :func:`aggregate_overlap` — their overlap_efficiency is None)."""
    cells = {}
    for path in paths:
        payload = _load_ds_bench(path)
        if payload is None:
            continue
        for row in payload["rows"]:
            if row.get("direction") != "serve":
                continue
            key = row.get("wire_dtype") or "fp"
            c = cells.setdefault(key, {
                "n": 0, "requests": 0, "preemptions": 0, "tok_s": 0.0,
                "ttft_p50": 0.0, "ttft_p99": 0.0, "tbt_p50": 0.0,
                "tbt_p99": 0.0, "lat_runs": 0, "mfu": 0.0, "mfu_n": 0,
                "peak_hbm": 0})
            c["n"] += 1
            c["requests"] += int(row.get("requests") or 0)
            c["preemptions"] += int(row.get("preemptions") or 0)
            c["tok_s"] += float(row.get("tokens_per_s_per_chip") or 0.0)
            if row.get("mfu") is not None:
                c["mfu"] += float(row["mfu"])
                c["mfu_n"] += 1
            if row.get("peak_hbm_bytes"):
                c["peak_hbm"] = max(c["peak_hbm"],
                                    int(row["peak_hbm_bytes"]))
            if row.get("ttft_p50_ms") is not None:
                c["lat_runs"] += 1
                c["ttft_p50"] += float(row["ttft_p50_ms"])
                c["ttft_p99"] += float(row.get("ttft_p99_ms") or 0.0)
                c["tbt_p50"] += float(row.get("tbt_p50_ms") or 0.0)
                c["tbt_p99"] += float(row.get("tbt_p99_ms") or 0.0)
    out = []
    for wd, c in cells.items():
        lr = max(1, c["lat_runs"])
        out.append({
            "wire_dtype": wd, "runs": c["n"], "requests": c["requests"],
            "preemptions": c["preemptions"],
            "tokens_per_s_per_chip": c["tok_s"] / c["n"],
            "ttft_p50_ms": c["ttft_p50"] / lr,
            "ttft_p99_ms": c["ttft_p99"] / lr,
            "tbt_p50_ms": c["tbt_p50"] / lr,
            "tbt_p99_ms": c["tbt_p99"] / lr,
            "mfu": (c["mfu"] / c["mfu_n"]) if c["mfu_n"] else None,
            "peak_hbm_bytes": c["peak_hbm"] or None,
        })
    out.sort(key=lambda r: -r["tokens_per_s_per_chip"])
    return out


def aggregate_moe(paths):
    """Merge expert-dispatch sweep rows (``direction: "moe"`` — ds_bench
    --moe) across runs: mean latency / drop-fraction / load-imbalance per
    (experts, capacity_factor, wire_dtype) candidate, fastest first.
    Coexists with overlap/serve/op rows in mixed archives (their
    ``direction`` differs and they are skipped here)."""
    cells = {}
    for path in paths:
        payload = _load_ds_bench(path)
        if payload is None:
            continue
        for row in payload["rows"]:
            if row.get("direction") != "moe":
                continue
            # tokens is part of the cell key: archives swept with different
            # --moe-tokens carry ~payload-proportional latencies and must
            # not be averaged into one number (the overlap aggregator keys
            # on its full parameter tuple for the same reason)
            key = (int(row.get("experts") or 0),
                   float(row.get("capacity_factor") or 0.0),
                   int(row.get("tokens") or 0),
                   row.get("wire_dtype") or "?")
            c = cells.setdefault(key, {"n": 0, "lat": 0.0, "drop": 0.0,
                                       "imb": 0.0, "wire_bytes": 0})
            c["n"] += 1
            c["lat"] += float(row.get("latency_us") or 0.0)
            c["drop"] += float(row.get("drop_fraction") or 0.0)
            c["imb"] += float(row.get("load_imbalance") or 0.0)
            c["wire_bytes"] = int(row.get("wire_bytes") or 0)
    out = [{"experts": e, "capacity_factor": cf, "tokens": tok,
            "wire_dtype": wd,
            "runs": c["n"], "latency_us": c["lat"] / c["n"],
            "drop_fraction": c["drop"] / c["n"],
            "load_imbalance": c["imb"] / c["n"],
            "wire_bytes": c["wire_bytes"]}
           for (e, cf, tok, wd), c in cells.items()]
    out.sort(key=lambda r: (r["experts"], r["capacity_factor"],
                            r["tokens"], r["latency_us"]))
    return out


def aggregate_zero_mode(paths):
    """Merge zero-mode lane rows (``direction: "zero_mode"`` — ds_bench
    --zero-mode, the flat-manual / GSPMD / GSPMD+quantized-islands
    three-way) across runs: mean step latency per (stage, wire_dtype,
    zero_mode) cell, fastest first within each (stage, wire).  Coexists
    with overlap/serve/moe/op rows in mixed archives (their ``direction``
    differs and they are skipped here)."""
    cells = {}
    for path in paths:
        payload = _load_ds_bench(path)
        if payload is None:
            continue
        for row in payload["rows"]:
            if row.get("direction") != "zero_mode":
                continue
            key = (int(row.get("stage") or 0),
                   row.get("wire_dtype") or "?",
                   row.get("zero_mode") or "?")
            c = cells.setdefault(key, {"n": 0, "lat": 0.0, "mfu": 0.0,
                                       "mfu_n": 0, "peak_hbm": 0,
                                       "wire_bytes": 0})
            c["n"] += 1
            c["lat"] += float(row.get("latency_us") or 0.0)
            # max, not last-seen: constant across rows of one lane today,
            # but merged archives must not pair one run's latency mean
            # with an arbitrary other run's bytes
            c["wire_bytes"] = max(c["wire_bytes"],
                                  int(row.get("wire_bytes") or 0))
            if row.get("mfu") is not None:
                c["mfu"] += float(row["mfu"])
                c["mfu_n"] += 1
            if row.get("peak_hbm_bytes"):
                c["peak_hbm"] = max(c["peak_hbm"],
                                    int(row["peak_hbm_bytes"]))
    out = [{"stage": s, "wire_dtype": wd, "zero_mode": zm,
            "runs": c["n"], "latency_us": c["lat"] / c["n"],
            "wire_bytes": c["wire_bytes"],
            "mfu": (c["mfu"] / c["mfu_n"]) if c["mfu_n"] else None,
            "peak_hbm_bytes": c["peak_hbm"] or None}
           for (s, wd, zm), c in cells.items()]
    out.sort(key=lambda r: (r["stage"], r["wire_dtype"], r["latency_us"]))
    return out


# keep in sync with deepspeed_tpu/autotuning/priors.py:PRIORS_SCHEMA (a
# unit test asserts they match; duplicated so this summarizer stays
# importable without pulling jax via the package __init__)
PRIORS_SCHEMA = "ds_tpu_autotune_priors/1"


def export_priors(paths, out_path):
    """Write the aggregated overlap bests as an autotuner priors file.
    Returns the payload (empty ``overlap`` list when no archive carries
    overlap rows — still a valid, ingestible file)."""
    payload = {
        "schema": PRIORS_SCHEMA,
        "generated_from": [os.path.basename(p) for p in paths],
        "overlap": aggregate_overlap(paths),
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {len(payload['overlap'])} overlap priors to {out_path}")
    return payload


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    priors_out = None
    if "--priors" in argv:
        i = argv.index("--priors")
        if i + 1 >= len(argv):
            raise SystemExit("--priors needs an output path")
        priors_out = argv[i + 1]
    runs = os.path.join(ROOT, "chiprun_out")
    paths = sorted(glob.glob(os.path.join(runs, "*.json")) +
                   glob.glob(os.path.join(runs, "sweeps", "*.json")))
    if priors_out:
        export_priors(paths, priors_out)
    rows = []
    for path in paths:
        rec = _load(path)
        if rec is None:
            continue
        name = os.path.relpath(path, runs).replace(".json", "")
        why = bench._untrustworthy(rec)
        rows.append((name, rec, why))
    serve = aggregate_serve(paths)
    if serve:
        print("serve bench (direction=serve), best tokens/s first:")
        for r in serve:
            print(f"  kv={r['wire_dtype']:<6} "
                  f"tok/s/chip={r['tokens_per_s_per_chip']:8.0f}"
                  f"  ttft p50/p99={r['ttft_p50_ms']:.1f}/"
                  f"{r['ttft_p99_ms']:.1f}ms"
                  f"  tbt p50/p99={r['tbt_p50_ms']:.2f}/"
                  f"{r['tbt_p99_ms']:.2f}ms"
                  f"  preempt={r['preemptions']}"
                  + (f"  mfu={r['mfu']:.4f}" if r.get("mfu") is not None
                     else "")
                  + (f"  peak_hbm={r['peak_hbm_bytes'] / 2**20:.0f}MiB"
                     if r.get("peak_hbm_bytes") else "")
                  + f" (n={r['runs']}, {r['requests']} reqs)")
        print()
    moe = aggregate_moe(paths)
    if moe:
        print("moe dispatch sweep (direction=moe), per (E, cf) fastest "
              "wire first:")
        for r in moe:
            print(f"  E={r['experts']:<4} cf={r['capacity_factor']:<4g} "
                  f"wire={r['wire_dtype']:<6}"
                  f" lat={r['latency_us']:10.1f}us"
                  f" drop={r['drop_fraction']:.3f}"
                  f" imb={r['load_imbalance']:.2f}"
                  f" (n={r['runs']})")
        # suggest the wire with the best PER-CELL speedup over that cell's
        # own gspmd baseline (raw cross-cell latency would let the
        # smallest-payload cell decide); "the measurements say keep the
        # default" must never print an enable-me block
        baselines = {(r["experts"], r["capacity_factor"], r["tokens"]):
                     r["latency_us"]
                     for r in moe if r["wire_dtype"] == "gspmd"}
        best, best_speedup = None, 1.0
        for r in moe:
            if r["wire_dtype"] in ("gspmd", "fp32"):
                continue
            base = baselines.get((r["experts"], r["capacity_factor"],
                                  r["tokens"]))
            if not base or r["latency_us"] <= 0:
                continue
            speedup = base / r["latency_us"]
            if speedup > best_speedup:
                best, best_speedup = r, speedup
        if best is not None:
            print(f"  → suggested moe block: {{\"enabled\": true, "
                  f"\"quantized_dispatch\": true, "
                  f"\"wire_dtype\": \"{best['wire_dtype']}\"}} "
                  f"({best_speedup:.2f}x vs gspmd at E={best['experts']} "
                  f"cf={best['capacity_factor']:g})")
        print()
    zero_mode = aggregate_zero_mode(paths)
    if zero_mode:
        print("zero-mode lane (direction=zero_mode), per (stage, wire) "
              "fastest micro first:")
        for r in zero_mode:
            print(f"  z{r['stage']} wire={r['wire_dtype']:<6} "
                  f"mode={r['zero_mode']:<12}"
                  f" step={r['latency_us']:10.1f}us"
                  + (f" mfu={r['mfu']:.4f}" if r.get("mfu") is not None
                     else "")
                  + f" (n={r['runs']})")
        # suggest flat_manual ONLY when it measurably beats the islands
        # default for the same quantized (stage, wire) cell; the GSPMD-
        # first default needs no enable-me block
        by_cell = {}
        for r in zero_mode:
            by_cell.setdefault((r["stage"], r["wire_dtype"]),
                               {})[r["zero_mode"]] = r["latency_us"]
        for (stage, wd), modes in sorted(by_cell.items()):
            fm, gq = modes.get("flat_manual"), modes.get("gspmd_q")
            if fm and gq and fm < gq:
                print(f"  → z{stage}/{wd}: flat_manual measured "
                      f"{gq / fm:.2f}x faster — consider "
                      f"comm_optimizations.zero_mode: \"flat_manual\"")
        print()
    overlap = aggregate_overlap(paths)
    if overlap:
        titles = {"reduce": "overlap sweep (bucketed grad-reduce)",
                  "gather": "gather-prefetch sweep (forward param-gather)"}
        for direction in ("reduce", "gather"):
            rows_d = [r for r in overlap if r["direction"] == direction]
            if not rows_d:
                continue
            print(f"{titles[direction]}, best first:")
            for r in rows_d:
                print(f"  bucket_mb={r['bucket_mb']:g} "
                      f"wire={r['wire_dtype']:<6}"
                      f" overlap_eff={r['overlap_efficiency']:.3f}"
                      f" exposed_frac={r['exposed_comm_frac']:.3f}"
                      f" (n={r['runs']})")
            best = rows_d[0]
            if direction == "reduce":
                print(f"  → suggested comm_optimizations.overlap: "
                      f"{{\"enabled\": true, "
                      f"\"bucket_mb\": {best['bucket_mb']:g}}}")
            else:
                print(f"  → suggested comm_optimizations.overlap.prefetch: "
                      f"{{\"enabled\": true, "
                      f"\"bucket_mb\": {best['bucket_mb']:g}}}")
            print()
    if not rows:
        if not overlap:
            print("no recorded runs yet (chiprun_out empty)")
        return
    for name, rec, why in rows:
        flag = f"  [UNTRUSTED: {why}]" if why else ""
        print(f"{name:18s} {rec['value']:>12} vs={rec['vs_baseline']:<7}"
              f" {rec['unit'][:90]}{flag}")

    # headline suggestion: best trustworthy device-mode MFU
    device = [(n, r) for n, r, w in rows if w is None
              and r["metric"].startswith("llama_train")]
    if device:
        best = max(device, key=lambda x: x[1]["vs_baseline"])
        print(f"\nbest headline: {best[0]} vs_baseline="
              f"{best[1]['vs_baseline']}")
        if "sweeps/" in best[0]:
            print("  → consider folding this leg's BENCH_* env into the "
                  "bench defaults and re-warming the cache")


if __name__ == "__main__":
    main()
