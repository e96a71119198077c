"""Measured-ground-truth priors for the model-based tuner.

Reference ``autotuning/tuner/model_based_tuner.py:19`` starts its cost
model cold — every tuning session re-measures points a previous on-chip
sweep already paid for.  Here trustworthy bench records from a runs
directory (summarized by ``tools/fold_sweeps.py``) seed
``ModelBasedTuner``'s regression, so TPU tuning starts from measured
ground truth and its FIRST proposal is the best measured config.
"""

import glob
import json
import os
import re

from ..utils.logging import logger

# Trust gate for recorded bench lines — the single source of truth shared
# with bench.py's _untrustworthy: a partial or fallback measurement must
# never be cited, folded, or become a tuning prior.
UNTRUSTED_MARKERS = ("partial", "warmup-estimate", "timing-implausible",
                     "backend=cpu", "cpu-fallback")


def untrustworthy(rec):
    """Why a recorded bench line must not be trusted, or None if it is a
    full, plausible measurement."""
    u = rec.get("unit", "")
    for m in UNTRUSTED_MARKERS:
        if m in u:
            return m
    return None


def _trusted(rec):
    return untrustworthy(rec) is None


def record_to_prior(rec):
    """One bench JSON record → {"ds_config": ..., "throughput": ...} or
    None.  The device bench encodes its config in the unit string
    (``B=<mbs> S=<seq> …``); stage/gas follow the bench's fixed config."""
    if not isinstance(rec, dict) or "metric" in rec and \
            not str(rec.get("metric", "")).startswith("llama_train"):
        return None
    if not _trusted(rec):
        return None
    m = re.search(r"\bB=(\d+)\b", rec.get("unit", ""))
    if m is None or not rec.get("value"):
        return None
    return {
        "ds_config": {
            "train_micro_batch_size_per_gpu": int(m.group(1)),
            "gradient_accumulation_steps": 1,
            "zero_optimization": {"stage": 0},
        },
        "throughput": float(rec["value"]),
    }


# ---------------------------------------------------------- priors files
# ``tools/fold_sweeps.py --priors OUT.json`` exports the aggregated
# (direction, bucket_mb, wire_dtype) bests from ds_bench --overlap archives
# under this schema tag; the autotuner ingests the file to seed its search
# (candidates matching the measured bests are proposed first).
PRIORS_SCHEMA = "ds_tpu_autotune_priors/1"


def load_priors_file(path):
    """Load a ``fold_sweeps --priors`` artifact.  Loud on a missing file or
    wrong schema — a stale/foreign JSON must not silently order the
    search."""
    with open(path) as f:
        data = json.load(f)
    schema = data.get("schema") if isinstance(data, dict) else None
    if schema != PRIORS_SCHEMA:
        raise ValueError(
            f"{path}: not an autotuner priors file (schema {schema!r}, "
            f"expected {PRIORS_SCHEMA!r}; generate one with "
            "tools/fold_sweeps.py --priors OUT.json)")
    if not isinstance(data.get("overlap"), list):
        raise ValueError(f"{path}: priors file has no 'overlap' aggregate "
                         "list")
    return data


def _block_matches_prior(co, best):
    """How many of the measured-best (direction, bucket_mb, wire) choices a
    candidate's comm block agrees with."""
    ov = (co.get("overlap") or {})
    pf = (ov.get("prefetch") or {})
    score = 0
    r = best.get("reduce")
    if r is not None and ov.get("enabled") and \
            float(ov.get("bucket_mb") or -1) == float(r["bucket_mb"]):
        score += 1
    g = best.get("gather")
    if g is not None and pf.get("enabled") and \
            float(pf.get("bucket_mb") or -1) == float(g["bucket_mb"]):
        score += 1
    if r is not None:
        wire = (co.get("wire_dtype", "int8")
                if co.get("enabled") and co.get("quantized_gradients")
                else "fp32")
        if wire == r.get("wire_dtype"):
            score += 1
    return score


def seed_exps_with_priors(exps, priors):
    """Stable-reorder candidate experiments so configs consistent with the
    priors' per-direction bests run first — the grid tuner's early
    stopping and the model-based tuner's cold phase both start from the
    measured ground truth instead of list order."""
    best = {}
    for row in priors.get("overlap", []):
        # fold_sweeps sorts best-first within each direction
        best.setdefault(row.get("direction"), row)
    if not best:
        return list(exps)
    return sorted(
        exps,
        key=lambda e: -_block_matches_prior(
            e["ds_config"].get("comm_optimizations") or {}, best))


def load_measured_priors(runs_dir="chiprun_out"):
    """Collect priors from every trustworthy record under ``runs_dir``
    (top-level ``*.json`` ladder legs + ``sweeps/*.json``)."""
    priors = []
    for path in sorted(glob.glob(os.path.join(runs_dir, "*.json")) +
                       glob.glob(os.path.join(runs_dir, "sweeps",
                                              "*.json"))):
        try:
            with open(path) as f:
                text = f.read().strip()
            if not text:
                continue
            rec = json.loads(text.splitlines()[-1])
        except (OSError, ValueError):
            continue
        p = record_to_prior(rec)
        if p is not None:
            priors.append(p)
    if priors:
        logger.info(f"autotuning: loaded {len(priors)} measured priors "
                    f"from {runs_dir}")
    return priors
