#!/usr/bin/env python
"""Closed-loop autotuner smoke gate (ISSUE-12 acceptance).

End-to-end on the virtual 8-device CPU mesh (~1 min):

1. runs the comm autotuner (``deepspeed_tpu.autotuning``) with a budgeted
   trial count over a tiny synthetic model: topology probe →
   per-(op, size, wire) micro-probes → measured search over the
   comm_optimizations/ZeRO surface (the hand-written default is always one
   of the candidates);
2. asserts the autotuned config's **measured step time ≤ the hand-written
   default's** (same trial protocol, same session — the tuner compares
   medians, so with ``tie_rtol: 0`` this holds by construction whenever
   the default was measured);
3. asserts the chosen config passes the existing ``comm_smoke``
   loss-parity gate: a run with the tuned ``comm_optimizations`` block
   must track the flat baseline to the same 1e-2 final-loss tolerance
   (tools/comm_smoke machinery — zero loss-parity regression).

Run:  python tools/autotune_smoke.py [--trials N]
Exit: 0 on PASS, 1 on any deviation.

``tests/unit/autotuning/test_autotune_smoke.py`` drives
:func:`run_autotune_smoke` in-process (loaded via importlib, no
subprocess).
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOLERANCE = 1e-2


def _smoke_autotuning_config(trials, results_dir):
    """Budgeted search knobs for the gate: tiny probe surface, one ZeRO
    stage, sub-KiB overlap bucket bound (the tiny model must form >1
    bucket for the overlap candidates to mean anything), tie_rtol 0 so
    the winner is the strict measured minimum (the ≤-default assertion
    holds by construction)."""
    return {
        "enabled": True,
        "tune_comm": True,
        "tuner_type": "gridsearch",
        "tuner_num_trials": trials,
        "tuner_early_stopping": trials,  # budget, not patience, ends it
        "zero_stages": [2],
        "probe_sizes": [12, 16],
        "probe_wires": ["int8"],
        "probe_iters": 2,
        "probe_warmup": 1,
        "probe_repeat": 3,
        "bucket_mb_candidates": [0.0005],
        "max_inflight_candidates": [2],
        "min_message_sizes": [0],
        "hierarchical_candidates": [True],
        "tie_rtol": 0.0,
        "results_dir": results_dir,
        "start_profile_step": 2,
        "end_profile_step": 6,
    }


def run_autotune_smoke(trials=8, results_dir=None):
    """Run the gate in-process; returns a dict with the measurements and a
    ``pass`` verdict — the CLI and the unit test both key off it."""
    from deepspeed_tpu.autotuning.autotuner import (
        Autotuner, _synthetic_trial_model)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ds_comm_smoke", os.path.join(REPO, "tools", "comm_smoke.py"))
    comm_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(comm_smoke)

    results_dir = results_dir or os.path.join(REPO, "autotuning_results")
    model, params, batch_fn = _synthetic_trial_model()
    base = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "sgd", "params": {"lr": 0.1}},
        "zero_optimization": {"stage": 2},
        "autotuning": _smoke_autotuning_config(trials, results_dir),
    }
    tuner = Autotuner(model, base, model_parameters=params,
                      batch_fn=batch_fn)
    best = tuner.tune()
    if best is None:
        return {"pass": False, "best_name": None, "best_step_ms": None,
                "default_step_ms": None, "beats_default": False,
                "parity_delta": None, "tolerance": TOLERANCE,
                "converged": False, "trials": len(tuner.results),
                "topology": tuner.topology,
                "wire_ladders": tuner.wire_ladders,
                "results_dir": results_dir}

    default_ms = None
    for r in tuner.results:
        if r["name"].endswith("_default") and r["result"] is not None:
            default_ms = r["result"]["step_time_ms"]
            break
    best_ms = best["result"]["step_time_ms"] if best else None

    # loss-parity gate (comm_smoke machinery) for the CHOSEN block: a
    # tuned config that wins on step time but breaks convergence must
    # fail here, not in training
    block_path = os.path.join(results_dir, "tuned_block.json")
    with open(block_path) as f:
        block = json.load(f)
    co = block.get("comm_optimizations")
    if co is not None and (co.get("enabled") or
                           (co.get("overlap") or {}).get("enabled")):
        flat = comm_smoke._one_run(None, 8, 0.2)
        tuned = comm_smoke._one_run(co, 8, 0.2)
        parity_delta = abs(flat[-1] - tuned[-1])
        converged = tuned[-1] < tuned[0] * 0.8
    else:
        # the search concluded the hand-written default wins — parity with
        # the flat baseline is vacuous (it IS the flat baseline)
        parity_delta, converged = 0.0, True

    result = {
        "best_name": best["name"] if best else None,
        "best_step_ms": best_ms,
        "default_step_ms": default_ms,
        "beats_default": (best_ms is not None and default_ms is not None
                          and best_ms <= default_ms),
        "parity_delta": parity_delta,
        "tolerance": TOLERANCE,
        "converged": converged,
        "trials": len(tuner.results),
        "topology": tuner.topology,
        "wire_ladders": tuner.wire_ladders,
        "results_dir": results_dir,
    }
    result["pass"] = bool(result["beats_default"]
                          and parity_delta <= TOLERANCE
                          and converged)
    return result


def main(argv=None):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    sys.path.insert(0, REPO)
    argv = list(sys.argv[1:] if argv is None else argv)
    trials = 8
    if "--trials" in argv:
        trials = int(argv[argv.index("--trials") + 1])

    r = run_autotune_smoke(trials=trials)
    print(f"topology: {r['topology']}")
    print(f"wire ladders: {r['wire_ladders']}")
    if r["best_step_ms"] is None or r["default_step_ms"] is None:
        # every trial failed (or the default trial did): a FAIL verdict,
        # not a formatting traceback
        print(f"trials: {r['trials']} | best: {r['best_name']} — "
              "search produced no measured best/default")
        print("FAIL: autotuner could not measure the space")
        return 1
    print(f"trials: {r['trials']} | best: {r['best_name']} "
          f"{r['best_step_ms']:.3f}ms vs default "
          f"{r['default_step_ms']:.3f}ms "
          f"(beats_default={r['beats_default']})")
    print(f"loss parity: delta {r['parity_delta']:.2e} "
          f"(tolerance {r['tolerance']}) converged={r['converged']}")
    if not r["pass"]:
        print("FAIL: autotuned config does not beat the default at parity")
        return 1
    print("PASS: autotuned config ≤ default step time with loss parity "
          f"(emitted block: {os.path.join(r['results_dir'], 'tuned_block.json')})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
