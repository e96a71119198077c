#!/usr/bin/env python
"""Telemetry smoke test: a tiny train with the telemetry subsystem ON must
produce valid trace artifacts and leave the training math untouched.

What it does (tiny MLP, 8 virtual CPU devices, ~30s):

1. trains ``steps`` ZeRO-2 steps with ``telemetry`` enabled (fence mode,
   comms logging on, quantized collectives engine installed so variant
   rows exist) and a few eager ``dist.*`` collectives so the per-variant
   attribution table is populated;
2. asserts the Chrome trace parses with the required event keys, the
   per-step JSONL parses with ``exposed_comm_fraction ∈ [0, 1]`` on every
   record, ``tools/trace_report.py`` summarizes it, and the Prometheus
   text endpoint renders the expected metric families;
3. re-runs the IDENTICAL training twice more — telemetry disabled vs. no
   ``telemetry`` key at all — and asserts the loss trajectories are
   **bit-identical** (the zero-overhead contract: disabled telemetry is
   not in the step path).

Run:  JAX_PLATFORMS=cpu python tools/telemetry_smoke.py
Exit: 0 on PASS, 1 on any deviation.

``tests/unit/telemetry/test_telemetry_smoke.py`` drives :func:`run_smoke`
in-process (loaded via importlib, no subprocess).
"""

import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HIDDEN = 16

COMM_OPTS = {
    "enabled": True,
    "quantized_gradients": True,
    "wire_dtype": "int8",
    "quantization_group_size": 128,
}


def _one_run(steps, lr, telemetry=None, trace_dir=None, eager_collectives=0):
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups
    from deepspeed_tpu import telemetry as tel

    rng = np.random.default_rng(0)
    params = {
        "w1": rng.standard_normal((HIDDEN, HIDDEN)).astype("float32") * 0.3,
        "w2": rng.standard_normal((HIDDEN, HIDDEN)).astype("float32") * 0.3,
    }

    def apply_fn(p, x, y):
        import jax.numpy as jnp
        h = jnp.tanh(x @ p["w1"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    config = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "sgd", "params": {"lr": lr}},
        "zero_optimization": {"stage": 2,
                              "stage3_param_persistence_threshold": 0},
        "comm_optimizations": COMM_OPTS,
        "comms_logger": {"enabled": True},
    }
    if telemetry is not None:
        config["telemetry"] = dict(telemetry)
        if trace_dir is not None:
            config["telemetry"]["trace_dir"] = trace_dir
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=apply_fn, model_parameters=params, config=config)
    xs = rng.standard_normal((4 * engine.dp_world_size, HIDDEN)
                             ).astype("float32")
    ys = np.tanh(xs * 0.5).astype("float32")
    losses = []
    import jax.numpy as jnp
    from deepspeed_tpu import comm as dist
    for _ in range(steps):
        loss = engine(xs, ys)
        engine.backward(loss)
        # eager facade traffic INSIDE the step window (before the boundary
        # closes it) so the trace carries per-variant comm rows and a
        # non-zero exposed fraction — the ZeRO-2 grad reduce itself runs
        # hidden inside the compiled step, which is exactly what
        # exposed-comm-fraction is supposed to show
        for _ in range(eager_collectives):
            dist.all_reduce(jnp.ones((1024, ), jnp.float32))
            dist.reduce_scatter(
                jnp.ones((1024 * engine.dp_world_size, ), jnp.float32))
        engine.step()
        losses.append(float(loss))
    from deepspeed_tpu.comm.comm import comms_logger
    prom = tel.prometheus_text() if tel.enabled else ""
    comms_summary = comms_logger.get_summary_dict()
    comms_logger.comms_dict = {}
    comms_logger.enabled = False
    tel.shutdown()
    groups.reset_mesh()
    deepspeed_tpu.comm.destroy_process_group()
    return losses, prom, comms_summary


def run_smoke(steps=6, lr=0.2):
    """Returns a dict of artifacts + per-check verdicts; ``pass`` rolls
    them up.  The CLI and the unit test both key off it."""
    sys.path.insert(0, REPO)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import trace_report

    trace_dir = tempfile.mkdtemp(prefix="ds_tpu_tel_smoke_")
    telemetry_cfg = {"enabled": True, "fence": True,
                     "metrics": {"enabled": True, "rank0_only": True}}
    traced, prom, comms = _one_run(steps, lr, telemetry=telemetry_cfg,
                                   trace_dir=trace_dir,
                                   eager_collectives=2)

    result = {"trace_dir": trace_dir, "traced_losses": traced}

    # chrome trace: parses + schema keys
    ok, detail = trace_report.validate_chrome_trace(
        os.path.join(trace_dir, "trace.json"))
    result["chrome_trace_valid"] = ok
    result["chrome_trace_detail"] = detail

    # per-step JSONL: parses, fraction in range, phases present
    step_records = trace_report.load_steps(trace_dir)
    result["step_records"] = len(step_records)
    fractions = [r["comm"]["exposed_comm_fraction"] for r in step_records]
    result["fractions"] = fractions
    result["fractions_in_range"] = bool(
        step_records and all(0.0 <= f <= 1.0 for f in fractions))
    result["phases_present"] = bool(step_records) and all(
        {"forward", "backward", "optimizer"} <=
        set(r.get("phases", {})) for r in step_records)

    # report summarizes without raising; variant rows present
    summary = trace_report.summarize(step_records)
    result["summary"] = summary
    result["variant_rows"] = [k for k in summary["comm_ops"] if "[" in k]

    # MFU/HBM gate (ISSUE 14): every step record carries a finite mfu
    # (compiled-cost feed) and finite hbm bytes (memory_stats snapshot),
    # and the trace metadata carries the compiled-programs table
    import math
    mfus = [r.get("metrics", {}).get("mfu") for r in step_records]
    result["mfus"] = mfus
    result["mfu_finite"] = bool(step_records) and all(
        isinstance(m, float) and math.isfinite(m) and m > 0 for m in mfus)
    hbms = [r.get("hbm") or {} for r in step_records]
    result["hbm_finite"] = bool(step_records) and all(
        isinstance(h.get("live_bytes"), int) and h["live_bytes"] > 0
        and isinstance(h.get("peak_bytes"), int) for h in hbms)
    meta = trace_report.load_trace_metadata(
        os.path.join(trace_dir, "trace.json"))
    result["compiled_programs"] = [p.get("name") for p in
                                   meta.get("compiled_programs") or []]
    result["compiled_programs_ok"] = any(
        n.startswith("train/micro_step") for n in
        result["compiled_programs"])

    # metrics endpoint renders the expected families
    result["prometheus_ok"] = all(
        fam in prom for fam in ("train_steps", "train_loss",
                                "train_exposed_comm_fraction"))
    result["comms_summary_ops"] = sorted(comms["ops"])

    # zero-overhead contract: disabled == absent, bit-identical
    disabled, _, _ = _one_run(steps, lr, telemetry={"enabled": False})
    absent, _, _ = _one_run(steps, lr, telemetry=None)
    result["disabled_losses"] = disabled
    result["disabled_bit_identical"] = disabled == absent
    result["traced_matches_close"] = all(
        abs(a - b) < 1e-5 for a, b in zip(traced, disabled))

    result["pass"] = bool(
        result["chrome_trace_valid"] and result["fractions_in_range"]
        and result["phases_present"] and result["prometheus_ok"]
        and result["variant_rows"] and result["disabled_bit_identical"]
        and result["mfu_finite"] and result["hbm_finite"]
        and result["compiled_programs_ok"]
        and result["step_records"] == steps)
    return result


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    r = run_smoke()
    print(f"chrome trace: {'OK' if r['chrome_trace_valid'] else 'FAIL'} "
          f"({r['chrome_trace_detail']})")
    print(f"step records: {r['step_records']} | fractions "
          f"{['%.3f' % f for f in r['fractions']]} "
          f"(in range={r['fractions_in_range']})")
    print(f"variant rows: {r['variant_rows']}")
    print(f"mfu finite on every record: {r['mfu_finite']} "
          f"({['%.5f' % m if m is not None else None for m in r['mfus']]})")
    print(f"hbm fields finite on every record: {r['hbm_finite']}")
    print(f"compiled programs captured: {r['compiled_programs']}")
    print(f"prometheus families: {'OK' if r['prometheus_ok'] else 'FAIL'}")
    print(f"disabled == absent losses (bit-identical): "
          f"{r['disabled_bit_identical']}")
    print()
    import trace_report
    steps = trace_report.load_steps(r["trace_dir"])
    trace_report.render_report(steps, r["summary"])
    if not r["pass"]:
        print("\nFAIL: telemetry smoke found deviations")
        return 1
    print(f"\nPASS: telemetry artifacts valid under {r['trace_dir']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
