"""tools/autotune_smoke.py — the ISSUE-12 tier-1 gate, driven in-process
(loaded via importlib, no subprocess)."""

import importlib.util
import json
import os

TOOLS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "tools")


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "autotune_smoke", os.path.join(TOOLS, "autotune_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_autotune_smoke_gate(tmp_path):
    """End-to-end acceptance: probe → budgeted search → autotuned config's
    measured step time ≤ the hand-written default's, chosen config passes
    the comm_smoke loss-parity gate, and the emit-stage artifacts land
    with the round-tripped block."""
    smoke = _load_smoke()
    results = tmp_path / "results"
    r = smoke.run_autotune_smoke(trials=8, results_dir=str(results))
    assert r["pass"], r
    assert r["beats_default"] and r["best_step_ms"] <= r["default_step_ms"]
    assert r["parity_delta"] <= r["tolerance"] and r["converged"]
    # emit-stage artifacts: trials in the uniform ds_bench row schema,
    # probes + topology, and the ready-to-paste round-tripped block
    trials = json.loads((results / "trials.json").read_text())
    assert trials["metric"] == "step_time"
    for row in trials["rows"]:
        assert {"op", "latency_us", "iqr_us", "repeat", "wire_dtype",
                "bucket_mb", "direction", "exposed_comm_frac"} <= set(row)
        assert row["op"] == "trial"
    probes = json.loads((results / "probes.json").read_text())
    assert probes["rows"] and "reduce_scatter" in probes["wire_ladders"]
    topo = json.loads((results / "topology.json").read_text())
    assert topo["world"] == 8
    block = json.loads((results / "tuned_block.json").read_text())
    # the emitted block is itself a loadable engine config
    import deepspeed_tpu
    cfg = deepspeed_tpu.DeepSpeedConfig(
        {"train_micro_batch_size_per_gpu": 1, **block})
    assert cfg is not None
