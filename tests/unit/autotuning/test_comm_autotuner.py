"""Closed-loop comm autotuner (ISSUE-12): tuner strategies on synthetic
cost surfaces, probe machinery + wire-ladder derivation, and the
emitted-config round-trip self-check."""

import json

import numpy as np
import pytest

from deepspeed_tpu.autotuning import (Autotuner, AutotuningError,
                                      GridSearchTuner, ModelBasedTuner,
                                      RandomTuner, derive_wire_ladder,
                                      featurize_config, probe_topology,
                                      run_probes)


# --------------------------------------------------- synthetic cost surface
def _comm_exps():
    """A small structured candidate space: step time improves smoothly with
    smaller wire bits and overlap on — structure a cost model can learn."""
    exps = []
    for bits, wire in ((32, None), (8, "int8"), (4, "int4")):
        for overlap in (False, True):
            co = {}
            if wire:
                co = {"enabled": True, "quantized_gradients": True,
                      "wire_dtype": wire}
            if overlap:
                co = dict(co)
                co["overlap"] = {"enabled": True, "bucket_mb": 4.0,
                                 "max_inflight": 2}
            ds = {"zero_optimization": {"stage": 2},
                  "train_micro_batch_size_per_gpu": 4}
            if co:
                ds["comm_optimizations"] = co
            exps.append({"name": f"w{bits}_ov{int(overlap)}",
                         "ds_config": ds, "_bits": bits, "_ov": overlap})
    return exps


def _surface_runner(noise=0.0, seed=0):
    """step_time = 10 + bits/4 - 2*overlap (+ noise): unique min at
    (int4, overlap)."""
    rng = np.random.default_rng(seed)

    def run(exp):
        t = 10.0 + exp["_bits"] / 4.0 - (2.0 if exp["_ov"] else 0.0)
        if noise:
            t += float(rng.normal(0.0, noise))
        return {"step_time": t, "step_time_ms": t,
                "exposed_comm_frac": 0.1}
    return run


def test_min_mode_grid_finds_exact_best():
    tuner = GridSearchTuner(_comm_exps(), _surface_runner(),
                            metric="step_time", mode="min")
    best = tuner.tune(n_trials=100)
    assert best["name"] == "w4_ov1"
    assert tuner.best_metric_val == 10.0 + 1.0 - 2.0


def test_min_mode_model_based_beats_random_at_equal_budget():
    """On a learnable surface the cost model reaches the optimum within a
    budget far too small for exhaustive search (6 candidates, budget 4:
    3 cold trials to reach _MIN_FIT, then the FIRST fitted proposal is
    the true optimum — regret 0 on every seed), while random order pays
    positive mean regret.  Seeds are fixed, so the comparison is
    deterministic."""
    budget = 4

    def regret(cls, seed):
        import random as _r
        _r.seed(seed)
        tuner = cls(_comm_exps(), _surface_runner(), metric="step_time",
                    mode="min")
        tuner.tune(n_trials=budget)
        return tuner.best_metric_val - 9.0  # 9.0 = true optimum

    model_r = [regret(ModelBasedTuner, s) for s in range(6)]
    random_r = [regret(RandomTuner, s) for s in range(6)]
    assert model_r == [0.0] * 6  # fitted proposal = exact optimum
    assert np.mean(model_r) < np.mean(random_r)


def test_early_stopping_min_mode():
    calls = []

    def run(exp):
        calls.append(exp["name"])
        return {"step_time": 5.0}  # flat — never improves after first

    tuner = GridSearchTuner(_comm_exps(), run, metric="step_time",
                            mode="min")
    tuner.tune(early_stopping=2)
    assert len(calls) <= 4


def test_tie_breaker_prefers_lower_exposed_frac():
    """Within tie_rtol on the primary metric the lower exposed_comm_frac
    wins; outside it the primary metric decides."""
    exps = [{"name": n, "ds_config": {}} for n in ("a", "b", "c")]
    results = {"a": {"step_time": 10.00, "exposed_comm_frac": 0.5},
               "b": {"step_time": 10.05, "exposed_comm_frac": 0.1},  # tie
               "c": {"step_time": 12.00, "exposed_comm_frac": 0.0}}  # worse

    tuner = GridSearchTuner(exps, lambda e: results[e["name"]],
                            metric="step_time", mode="min",
                            tie_breaker="exposed_comm_frac", tie_rtol=0.02)
    best = tuner.tune()
    assert best["name"] == "b"  # 0.5% slower but hides 5× more comm
    # without the tie-breaker, strict comparison keeps "a"
    tuner = GridSearchTuner(exps, lambda e: results[e["name"]],
                            metric="step_time", mode="min")
    assert tuner.tune()["name"] == "a"


def test_tie_breaker_does_not_ratchet_past_best():
    """Chained within-margin ties must stay anchored to the TRUE measured
    minimum: accepting a tie-break winner must not move the margin
    baseline, or each tie would ratchet it further from the best."""
    exps = [{"name": n, "ds_config": {}} for n in ("a", "b", "c")]
    results = {"a": {"step_time": 100.0, "exposed_comm_frac": 0.5},
               "b": {"step_time": 101.9, "exposed_comm_frac": 0.4},
               "c": {"step_time": 103.8, "exposed_comm_frac": 0.3}}
    tuner = GridSearchTuner(exps, lambda e: results[e["name"]],
                            metric="step_time", mode="min",
                            tie_breaker="exposed_comm_frac", tie_rtol=0.02)
    best = tuner.tune()
    # b ties with a (1.9% < 2%) and wins on the tie-breaker; c is within
    # 2% of b but 3.8% past the true best — must NOT be accepted
    assert best["name"] == "b"
    assert tuner.best_metric_val == 100.0  # anchor = measured extreme


def test_featurize_covers_comm_surface():
    exps = _comm_exps()
    feats = {e["name"]: featurize_config(e["ds_config"]) for e in exps}
    # wire bits feature separates the candidates
    assert feats["w32_ov0"][5] == 32.0
    assert feats["w8_ov0"][5] == 8.0
    assert feats["w4_ov1"][5] == 4.0
    # overlap gate feature flips
    assert feats["w4_ov1"][7] == 1.0 and feats["w4_ov0"][7] == 0.0
    # a ladder averages its rung bits
    f = featurize_config({"comm_optimizations": {
        "enabled": True, "quantized_gradients": True,
        "wire_dtype_by_size": [[65536, "fp32"], [None, "int8"]]}})
    assert f[5] == 20.0  # (32 + 8) / 2


# ------------------------------------------------------------------ probes
def test_probe_topology_reports_hierarchy():
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.utils import groups
    dist.init_distributed()
    try:
        flat = probe_topology(axis="dp")
        assert flat["world"] == 8 and flat["hierarchy"] is None
        hier = probe_topology(axis="dp", intra_node_size=2)
        assert hier["hierarchy"] == {"outer_axes": ["dp_out"],
                                     "inner_axes": ["dp_in"],
                                     "inter": 4, "intra": 2}
    finally:
        groups.reset_mesh()
        dist.destroy_process_group()


def test_run_probes_schema_and_ladder():
    """Probes cover (op × size × {fp32 + wires}) with the uniform ds_bench
    row schema; derive_wire_ladder picks the measured-fastest wire per
    size bucket and merges contiguous runs."""
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.utils import groups
    dist.init_distributed()
    try:
        rows = run_probes(ops=("reduce_scatter", ), sizes_log2=(12, 14),
                          wires=("int8", ), iters=1, warmup=0, repeat=2)
    finally:
        groups.reset_mesh()
        dist.destroy_process_group()
    assert len(rows) == 4  # 2 sizes × (fp32 + int8)
    for r in rows:
        assert r["probe_op"] == "reduce_scatter"
        assert r["repeat"] == 2 and r["latency_us"] > 0 and r["iqr_us"] >= 0
        assert r["wire_dtype"] in ("fp32", "int8")
        assert {"bytes", "wire_bytes", "algbw_gbps", "size_log2"} <= set(r)
    ladder = derive_wire_ladder(rows, op="reduce_scatter")
    assert ladder is not None and ladder[-1][0] is None
    # no rows for an unprobed op → no ladder candidate
    assert derive_wire_ladder(rows, op="all_gather") is None


def test_derive_wire_ladder_merges_runs():
    def row(p, wire, lat):
        return {"probe_op": "reduce_scatter", "size_log2": p,
                "wire_dtype": wire, "latency_us": lat}

    rows = [row(12, "fp32", 1.0), row(12, "int8", 2.0),   # small: fp32 wins
            row(16, "fp32", 5.0), row(16, "int8", 4.0),   # mid: int8
            row(20, "fp32", 9.0), row(20, "int8", 6.0)]   # large: int8
    ladder = derive_wire_ladder(rows, op="reduce_scatter")
    assert ladder == [[1 << 12, "fp32"], [None, "int8"]]


# --------------------------------------------------------- emit round-trip
def _tuner_for_emit(tmp_path):
    return Autotuner(lambda p, x: x, {"autotuning": {
        "enabled": True, "results_dir": str(tmp_path / "results")}})


def test_emit_block_round_trips(tmp_path):
    at = _tuner_for_emit(tmp_path)
    best = {"name": "x", "ds_config": {
        "zero_optimization": {"stage": 2},
        "comm_optimizations": {
            "enabled": True, "quantized_gradients": True,
            "wire_dtype": "int8",
            "wire_dtype_by_size": [[65536, "fp32"], [None, "int8"]],
            "overlap": {"enabled": True, "bucket_mb": 4.0,
                        "max_inflight": 2}}}}
    block = at.emit_block(best)
    assert block["zero_optimization"]["stage"] == 2
    assert block["comm_optimizations"]["wire_dtype_by_size"] == \
        [[65536, "fp32"], [None, "int8"]]
    # the emitted block must itself be a loadable engine config
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1, **block})
    assert cfg.comm_optimizations_config.overlap.bucket_mb == 4.0


def test_emit_block_accepts_alias_spellings(tmp_path):
    """The documented stage3_* alias keys are renames the pydantic model
    itself honors — the round-trip self-check must read them back through
    the alias map, not flag them as drift."""
    at = _tuner_for_emit(tmp_path)
    best = {"name": "x", "ds_config": {"zero_optimization": {
        "stage": 3, "stage3_prefetch_bucket_size": 5e7,
        "stage3_max_live_parameters": 1e9}}}
    block = at.emit_block(best)
    assert block["zero_optimization"]["stage3_prefetch_bucket_size"] == 5e7


def test_emit_block_rejects_invalid_config(tmp_path):
    at = _tuner_for_emit(tmp_path)
    bad = {"name": "x", "ds_config": {"comm_optimizations": {
        "enabled": True, "overlap": {"enabled": True, "bucket_mb": -1}}}}
    with pytest.raises(Exception):  # pydantic ValidationError surfaces
        at.emit_block(bad)


def test_emit_block_detects_silent_value_drift(tmp_path):
    """A value the pydantic model would coerce (string bucket_mb) must not
    be emitted as-is: the round-trip self-check rejects the block."""
    at = _tuner_for_emit(tmp_path)
    drift = {"name": "x", "ds_config": {"comm_optimizations": {
        "enabled": True, "overlap": {"enabled": True, "bucket_mb": "4"}}}}
    with pytest.raises(AutotuningError, match="round-trip"):
        at.emit_block(drift)


# ------------------------------------------------------------ config guard
@pytest.mark.parametrize("key, value", [
    ("bucket_mb_candiates", [1.0]),        # typo
    # stale reference-only fields are gone, not silently accepted
    ("arg_mappings", {"a": "b"}),
    ("metric", "tokens"),
    ("tuner_type", "bayes"),
    ("probe_wires", ["int7"]),
    # the tuner starts cold: the two keys that seeded it are gone
    ("priors_file", "x"),
    ("priors_path", "x"),
])
def test_autotuning_config_rejects_unknown_keys(key, value):
    from deepspeed_tpu.autotuning import AutotuningConfig
    with pytest.raises(Exception, match=key):
        AutotuningConfig(enabled=True, **{key: value})


def test_runtime_config_validates_autotuning_block():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                              DeepSpeedConfigError)
    with pytest.raises(DeepSpeedConfigError, match="autotuning"):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "autotuning": {"enabled": True, "trialz": 9}})
    cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                           "autotuning": {"enabled": False}})
    assert cfg.autotuning_config.enabled is False


def test_autotuning_disabled_is_program_identical():
    """ISSUE-12 acceptance: ``autotuning: {enabled: false}`` compiles the
    micro-step to the exact program of a config without the key (same
    normalized jaxpr — the PR 8/9 recipe)."""
    import re
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups
    from tests.unit.simple_model import (batches, make_simple_mlp_params,
                                         random_dataset, simple_mlp_apply)

    def _jaxpr(extra):
        cfg = {"train_micro_batch_size_per_gpu": 4,
               "optimizer": {"type": "sgd", "params": {"lr": 0.1}},
               "zero_optimization": {"stage": 2}, **extra}
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=simple_mlp_apply,
            model_parameters=make_simple_mlp_params(16), config=cfg)
        try:
            data = batches(random_dataset(64, 16), 4 * engine.dp_world_size)
            inputs = engine.shard_batch(*data[0])
            micro = engine._micro_step_fn()
            args = (engine.params, engine.scale_state.scale, inputs)
            return str(jax.make_jaxpr(micro)(*args))
        finally:
            groups.reset_mesh()
            deepspeed_tpu.comm.destroy_process_group()

    norm = lambda j: re.sub(r"0x[0-9a-f]+", "0x…", j)
    assert norm(_jaxpr({"autotuning": {"enabled": False}})) == \
        norm(_jaxpr({}))


def test_wire_ladder_steers_zero_training_path():
    """The ladder is honored where the training traffic actually flows:
    the manual qgZ micro-step resolves the wire PER LEAF through the same
    ladder as the eager dispatch.  An [[null, int8]] ladder must be
    bitwise-identical to the global int8 config (same format every leaf),
    and an [[null, fp32]] ladder must match the flat baseline to float
    tolerance (unquantized payload on the identical schedule)."""
    import deepspeed_tpu
    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.utils import groups

    def train(co):
        rng = np.random.default_rng(0)
        params = {
            "w1": rng.standard_normal((16, 16)).astype("f4") * 0.3,
            "w2": rng.standard_normal((16, 16)).astype("f4") * 0.3,
        }

        def apply_fn(p, x, y):
            import jax.numpy as jnp
            h = jnp.tanh(x @ p["w1"])
            return jnp.mean((h @ p["w2"] - y) ** 2)

        # persistence threshold 0: at the default every leaf of this tiny
        # model would stay replicated and reduce via full-precision pmean,
        # making every assertion below vacuous (comm_smoke's de-vacuizer)
        cfg = {"train_micro_batch_size_per_gpu": 4,
               "optimizer": {"type": "sgd", "params": {"lr": 0.2}},
               "zero_optimization": {"stage": 2,
                                     "stage3_param_persistence_threshold": 0}}
        if co:
            cfg["comm_optimizations"] = co
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=apply_fn, model_parameters=params, config=cfg)
        xs = rng.standard_normal((4 * engine.dp_world_size, 16)
                                 ).astype("f4")
        ys = np.tanh(xs * 0.5).astype("f4")
        losses = []
        for _ in range(6):
            loss = engine(xs, ys)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        groups.reset_mesh()
        dist.destroy_process_group()
        return losses

    base = {"enabled": True, "quantized_gradients": True,
            "hierarchical_allreduce": False,
            "quantization_group_size": 128, "wire_dtype": "int8"}
    flat = train(None)
    global_int8 = train(dict(base))
    ladder_int8 = train(dict(base, wire_dtype_by_size=[[None, "int8"]]))
    ladder_fp32 = train(dict(base, wire_dtype_by_size=[[None, "fp32"]]))
    assert ladder_int8 == global_int8          # same codec per leaf
    assert global_int8 != flat                 # int8 DID quantize
    assert max(abs(a - b) for a, b in
               zip(ladder_fp32, flat)) <= 1e-6  # fp32 rung = unquantized


def test_comm_space_pins_user_block_and_gather_candidates():
    """The user's own hand-written comm block is a pinned candidate (the
    ≤-baseline covers what the user already had), and stage-3 spaces
    carry prefetch candidates in the order of ``bucket_mb_candidates``."""
    at = Autotuner(lambda p, x: x, {
        "zero_optimization": {"stage": 3},
        "comm_optimizations": {"enabled": True, "wire_dtype": "fp8",
                               "quantized_gradients": True},
        "autotuning": {"enabled": True, "tune_comm": True,
                       "zero_stages": [3],
                       "bucket_mb_candidates": [4.0, 16.0],
                       "probe_wires": ["int8"]}})
    # skip the measured probe stage: candidate construction is under test
    at.probe_rows = []
    at.topology = {}
    exps = at.build_comm_space()
    names = [e["name"] for e in exps]
    # pinned order: default first, user block next
    assert names[0] == "z3_default" and names[1] == "z3_user"
    assert exps[1]["ds_config"]["comm_optimizations"]["wire_dtype"] == "fp8"
    # stage-3 space carries prefetch candidates...
    pf = [e for e in exps if "_pf" in e["name"]]
    assert pf, names
    # ...in the order the config lists their bucket sizes
    pf_names = [n for n in names if "_pf" in n]
    assert pf_names[0].startswith("z3_pf4"), pf_names


def test_run_autotuning_refuses_disabled():
    from deepspeed_tpu.autotuning import run_autotuning
    with pytest.raises(AutotuningError, match="enabled"):
        run_autotuning(base_config={"autotuning": {"enabled": False}})


# -------------------------------------------- memory-feasibility filter (PR 14)
def _filter_autotuner(num_params):
    at = Autotuner(lambda p, x: x, {"autotuning": {"enabled": True}})
    at.model_info = {"num_params": num_params}
    return at


def test_memory_filter_rejects_infeasible_keeps_pinned(monkeypatch):
    from deepspeed_tpu import accelerator as acc_mod
    acc = acc_mod.get_accelerator()
    # pretend a 16 GiB chip
    monkeypatch.setattr(type(acc), "total_memory",
                        lambda self, device_index=None: 16 * 2**30)
    # 3B params fp32 Adam: 60 GB of states — stage 0 (full) and stage 2
    # (12 GB params + 18 GB sharded-state share) cannot fit, stage 3 (/8)
    # fits
    at = _filter_autotuner(int(3e9))
    exps = [
        {"name": "z0_default", "pinned": True,
         "ds_config": {"zero_optimization": {"stage": 0}}},
        {"name": "z0_w8", "ds_config": {"zero_optimization": {"stage": 0}}},
        {"name": "z2_w8", "ds_config": {"zero_optimization": {"stage": 2}}},
        {"name": "z3_w8", "ds_config": {"zero_optimization": {"stage": 3}}},
    ]
    kept = at.memory_feasibility_filter(list(exps))
    names = [e["name"] for e in kept]
    # the doomed non-pinned candidates are gone BEFORE any trial runs …
    assert "z0_w8" not in names and "z2_w8" not in names
    # … the feasible one survives, and the pinned baseline is NEVER dropped
    assert "z3_w8" in names and "z0_default" in names


def test_memory_filter_noop_without_model_or_limit(monkeypatch):
    exps = [{"name": "z0", "ds_config": {"zero_optimization": {"stage": 0}}}]
    # unknown model size → untouched
    at = _filter_autotuner(0)
    assert at.memory_feasibility_filter(list(exps)) == exps
    # unknown memory limit → untouched
    from deepspeed_tpu import accelerator as acc_mod
    acc = acc_mod.get_accelerator()
    monkeypatch.setattr(type(acc), "total_memory",
                        lambda self, device_index=None: 0)
    at = _filter_autotuner(int(1e9))
    assert at.memory_feasibility_filter(list(exps)) == exps


def test_memory_filter_never_empties_the_space(monkeypatch):
    from deepspeed_tpu import accelerator as acc_mod
    acc = acc_mod.get_accelerator()
    monkeypatch.setattr(type(acc), "total_memory",
                        lambda self, device_index=None: 2**20)  # 1 MiB chip
    at = _filter_autotuner(int(1e9))
    exps = [{"name": f"z{s}", "ds_config":
             {"zero_optimization": {"stage": s}}} for s in (0, 2, 3)]
    kept = at.memory_feasibility_filter(list(exps))
    # nothing fits in 1 MiB, but the tuner still gets one candidate to
    # deliver a measured verdict
    assert len(kept) == 1 and kept[0]["name"] == "z0"


def test_memory_filter_prices_mesh_and_precision(monkeypatch):
    from deepspeed_tpu import accelerator as acc_mod
    acc = acc_mod.get_accelerator()
    monkeypatch.setattr(type(acc), "total_memory",
                        lambda self, device_index=None: 16 * 2**30)
    at = _filter_autotuner(int(2e9))
    # same stage-0, but bf16 + tp=4 divides the resident states under 16 GiB
    exps = [
        {"name": "z0_fp32", "ds_config": {"zero_optimization": {"stage": 0}}},
        {"name": "z0_bf16_tp4", "ds_config": {
            "zero_optimization": {"stage": 0},
            "bfloat16": {"enabled": True}, "mesh": {"tp": 4}}},
    ]
    kept = [e["name"] for e in at.memory_feasibility_filter(list(exps))]
    assert kept == ["z0_bf16_tp4"]


def test_comm_space_qwz_group_size_and_zero_mode_candidates():
    """ISSUE-15 satellite: the trial surface covers qwZ
    (quantized_weights bases per probe wire, stage ≥ 3 only — below that
    qwZ never engages and the trial would duplicate its flat sibling),
    quantization_group_size candidates composed onto BOTH quantized
    families, and a flat-manual zero-mode sibling for every
    quantized-gradient wire base — all of it priced through the same
    space the memory filter sees."""
    at = Autotuner(lambda p, x: x, {
        "zero_optimization": {"stage": 3},
        "autotuning": {"enabled": True, "tune_comm": True,
                       "zero_stages": [2, 3],
                       "probe_wires": ["int8"],
                       "group_size_candidates": [256]}})
    at.probe_rows = []
    at.topology = {}
    exps = at.build_comm_space()
    z2 = {e["name"]: e["ds_config"].get("comm_optimizations", {})
          for e in exps if e["name"].startswith("z2")}
    assert not any(b.get("quantized_weights") and
                   not b.get("quantized_gradients")
                   for b in z2.values()), sorted(z2)
    blocks = {e["name"]: e["ds_config"].get("comm_optimizations", {})
              for e in exps if e["name"].startswith("z3")}
    qw = [b for b in blocks.values()
          if b.get("quantized_weights") and not b.get("quantized_gradients")]
    assert qw, sorted(blocks)  # qwZ-only bases exist at stage 3
    gs = [b for b in blocks.values()
          if b.get("quantization_group_size") == 256]
    # group size composed onto both quantized families
    assert any(b.get("quantized_weights") for b in gs), sorted(blocks)
    assert any(b.get("quantized_gradients") for b in gs), sorted(blocks)
    fm = [n for n, b in blocks.items()
          if b.get("zero_mode") == "flat_manual"]
    assert fm and all("fm" in n for n in fm), sorted(blocks)
    # names stay unique across the whole space (the qwZ wire is in the
    # name, so probe wires cannot collide on one "qw" candidate)
    all_blocks = {e["name"]: e["ds_config"].get("comm_optimizations", {})
                  for e in exps}
    assert len(all_blocks) == len(exps)
    # every emitted block round-trips the runtime config validator
    from deepspeed_tpu.runtime.config import CommOptimizationsConfig
    for name, b in all_blocks.items():
        if b:
            CommOptimizationsConfig(**b)


def test_autotuning_config_validates_zero_mode_and_group_size():
    from deepspeed_tpu.autotuning.config import AutotuningConfig
    with pytest.raises(Exception, match="zero_mode"):
        AutotuningConfig(enabled=True, zero_mode_candidates=["bogus"])
    with pytest.raises(Exception, match="group_size"):
        AutotuningConfig(enabled=True, group_size_candidates=[64])
    cfg = AutotuningConfig(enabled=True, group_size_candidates=[128, 512],
                           zero_mode_candidates=["gspmd"])
    assert cfg.group_size_candidates == [128, 512]
