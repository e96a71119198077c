"""Autotuning tests (reference ``tests/unit/autotuning/``)."""

import json
import os

import numpy as np
import pytest

from deepspeed_tpu.autotuning import (Autotuner, AutotuningConfig,
                                      GridSearchTuner, ModelBasedTuner,
                                      RandomTuner)
from tests.unit.simple_model import make_simple_mlp_params, simple_mlp_apply

HIDDEN = 16


def _exps():
    return [{"name": f"e{i}",
             "ds_config": {"zero_optimization": {"stage": i % 4},
                           "train_micro_batch_size_per_gpu": 2**i,
                           "gradient_accumulation_steps": 1}}
            for i in range(6)]


def _runner_best_at(best_idx):
    def run(exp):
        i = int(exp["name"][1:])
        return {"throughput": 100.0 - abs(i - best_idx) * 10}
    return run


@pytest.mark.parametrize("cls", [GridSearchTuner, RandomTuner,
                                 ModelBasedTuner])
def test_tuners_find_best(cls):
    tuner = cls(_exps(), _runner_best_at(3))
    best = tuner.tune(n_trials=100)
    assert best["name"] == "e3"
    assert tuner.best_metric_val == 100.0


def test_grid_tuner_early_stopping():
    calls = []

    def run(exp):
        calls.append(exp["name"])
        return {"throughput": 1.0}  # flat — never improves after first

    tuner = GridSearchTuner(_exps(), run)
    tuner.tune(early_stopping=2)
    assert len(calls) <= 4  # 1 best + 2 non-improving + batch slack


def test_tuner_skips_failed_experiments():
    def run(exp):
        return None if exp["name"] == "e0" else {"throughput": 5.0}

    tuner = GridSearchTuner(_exps(), run)
    best = tuner.tune()
    assert best is not None and best["name"] != "e0"


def test_autotuner_end_to_end(tmp_path):
    params = make_simple_mlp_params(HIDDEN)

    def batch_fn(global_batch):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((global_batch, HIDDEN)).astype(np.float32)
        return (x, x)

    base = {
        "optimizer": {"type": "adam", "params": {"lr": 0.01}},
        "autotuning": {"enabled": True, "fast": True,
                       "results_dir": str(tmp_path / "results"),
                       "num_tuning_micro_batch_sizes": 2,
                       "max_train_micro_batch_size_per_gpu": 2,
                       "end_profile_step": 3},
    }
    tuner = Autotuner(simple_mlp_apply, base, model_parameters=params,
                      batch_fn=batch_fn)
    space = tuner.build_tuning_space()
    assert len(space) == 4  # fast → 2 stages × 2 mbs
    best = tuner.tune()
    assert best is not None and best["result"]["throughput"] > 0
    res_dir = base["autotuning"]["results_dir"]
    assert os.path.exists(os.path.join(res_dir, "ds_config_optimal.json"))
    with open(os.path.join(res_dir, "exps.json")) as f:
        exps = json.load(f)
    assert len(exps) >= 1
    info = json.load(open(os.path.join(res_dir, "model_info.json")))
    assert info["num_params"] == sum(
        int(np.prod(x.shape)) for x in
        [params["layer_0"]["w"], params["layer_0"]["b"],
         params["layer_1"]["w"], params["layer_1"]["b"]])


def test_mesh_tuning_space_and_trial(tmp_path):
    """tune_mesh explores mesh factorizations; trials on a flax model run
    (born-sharded init per candidate mesh) and a best config wins."""
    import numpy as np
    import jax.numpy as jnp
    import flax.linen as nn
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu.comm as dist

    class TinyMLP(nn.Module):
        @nn.compact
        def __call__(self, x, y):
            h = nn.tanh(nn.Dense(32, name="fc1")(x))
            return jnp.mean((nn.Dense(16, name="fc2")(h) - y) ** 2)

    rng = np.random.default_rng(0)

    def batch_fn(gbs):
        x = rng.standard_normal((gbs, 16)).astype(np.float32)
        return (x, 0.5 * x)

    tuner = Autotuner(
        TinyMLP(), base_config={
            "optimizer": {"type": "adam", "params": {"lr": 0.01}},
            "gradient_accumulation_steps": 1,
            "autotuning": {"enabled": True, "fast": True,
                           "tune_mesh": True, "zero_stages": [1],
                           "results_dir": str(tmp_path / "results"),
                           "exps_dir": str(tmp_path / "exps"),
                           "mesh_candidates": [{"dp": -1},
                                               {"dp": -1, "sp": 2}],
                           "num_tuning_micro_batch_sizes": 1,
                           "max_train_micro_batch_size_per_gpu": 2,
                           "min_train_micro_batch_size_per_gpu": 2}},
        batch_fn=batch_fn, steps_per_trial=2)
    space = tuner.build_tuning_space()
    names = [e["name"] for e in space]
    assert any("sp2" in n for n in names), names
    best = tuner.tune()
    assert best is not None
    assert all(r["result"] is not None for r in tuner.results), tuner.results
    groups.reset_mesh()
    dist.destroy_process_group()
