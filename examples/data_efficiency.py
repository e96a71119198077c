#!/usr/bin/env python
"""Data-efficiency pipeline end to end — offline analysis feeding a
config-driven curriculum, with exact-stream checkpoint resume.

Run (any backend; on CPU use the virtual mesh):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/data_efficiency.py --steps 6

Mirrors the reference data-efficiency tutorial flow: DataAnalyzer writes
per-sample difficulty artifacts; ``data_efficiency.data_sampling.
curriculum_learning`` in the config makes ``initialize(training_data=…)``
build a curriculum sampler over them; the engine checkpoint carries the
sampler + schedule so resume continues the exact stream.
"""

import argparse
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import flax.linen as nn
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.runtime.data_pipeline import DataAnalyzer

D = 16


class Net(nn.Module):
    @nn.compact
    def __call__(self, x, y):
        h = jnp.tanh(nn.Dense(64)(x))
        return jnp.mean((nn.Dense(D)(h) - y) ** 2)


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("--samples", type=int, default=96)
    args = p.parse_args()

    # dataset whose difficulty = feature magnitude (easy → hard)
    rng = np.random.default_rng(0)
    scale = np.linspace(0.1, 2.0, args.samples).astype(np.float32)
    xs = (rng.standard_normal((args.samples, D)) * scale[:, None]).astype(
        np.float32)
    data = [(xs[i], 0.5 * xs[i]) for i in range(args.samples)]

    work = tempfile.mkdtemp(prefix="ds_data_eff_")
    an_dir = os.path.join(work, "analysis")
    # (cleaned up in the finally below — the smoke test runs this on every
    # CI invocation)

    # 1) offline analysis → difficulty artifacts (multiprocess map-reduce;
    #    DistributedDataAnalyzer does the same across training ranks)
    try:
        _run_pipeline(args, data, xs, work, an_dir)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _run_pipeline(args, data, xs, work, an_dir):
    DataAnalyzer(
        data, an_dir, metric_names=["difficulty"],
        metric_functions=[lambda s: float(round(np.abs(s[0]).max() * 32))],
        metric_types=["single_value_per_sample"]).run_map_reduce(
            num_workers=2)
    print(f"analysis artifacts → {an_dir}")

    # 2) curriculum-configured engine: easy samples first, difficulty grows
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adam", "params": {"lr": 0.01}},
        "data_efficiency": {"enabled": True, "data_sampling": {
            "enabled": True, "curriculum_learning": {
                "enabled": True, "curriculum_metrics": {"difficulty": {
                    "output_path": an_dir,
                    "min_difficulty": 8, "max_difficulty": 64,
                    "schedule_type": "fixed_linear",
                    "schedule_config": {
                        "total_curriculum_step": args.steps,
                        "difficulty_step": 1}}}}}},
    }

    def build():
        eng, _, _, _ = deepspeed_tpu.initialize(
            model=Net(), model_parameters=Net().init(
                jax.random.PRNGKey(0), xs[:1], xs[:1])["params"],
            config=config, training_data=data)
        return eng

    engine = build()
    sampler = engine.training_dataloader.data_sampler
    it = iter(engine.training_dataloader)
    for step in range(args.steps // 2):
        loss = engine.train_batch(it)
        d = sampler.curriculum_scheduler.get_current_difficulty()
        print(f"step {step}: loss={float(loss):.4f} difficulty<={d}")

    # the draw stream is deterministic in the step counter, so a fresh twin
    # sampler replays exactly the samples the engine consumed pre-checkpoint
    from deepspeed_tpu.runtime.data_pipeline import DeepSpeedDataSampler
    twin = DeepSpeedDataSampler(
        total_samples=len(data),
        global_batch_size=engine.train_batch_size(),
        metric_values=DataAnalyzer.load_metric(an_dir, "difficulty"),
        curriculum_config=dict(
            min_difficulty=8, max_difficulty=64,
            schedule_type="fixed_linear",
            schedule_config={"total_curriculum_step": args.steps,
                             "difficulty_step": 1}))
    t_it = iter(twin)
    pre_drawn = {int(i) for _ in range(args.steps // 2)
                 for i in next(t_it)}

    # 3) checkpoint + resume: the stream continues, never restarts easy
    ck = os.path.join(work, "ckpt")
    engine.save_checkpoint(ck, tag="mid")
    engine2 = build()
    engine2.load_checkpoint(ck, tag="mid")
    s2 = engine2.training_dataloader.data_sampler
    assert s2.batch_step == sampler.batch_step
    post_drawn = set()
    orig_draw = s2._draw

    def spy(remaining, step):
        batch = orig_draw(remaining, step)
        if step >= args.steps // 2:       # skip the replayed prefix
            post_drawn.update(int(i) for i in batch)
        return batch

    s2._draw = spy
    it2 = iter(engine2.training_dataloader)
    for step in range(args.steps // 2, args.steps):
        loss = engine2.train_batch(it2)
        d = s2.curriculum_scheduler.get_current_difficulty()
        print(f"step {step} (resumed): loss={float(loss):.4f} "
              f"difficulty<={d}")
    assert not (pre_drawn & post_drawn), \
        f"re-drew consumed samples: {sorted(pre_drawn & post_drawn)}"
    print("done — curriculum resumed mid-schedule, consumed samples "
          "never re-drawn")


if __name__ == "__main__":
    main()
