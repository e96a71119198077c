#!/usr/bin/env python
"""Minimal ZeRO training loop — the reference's 3-call API on a TPU mesh.

Run (any backend; on CPU use the virtual mesh):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_zero.py --stage 2 --steps 10

The same script runs unmodified on a TPU slice under `bin/deepspeed`
(reference launcher semantics): one process per host, mesh axes span chips.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import llama


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--stage", type=int, default=2)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--local_rank", type=int, default=-1)  # launcher-compat
    args = ap.parse_args()

    cfg = llama.llama_tiny(dtype="float32", remat=False)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=llama.LlamaModel(cfg),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "fusedadam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": args.stage},
        })

    rng = np.random.default_rng(0)
    rows = 2 * engine.dp_world_size
    ids = rng.integers(0, cfg.vocab_size, size=(rows, 32)).astype(np.int32)
    engine.initialize_parameters(0, ids, ids)

    for step in range(args.steps):
        for _ in range(engine.gradient_accumulation_steps()):
            batch = rng.integers(0, cfg.vocab_size,
                                 size=(rows, 32)).astype(np.int32)
            loss = engine(batch, batch)
            engine.backward(loss)
            engine.step()
        print(f"step {engine.global_steps}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
