#!/usr/bin/env python
"""ZeRO-Infinity parameter streaming: params + optimizer state live in host
RAM (or NVMe via offload_optimizer.nvme_path); the chip holds one block at
a time.  The config below is the reference's offload vocabulary unchanged.

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/infinity_offload.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models import llama


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = llama.llama_tiny(dtype="float32", remat=False)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=llama.LlamaModel(cfg),
        config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "fusedadam", "params": {"lr": 1e-3}},
            "zero_optimization": {
                "stage": 3,
                "offload_param": {"device": "cpu"},
                "offload_optimizer": {"device": "cpu"},
            },
        })
    rng = np.random.default_rng(0)
    rows = 2 * engine.dp_world_size
    ids = rng.integers(0, cfg.vocab_size, size=(rows, 32)).astype(np.int32)
    engine.initialize_parameters(0, ids, ids)
    for _ in range(3):
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
    print(f"loss {float(loss):.4f}; hbm_param_bytes={engine.hbm_param_bytes()} "
          f"max_resident_blocks={engine.max_resident_blocks}")


if __name__ == "__main__":
    main()
