#!/usr/bin/env python
"""FastGen-style continuous-batching serving (inference v2): put/query/flush
scheduling over a paged KV cache, plus the one-call generate wrapper.

  JAX_PLATFORMS=cpu python examples/serve_fastgen.py [--quant int8]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax

from deepspeed_tpu.models import llama
from deepspeed_tpu.inference.v2 import InferenceEngineV2


def main():
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quant", default=None, choices=("int8", "int4"),
                    help="weight-only quantized serving (wire-format "
                    "resident weights, ~1 byte/weight)")
    ap.add_argument("--serve", action="store_true",
                    help="drive the production serving scheduler "
                    "(admission queue + streaming + preemption; "
                    "docs/serving.md) instead of one-shot generate")
    ap.add_argument("--kv-dtype", default=None, choices=("int8", "fp8"),
                    help="quantized paged-KV cache (docs/serving.md)")
    args = ap.parse_args()

    cfg = llama.llama_tiny(dtype="float32", remat=False)
    model = llama.LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    eng = InferenceEngineV2(
        model, params=params,
        config=dict(dtype=cfg.dtype,
                    quantization_mode=args.quant,
                    kv_cache_dtype=args.kv_dtype,
                    state_manager=dict(max_tracked_sequences=8,
                                       max_ragged_batch_size=64,
                                       max_ragged_sequence_count=8,
                                       max_context=128, block_size=16,
                                       num_blocks=40)))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=12).tolist()
               for _ in range(4)]
    if args.serve:
        from deepspeed_tpu.serving import ServingScheduler
        sched = ServingScheduler(eng)
        streams = {}
        for i, p in enumerate(prompts):
            streams[i] = []
            sched.submit(p, max_new_tokens=8,
                         on_token=lambda t, d, i=i: streams[i].append(t))
        sched.drain()
        for i in range(len(prompts)):
            req = sched.query(i)
            print(f"req {i}: +{len(streams[i])} tokens -> {streams[i]} "
                  f"(ttft {req.ttft * 1e3:.1f} ms)")
        print(f"serving: {sched.completed} completed, "
              f"{sched.preemptions} preemptions, "
              f"peak {sched.peak_running} in flight (docs/serving.md)")
        return
    out = eng.generate(prompts, max_new_tokens=8)
    for i, toks in enumerate(out):
        print(f"seq {i}: +{len(toks)} tokens -> {toks}")
    print(f"fused decode bursts used: {getattr(eng, 'burst_steps', 0)} "
          "(decode_burst config; docs/inference.md)")


if __name__ == "__main__":
    main()
