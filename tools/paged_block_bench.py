#!/usr/bin/env python
"""What a page costs ``ds_paged_runs`` when a long run's tile item takes a
BLOCK of ``P`` pages through one softmax update, on the chip: the sweep
``paged_attention.item_pages`` follows.

    python tools/paged_block_bench.py            # chip only, ~4 min

One prefill chunk of one sequence (every item a tile item) against 4 k and
16 k of context at the three serving cells' shapes, ``P`` in 1 / 2 / 4 / 8
and ``_STACK_ROWS`` in 512 / 1 024 / 2 048 (both set from here; the program
has no such option).  Prints one JSON line a variant: the kernel's own time
from a device trace of ``--reps`` calls (the events ``ds_paged_runs*``), its
page loads, microseconds a PAGE, the share of pages in blocks, and the largest
difference from the one-page kernel's output.  docs/kernels.md and PERF.md
hold the readings.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from deepspeed_tpu.ops.pallas import paged_attention as paged  # noqa: E402

BS, DH = 128, 128
#: name: (heads, KV heads, rows of the chunk, window), as the cells run them
SHAPES = {
    "command_a_plus_128_8": (128, 8, 2048, 0),
    "command_a_plus_128_8_window": (128, 8, 2048, 4096),
    "mistral_32_8": (32, 8, 768, 4096),
    "evabyte_32_32": (32, 32, 768, 0),
}


def kernel_ms(fn, args, reps, kernel="ds_paged_runs"):
    """Milliseconds a call of the device's ``<kernel>*`` events over ``reps``
    traced calls, and the last output."""
    out = jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory(prefix="paged_block_") as d:
        jax.profiler.start_trace(d)
        for _ in range(reps):
            out = jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        trace = jax.profiler.ProfileData.from_file(path)
    ns = [e.duration_ns for plane in trace.planes
          if plane.name.startswith("/device:TPU:0")
          for line in plane.lines if line.name == "XLA Ops"
          for e in line.events
          if e.name.lstrip("%").startswith(kernel)]
    if len(ns) != reps:
        sys.exit(f"{len(ns)} {kernel} events in a trace of {reps} calls")
    return sum(ns) / reps / 1e6, out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--pages", default="1,2,4,8")
    ap.add_argument("--stack", default="512,1024,2048")
    ap.add_argument("--contexts", default="4096,16384")
    ap.add_argument("--reps", type=int, default=5)
    opts = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        sys.exit("paged_block_bench times a kernel: it needs the chip")
    ints = lambda s: [int(x) for x in s.split(",")]
    stack0 = paged._STACK_ROWS
    rng = np.random.default_rng(0)
    for name in opts.shapes.split(","):
        heads, kv_heads, T, window = SHAPES[name]
        maxb = (max(ints(opts.contexts)) + T) // BS + 1
        kc, vc = (jnp.asarray(rng.standard_normal(
            (maxb + 1, BS, kv_heads, DH), np.float32), jnp.bfloat16)
            for _ in range(2))
        q = jnp.asarray(rng.standard_normal((T, heads, DH), np.float32),
                        jnp.bfloat16)
        tables = np.zeros((2, maxb), np.int32)
        tables[1] = rng.permutation(np.arange(1, maxb + 1))
        slots = np.ones(T, np.int32)
        for ctx in ints(opts.contexts):
            pos = ctx + np.arange(T, dtype=np.int32)
            args = (q, kc, vc, jnp.asarray(tables), jnp.asarray(slots),
                    jnp.asarray(pos))
            base = None
            for P in ints(opts.pages):
                for stack in ([stack0] if P == 1 else ints(opts.stack)):
                    paged.item_pages = lambda *a, _p=P: _p
                    paged._STACK_ROWS = stack
                    fn = jax.jit(lambda *a: paged.paged_attention.__wrapped__(
                        *a, window=window))
                    try:
                        fn = fn.lower(*args).compile()
                    except Exception as e:   # a variant Mosaic refuses
                        print(json.dumps({
                            "shape": name, "context": ctx, "P": P,
                            "stack_rows": stack, "error": str(e)[:200]}),
                            flush=True)
                        continue
                    ms, out = kernel_ms(fn, args, opts.reps)
                    grid, _, _, block = paged.kernel_page_loads(
                        slots, pos, heads=heads, kv_heads=kv_heads,
                        head_dim=DH, kv_dtype=kc.dtype, block_size=BS,
                        maxb=maxb, window=window)
                    out = np.asarray(out, np.float32)
                    if base is None:
                        base = out
                    print(json.dumps({
                        "shape": name, "context": ctx, "P": P,
                        "stack_rows": stack, "kernel_ms": round(ms, 4),
                        "grid_pages": grid, "block_pages": block,
                        "us_a_page": round(ms * 1e3 / grid, 4),
                        "max_abs_diff_from_one_page": float(
                            np.abs(out - base).max()),
                        "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
