#!/usr/bin/env python
"""What the three flash kernels cost on the chip at the shapes the training
cells run: the gate a change to ``ops/pallas/flash_attention.py`` is read
with.

    python tools/flash_block_bench.py            # chip only, ~1 min a version

One call of ``flash_attention`` (batch 1, bfloat16, causal) at Mistral-7B's
shape (S 4096, 32 heads, window 4096), SmallThinker's two (S 8192, 28 heads,
window 4096 and none) and one grouped shape (28 query heads on 4 KV heads,
un-repeated: what the models do not send yet), forward alone and forward with
the three gradients.  Prints one JSON line a shape and pair of block sizes:
each kernel's own time from a device trace of ``--reps`` calls (the events
``ds_flash_fwd*``, ``ds_flash_bwd_dq*``, ``ds_flash_bwd_dkv*``; the XLA
transposes, pads and ``delta`` around them are not counted), TFLOP/s by
docs/kernels.md's count (4 x heads x 128 x the (query, key) pairs the mask
admits, x 3.5 with the backward), and ``block_counts`` of the shape: grid
steps, live blocks and blocks that need a mask, a head.

``--kernel-file`` times other versions of ``flash_attention.py`` in the same
process on the same chip (``git show <commit>:deepspeed_tpu/ops/pallas/
flash_attention.py > .chip_checkout/parent.py``), each after the tree's own.
docs/kernels.md and PERF.md hold the readings.
"""

import argparse
import glob
import importlib.util
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# (the package's attribute of that name is the function)
tree = importlib.import_module(
    "deepspeed_tpu.ops.pallas.flash_attention")

D = 128
KERNELS = ("ds_flash_fwd", "ds_flash_bwd_dq", "ds_flash_bwd_dkv")
#: name: (S, heads, KV heads, window): the three the cells run, the grouped
#: shape no model sends yet, and (by name only) a short one no cell runs, for
#: the block sizes
SHAPES = {
    "mistral_4k": (4096, 32, 32, 4096),
    "smallthinker_8k_window": (8192, 28, 28, 4096),
    "smallthinker_8k_full": (8192, 28, 28, 0),
    "gqa_28_on_4_window": (8192, 28, 4, 4096),
    "short_2k": (2048, 32, 32, 0),
}
DEFAULT_SHAPES = tuple(SHAPES)[:4]


def admitted_pairs(S, window):
    """(query, key) pairs of a causal mask over ``S`` tokens."""
    if not window or window >= S:
        return S * (S + 1) // 2
    return window * (window + 1) // 2 + (S - window) * window


def kernels_ms(fn, args, reps):
    """Milliseconds a call of each flash kernel's device events over ``reps``
    traced calls of ``fn``."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory(prefix="flash_block_") as d:
        jax.profiler.start_trace(d)
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        trace = jax.profiler.ProfileData.from_file(path)
    ns = dict.fromkeys(KERNELS, 0)
    for plane in trace.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                # the instruction's own name; under jax.grad it is wrapped
                # (%jvp_ds_flash_fwd_.1, %transpose_jvp_ds_flash_bwd_dq__.1)
                op = e.name.split(" = ")[0]
                for kernel in KERNELS:
                    if kernel in op:
                        ns[kernel] += e.duration_ns
                        break
    return {k: v / reps / 1e6 for k, v in ns.items()}


def load(path):
    """Another version of ``flash_attention.py``, as a module of this tree's
    ``deepspeed_tpu.ops.pallas`` (its relative imports are the tree's)."""
    spec = importlib.util.spec_from_file_location(
        "deepspeed_tpu.ops.pallas._flash_" + os.path.basename(path)
        .replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(DEFAULT_SHAPES))
    ap.add_argument("--blocks", default="default", help="pairs block_q x "
                    "block_k with commas between (512x512,256x512); "
                    "'default' is the version's own")
    ap.add_argument("--kernel-file", default="", help="other versions of "
                    "flash_attention.py to time after the tree's, commas "
                    "between; 'only:' before the list leaves the tree's out")
    ap.add_argument("--reps", type=int, default=5)
    opts = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        sys.exit("flash_block_bench times kernels: it needs the chip")
    files = opts.kernel_file
    versions = [] if files.startswith("only:") else [("tree", tree)]
    versions += [(os.path.basename(p), load(p))
                 for p in files.removeprefix("only:").split(",") if p]
    key = jax.random.PRNGKey(0)
    for name in opts.shapes.split(","):
        S, heads, kv_heads, window = SHAPES[name]
        q, cot = (jax.random.normal(jax.random.fold_in(key, i),
                                    (1, S, heads, D), jnp.bfloat16)
                  for i in (0, 1))
        k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                  (1, S, kv_heads, D), jnp.bfloat16)
                for i in (2, 3))
        flops = 4 * heads * D * admitted_pairs(S, window)
        for version, module in versions:
            for blocks in opts.blocks.split(","):
                bq, bk = ((module.DEFAULT_BLOCK_Q, module.DEFAULT_BLOCK_K)
                          if blocks == "default"
                          else map(int, blocks.split("x")))
                attend = lambda q, k, v: module.flash_attention(
                    q, k, v, causal=True, window=window, block_q=bq,
                    block_k=bk)
                row = {"shape": name, "version": version,
                       "block_q": bq, "block_k": bk}
                try:
                    fwd = jax.jit(attend).lower(q, k, v).compile()
                    both = jax.jit(jax.grad(
                        lambda q, k, v: jnp.sum(
                            attend(q, k, v).astype(jnp.float32)
                            * cot.astype(jnp.float32)),
                        argnums=(0, 1, 2))).lower(q, k, v).compile()
                except Exception as e:   # a variant Mosaic refuses
                    print(json.dumps({**row, "error": str(e)[:300]}),
                          flush=True)
                    continue
                f = kernels_ms(fwd, (q, k, v), opts.reps)["ds_flash_fwd"]
                b = kernels_ms(both, (q, k, v), opts.reps)
                both_ms = sum(b.values())
                steps, live, masked = tree.block_counts(
                    S, S, bq, bk, True, window)
                print(json.dumps({
                    **row, "fwd_ms": round(f, 4),
                    "fwd_bwd_ms": round(both_ms, 4),
                    **{k.removeprefix("ds_flash_") + "_ms": round(ms, 4)
                       for k, ms in b.items() if k != "ds_flash_fwd"},
                    "fwd_tflops": round(flops / f / 1e9, 1),
                    "fwd_bwd_tflops": round(3.5 * flops / both_ms / 1e9, 1),
                    "grid_steps": steps, "live_blocks": live,
                    "masked_blocks": masked,
                    "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
