#!/usr/bin/env python
"""What the lm-head and its loss cost on the chip at the shapes the training
cells run: the gate a change to ``sequence/cross_entropy.py`` or to the two
loss paths of ``models/llama.py`` is read with.

    python tools/head_loss_bench.py            # chip only, ~2 min

One sequence of ``S`` tokens (bfloat16 hidden states ``[1, S, D]``, a float32
head kernel ``[D, V]``, the shifted labels), through the two paths a model
takes: ``dense`` (the head's product, then ``_lm_loss`` on ``[1, S, V]``
logits) and ``fused`` (``_lm_loss_chunked`` -> ``fused_linear_cross_entropy``
at ``--chunk``, the configuration's ``loss_chunk_vocab``; 0 = one chunk).
Defaults are the two shapes the cells run: SmallThinker's (S 8192, D 2560,
V 37 984, chunk 9496) and Mistral's (S 4096, D 4096, V 32 000, which runs
``dense``).  Prints one JSON line a shape, path and chunk: the device's busy
time a call from a trace of ``--reps`` calls, forward alone and forward with
both gradients (the union of the op line's events: a loop's own event covers
its body's); the compiled programs' temporary bytes (``memory_analysis``);
the products of the optimised HLO with an operand or result as large as the
chunk's logits (instructions: a loop's body counts once, so a chunked path
reads its products A CHUNK); and with ``--ops K`` the K longest ops.

To read another commit, unpack it (``git archive <commit> | tar -x -C
.chip_checkout/parent``), copy this file into its ``tools/`` and run it there:
it calls the two functions by the names and arguments they have had since
they were written.  docs/kernels.md and PERF.md hold the readings.
"""

import argparse
import glob
import json
import math
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.models.llama import _lm_loss, _lm_loss_chunked  # noqa: E402

#: name: (S, D, V, chunks to read the fused path at)
SHAPES = {
    "smallthinker_8k": (8192, 2560, 37984, (9496,)),
    "mistral_4k": (4096, 4096, 32000, (0,)),
}
_PRODUCT = re.compile(r" (?:convolution|dot)\(")
_DEFINITION = re.compile(r"\s*(?:ROOT )?%(\S+) = [a-z]+[0-9]*\[([0-9,]+)\]")


def device_ms(fn, args, reps, top=0):
    """The device's busy milliseconds a call over ``reps`` traced calls of
    ``fn``, and the ``top`` longest ops ``[[label, ms a call]]``."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory(prefix="head_loss_") as d:
        jax.profiler.start_trace(d)
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        trace = jax.profiler.ProfileData.from_file(path)
    spans, ops = [], {}
    for plane in trace.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                spans.append((e.start_ns, e.start_ns + e.duration_ns))
                label = e.name.split("(")[0][:90]
                ops[label] = ops.get(label, 0) + e.duration_ns
    busy, end = 0, 0
    for s, e in sorted(spans):
        if e > end:
            busy += e - max(s, end)
            end = e
    longest = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return busy / reps / 1e6, [[k, round(v / reps / 1e6, 4)]
                               for k, v in longest]


def large_products(compiled, elements):
    """Product instructions of the optimised HLO that read or write an array
    of at least ``elements`` elements (an operand is named, not typed, on
    the product's line: its size is its own definition's)."""
    lines = compiled.as_text().splitlines()
    size = {}
    for line in lines:
        m = _DEFINITION.match(line)
        if m:
            size[m.group(1)] = math.prod(map(int, m.group(2).split(",")))
    count = 0
    for line in lines:
        m = _PRODUCT.search(line)
        if m:
            operands = re.findall(r"%([^\s,()]+)", line[m.end():])[:2]
            count += any(size.get(name, 0) >= elements for name in
                         [_DEFINITION.match(line).group(1), *operands])
    return count


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES), help="names of "
                    "SHAPES, or SxDxV (8192x2560x37984), commas between")
    ap.add_argument("--paths", default="dense,fused")
    ap.add_argument("--chunk", default="", help="loss_chunk_vocab values "
                    "for the fused path, commas between (0 = one chunk); "
                    "default: the shape's own")
    ap.add_argument("--head-dtype", default="float32")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--ops", type=int, default=0,
                    help="also print the K longest device ops of each program")
    opts = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        sys.exit("head_loss_bench times device programs: it needs the chip")
    hd = jnp.dtype(opts.head_dtype)
    key = jax.random.PRNGKey(0)
    for name in opts.shapes.split(","):
        S, D, V, chunks = SHAPES.get(name) or (
            *map(int, name.split("x")), (0,))
        if opts.chunk:
            chunks = tuple(int(c) for c in opts.chunk.split(","))
        x = jax.random.normal(key, (1, S, D), jnp.bfloat16)
        w = 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (D, V),
                                     jnp.float32)
        labels = jax.random.randint(jax.random.fold_in(key, 2), (1, S), 0, V)
        runs = []
        if "dense" in opts.paths.split(","):
            runs.append(("dense", 0, lambda x, w: _lm_loss(
                x.astype(hd) @ w.astype(hd), labels)))
        if "fused" in opts.paths.split(","):
            runs += [("fused", c, lambda x, w, c=c: _lm_loss_chunked(
                x, w, labels, None, c or V, hd)) for c in chunks]
        for path, chunk, loss in runs:
            row = {"shape": name, "S": S, "D": D, "V": V, "path": path,
                   "chunk": chunk, "head_dtype": hd.name}
            try:
                fwd = jax.jit(loss).lower(x, w).compile()
                both = jax.jit(jax.value_and_grad(
                    loss, argnums=(0, 1))).lower(x, w).compile()
                f, f_ops = device_ms(fwd, (x, w), opts.reps, opts.ops)
                b, b_ops = device_ms(both, (x, w), opts.reps, opts.ops)
            except Exception as e:      # a program that does not fit
                print(json.dumps({**row, "error": str(e)[:300]}), flush=True)
                continue
            logits = (S - 1) * (chunk or V) * 9 // 10
            row.update({
                "fwd_ms": round(f, 4), "fwd_bwd_ms": round(b, 4),
                "bwd_ms": round(b - f, 4),
                "fwd_temp_bytes": fwd.memory_analysis().temp_size_in_bytes,
                "fwd_bwd_temp_bytes":
                    both.memory_analysis().temp_size_in_bytes,
                "fwd_large_products": large_products(fwd, logits),
                "fwd_bwd_large_products": large_products(both, logits),
                "device": jax.devices()[0].device_kind})
            if opts.ops:
                row.update({"fwd_ops": f_ops, "fwd_bwd_ops": b_ops})
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
