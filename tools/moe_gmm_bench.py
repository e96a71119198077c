#!/usr/bin/env python
"""Time the two grouped matmuls of the repo at an expert layer's serving
shapes, on the attached chip: XLA's ``lax.ragged_dot`` against the Pallas
kernel ``ds_grouped_matmul`` (``ops/pallas/grouped_matmul.gmm``), as the
SwiGLU of ``E`` experts of width ``D x I`` over a buffer of ``C`` rows sorted
by expert of which the first ``N`` are live (in a group).

    python tools/moe_gmm_bench.py [--experts 16] [--width 4096] [--out FILE]

One JSON line a case: ``{"path", "rows", "live", "ms", "gbps", "tflops"}``
(``gbps``: the experts' weights once over the time; ``tflops``: the live
rows' products over the time).  docs/kernels.md has the v5e readings and
which path the serving step kept.
"""

import argparse
import json
import sys
import time
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def swiglu(dot):
    def ffn(x, sizes, w1, w2, w3):
        return dot(jax.nn.silu(dot(x, w1, sizes)) * dot(x, w3, sizes), w2,
                   sizes)
    return jax.jit(ffn)


def timed(fn, args, reps):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--experts", type=int, default=16)
    ap.add_argument("--width", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out")
    opts = ap.parse_args()
    from deepspeed_tpu.ops.pallas.grouped_matmul import gmm
    E, D = opts.experts, opts.width
    key = jax.random.PRNGKey(0)
    w1, w2, w3 = (jax.random.normal(jax.random.fold_in(key, i), (E, D, D),
                                    jnp.bfloat16) / np.sqrt(D)
                  for i in range(3))
    paths = {"ragged_dot": swiglu(jax.lax.ragged_dot)}
    for bm, bn, bk in ((128, 128, 128), (128, 512, 512), (128, 1024, 1024),
                       (128, 1024, 2048), (256, 1024, 1024),
                       (128, 2048, 1024), (256, 512, 2048)):
        paths[f"gmm_{bm}x{bn}x{bk}"] = swiglu(
            lambda x, w, s, b=(bm, bn, bk): gmm(
                x, w, s, block_m=b[0], block_n=b[1], block_k=b[2]))
    lines = []
    for rows, live in ((272, 264), (512, 256), (512, 512), (2560, 1024),
                       (2560, 2048), (2560, 2560), (16384, 2048),
                       (16384, 8192), (16384, 16384)):
        x = jax.random.normal(jax.random.fold_in(key, rows), (rows, D),
                              jnp.bfloat16)
        # uneven groups that sum to ``live``
        rng = np.random.default_rng(rows + live)
        cuts = np.sort(rng.integers(0, live + 1, E - 1))
        sizes = jnp.asarray(np.diff(np.concatenate([[0], cuts, [live]])),
                            jnp.int32)
        want = None
        for name, fn in paths.items():
            try:
                s, out = timed(fn, (x, sizes, w1, w2, w3), opts.reps)
            except Exception as e:      # a tiling Mosaic refuses is a result
                lines.append({"path": name, "rows": rows, "live": live,
                              "error": f"{type(e).__name__}: {str(e)[:200]}"})
                print(json.dumps(lines[-1]), flush=True)
                continue
            got = np.asarray(out[:live].astype(jnp.float32))
            want = got if want is None else want
            lines.append({
                "path": name, "rows": rows, "live": live, "ms": 1e3 * s,
                "gbps": 3 * E * D * D * 2 / s / 1e9,
                "tflops": live * 6 * D * D / s / 1e12,
                "max_diff_to_ragged_dot": float(np.max(np.abs(got - want)))})
            print(json.dumps(lines[-1]), flush=True)
    if opts.out:
        os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)
        with open(opts.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
