#!/usr/bin/env python
"""What the gated delta rule's ONE-TOKEN form costs on the chip at the shape
``qwen3_next_serve_reason`` runs: the gate a change to
``ops/pallas/gated_delta_rule.py`` is read with.

    python tools/gdn_slot_bench.py            # chip only, ~2 min

Six layers' state buffers ``[257, 32, 128, 128]`` float32 (539 MB each,
donated), ``--live`` slots live (256: every slot but slot 0), through a
``fori_loop`` of 16 iterations (a burst's ``while``: a layer's output feeds
the next layer's ``q``, so nothing is hoisted): the XLA form
(``ragged_forward._rule_slots`` at ``use_kernel=False``) and
``ds_gated_delta_slot`` by the heads of a grid step (``--hb``).  Prints one
JSON line a variant: milliseconds an iteration (host
clock around the whole loop, the median of ``--reps`` calls), and that time's
share of the form's floor, 4 MiB a live row and layer (a row read once and
written once) at the chip's HBM bandwidth.  Called by no cell;
docs/kernels.md holds the readings.
"""

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.inference.v2 import ragged_forward as rf  # noqa: E402
from deepspeed_tpu.models.qwen3_next import l2_norm  # noqa: E402
from deepspeed_tpu.ops.pallas.gated_delta_rule import (  # noqa: E402
    gated_delta_slot)

LAYERS, ITERATIONS, DIM = 6, 16, 128


def inputs(slots, heads, seed, live):
    """A burst's rows as ``gdn_rule_inputs`` leaves them (unit keys, scaled
    unit queries, decays of the published range), slots ``1 .. live`` live."""
    k = jax.random.split(jax.random.PRNGKey(seed % (1 << 31)), 5)
    rows = lambda key: jax.random.normal(key, (slots, heads, DIM))
    g = -jnp.exp(jax.random.uniform(k[3], (slots, heads), minval=-7.0,
                                    maxval=0.0))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (slots, heads)))
    return (l2_norm(rows(k[0])) * DIM ** -0.5, l2_norm(rows(k[1])),
            rows(k[2]), g, beta,
            (jnp.arange(slots) != 0) & (jnp.arange(slots) <= live),
            jnp.zeros((slots, ), bool))


def looped(rule):
    """``ITERATIONS`` turns of ``LAYERS`` calls of ``rule`` over donated
    buffers: ``(states, rows) -> (states, a checksum)``."""
    @functools.partial(jax.jit, donate_argnums=(0, ))
    def run(states, q, k, v, g, beta, live, fresh):
        def turn(_, carry):
            states, o = carry
            out = []
            for state in states:
                o, state = rule(q + 1e-3 * o, k, v, g, beta, state, live,
                                fresh)
                out.append(state)
            return tuple(out), o
        states, o = jax.lax.fori_loop(0, ITERATIONS, turn,
                                      (states, jnp.zeros_like(v)))
        return states, jnp.sum(o)
    return run


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=257)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--hb", default="8,16,32")
    ap.add_argument("--live", type=int, default=256,
                    help="live slots (slot 0 never is), the first ones")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not os.environ.get("DS_TPU_FORCE_PALLAS"):
        sys.exit("gdn_slot_bench: a chip's times: no TPU here")
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks = json.load(f).get(device.device_kind)
    live = min(opts.live, opts.slots - 1)
    rows = inputs(opts.slots, opts.heads, opts.seed, live)
    # a CPU rehearsal (DS_TPU_FORCE_PALLAS=1) has no peak and no share
    floor_ms = peaks and LAYERS * live * 2 * opts.heads * DIM * DIM * 4 \
        / peaks["hbm_bytes_per_s"] * 1e3
    variants = [("xla", functools.partial(rf._rule_slots, use_kernel=False))]
    variants += [(f"kernel hb={hb}", functools.partial(
        gated_delta_slot, hb=int(hb))) for hb in opts.hb.split(",")]
    for name, rule in variants:
        run = looped(rule)
        states = tuple(jnp.zeros((opts.slots, opts.heads, DIM, DIM),
                                 jnp.float32) + 0.01 * (l + 1)
                       for l in range(LAYERS))
        times = []
        try:
            for _ in range(opts.reps + 1):          # the first call compiles
                t0 = time.perf_counter()
                states, check = jax.block_until_ready(run(states, *rows))
                times.append(time.perf_counter() - t0)
        except Exception as e:       # a variant Mosaic refuses is a line too
            print(json.dumps({"variant": name, "error":
                              f"{type(e).__name__}: {str(e)[:300]}"}),
                  flush=True)
            continue
        ms = statistics.median(times[1:]) / ITERATIONS * 1e3
        print(json.dumps({
            "variant": name, "device": device.device_kind, "live": live,
            "ms_per_iteration": round(ms, 3),
            "floor_ms": floor_ms and round(floor_ms, 3),
            "floor_share_pct": floor_ms and round(100 * floor_ms / ms, 2),
            "compile_s": round(times[0] - times[1], 2),
            # every variant makes the same calls from the same state
            "checksum": float(check)}), flush=True)
        del states


if __name__ == "__main__":
    main()
