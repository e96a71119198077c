"""Does a small fetch wait for the program it belongs to, or for the queue?

The serving scheduler launches step n+1 before it fetches step n's tokens
(``serving/scheduler.py``).  That hides the host's turn only if the fetch of
n's small token array returns when n ENDS, with n+1 queued behind it.  This
probe enqueues two chained programs A and B of equal length and fetches a
small output of A three ways:

  alone          A, tokens(A); fetch                  (the serial loop)
  before_queued  A, tokens(A), B; fetch               (the run-ahead loop)
  after_queued   A, B, tokens(A); fetch               (an argmax enqueued at
                                                       fetch time: behind B)

``before_queued`` has to read about ``alone``, and ``after_queued`` about twice
that.  Chip only (a CPU's numbers say nothing about the device's queue):

    chiprun -- python3 tools/fetch_order_probe.py
"""

import argparse
import json
import statistics
import time

import numpy as np

import jax
import jax.numpy as jnp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=4096)
    ap.add_argument("--matmuls", type=int, default=56,
                    help="chained bf16 matmuls a program (56 of 4096^3: ~40 "
                    "ms on a v5e)")
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        raise SystemExit("fetch_order_probe measures a device's queue: no "
                         "chip here")

    @jax.jit
    def program(x, w):
        """A step: its big output feeds the next one (the cache), and logits
        of which the host wants the argmax."""
        def body(x, _):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, None, length=args.matmuls)
        return x, x[:65, :].astype(jnp.float32)

    tokens = jax.jit(lambda logits: jnp.argmax(logits, -1).astype(jnp.int32))
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, (args.size, args.size), jnp.bfloat16)
    w = (jax.random.normal(key, (args.size, args.size), jnp.float32)
         / np.sqrt(args.size)).astype(jnp.bfloat16)
    jax.block_until_ready(tokens(program(x0, w)[1]))      # compiled

    def alone():
        t0 = time.perf_counter()
        x, logits = program(x0, w)
        toks = tokens(logits)
        np.asarray(toks)
        t = time.perf_counter() - t0
        jax.block_until_ready(x)
        return t, t

    def before_queued():
        t0 = time.perf_counter()
        x, logits = program(x0, w)
        toks = tokens(logits)
        x2, _ = program(x, w)
        np.asarray(toks)
        t = time.perf_counter() - t0
        jax.block_until_ready(x2)
        return t, time.perf_counter() - t0

    def after_queued():
        t0 = time.perf_counter()
        x, logits = program(x0, w)
        x2, _ = program(x, w)
        toks = tokens(logits)
        np.asarray(toks)
        t = time.perf_counter() - t0
        jax.block_until_ready(x2)
        return t, time.perf_counter() - t0

    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "matmuls": args.matmuls, "size": args.size}
    for fn in (alone, before_queued, after_queued):
        fn()
        rows = [fn() for _ in range(args.repeats)]
        out[fn.__name__] = {
            "fetch_returned_ms": 1e3 * statistics.median(r[0] for r in rows),
            "fetch_returned_ms_max": 1e3 * max(r[0] for r in rows),
            "all_done_ms": 1e3 * statistics.median(r[1] for r in rows)}
    a = out["alone"]["fetch_returned_ms"]
    out["before_queued_over_alone"] = \
        out["before_queued"]["fetch_returned_ms"] / a
    out["after_queued_over_alone"] = \
        out["after_queued"]["fetch_returned_ms"] / a
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
