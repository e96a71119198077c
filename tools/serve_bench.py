#!/usr/bin/env python
"""Synthetic heavy-traffic driver for the serving engine (docs/serving.md).

Drives a :class:`ServingScheduler` replica with seeded Poisson arrivals and
a mixed prompt-length distribution, and reports the serving SLO numbers:
p50/p99 TTFT (submit → first token), p50/p99 per-token latency (TBT), and
tokens/s/chip — in the same ``--json`` row schema ``ds_bench`` emits and
``tools/fold_sweeps.py`` aggregates (rows carry ``direction: "serve"``).

Modes:

* default — the traffic bench: ``--requests`` arrivals at ``--rate`` req/s
  (seeded exponential inter-arrival gaps), prompt lengths drawn from a
  mixed distribution, optional ``--kv-dtype int8|fp8`` quantized paged-KV;
* ``--smoke`` — the deterministic CPU acceptance gate (tier-1): 8
  concurrent requests on a KV cache deliberately sized too small for them
  simultaneously (forcing ≥1 LIFO preemption), every request must
  complete with streamed tokens matching the one-shot engine, AND int8-KV
  greedy decode must be token-identical to the fp baseline over ≥64 steps.

Usage:
    JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke
    python tools/serve_bench.py --requests 64 --rate 32 --json out.json
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np   # noqa: E402

import jax           # noqa: E402

#: prompt-length mixture (tokens, weight) — short chat turns dominate,
#: with a long-document tail (mixed prefill pressure)
PROMPT_MIX = ((8, 0.35), (16, 0.3), (32, 0.2), (64, 0.15))


def probe_model(seed=0, vocab=64, alpha=12.0, beta=8.0):
    """Decisive-logits probe: a tiny llama whose greedy decode is a
    deterministic walk with LARGE argmax margins (≫ int8-KV quantization
    noise), so the token-identity parity gate measures the cache codec,
    not coin-flips on a random-init model's near-uniform logits.

    Construction: identity embeddings scaled by ``alpha`` make the residual
    stream dominated by the last token's coordinate; a permutation lm_head
    (×``beta``) maps that coordinate to a shifted next token — the model
    walks a 64-cycle modulated by the (random-init, fully exercised)
    attention/MLP blocks.  Measured on this config: top-1/top-2 margin
    ≈ 20-30 vs ≤ 0.1 int8-KV logit error — a >200× safety factor.
    Returns (model, params, vocab)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import llama

    cfg = llama.llama_tiny(dtype="float32", remat=False, vocab_size=vocab,
                           hidden_size=vocab, num_key_value_heads=2)
    model = llama.LlamaModel(cfg)
    params = dict(model.init(jax.random.PRNGKey(seed),
                             jnp.zeros((1, 8), jnp.int32))["params"])
    params["embed_tokens"] = {
        "embedding": alpha * jnp.eye(vocab, dtype=jnp.float32)}
    perm = (np.arange(vocab) + 17) % vocab    # coprime shift → full cycle
    head = np.zeros((vocab, vocab), np.float32)
    head[np.arange(vocab), perm] = 1.0
    params["lm_head"] = {"kernel": beta * jnp.asarray(head)}
    return model, params, vocab


def _tiny_engine(kv_dtype=None, num_blocks=None, block_size=16,
                 max_context=256, max_seqs=12, budget=64, decode_burst=8,
                 dtype="float32", seed=0, probe=False):
    """Deterministic tiny-llama replica (the CPU stand-in for a real
    checkpoint — swap ``build_hf_engine`` in for TPU runs).  ``probe=True``
    uses the decisive-logits :func:`probe_model` (the parity gates)."""
    import jax.numpy as jnp
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    if probe:
        model, params, _ = probe_model(seed=seed)
        cfg = model.config
    else:
        cfg = llama.llama_tiny(dtype=dtype, remat=False,
                               num_key_value_heads=2)
        model = llama.LlamaModel(cfg)
        params = model.init(jax.random.PRNGKey(seed),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    sm = dict(max_tracked_sequences=max_seqs + 4,
              max_ragged_batch_size=budget,
              max_ragged_sequence_count=max_seqs,
              max_context=max_context, block_size=block_size)
    if num_blocks is not None:
        sm["num_blocks"] = num_blocks
    eng = InferenceEngineV2(
        model, params=params,
        config=dict(dtype=dtype, decode_burst=decode_burst,
                    kv_cache_dtype=kv_dtype, state_manager=sm))
    return eng, cfg


def make_workload(n_requests, rate_rps, seed, max_new_tokens):
    """Seeded Poisson arrival plan: [(t_arrival_s, prompt, max_new), ...].
    Deterministic in (n, rate, seed) — the bench's repeatability contract."""
    rng = np.random.default_rng(seed)
    lengths = [l for l, _ in PROMPT_MIX]
    weights = np.array([w for _, w in PROMPT_MIX])
    weights = weights / weights.sum()
    t = 0.0
    plan = []
    for _ in range(n_requests):
        t += float(rng.exponential(1.0 / rate_rps)) if rate_rps > 0 else 0.0
        n = int(rng.choice(lengths, p=weights))
        prompt = rng.integers(1, 96, size=n).tolist()
        plan.append((t, prompt, int(max_new_tokens)))
    return plan


def _pct(values, q):
    if not values:
        return None
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def run_traffic(scheduler, plan, max_steps=200_000):
    """Drive the plan against the scheduler in arrival order: submit each
    request when its arrival time (relative to the run start) has passed,
    stepping the engine in between.  Returns the summary row."""
    from deepspeed_tpu.profiling import cost_model
    # arm compiled-cost capture so the serving programs (ragged step /
    # decode bursts) land in the registry — feeds the row's uniform
    # mfu/peak_hbm_bytes fields without enabling the full telemetry spine.
    # The registry is PROCESS-WIDE (a co-resident training engine keeps
    # its entries), so this run's accounting is a call-count DELTA, not a
    # registry reset.
    reg = cost_model.registry()
    calls_before = {p.name: p.calls for p in reg.programs()}
    cost_model.enable_capture(True)
    t0 = time.perf_counter()
    pending = list(plan)
    uids = []
    steps = 0
    try:
        while pending or not scheduler.idle:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                _, prompt, max_new = pending.pop(0)
                uids.append(scheduler.submit(prompt,
                                             max_new_tokens=max_new))
            if scheduler.idle:
                if pending:   # idle gap before the next arrival
                    time.sleep(min(0.001, pending[0][0] - now))
                continue
            scheduler.step()
            steps += 1
            if steps >= max_steps:
                raise RuntimeError("serve_bench did not converge")
        wall_s = time.perf_counter() - t0
    finally:
        # an aborted drive must not leave the process paying an analysis
        # compile per new serving program forever
        cost_model.enable_capture(False)
    reqs = [scheduler.query(u) for u in uids]
    ttfts = [r.ttft for r in reqs if r.ttft is not None]
    gaps = [g for r in reqs for g in r.token_gaps]
    n_chips = jax.device_count()
    toks = scheduler.tokens_generated
    # compiled-cost fields over THIS run's executions only: MFU =
    # Σ(program flops × call delta) over the wall against the per-chip
    # peak — registry flops are already PER-DEVICE (the partitioned SPMD
    # executable), so no further /n_chips.  Peak HBM is the static
    # compiled estimate of the run's programs: the allocator's
    # max_memory_allocated is process-lifetime (and whole-host on the CPU
    # backend), so mixing it in would report unrelated memory as ours.
    executed = 0.0
    peaks = []
    for p in reg.programs():
        delta = p.calls - calls_before.get(p.name, 0)
        if delta <= 0:
            continue
        if p.flops:
            executed += p.flops * delta
        if p.peak_hbm_bytes:
            peaks.append(p.peak_hbm_bytes)
    serve_mfu = cost_model.mfu(executed / wall_s
                               if executed and wall_s > 0 else None)
    from deepspeed_tpu.benchmarks.comm_bench import bench_row
    from deepspeed_tpu.inference.v2.kv_codec import kv_bytes_per_token
    mc = scheduler.engine.model_config
    kv_bytes = kv_bytes_per_token(
        mc.num_hidden_layers, mc.num_key_value_heads, mc.head_dim,
        scheduler.engine._kv_dtype,
        fp_dtype=scheduler.engine._config.dtype)
    # bench_row = THE uniform ds_bench schema (fold_sweeps never
    # key-errors; new uniform fields land here without a second edit)
    return bench_row(
        op="serve", direction="serve",
        mfu=serve_mfu,
        peak_hbm_bytes=max(peaks) if peaks else None,
        wire_dtype=scheduler.engine._kv_dtype or "fp",
        kv_cache_dtype=scheduler.engine._kv_dtype,
        kv_bytes_per_token=int(kv_bytes),
        requests=len(uids), completed=scheduler.completed,
        preemptions=scheduler.preemptions,
        peak_running=scheduler.peak_running,
        engine_steps=steps, wall_s=wall_s,
        tokens_total=toks,
        tokens_per_s_per_chip=toks / wall_s / n_chips if wall_s else 0.0,
        ttft_p50_ms=_pct(ttfts, 50) * 1e3 if ttfts else None,
        ttft_p99_ms=_pct(ttfts, 99) * 1e3 if ttfts else None,
        tbt_p50_ms=_pct(gaps, 50) * 1e3 if gaps else None,
        tbt_p99_ms=_pct(gaps, 99) * 1e3 if gaps else None)


# ---------------------------------------------------------------- smoke gate
def run_smoke(seed=0, print_fn=print):
    """The deterministic acceptance gate (wired into tier-1).  Returns a
    result dict with a top-level ``pass`` bool; see module docstring for
    the three sub-gates."""
    from deepspeed_tpu.serving import ServingScheduler

    rng = np.random.default_rng(seed)
    r = {}

    # gate 1 — continuous batching under deliberate KV starvation: 8
    # one-block prompts, 14 usable blocks, each request grows to 3 blocks
    # by completion (8×3 = 24 > 14) → admission backpressure + ≥1 LIFO
    # preemption, and every request must still complete.
    prompts = [rng.integers(1, 96, size=8).tolist() for _ in range(8)]
    # one-shot baseline on a ROOMY pool (generate has no preemption; each
    # sequence's greedy tokens depend only on its own prefix, so pool size
    # cannot change them)
    eng, _ = _tiny_engine(num_blocks=96, block_size=8, max_context=64,
                          max_seqs=12, seed=seed)
    ref = eng.generate(prompts, max_new_tokens=16)
    eng2, _ = _tiny_engine(num_blocks=15, block_size=8, max_context=64,
                           max_seqs=12, seed=seed)
    streams = {i: [] for i in range(len(prompts))}
    # optimistic admission (no decode reserve): all 8 go in flight at once
    # and the pool deliberately cannot hold them — preemption must engage
    sched = ServingScheduler(eng2, config=dict(kv_admit_reserve_tokens=0))
    for i, p in enumerate(prompts):
        sched.submit(p, max_new_tokens=16,
                     on_token=lambda t, d, i=i: streams[i].append(t))
    sched.drain()
    r["completed"] = sched.completed
    r["preemptions"] = sched.preemptions
    r["peak_running"] = sched.peak_running
    r["streams_match_generate"] = \
        [streams[i] for i in range(len(prompts))] == ref
    r["gate_preemption"] = (sched.completed == len(prompts)
                            and sched.preemptions >= 1
                            and sched.peak_running >= 8
                            and r["streams_match_generate"])

    # gate 2 — int8 paged-KV parity: greedy decode over ≥64 steps must be
    # token-identical to the fp cache (kv_codec per-head rowwise scales),
    # measured on the decisive-logits probe model (see probe_model)
    prompts64 = [rng.integers(1, 64, size=n).tolist() for n in (15, 6, 9)]
    eng_fp, _ = _tiny_engine(num_blocks=96, seed=seed, probe=True)
    out_fp = eng_fp.generate(prompts64, max_new_tokens=64)
    eng_q, _ = _tiny_engine(kv_dtype="int8", num_blocks=96, seed=seed,
                            probe=True)
    out_q = eng_q.generate(prompts64, max_new_tokens=64)
    r["int8_kv_token_identical"] = out_q == out_fp
    r["decode_steps_compared"] = min(len(o) for o in out_fp)

    # gate 3 — kv_cache_dtype unset serves bit-identically to the raw
    # engine loop (the scheduler is a policy layer, not a math layer)
    eng3, _ = _tiny_engine(num_blocks=96, seed=seed, probe=True)
    out_sched = ServingScheduler(eng3).serve(prompts64, max_new_tokens=64)
    r["unset_bit_identical"] = out_sched == out_fp

    r["pass"] = bool(r["gate_preemption"] and r["int8_kv_token_identical"]
                     and r["decode_steps_compared"] >= 64
                     and r["unset_bit_identical"])
    print_fn(f"serve smoke: completed={r['completed']}/8 "
             f"preemptions={r['preemptions']} "
             f"peak_running={r['peak_running']} "
             f"streams_match={r['streams_match_generate']}")
    print_fn(f"serve smoke: int8-KV parity over "
             f"{r['decode_steps_compared']} decode steps: "
             f"{r['int8_kv_token_identical']}; unset-dtype identical: "
             f"{r['unset_bit_identical']}")
    print_fn(f"serve smoke: {'PASS' if r['pass'] else 'FAIL'}")
    return r


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="deterministic CPU acceptance gate (tier-1)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=16.0,
                    help="Poisson arrival rate, requests/s (0 = all at t=0)")
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-dtype", default=None,
                    choices=("int8", "fp8"),
                    help="quantized paged-KV mode (unset = fp cache)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size (None = engine default sizing)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the ds_bench-schema row payload")
    args = ap.parse_args(argv)

    if args.smoke:
        r = run_smoke(seed=args.seed)
        if args.json:
            with open(args.json, "w") as f:
                json.dump({"smoke": r, "rows": []}, f, indent=2)
        return 0 if r["pass"] else 1

    from deepspeed_tpu.serving import ServingScheduler
    eng, _ = _tiny_engine(kv_dtype=args.kv_dtype,
                          num_blocks=args.num_blocks, seed=args.seed)
    sched = ServingScheduler(eng)
    plan = make_workload(args.requests, args.rate, args.seed, args.max_new)
    row = run_traffic(sched, plan)
    print(f"requests={row['requests']} completed={row['completed']} "
          f"preemptions={row['preemptions']} "
          f"peak_running={row['peak_running']} kv={row['wire_dtype']}")
    if row["ttft_p50_ms"] is not None:
        print(f"TTFT p50/p99: {row['ttft_p50_ms']:.1f} / "
              f"{row['ttft_p99_ms']:.1f} ms")
    if row["tbt_p50_ms"] is not None:
        print(f"TBT  p50/p99: {row['tbt_p50_ms']:.2f} / "
              f"{row['tbt_p99_ms']:.2f} ms")
    print(f"tokens/s/chip: {row['tokens_per_s_per_chip']:.0f} "
          f"({row['tokens_total']} tokens in {row['wall_s']:.2f}s)")
    if args.json:
        payload = {"bench": "serve", "seed": args.seed,
                   "rate_rps": args.rate, "rows": [row]}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote 1 row to {args.json}")
    if row["completed"] != row["requests"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
