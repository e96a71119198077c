"""Benchmark: Llama train-step throughput on the available accelerator.

Prints ONE json line: {"metric", "value", "unit", "vs_baseline"}.

Model: Llama-style causal LM sized to a single v5e chip (16G HBM), bf16,
full train step (fwd+bwd+Adam) through the DeepSpeedEngine.

MFU accounting: flops/token = 6N + 12·L·S·D (PaLM convention: 6N for the
matmuls fwd+bwd, attention quadratic term; remat recompute NOT credited).
``vs_baseline``: BASELINE.md's north-star target is ≥0.8× the per-chip MFU of
the A100+NCCL reference, for which no in-repo number exists; we take 50% MFU
as the A100 reference point (Ulysses blog reports >54% of peak as its best,
blogs/deepspeed-ulysses/README.md:82), so vs_baseline = MFU / 0.40 — 1.0 means
the 0.8× target is met.

One process, on a TPU: ``python bench.py [--mode MODE]`` runs the chosen
benchmark on the chip(s) jax finds, or exits non-zero when there is none — it
never falls back to a CPU, and an unknown ``device_kind`` is an error in the
one peak table (``profiling/cost_model.PEAK_FLOPS_BY_KIND``).  The compile
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to the checkout's
fixed ``.jax_cache`` (``utils/compile_cache.py``).
"""

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np


def _tpu_peak_flops() -> float:
    """Per-chip bf16 peak (MFU denominator) from THE peak table; an unknown
    ``device_kind`` raises."""
    from deepspeed_tpu.profiling import cost_model
    return cost_model.peak_flops_per_chip()


def _logt(msg: str):
    """Phase timestamps on stderr — when a run dies on a time limit, the
    stderr tail says which phase ate the budget."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _implausible(achieved_flops_per_sec: float, peak_flops: float) -> bool:
    """>100% of chip peak is physically impossible: the timing fence did not
    actually wait for execution (async-dispatch lie, see _host_sync)."""
    return achieved_flops_per_sec > peak_flops


def _untrustworthy(rec: dict):
    """Why a recorded bench line must not be cited/folded, or None if it is
    a full, plausible measurement.  Delegates to the package's shared trust
    gate (autotuning/priors.py) so tools/fold_sweeps.py and the tuner-priors
    loader can never diverge on what counts as trustworthy."""
    from deepspeed_tpu.autotuning.priors import untrustworthy
    return untrustworthy(rec)


def _host_sync(x):
    """Timing fence: round-trips one element of ``x`` (array or pytree)
    through the host, so the clock stops only when the computation that
    produced it has really finished.  Indexing down to one element keeps
    the transfer at a few bytes."""
    import jax
    leaf = jax.tree_util.tree_leaves(x)[0]
    if getattr(leaf, "ndim", 0):
        leaf = leaf[(0,) * leaf.ndim]
    return np.asarray(jax.device_get(leaf))


def run_bench(on_tpu: bool) -> dict:
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu.comm as dist

    backend = jax.default_backend()
    # (B, remat, policy) candidates, fastest first: measured on v5e-16G,
    # remat-off at B=4 gives ~0.39 MFU vs ~0.33 for B=8+full-remat (recompute
    # is not credited); larger B OOMs without remat, so fall back on
    # ResourceExhausted.
    n_layers = int(os.environ.get("BENCH_LAYERS", "8"))
    if on_tpu:
        attempts = [(4, False, "none"), (8, True, "nothing_saveable")]
        if os.environ.get("BENCH_BATCH"):
            b = int(os.environ["BENCH_BATCH"])
            attempts = [(b, False, "none")] + attempts
        S = int(os.environ.get("BENCH_SEQ", "2048"))
        steps, warmup = int(os.environ.get("BENCH_STEPS", "10")), 2
        peak_flops = _tpu_peak_flops()
    else:  # CPU smoke mode (sanity only)
        attempts = [(4, False, "none")]
        S, steps, warmup = 64, 3, 1
        peak_flops = 1e12

    for B, remat, policy in attempts:
        try:
            if on_tpu:
                cfg = llama.LlamaConfig(
                    vocab_size=32000, hidden_size=2048, intermediate_size=5504,
                    num_hidden_layers=n_layers, num_attention_heads=16,
                    num_key_value_heads=16,
                    max_position_embeddings=max(2048, S),
                    dtype="bfloat16", remat=remat, remat_policy=policy,
                    # bf16 logits matmul: the fp32 head runs the [B*S,D]×
                    # [D,32k] matmul at the slow MXU rate (CE upcasts to
                    # fp32 for logsumexp regardless)
                    head_dtype=os.environ.get("BENCH_HEAD_DTYPE",
                                              "bfloat16"),
                    # fused head+loss in chunks of rows (no [B,S,V]
                    # logits); 6400 of V=32000: a fifth of the rows a chunk
                    loss_chunk_vocab=int(os.environ.get("BENCH_LOSS_CHUNK",
                                                        "0")))
            else:
                cfg = llama.llama_tiny(dtype="float32", remat=False)
            model = llama.LlamaModel(cfg)
            bench_cfg = {
                "train_micro_batch_size_per_gpu": B,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "fusedadam", "params": {"lr": 1e-4}},
                "bf16": {"enabled": on_tpu},
                "zero_optimization": {"stage": 0},
            }
            if os.environ.get("BENCH_GRAD_DTYPE"):  # on-chip sweep knob
                bench_cfg["data_types"] = {
                    "grad_accum_dtype": os.environ["BENCH_GRAD_DTYPE"]}
            if os.environ.get("BENCH_TRACE", "0") != "0":
                # archive step traces next to the BENCH_*.json record so a
                # headline number can be decomposed with trace_report.py
                # (fence OFF: tracing must not change what is measured)
                trace_dir = os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "chiprun_out", f"bench_trace_{backend}")
                bench_cfg["telemetry"] = {"enabled": True,
                                          "trace_dir": trace_dir}
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=model, config=bench_cfg)

            rng = np.random.default_rng(0)
            ids = rng.integers(0, cfg.vocab_size, size=(B, S)).astype(np.int32)
            _logt(f"engine built (B={B} layers={cfg.num_hidden_layers} "
                  f"remat={remat}); initializing params…")
            engine.initialize_parameters(0, ids, ids)
            _host_sync(engine.params)
            _logt("params initialized; warmup (train-step compile)…")

            def one_step():
                loss = engine(ids, ids)
                engine.backward(loss)
                engine.step()
                return loss

            tw = time.perf_counter()
            one_step()
            _host_sync(engine.params)
            _logt(f"warmup step 1 (compile) done in "
                  f"{time.perf_counter()-tw:.1f}s")
            tw = time.perf_counter()
            for _ in range(warmup - 1):
                one_step()
            _host_sync(engine.params)
            warm_step = ((time.perf_counter() - tw) / max(1, warmup - 1))
            _logt(f"warmup done; steady step ≈ {warm_step*1000:.0f}ms")
            break
        except Exception as e:  # OOM → next (smaller-footprint) config
            if "RESOURCE_EXHAUSTED" not in str(e) or \
                    (B, remat, policy) == attempts[-1]:
                raise
            # drop every reference to the failed attempt's device buffers
            # BEFORE the retry allocates, or both copies coexist and the
            # fallback OOMs too
            engine = model = ids = None
            import gc
            gc.collect()
            groups.reset_mesh()
            dist.destroy_process_group()
            continue

    n_params = llama.param_count(cfg)
    flops_per_token = 6 * n_params + 12 * cfg.num_hidden_layers * S * cfg.hidden_size

    def record(step_time, note=""):
        tokens_per_sec = B * S / step_time
        mfu = tokens_per_sec * flops_per_token / peak_flops
        if _implausible(mfu * peak_flops, peak_flops):
            # mark the record so _untrustworthy() refuses to keep/fold it
            note += " [timing-implausible]"
        return {
            "metric": "llama_train_tokens_per_sec_per_chip",
            "value": round(tokens_per_sec, 1),
            "unit": f"tokens/s (B={B} S={S} params={n_params/1e6:.0f}M "
                    f"step={step_time*1000:.0f}ms MFU={mfu:.3f} "
                    f"backend={backend}{note})",
            "vs_baseline": round(mfu / 0.40, 3),
        }

    if on_tpu and warm_step > 0:
        # provisional record NOW: if a time limit kills the timed loop
        # below, the last stdout JSON line is still a real-chip number
        print(json.dumps(record(warm_step, " [warmup-estimate]")), flush=True)

    done = 0
    rec = None
    best = None  # best (min) per-chunk step time: host-side latency spikes
    #              are additive positive noise, so min-over-chunks is the
    #              estimator of the device step time
    schedule = ([1, 2, 3] if on_tpu else [steps])
    while sum(schedule) < steps:
        schedule.append(min(4, steps - sum(schedule)))
    for chunk in schedule:
        chunk = min(chunk, steps - done)
        if chunk <= 0:
            break
        tc = time.perf_counter()
        for _ in range(chunk):
            one_step()
        _host_sync(engine.params)
        per_step = (time.perf_counter() - tc) / chunk
        best = per_step if best is None else min(best, per_step)
        done += chunk
        rec = record(best, (f" chunks_done={done}/{steps}"
                            if done >= steps else
                            f" [partial {done}/{steps}]"))
        if on_tpu and done < steps:
            print(json.dumps(rec), flush=True)
            _logt(f"measured {done}/{steps} steps "
                  f"(chunk {per_step*1e3:.0f}ms/step, best "
                  f"{best*1e3:.0f}ms)")
    from deepspeed_tpu import telemetry as _tel
    if _tel.enabled:
        _tel.shutdown()   # flush trace.json/steps.jsonl now, not at atexit
    return rec


def _count_params(tree) -> int:
    import jax
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def _hbm_stats() -> dict:
    """Device memory stats where the backend exposes them (TPU does)."""
    import jax
    try:
        st = jax.local_devices()[0].memory_stats() or {}
        return {k: int(v) for k, v in st.items()
                if k in ("bytes_in_use", "peak_bytes_in_use",
                         "bytes_limit")}
    except Exception:
        return {}


def run_gpt2_bench(on_tpu: bool) -> dict:
    """BASELINE.json config 2: GPT-2 350M fp16 ZeRO-1 + FusedAdam."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2

    if on_tpu:
        cfg = gpt2.gpt2_350m(
            dtype="float16",
            remat=os.environ.get("BENCH_GPT2_REMAT", "1") != "0",
            loss_chunk_vocab=int(os.environ.get("BENCH_LOSS_CHUNK", "0")))
        B, S, steps, warmup = 8, 1024, 10, 2
        peak_flops = _tpu_peak_flops()
    else:
        cfg = gpt2.gpt2_tiny(dtype="float32", remat=False)
        B, S, steps, warmup = 4, 64, 3, 1
        peak_flops = 1e12
    model = gpt2.GPT2Model(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": B,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "fusedadam", "params": {"lr": 1e-4}},
                "fp16": {"enabled": on_tpu, "initial_scale_power": 16},
                "zero_optimization": {"stage": 1}})
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(B, S)).astype(np.int32)
    _logt("gpt2: initializing params…")
    engine.initialize_parameters(0, ids, ids)

    def one():
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()

    for i in range(warmup):
        one()
        _host_sync(engine.params)
        _logt(f"gpt2: warmup step {i+1} done")
    t0 = time.perf_counter()
    for _ in range(steps):
        one()
    _host_sync(engine.params)
    step_time = (time.perf_counter() - t0) / steps
    n = _count_params(engine.params)
    tps = B * S / step_time
    flops_per_token = 6 * n + 12 * cfg.num_hidden_layers * S * cfg.hidden_size
    mfu = tps * flops_per_token / peak_flops
    bad = (" [timing-implausible]"
           if _implausible(mfu * peak_flops, peak_flops) else "")
    return {
        "metric": "gpt2_350m_fp16_zero1_tokens_per_sec",
        "value": round(tps, 1),
        "unit": f"tokens/s (B={B} S={S} params={n/1e6:.0f}M "
                f"step={step_time*1000:.0f}ms MFU={mfu:.3f} "
                f"backend={jax.default_backend()}{bad})",
        "vs_baseline": round(mfu / 0.40, 3),
    }


def run_offload_bench(on_tpu: bool) -> dict:
    """BASELINE.json config 4 analog (+ docs/_pages/training.md:302 '13B on
    one 32G V100'): the largest Llama trainable on ONE chip.

    Round 4: ZeRO-Infinity param STREAMING (``offload_param``) — params,
    fp32 master and moments are host/NVMe-resident; the chip holds ≤3
    blocks + activations, and the optimizer step runs on the host CPU
    kernels.  Falls back to the optimizer-state-only offload (FusedLamb)
    if the streaming path fails.  vs_baseline = params / 13B pro-rata to
    the reference's 13B-on-32G claim (one v5e has 16G)."""
    import gc
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu.comm as dist

    swap_dir = os.environ.get("BENCH_NVME_PATH",
                              os.path.join(tempfile.gettempdir(),
                                           "ds_bench_swap"))
    if on_tpu:
        # descending param counts per mode; first that completes a step
        # wins.  stream: host budget ~14 bytes/param RAM (fp32 master+m+v +
        # bf16 cache) + bf16 grad stash ⇒ ~7B fits the 125G host.
        # state-only: bf16 params+grads must fit 16G HBM ⇒ ≤ ~3B.
        # stream candidates may pin the optimizer-state device: the 6.7B
        # model's fp32 master+moments (~80G) beat this box's ~79G free disk
        # but fit its 126G RAM next to the 13.4G bf16 cache — try all-RAM
        # first, then the NVMe-state variants at descending size
        ladders = {
            "stream": [
                dict(hidden_size=4096, intermediate_size=11008,
                     num_hidden_layers=32, num_attention_heads=32,
                     _state_dev="cpu"),                              # ~6.7B
                dict(hidden_size=4096, intermediate_size=11008,
                     num_hidden_layers=16, num_attention_heads=32),  # ~3.7B
                dict(hidden_size=3072, intermediate_size=8192,
                     num_hidden_layers=16, num_attention_heads=24),  # ~2.0B
            ],
            "state-only": [
                dict(hidden_size=3072, intermediate_size=8192,
                     num_hidden_layers=26, num_attention_heads=24),  # ~3.1B
                dict(hidden_size=2560, intermediate_size=6912,
                     num_hidden_layers=24, num_attention_heads=20),  # ~2.1B
                dict(hidden_size=2048, intermediate_size=5504,
                     num_hidden_layers=22, num_attention_heads=16),  # ~1.3B
            ],
        }
        B, S, steps = 1, 1024, 2
    else:
        tiny = [dict(hidden_size=64, intermediate_size=128,
                     num_hidden_layers=2, num_attention_heads=4)]
        ladders = {"stream": tiny, "state-only": tiny}
        B, S, steps = 2, 64, 2

    last_exc = None
    for mode in ("stream", "state-only"):
        candidates = ladders[mode]
        for cand in candidates:
            try:
                cand = dict(cand)
                state_dev = cand.pop("_state_dev", "nvme")
                cfg = llama.LlamaConfig(
                    vocab_size=32000, num_key_value_heads=cand[
                        "num_attention_heads"],
                    max_position_embeddings=S,
                    dtype="bfloat16" if on_tpu else "float32",
                    remat=(on_tpu and mode == "state-only"),
                    remat_policy="nothing_saveable", **cand)
                model = llama.LlamaModel(cfg)
                zero = {"stage": 3}
                if mode == "stream":
                    zero["offload_param"] = {"device": "cpu"}
                    zero["offload_optimizer"] = {"device": state_dev,
                                                 "nvme_path": swap_dir}
                    opt = {"type": "fusedadam", "params": {"lr": 1e-4}}
                else:
                    zero["offload_optimizer"] = {"device": "nvme",
                                                 "nvme_path": swap_dir}
                    opt = {"type": "fusedlamb", "params": {"lr": 1e-4}}
                engine, _, _, _ = deepspeed_tpu.initialize(
                    model=model,
                    config={"train_micro_batch_size_per_gpu": B,
                            "gradient_accumulation_steps": 1,
                            "optimizer": opt,
                            "bf16": {"enabled": on_tpu},
                            "zero_optimization": zero})
                rows = B * engine.dp_world_size
                ids = np.random.default_rng(0).integers(
                    0, cfg.vocab_size, size=(rows, S)).astype(np.int32)
                _logt(f"offload[{mode}]: init "
                      f"{llama.param_count(cfg)/1e9:.2f}B params…")
                engine.initialize_parameters(0, ids, ids)

                def one():
                    loss = engine(ids, ids)
                    engine.backward(loss)
                    engine.step()
                    return loss

                loss = one()
                _host_sync(loss)
                _logt(f"offload[{mode}]: warm step done")
                t0 = time.perf_counter()
                for _ in range(steps):
                    loss = one()
                _host_sync(loss)
                step_time = (time.perf_counter() - t0) / steps
                n = llama.param_count(cfg)
                stats = _hbm_stats()
                if mode == "stream":
                    offloaded = (engine.hbm_param_bytes() == 0
                                 and engine.params is None)
                    kind = (f"param_streaming max_resident_blocks="
                            f"{engine.max_resident_blocks}")
                else:
                    offloaded = bool(getattr(engine, "_state_on_nvme",
                                             False)) and \
                        engine.master is None
                    kind = "fusedlamb state_only"
                return {
                    "metric":
                        "max_model_one_chip_nvme_offload_tokens_per_sec",
                    "value": round(rows * S / step_time, 1),
                    "unit": (f"tokens/s (params={n/1e9:.2f}B B={rows} S={S} "
                             f"step={step_time*1000:.0f}ms {kind} "
                             f"state_offloaded={offloaded} "
                             f"hbm_peak="
                             f"{stats.get('peak_bytes_in_use', 0)/2**30:.1f}G "
                             f"backend={jax.default_backend()})"),
                    "vs_baseline": round(n / 13e9, 3),
                }
            except Exception as e:
                # OOM → next smaller candidate; other errors → next mode
                # (the streaming path degrades to state-only, never silently)
                last_exc = e
                _logt(f"offload[{mode}] candidate failed: "
                      f"{type(e).__name__}: {str(e)[:200]}")
                engine = model = None
                gc.collect()
                groups.reset_mesh()
                dist.destroy_process_group()
                # device OOM, host OOM, or disk-full (the 6.7B candidate
                # needs ~80G of NVMe swap; this box has ~79G free) → next
                # (smaller) candidate; anything else is a real failure →
                # next mode's ladder
                if "RESOURCE_EXHAUSTED" not in str(e) and \
                        not isinstance(e, (MemoryError, OSError)):
                    break
    raise RuntimeError(
        "all offload candidates failed on both modes") from last_exc


def run_bert_bench(on_tpu: bool) -> dict:
    """BASELINE.md row 'BERT-Large pretraining kernel throughput': 64 TFLOPS
    @ seq128 (272 samples/s) on one V100.  Same model shape here (BERT-Large
    MLM, seq 128, bf16, ZeRO-0 + FusedAdam); vs_baseline = achieved TFLOPS /
    the reference's 64 — ≥1.0 beats the V100 number outright."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import bert

    if on_tpu:
        cfg = bert.bert_large(dtype="bfloat16",
                              max_position_embeddings=128)
        B, S, steps, warmup = 64, 128, 10, 2
    else:
        cfg = bert.bert_tiny(dtype="float32")
        B, S, steps, warmup = 4, 32, 2, 1
    model = bert.BertModel(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": B,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "fusedadam", "params": {"lr": 1e-4}},
                "bf16": {"enabled": on_tpu},
                "zero_optimization": {"stage": 0}})
    rng = np.random.default_rng(0)
    rows = B * engine.dp_world_size
    ids = rng.integers(0, cfg.vocab_size, size=(rows, S)).astype(np.int32)
    labels = np.where(rng.random((rows, S)) < 0.15, ids, -100).astype(np.int32)
    _logt("bert: initializing params…")
    engine.initialize_parameters(0, ids, labels)

    def one():
        loss = engine(ids, labels)
        engine.backward(loss)
        engine.step()
        return loss

    for i in range(warmup):
        one()
        _host_sync(engine.params)
        _logt(f"bert: warmup step {i+1} done")
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = one()
    _host_sync(engine.params)
    step_time = (time.perf_counter() - t0) / steps
    n = _count_params(engine.params)
    samples_per_sec = rows / step_time
    # 6N per token fwd+bwd + attention quadratic term (PaLM convention)
    flops_per_token = 6 * n + 12 * cfg.num_hidden_layers * S * cfg.hidden_size
    tflops = samples_per_sec * S * flops_per_token / 1e12
    bad = (" [timing-implausible]"
           if on_tpu and _implausible(tflops * 1e12, _tpu_peak_flops())
           else "")
    return {
        "metric": "bert_large_seq128_tflops",
        "value": round(tflops, 1),
        "unit": (f"TFLOPS ({samples_per_sec:.0f} samples/s B={rows} S={S} "
                 f"params={n/1e6:.0f}M step={step_time*1000:.0f}ms "
                 f"backend={jax.default_backend()}; reference V100: "
                 f"64 TFLOPS / 272 samples/s){bad}"),
        "vs_baseline": round(tflops / 64.0, 3),
    }


def run_hostopt_bench(on_tpu: bool) -> dict:
    """A/B the host-side optimizer step for NVMe optimizer-state offload:
    same model/config, DS_TPU_HOST_OFFLOAD_STEP=1 (grads down + params up,
    host SIMD Adam) vs =0 (fp32 master+moments HBM round-trip + device
    apply).  Reports both step times and the analytic bytes/param."""
    import gc
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu.comm as dist

    swap_dir = os.environ.get("BENCH_NVME_PATH",
                              os.path.join(tempfile.gettempdir(),
                                           "ds_bench_swap_ab"))
    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5504,
            num_hidden_layers=16, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=1024,
            dtype="bfloat16", remat=True, remat_policy="nothing_saveable")
        B, S, steps = 1, 1024, 3
    else:
        cfg = llama.llama_tiny(dtype="float32", remat=False)
        B, S, steps = 2, 64, 2

    times = {}
    engine = None
    for host_flag in ("1", "0"):
        os.environ["DS_TPU_HOST_OFFLOAD_STEP"] = host_flag
        engine = None   # release the previous leg's HBM before rebuilding
        groups.reset_mesh()
        dist.destroy_process_group()
        gc.collect()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=llama.LlamaModel(cfg),
            config={"train_micro_batch_size_per_gpu": B,
                    "gradient_accumulation_steps": 1,
                    "optimizer": {"type": "fusedadam",
                                  "params": {"lr": 1e-4}},
                    "bf16": {"enabled": on_tpu},
                    "zero_optimization": {
                        "stage": 2,
                        "offload_optimizer": {"device": "nvme",
                                              "nvme_path": swap_dir}}})
        rows = B * engine.dp_world_size
        ids = np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=(rows, S)).astype(np.int32)
        engine.initialize_parameters(0, ids, ids)

        def one():
            loss = engine(ids, ids)
            engine.backward(loss)
            engine.step()
            return loss

        _host_sync(one())
        _logt(f"hostopt[{host_flag}]: warm step done "
              f"(host_steps={getattr(engine, 'host_offload_steps', 0)})")
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = one()
        _host_sync(loss)
        times[host_flag] = (time.perf_counter() - t0) / steps
        engaged = getattr(engine, "host_offload_steps", 0)
        if host_flag == "1" and engaged == 0:
            raise RuntimeError("host offload step did not engage")
    os.environ.pop("DS_TPU_HOST_OFFLOAD_STEP", None)
    n = llama.param_count(cfg)
    speedup = times["0"] / times["1"]
    return {
        "metric": "host_optimizer_step_speedup",
        "value": round(speedup, 3),
        "unit": (f"device-apply/host-step step-time ratio "
                 f"(host={times['1']*1e3:.0f}ms device={times['0']*1e3:.0f}ms"
                 f" params={n/1e6:.0f}M; device traffic/step: host path "
                 f"≈6B/param (fp32 grads down + bf16 params up) vs device "
                 f"path ≈24B/param (fp32 master+2 moments both ways) "
                 f"backend={jax.default_backend()})"),
        "vs_baseline": round(speedup, 3),
    }


def run_fpdt_bench(on_tpu: bool) -> dict:
    """FPDT host-offload streaming at long context: tokens/s prefill rate
    and (on TPU) the flat-HBM evidence — pinned_host chunk residency +
    peak HBM."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.sequence import FPDTHostOffloadAttention
    from deepspeed_tpu.sequence.fpdt_layer import _host_sharding

    if on_tpu:
        B, H, D, CHUNK, TOTAL = 1, 8, 128, 8192, 131072
    else:
        B, H, D, CHUNK, TOTAL = 1, 1, 16, 2048, 16384
    rng = np.random.default_rng(0)
    attn = FPDTHostOffloadAttention(chunk_size=CHUNK)
    blk = jnp.asarray(rng.standard_normal((B, CHUNK, H, D)) * 0.1,
                      jnp.bfloat16 if on_tpu else jnp.float32)
    # compile BOTH executables: the causal tail (1st attend) and the
    # causal=False streamed-chunk merge (2nd attend sees a cached chunk)
    _logt("fpdt: compiling tail + merge executables…")
    attn.attend(blk, k_new=blk, v_new=blk)
    attn.attend(blk, k_new=blk, v_new=blk)
    attn.reset()
    _logt("fpdt: compile done; streaming…")

    def stream(double_buffer):
        attn.reset()
        attn.double_buffer = double_buffer
        t0 = time.perf_counter()
        for _ in range(TOTAL // CHUNK):
            out = attn.attend(blk, k_new=blk, v_new=blk)
        _host_sync(out)
        return time.perf_counter() - t0

    dt_sync = stream(False)   # sync-fetch reference
    dt = stream(True)         # prefetch-ahead pipeline (the shipped default)
    resident = "n/a"
    if _host_sharding() is not None:
        resident = all(c.k.sharding.memory_kind == "pinned_host"
                       for c in attn.chunks)
    stats = _hbm_stats()
    return {
        "metric": "fpdt_stream_tokens_per_sec",
        "value": round(TOTAL / dt, 1),
        "unit": (f"tokens/s (context={TOTAL} chunk={CHUNK} H={H} D={D} "
                 f"host_resident={resident} "
                 f"db_speedup={dt_sync / dt:.3f}x "
                 f"hbm_peak={stats.get('peak_bytes_in_use', 0)/2**30:.2f}G "
                 f"backend={jax.default_backend()})"),
        "vs_baseline": 0.0,  # no in-repo reference number (BASELINE.md)
    }


def run_serve_bench(on_tpu: bool) -> dict:
    """FastGen-v2 serving throughput: continuous batching over the ragged
    engine with the paged KV cache (reference FastGen headline is effective
    tokens/s; BASELINE.md row 'FastGen serving')."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import llama, mixtral
    from deepspeed_tpu.inference.v2 import InferenceEngineV2

    moe = os.environ.get("DS_SERVE_MODEL") == "mixtral"
    if on_tpu:
        if moe:  # sparse top-2 MoE serving leg (ragged_dot expert FFN)
            cfg = mixtral.MixtralConfig(
                vocab_size=32000, hidden_size=1024, intermediate_size=2816,
                num_hidden_layers=6, num_attention_heads=16,
                num_key_value_heads=8, max_position_embeddings=2048,
                num_local_experts=8, num_experts_per_tok=2,
                dtype="bfloat16", remat=False)
        else:
            cfg = llama.LlamaConfig(
                vocab_size=32000, hidden_size=2048, intermediate_size=5504,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=16, max_position_embeddings=2048,
                dtype="bfloat16", remat=False)
        n_seqs, prompt_len, new_tokens = 32, 256, 64
        sm = dict(max_tracked_sequences=64, max_ragged_batch_size=512,
                  max_ragged_sequence_count=64, max_context=1024,
                  block_size=128)
    else:
        cfg = (mixtral.mixtral_tiny(dtype="float32", remat=False) if moe
               else llama.llama_tiny(dtype="float32", remat=False))
        n_seqs, prompt_len, new_tokens = 4, 16, 8
        sm = dict(max_tracked_sequences=8, max_ragged_batch_size=64,
                  max_ragged_sequence_count=8, max_context=128,
                  block_size=16, num_blocks=40)
    econf = dict(dtype=cfg.dtype, state_manager=sm)
    if os.environ.get("DS_SERVE_BURST") is not None:  # A/B fused decode
        econf["decode_burst"] = int(os.environ["DS_SERVE_BURST"])

    model = (mixtral.MixtralModel(cfg) if moe else llama.LlamaModel(cfg))
    rng = np.random.default_rng(0)
    ids0 = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids0)["params"]
    eng = InferenceEngineV2(model, params=params, config=econf)
    prompts = [rng.integers(0, cfg.vocab_size, size=prompt_len).tolist()
               for _ in range(n_seqs)]
    # warmup with the SAME max_new_tokens as the timed run: the burst
    # executors are static in k, and the k schedule is a function of
    # remaining tokens — an identical generation length compiles exactly
    # the programs the timed loop will replay (2 seqs suffice: the step is
    # shape-static in the token budget, not the sequence count)
    _logt("serve: warmup generate (compile prefill+decode+burst)…")
    eng.generate(prompts[:2], max_new_tokens=new_tokens)
    eng.flush(range(2))
    _logt("serve: warmup done; timed generate…")
    t0 = time.perf_counter()
    out = eng.generate(prompts, max_new_tokens=new_tokens)
    dt = time.perf_counter() - t0
    generated = sum(len(o) for o in out)
    effective = generated + n_seqs * prompt_len  # FastGen headline counts
    #                                              prompt processing too
    return {
        "metric": ("fastgen_serve_moe_tokens_per_sec" if moe else "fastgen_serve_tokens_per_sec"),
        "value": round(generated / dt, 1),
        "unit": (f"generated tokens/s (effective={effective / dt:.0f} "
                 f"incl. prompts; seqs={n_seqs} prompt={prompt_len} "
                 f"new={new_tokens} "
                 f"burst_steps={getattr(eng, 'burst_steps', 0)} "
                 f"backend={jax.default_backend()})"),
        "vs_baseline": 0.0,  # no in-repo reference number (BASELINE.md)
    }


MODES = {
    "train": run_bench,
    "gpt2": run_gpt2_bench,
    "offload": run_offload_bench,
    "fpdt": run_fpdt_bench,
    "hostopt": run_hostopt_bench,
    "bert": run_bert_bench,
    "serve": run_serve_bench,
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", default="train", choices=sorted(MODES))
    args = ap.parse_args(argv)

    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"bench.py measures on a TPU only; jax found platform "
              f"{dev.platform!r} ({dev.device_kind}) — no result",
              file=sys.stderr)
        return 2
    _logt(f"mode={args.mode} device={device}")
    rec = MODES[args.mode](True)
    rec["device"] = device
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
