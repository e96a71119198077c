#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at the
full WIDTH of Llama-2-7B (``models/llama.py`` ``llama_7b``: hidden 4096, FFN
11008, 32 heads x 128, vocab 32000).  DEPTH is the only cut (2-8 of the 32
layers, sized to the HBM of the chips found) and the weights are random, made
from a seed.

* Phase A, training: ``deepspeed_tpu.initialize`` -> ``initialize_parameters``
  -> ``engine(ids, ids); engine.backward(loss); engine.step()`` — bf16,
  FusedAdam, ZeRO stage 3 over every local chip, S=2048, no remat.
* Phase B, serving: ``serving.build_serving_engine`` -> ``submit(on_token=)``
  / ``drain`` on one chip, checked against one-shot ``engine.generate``.

One process, the only one that touches JAX.  Refuses to run on anything but a
TPU.  Any failed assertion or exception in a phase is a non-zero exit; every
WARNING-or-above log record of the run must be on ``ALLOWED_WARNINGS`` below.
The last line of stdout is ``{"ok": true, "device": {...}}``.

The phase functions take their sizes as arguments so a tier-1 test can call
them at ``llama_tiny`` size on the CPU mesh; the command itself never runs on
a CPU.  Times and bytes printed here are information, not metrics.
"""

import gc
import json
import logging
import re
import sys
import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
from jax import monitoring

#: (regex, reason) — the only WARNING-or-above records a passing run emits.
ALLOWED_WARNINGS = ()

#: depth and per-chip micro-batch of Phase A by chip count: ~16-18 B/param of
#: bf16 params + fp32 master/moments/grads must fit 16 GB per chip beside
#: the S=2048 activations.
TRAIN_SIZES = {1: dict(layers=2, micro_batch=2),
               4: dict(layers=8, micro_batch=2)}
SERVE_LAYERS = 8

_MOSAIC_CALL = re.compile(r'custom_call_target="tpu_custom_call"')


# ------------------------------------------------------------- observation
class WarningCollector(logging.Handler):
    """Collects WARNING-or-above records of the repo's and jax's loggers —
    the warn-and-carry-on sites must not hide a degraded run."""

    LOGGERS = ("DeepSpeedTPU", "jax")

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records = []

    def emit(self, record):
        self.records.append(f"{record.name}: {record.getMessage()}")

    def __enter__(self):
        for name in self.LOGGERS:
            logging.getLogger(name).addHandler(self)
        return self

    def __exit__(self, *exc):
        for name in self.LOGGERS:
            logging.getLogger(name).removeHandler(self)

    def unexpected(self, allowed=ALLOWED_WARNINGS):
        return [r for r in self.records
                if not any(re.search(pat, r) for pat, _ in allowed)]


class CompileLog:
    """Every XLA backend compile of the process, in order, with what the
    persistent cache said about it (``hit`` / ``miss`` / ``off``)."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _VERDICTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}

    def __init__(self):
        self.events = []        # (fun_name, seconds, verdict)
        self._verdict = "off"

    def _on_event(self, event, **kw):
        if event in self._VERDICTS:
            self._verdict = self._VERDICTS[event]

    def _on_duration(self, event, secs, **kw):
        if event == self._BACKEND:
            self.events.append((kw.get("fun_name", "?"), secs,
                                self._verdict))
            self._verdict = "off"

    def __enter__(self):
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_event_duration_listener(self._on_duration)

    def mark(self):
        return len(self.events)

    def since(self, mark):
        return self.events[mark:]

    def report(self, mark, title, big_secs=1.0):
        evs = self.since(mark)
        hits = sum(v == "hit" for _, _, v in evs)
        print(f"[{title}] {len(evs)} programs compiled or loaded: "
              f"{hits} cache hits, "
              f"{sum(v == 'miss' for _, _, v in evs)} misses, "
              f"{sum(s for _, s, _ in evs):.1f}s in the backend")
        for name, secs, verdict in evs:
            if secs >= big_secs or verdict != "hit":
                print(f"    {verdict:4s} {secs:7.2f}s  {name}")


def _device_memory():
    return [d.memory_stats() or {} for d in jax.local_devices()]


# ------------------------------------------------------------------ phase A
def phase_train(cfg, compiles, *, micro_batch, seq_len, steady_steps=5,
                lr=1e-4, mosaic_calls_per_layer=3,
                collectives=("all-gather", "reduce-scatter"), seed=0):
    """One compile step + ``steady_steps`` steps of the 3-call loop on a
    fixed seeded batch, ZeRO-3 over all devices.  Returns a dict of what was
    observed; raises AssertionError on any broken invariant.
    ``compiles``: the live :class:`CompileLog`.
    ``mosaic_calls_per_layer``: expected ``tpu_custom_call``s per layer in
    the compiled micro-step (flash fwd, dq, dk+dv); 0 where the kernels are
    interpreted (CPU tests).  ``collectives``: substrings the compiled
    micro-step must hold on > 1 device (the TPU compiler prints the grad
    reduce-scatter as an ``all-reduce-scatter`` fusion; the CPU one as
    all-reduce + slice)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.profiling import cost_model

    cost_model.reset()      # the registry is process-wide; this phase's only
    n = jax.device_count()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=llama.LlamaModel(cfg),
        tp_rules=llama.tp_rules(cfg),   # pins where the ZeRO-3 shard lands
        config={
            "train_micro_batch_size_per_gpu": micro_batch,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "fusedadam", "params": {"lr": lr}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 3},
            "mesh": {"dp": n},
        })
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, size=(micro_batch * n, seq_len)).astype(np.int32)

    def one_step():
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        return float(loss)     # waits for the device

    t0 = time.perf_counter()
    engine.initialize_parameters(seed, ids, ids)
    jax.block_until_ready(engine.params)
    init_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    losses = [one_step()]
    jax.block_until_ready(engine.params)
    compile_step_s = time.perf_counter() - t0

    mark = compiles.mark()
    step_s = []
    for _ in range(steady_steps):
        t0 = time.perf_counter()
        losses.append(one_step())
        jax.block_until_ready(engine.params)
        step_s.append(time.perf_counter() - t0)
    late = compiles.since(mark)

    assert all(np.isfinite(l) for l in losses), f"non-finite loss: {losses}"
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert not late, f"compilation after the warm-up step: {late}"

    # the executable that trained, as the cost-model registry holds it
    micro = [p for p in cost_model.registry().programs()
             if p.name.startswith("train/micro_step")]
    assert len(micro) == 1, [p.name for p in micro]
    hlo = micro[0].compiled.as_text()
    mosaic = len(_MOSAIC_CALL.findall(hlo))
    want = mosaic_calls_per_layer * cfg.num_hidden_layers
    assert mosaic == want, (
        f"{micro[0].name} holds {mosaic} Mosaic calls, expected {want} — "
        "flash attention is not what was compiled")

    threshold = engine.plan.min_partition_size
    state = {"params": engine.params, "master": engine.master,
             "opt_state": engine.opt_state}
    sharded = 0
    if n > 1:
        devices = set(jax.devices())
        for path, leaf in jax.tree_util.tree_leaves_with_path(state):
            if getattr(leaf, "size", 0) < threshold:
                continue
            where = {s.device for s in leaf.addressable_shards}
            assert where == devices and \
                not leaf.sharding.is_fully_replicated, (
                    f"{jax.tree_util.keystr(path)} {leaf.shape} is not "
                    f"partitioned over all {n} devices: {leaf.sharding}")
            sharded += 1
        assert sharded, "no state leaf above the persistence threshold"
        for op in collectives:
            assert op in hlo, f"{micro[0].name} holds no {op}"
    mem = _device_memory()
    if all("bytes_in_use" in m for m in mem):    # the CPU backend has none
        assert all(m["bytes_in_use"] > 0 for m in mem), mem

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(engine.params))
    result = {
        "devices": n, "layers": cfg.num_hidden_layers,
        "params_m": round(n_params / 1e6, 1),
        "micro_batch_per_chip": micro_batch, "seq_len": seq_len,
        "program": micro[0].name, "mosaic_calls": mosaic,
        "sharded_state_leaves": sharded,
        "losses": [round(l, 4) for l in losses],
        "init_s": round(init_s, 1),
        "compile_step_s": round(compile_step_s, 1),
        "steady_step_ms": [round(1e3 * s, 1) for s in step_s],
        "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in mem],
        "bytes_limit": [m.get("bytes_limit") for m in mem],
    }
    # give the HBM back before the next phase
    del engine, state
    gc.collect()
    return result


# ------------------------------------------------------------------ phase B
def probe_params(model, seed=0, alpha=48.0, beta=8.0, shift=17):
    """Seeded bf16 weights with DECISIVE greedy margins: scaled identity
    embeddings put the last token's coordinate far above what the
    (random-init, fully exercised) attention/MLP blocks add, and a
    permutation lm_head maps it to a shifted next token.  Random-init logits at this width are nearly
    flat, so argmax would flip with batch composition; with these weights a
    token mismatch means the path is broken, not that a coin landed
    otherwise.  Only token ids below hidden_size take part."""
    cfg = model.config
    d = cfg.hidden_size
    assert cfg.vocab_size >= d and not cfg.tie_word_embeddings

    def build(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), dict(params))
        eye = jnp.arange(d)
        params["embed_tokens"] = {"embedding": jnp.zeros(
            (cfg.vocab_size, d), jnp.bfloat16).at[eye, eye].set(alpha)}
        params["lm_head"] = {"kernel": jnp.zeros(
            (d, cfg.vocab_size), jnp.bfloat16).at[
                eye, (eye + shift) % d].set(beta)}
        return params

    return jax.jit(build)(jax.random.PRNGKey(seed))


def phase_serve(cfg, compiles, *, n_requests=8, prompt_range=(128, 512),
                new_tokens=32, block_size=128, expect_mosaic=True, seed=0):
    """8 streamed requests through ``ServingScheduler.submit``/``drain`` on
    one chip, compared with one-shot ``engine.generate`` and with the
    training-path forward (``LlamaModel.apply``) on the same prompts."""
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.profiling import cost_model
    from deepspeed_tpu.serving import build_serving_engine

    cost_model.reset()      # the registry is process-wide; this phase's only
    model = llama.LlamaModel(cfg)
    t0 = time.perf_counter()
    params = probe_params(model, seed=seed)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed)
    lo, hi = prompt_range
    lengths = np.linspace(lo, hi, n_requests).astype(int)
    prompts = [rng.integers(1, cfg.hidden_size, size=int(n)).tolist()
               for n in lengths]
    blocks_per_seq = -(-(hi + new_tokens) // block_size)
    sched = build_serving_engine(
        model, params=params,
        engine_config={"dtype": "bfloat16", "state_manager": {
            "max_tracked_sequences": 2 * n_requests,
            "max_ragged_sequence_count": 2 * n_requests,
            "max_context": blocks_per_seq * block_size,
            "block_size": block_size,
            "num_blocks": 1 + n_requests * blocks_per_seq}})
    engine = sched.engine

    def stream_all():
        streams = [[] for _ in prompts]
        for i, p in enumerate(prompts):
            sched.submit(p, max_new_tokens=new_tokens,
                         on_token=lambda t, done, i=i: streams[i].append(t))
        sched.drain()
        return streams

    cost_model.enable_capture(True)   # registry keeps the serving programs
    try:
        # warm-up: the one-shot reference, then one scheduler pass — between
        # them the step and every burst length the timed pass uses compile
        t0 = time.perf_counter()
        reference = engine.generate(prompts, max_new_tokens=new_tokens)
        generate_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        first = stream_all()
        first_pass_s = time.perf_counter() - t0
        mark = compiles.mark()
        done_before = sched.completed
        t0 = time.perf_counter()
        streams = stream_all()
        pass_s = time.perf_counter() - t0
        late = compiles.since(mark)
    finally:
        cost_model.enable_capture(False)

    assert sched.completed - done_before == n_requests, sched.completed
    assert all(len(s) == new_tokens for s in streams), \
        [len(s) for s in streams]
    assert first == reference and streams == reference, (
        "streamed tokens differ from one-shot generate", streams, reference)
    assert not late, f"compilation after warm-up: {late}"

    # cross-path reference on a small input: the first generated token of
    # each request is the argmax of the training-path forward (flash
    # attention, dense [B, S]) at the prompt's last position
    padded = np.zeros((n_requests, hi), np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p

    @jax.jit
    def dense_next(params, ids, last):
        logits = model.apply({"params": params}, ids)
        logits = logits[jnp.arange(ids.shape[0]), last]
        return jnp.argmax(logits, axis=-1), jnp.all(jnp.isfinite(logits))

    dense_first, finite = dense_next(params, padded, lengths - 1)
    assert bool(finite), "non-finite reference logits"
    dense_first = np.asarray(dense_first).tolist()
    assert dense_first == [s[0] for s in streams], (
        "paged serving path and dense training forward disagree",
        dense_first, [s[0] for s in streams])

    # which kernels the serving programs really hold
    programs = {p.name: p for p in cost_model.registry().programs()
                if p.name.startswith("serve/")}
    kernels = {}
    for name, prog in programs.items():
        kernels[name] = len(_MOSAIC_CALL.findall(prog.compiled.as_text()))
    assert "serve/ragged_step" in kernels, sorted(kernels)
    if expect_mosaic:
        # one paged-kernel call per layer; scan bodies print once
        for name, count in kernels.items():
            assert count >= 1, f"{name} holds no Mosaic paged kernel"
        assert kernels["serve/ragged_step"] == cfg.num_hidden_layers, kernels

    mem = _device_memory()
    return {
        "replicas": 1, "devices_used": 1, "layers": cfg.num_hidden_layers,
        "requests": n_requests, "prompt_lengths": lengths.tolist(),
        "new_tokens": new_tokens, "block_size": block_size,
        "mosaic_calls": kernels,
        "burst_steps": getattr(engine, "burst_steps", 0),
        "init_s": round(init_s, 1), "generate_s": round(generate_s, 1),
        "first_stream_pass_s": round(first_pass_s, 1),
        "warm_stream_pass_s": round(pass_s, 2),
        "peak_bytes_in_use": mem[0].get("peak_bytes_in_use"),
    }


# --------------------------------------------------------------------- main
def _versions():
    from importlib import metadata
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = "absent"
    return out


@contextmanager
def _phase(title, compiles):
    print(f"==== {title}", flush=True)
    mark = compiles.mark()
    t0 = time.perf_counter()
    yield
    print(f"[{title}] passed in {time.perf_counter() - t0:.1f}s")
    compiles.report(mark, title)
    sys.stdout.flush()


def main():
    t_start = time.perf_counter()
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"platform={device['platform']} device_kind={device['kind']!r} "
          f"devices={device['count']} versions={_versions()} "
          f"compile_cache={cache_dir}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke.py runs on a TPU only; jax found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    if device["count"] not in TRAIN_SIZES:
        print(f"chip_smoke.py has sizes for {sorted(TRAIN_SIZES)} chips, "
              f"found {device['count']}", file=sys.stderr)
        return 2

    from deepspeed_tpu.models import llama
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu.comm as dist

    with WarningCollector() as warnings, CompileLog() as compiles:
        size = TRAIN_SIZES[device["count"]]
        full = llama.llama_7b().num_hidden_layers
        with _phase(f"phase A: training, ZeRO-3 over {device['count']} "
                    f"chip(s), {size['layers']} of {full} layers", compiles):
            train = phase_train(
                llama.llama_7b(num_hidden_layers=size["layers"],
                               remat=False),
                compiles, micro_batch=size["micro_batch"], seq_len=2048)
            print(json.dumps({"phase": "train", **train}))
        groups.reset_mesh()
        dist.destroy_process_group()

        with _phase("phase B: serving, one replica on one chip"
                    + (f" (of {device['count']})"
                       if device["count"] > 1 else "")
                    + f", {SERVE_LAYERS} of {full} layers", compiles):
            serve = phase_serve(
                llama.llama_7b(num_hidden_layers=SERVE_LAYERS, remat=False),
                compiles)
            print(json.dumps({"phase": "serve", **serve}))

    bad = warnings.unexpected()
    print(f"warnings: {len(warnings.records)} collected, "
          f"{len(bad)} not allow-listed")
    for r in bad:
        print(f"    UNEXPECTED {r}")
    print(f"total {time.perf_counter() - t_start:.1f}s")
    if bad:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
