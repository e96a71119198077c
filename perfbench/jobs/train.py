"""Job ``train``: the engine's three-call loop on seeded batches.

Set-up (all of it counted in ``setup_s``): build the engine through
``deepspeed_tpu.initialize`` / ``initialize_parameters``; run its first
optimizer steps on one fixed seeded batch and compare the losses with the
plain float32 reference doing the same AdamW steps from the same initial
weights; rebuild the engine from the same seed (the reference and the engine
do not fit on the chip together), see that it reproduces the first loss, warm
it, and only then open the timed window.
"""

import gc
import math
import time

import numpy as np

#: The reference comparison.  The rule: tolerance = TOL_FACTOR x the worst
#: error measured over every seed run of the configuration, which is data of
#: the configuration: ``measured_worst["train.<check>"]`` of its file, with
#: where it was measured (``ctx.measured_worst``).  The factor is 4 and not 3
#: because the error of the loss after two updates has a long tail over seeds
#: (v5e, PR 23: the worst is three times the median).  The checks are
#: ``loss<k>_rel_err``: |engine - reference| / reference, the loss of the
#: check batch before any update (k=0) and after update k; and
#: ``drop<k>_rel_err``: the same for what k updates did to the loss, l0 - lk:
#: what a wrong update rule (no bias correction, another learning rate, a
#: dropped moment) changes.  tests/unit/perfbench applies the same rule to the
#: error measured on the CPU at tiny size and shows what it rejects.
TOL_FACTOR = 4.0
#: the rebuilt engine's first loss against the compared engine's first loss:
#: the same program on the same weights and batch (measured: exactly 0)
REBUILD_REL_TOL = 1e-6


def tolerances(ctx, steps):
    """``{check: tolerance}`` of the checks a comparison over ``steps``
    updates makes; read before anything is built, so that a configuration
    without a measured worst fails at once, by name."""
    names = [f"train.loss{k}_rel_err" for k in range(steps + 1)] \
        + [f"train.drop{k}_rel_err" for k in range(1, steps + 1)]
    return {n: TOL_FACTOR * ctx.measured_worst(n) for n in names}


def _engine_config(traffic, n_chips):
    return {
        "train_micro_batch_size_per_gpu": traffic["micro_batch_per_chip"],
        "gradient_accumulation_steps":
            traffic["gradient_accumulation_steps"],
        "optimizer": {"type": traffic["optimizer"]["type"],
                      "params": dict(traffic["optimizer"]["params"])},
        "bf16": {"enabled": traffic["dtype"] == "bfloat16"},
        "zero_optimization": {"stage": traffic["zero_stage"]},
        "mesh": {"dp": n_chips},
    }


def _build_engine(ctx, model, tp_rules, key, sample):
    import deepspeed_tpu
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, tp_rules=tp_rules,
        config=_engine_config(ctx.traffic, len(ctx.devices)))
    engine.initialize_parameters(key, sample, sample)
    return engine


def _step(engine, ids):
    loss = engine(ids, ids)
    engine.backward(loss)
    engine.step()
    return loss


def _release():
    """Give a dropped engine's device memory back (the caller has let go of
    its last reference)."""
    import jax
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu.comm as dist
    groups.reset_mesh()
    dist.destroy_process_group()
    gc.collect()
    jax.clear_caches()
    gc.collect()


def _shard_over(devices, tree):
    """Place a host tree over the devices: each leaf split along its largest
    dimension that the device count divides (plain placement, no program
    code), or on the one device."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("x",))

    def sharding(leaf):
        dims = sorted(range(leaf.ndim), key=lambda d: -leaf.shape[d])
        for d in dims:
            if len(devices) > 1 and leaf.shape[d] % len(devices) == 0 \
                    and leaf.size >= 4096:
                return NamedSharding(mesh, P(*[None] * d, "x"))
        return NamedSharding(mesh, P())

    shardings = jax.tree_util.tree_map(sharding, tree)
    return jax.device_put(tree, shardings), shardings


def loss_errors(engine_losses, reference_losses):
    """``loss<k>_rel_err``: |engine - reference| / reference for the loss
    before any update (k=0) and after update k; ``drop<k>_rel_err``: the same
    for what k updates did to the loss, ``l0 - lk``."""
    out = {}
    for k, (e, r) in enumerate(zip(engine_losses, reference_losses)):
        out[f"loss{k}_rel_err"] = abs(e - r) / abs(r)
    for k in range(1, len(engine_losses)):
        de = engine_losses[0] - engine_losses[k]
        dr = reference_losses[0] - reference_losses[k]
        out[f"drop{k}_rel_err"] = abs(de - dr) / max(abs(dr), 1e-12)
    return out


def reference_check(ctx, engine_losses, w0, batch, sizes, adam, tols):
    """The plain reference's losses on ``batch`` beside the engine's."""
    steps = len(engine_losses) - 1
    params, shardings = _shard_over(ctx.devices, w0)
    ref = ctx.reference.train_losses(
        params, batch, sizes, steps=steps, adam=adam,
        shardings=shardings if len(ctx.devices) > 1 else None)
    del params
    ctx.info("reference_losses", engine=engine_losses, reference=ref)
    for name, err in loss_errors(engine_losses, ref).items():
        ctx.checks.at_most(f"train.{name}", err, tols[f"train.{name}"])


def run(ctx):
    import jax
    from perfbench import flops
    from perfbench.harness import fold_seed, weights_seed

    traffic, config = ctx.traffic, ctx.config
    n = len(ctx.devices)
    seq = traffic["seq_len"]
    rows = traffic["micro_batch_per_chip"] * n
    tokens_per_step = rows * seq * traffic["gradient_accumulation_steps"]
    model, tp_rules = ctx.arch.build(config, "train")
    sizes = ctx.arch.reference_sizes(config, "train")
    depth = sizes["num_hidden_layers"]
    vocab = sizes["vocab_size"]
    key = fold_seed(weights_seed(ctx))
    rng = np.random.default_rng([ctx.seed, 1])
    check_batch = rng.integers(0, vocab, size=(rows, seq)).astype(np.int32)
    opt = traffic["optimizer"]["params"]
    adam = {"lr": opt["lr"], "b1": opt.get("betas", [0.9, 0.999])[0],
            "b2": opt.get("betas", [0.9, 0.999])[1],
            "eps": opt.get("eps", 1e-8),
            "weight_decay": opt.get("weight_decay", 0.0)}
    check_steps = int(traffic.get("check_steps", 2))
    tols = tolerances(ctx, check_steps)

    # ---- 1. the engine's first steps on the check batch
    t0 = time.perf_counter()
    engine = _build_engine(ctx, model, tp_rules, key, check_batch)
    w0 = engine.get_fp32_param()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(w0))
    engine_losses = [float(_step(engine, check_batch))
                     for _ in range(check_steps + 1)]
    t_engine = time.perf_counter() - t0
    engine = None
    _release()
    ctx.info("released", bytes_in_use=[
        (d.memory_stats() or {}).get("bytes_in_use") for d in ctx.devices])

    # ---- 2. the reference, alone on the chip(s)
    t0 = time.perf_counter()
    reference_check(ctx, engine_losses, w0, check_batch, sizes, adam, tols)
    del w0
    gc.collect()
    t_reference = time.perf_counter() - t0

    # ---- 3. the engine that is timed: same seed, same weights
    t0 = time.perf_counter()
    engine = _build_engine(ctx, model, tp_rules, key, check_batch)
    first = float(_step(engine, check_batch))
    ctx.checks.at_most(
        "train.rebuilt_engine_loss0_rel_err",
        abs(first - engine_losses[0]) / abs(engine_losses[0]),
        REBUILD_REL_TOL, "the timed engine starts where the compared one did")
    data = np.random.default_rng([ctx.seed, 2])

    def batch():
        return data.integers(0, vocab, size=(rows, seq)).astype(np.int32)

    for _ in range(3):                      # warm: every shape of the loop
        loss = _step(engine, batch())
    float(loss)
    jax.block_until_ready(engine.params)
    t_rebuild = time.perf_counter() - t0
    ctx.info("setup", params_m=round(n_params / 1e6, 1), depth=depth,
             engine_check_s=round(t_engine, 2),
             reference_s=round(t_reference, 2),
             rebuild_and_warm_s=round(t_rebuild, 2),
             tolerances=tols, tolerances_from=ctx.config_file,
             weights_seed=weights_seed(ctx),
             compiles=ctx.compiles.summary())

    # ---- 4. the timed window
    sync_every = int(traffic["sync_every_steps"])
    trace_steps = int(traffic.get("trace_steps", 20))
    spans = ctx.spans
    mark = ctx.compiles.mark()
    losses, pending = [], []
    failed = 0
    steps = 0
    traced_steps = 0
    trace_from = 2 * sync_every if ctx.trace else None
    traced_ctx = None
    t_window = time.perf_counter()
    setup_s = t_window - ctx.t_process_start
    sync_at = [t_window]
    while True:
        if ctx.trace and steps == trace_from:
            ctx.profiler.start()
            traced_ctx = jax.profiler.TraceAnnotation("pb:traced")
            traced_ctx.__enter__()
        try:
            with spans.span("input"):
                ids = batch()
            with spans.span("forward"):
                loss = engine(ids, ids)
            with spans.span("backward"):
                engine.backward(loss)
            with spans.span("step"):
                engine.step()
            pending.append(loss)
        except Exception as e:              # a step that raised has failed
            print(f"step {steps} raised {type(e).__name__}: {e}", flush=True)
            failed += 1
        steps += 1
        if ctx.profiler.on:
            traced_steps += 1
        if steps % sync_every == 0:
            with spans.span("wait_for_device"):
                losses += [float(x) for x in pending]
            pending = []
            sync_at.append(time.perf_counter())
        if ctx.profiler.on and traced_steps >= trace_steps:
            with spans.span("wait_for_device"):
                losses += [float(x) for x in pending]
            pending = []
            traced_ctx.__exit__(None, None, None)
            ctx.profiler.stop()
        if time.perf_counter() - t_window >= ctx.seconds \
                and not ctx.profiler.on:
            break
    losses += [float(x) for x in pending]
    jax.block_until_ready(engine.params)
    window_s = time.perf_counter() - t_window
    in_window = ctx.compiles.since(mark)
    failed += sum(not math.isfinite(x) for x in losses)

    tokens_per_s_per_chip = steps * tokens_per_step / window_s / n
    flops_per_token = flops.train_flops_per_token(sizes, depth, seq)
    mfu = (tokens_per_s_per_chip * flops_per_token
           / ctx.peaks["bf16_flops_per_s"]) if ctx.peaks else None
    ctx.checks.equal("train.nonfinite_or_raised_steps", failed, 0)
    ctx.checks.equal("train.compilations_in_window", len(in_window), 0,
                     str(in_window[:3]))
    ctx.info("window", steps=steps, window_s=window_s,
             step_ms=1e3 * window_s / steps,
             tokens_per_s_per_chip=tokens_per_s_per_chip,
             model_flops_per_token=flops_per_token, mfu=mfu,
             lm_head_share_of_flops=flops.lm_head_share(sizes, depth, seq),
             loss_first=losses[0] if losses else None,
             loss_last=losses[-1] if losses else None,
             compilations_in_window=len(in_window), setup_s=setup_s,
             slowest_host_calls=spans.slowest(t_window),
             ms_per_step_by_sync=[
                 round(1e3 * (b - a) / sync_every, 1)
                 for a, b in zip(sync_at, sync_at[1:])])

    return {
        "job": "train", "attempted": steps, "failed": failed,
        "window_s": window_s, "steps": steps, "n_chips": n,
        "tokens_per_step": tokens_per_step,
        "end_to_end": {"train_tokens_per_s_per_chip": tokens_per_s_per_chip,
                       "setup_s": setup_s},
        "traced_steps": traced_steps,
        "trace": ctx.profiler.reduce(n) if ctx.trace else None,
    }
