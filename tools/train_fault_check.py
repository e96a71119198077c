#!/usr/bin/env python
"""Would a training cell's comparison catch a planted fault AT THE TIMED SIZE?

    python tools/train_fault_check.py --workload smallthinker_21b_train_8k \\
        --seed N [--out FILE]

Runs, on the attached chip, what ``perfbench/jobs/train.py`` runs before its
window: the engine's first optimizer steps on the seeded check batch, then the
plain float32 reference alone on the chip.  The reference is then run AGAIN
with one thing changed at a time (another reading of what the architecture's
``config.json`` leaves open, a layout changed, the parameters kept in
bfloat16 with no float32 master copy), and each run's losses are read against
the engine's by the job's own rule and limits (``loss_errors``, ``TOL_FACTOR``
x the configuration's ``measured_worst``).  One JSON line a run:
``{"fault", "errors", "limits", "caught_by"}``; ``fault: null`` is the sound
reference, which must be caught by nothing.  The faults are planted in the
reference because it has the switches; the comparison is symmetric.
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: sizes a reference reads -> another value: a fault of that architecture
FAULTS = {
    "smallthinker": {
        "unnormalised_top_k_weights": {"norm_topk_prob": False},
        "silu_for_relu": {"expert_activation": "silu"},
        "router_fed_the_attention_norm": {"router_input": "attention_norm"},
        "rotary_on_the_full_layer": {"rope_layout": (1, 1, 1, 1)},
        "no_window": {"sliding_window_layout": (0, 0, 0, 0)},
    },
}


def without_master_weights(ref, sizes, w0, batch, steps, adam):
    """The reference's losses with its parameters kept in bfloat16: rounded
    before the first loss and after every update."""
    import jax
    import jax.numpy as jnp
    fn = ref.make_loss_and_grad(sizes, batch.shape[0])
    # reduce_precision, not a pair of casts: the TPU compiler may keep the
    # excess precision of float32 -> bfloat16 -> float32 (it did: the run read
    # the sound reference's losses to every digit)
    rounded = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                           mantissa_bits=7), t),
        donate_argnums=0)
    params = rounded(w0)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for t in range(steps + 1):
        loss, grads = ref.batch_loss_and_grad(fn, params, batch)
        losses.append(float(loss))
        if t < steps:
            params, m, v = ref.base.adamw_step(
                params, grads, m, v, jnp.float32(t + 1), **adam)
            params = rounded(params)
        del grads
    return losses


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out")
    ap.add_argument("--only", help="comma-separated faults to run beside "
                    "the sound reference; default: all")
    ap.add_argument("--root", help="another benchmark root (the tests' "
                    "tiny cells); default: this checkout")
    opts = ap.parse_args()
    import jax
    import numpy as np
    from perfbench import harness, loader
    root = opts.root or loader.ROOT
    manifest = loader.load_manifest(root)
    cell = loader.find(manifest["workloads"], opts.workload, "workload")
    entry = loader.find(manifest["configs"], cell["config"], "config")
    config = loader.load_json(os.path.join(root, entry["file"]))
    traffic = loader.load_json(loader.part_path(root, "traffic",
                                                cell["traffic"], "json"))
    train = loader.load_part(root, "jobs", "train")
    arch = loader.load_part(root, "models", config["arch"])
    ref = loader.load_part(root, "reference", config["arch"])
    devices = jax.devices()[:cell["chips"]]
    ctx = harness.Context(traffic=traffic, devices=devices, config=config,
                          config_file=entry["file"])
    steps = int(traffic.get("check_steps", 2))
    limits = train.tolerances(ctx, steps)
    sizes = arch.reference_sizes(config, "train")
    model, tp_rules = arch.build(config, "train")
    rows = traffic["micro_batch_per_chip"] * len(devices)
    batch = np.random.default_rng([opts.seed, 1]).integers(
        0, sizes["vocab_size"], size=(rows, traffic["seq_len"])).astype(
            np.int32)
    opt = traffic["optimizer"]["params"]
    adam = {"lr": opt["lr"], "b1": 0.9, "b2": 0.999, "eps": 1e-8,
            "weight_decay": 0.0}

    engine = train._build_engine(ctx, model, tp_rules,
                                 harness.fold_seed(opts.seed), batch)
    w0 = engine.get_fp32_param()
    got = [float(train._step(engine, batch)) for _ in range(steps + 1)]
    engine = None
    train._release()

    def fresh():
        return train._shard_over(devices, w0)[0]

    runs = [(None, lambda: ref.train_losses(fresh(), batch, sizes,
                                            steps=steps, adam=adam))]
    for name, change in FAULTS.get(config["arch"], {}).items():
        runs.append((name, lambda c=change: ref.train_losses(
            fresh(), batch, dict(sizes, **c), steps=steps, adam=adam)))
    runs.append(("no_master_weights", lambda: without_master_weights(
        ref, sizes, fresh(), batch, steps, adam)))
    if opts.only:
        runs = [r for r in runs if r[0] is None or r[0] in
                opts.only.split(",")]
    lines = []
    for name, run in runs:
        losses = run()
        errors = train.loss_errors(got, losses)
        lines.append({
            "fault": name, "seed": opts.seed, "engine": got,
            "reference": losses, "errors": errors,
            "limits": {k[6:]: v for k, v in limits.items()},
            "caught_by": [k for k, v in errors.items()
                          if not v <= limits["train." + k]]})
        print(json.dumps(lines[-1]), flush=True)
        gc.collect()
        jax.clear_caches()
    if opts.out:
        os.makedirs(os.path.dirname(os.path.abspath(opts.out)), exist_ok=True)
        with open(opts.out, "a") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
