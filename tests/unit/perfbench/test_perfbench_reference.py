"""The plain references against the program's models in float32 at tiny size,
and the tightness of the checks that decide ``correct``: with a tolerance set
by the rule used on the chip (TOL_FACTOR x the worst error measured, here on
the CPU), each check rejects a dropped layer, a window off by one block, an
optimizer step without bias correction, and weights rounded to 4 bits (8 bits
move no token at tiny size)."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pb_helpers as pb
from perfbench import harness, loader, weights

SEEDS = (1, 2, 3_000_000_000)


@pytest.mark.parametrize("config_name", ["tiny_mistral", "tiny_mixtral"])
def test_reference_equals_the_programs_model_in_float32(config_name):
    config, arch, ref = pb.parts(config_name)
    config = dict(config, program={"train": {"model": {
        "remat": False, "dtype": "float32"}}})
    model, _ = arch.build(config, "train")
    sizes = arch.reference_sizes(config, "train")
    ids = np.random.default_rng(0).integers(0, 256, size=(2, 96))
    params = model.init(jax.random.PRNGKey(3), jnp.asarray(ids))["params"]
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, jnp.asarray(ids))
        want_loss = model.apply({"params": params}, jnp.asarray(ids),
                                jnp.asarray(ids))
    for b in range(2):
        got = ref.logits_at(params, ids[b], np.arange(96), sizes)
        np.testing.assert_allclose(got, want[b], atol=2e-4, rtol=2e-4)
    # the window binds at 96 > 48 tokens: the reference without it differs
    if sizes["sliding_window"]:
        off = ref.logits_at(params, ids[0], np.arange(96),
                            dict(sizes, sliding_window=0))
        assert float(jnp.max(jnp.abs(off - want[0]))) > 1e-2
    # the routed block as published: two experts a token, renormalised
    if "num_experts_per_tok" in sizes:
        for change in ({"num_experts_per_tok": 1}, {"norm_topk_prob": False}):
            off = ref.logits_at(params, ids[0], np.arange(96),
                                dict(sizes, **change))
            assert float(jnp.max(jnp.abs(off - want[0]))) > 1e-2, change
        same, margins = ref.logits_and_routing_at(params, ids[0],
                                                  np.arange(96), sizes)
        np.testing.assert_array_equal(same, ref.logits_at(
            params, ids[0], np.arange(96), sizes))
        assert margins.shape == (96, sizes["num_hidden_layers"])
        assert float(margins.min()) > 0
    fn = ref.make_loss_and_grad(sizes, 2)
    loss, _ = ref.batch_loss_and_grad(fn, ref.f32(params), jnp.asarray(ids))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)


def test_reference_adamw_is_the_engines_fused_adam():
    from deepspeed_tpu.ops.adam import fused_adam
    _, _, ref = pb.parts("tiny_mistral")
    rng = np.random.default_rng(0)
    p = {"a": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)}
    tx = fused_adam(lr=1e-2, weight_decay=0.1)
    state = tx.init(p)
    mine = (jax.tree_util.tree_map(jnp.copy, p),
            jax.tree_util.tree_map(jnp.zeros_like, p),
            jax.tree_util.tree_map(jnp.zeros_like, p))
    for t in (1, 2, 3):
        g = {"a": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)}
        upd, state = tx.update(g, state, p)
        p = jax.tree_util.tree_map(jnp.add, p, upd)
        mine = ref.adamw_step(mine[0], g, mine[1], mine[2], jnp.float32(t),
                              lr=1e-2, weight_decay=0.1)
        np.testing.assert_allclose(mine[0]["a"], p["a"], rtol=1e-6,
                                   atol=1e-7)


# ------------------------------------------------------------- tightness
def _worst(serve, ref, params, sizes, prompts, produced):
    rows = serve.logit_gaps(ref.logits_at, params, sizes, prompts, produced)
    return max(r[1] for r in rows)


@pytest.mark.parametrize("config_name,breaks", [
    ("tiny_mistral", {
        "dropped_layer": lambda s: dict(s, num_hidden_layers=1),
        "window_off_by_a_block": lambda s: dict(
            s, sliding_window=s["sliding_window"] - 16)}),
])
def test_serving_check_is_tight(config_name, breaks):
    runs = [pb.streamed(config_name, seed) for seed in SEEDS]
    serve = runs[0][0]
    measured = max(_worst(*run) for run in runs)
    tolerance = max(serve.TOL_FACTOR * measured, serve.TOL_FLOOR)
    # the tolerance a run uses is set by the same rule from the worst error
    # the configuration's own file states, and that covers what is found here
    ctx = pb.serve_ctx(config_name)
    stated = ctx.config["measured_worst"]["serve.logit_gap"]["value"]
    assert serve.tolerances(ctx) == {"serve.logit_gap": pytest.approx(max(
        serve.TOL_FACTOR * stated, serve.TOL_FLOOR))}
    assert measured <= stated
    for name, change in breaks.items():
        broken = max(_worst(s, ref, params, change(sizes), prompts, produced)
                     for s, ref, params, sizes, prompts, produced in runs)
        assert broken > tolerance, (name, broken, tolerance, measured)
    # weights rounded to few bits, served against the bf16 reference.  The
    # check sees tokens, not logits, so it fires only where rounding moves
    # the argmax: over 24 positions of a 256-entry vocabulary 8-bit weights
    # move none (PERF.md, "Correctness", says what the chip-size check
    # resolves); 4-bit weights do.
    rounded = {bits: max(_worst(*pb.streamed(config_name, seed,
                                           pb.rounded_to(bits)))
                         for seed in SEEDS) for bits in (8, 4)}
    assert rounded[4] > tolerance, (rounded, tolerance)
    assert rounded[4] >= rounded[8]


def test_training_check_is_tight():
    config, arch, ref = pb.parts("tiny_mistral")
    train = loader.load_part(pb.ROOT, "jobs", "train")
    traffic = loader.load_json(loader.part_path(
        pb.ROOT, "traffic", "tiny_train", "json"))
    sizes = arch.reference_sizes(config, "train")
    model, tp_rules = arch.build(config, "train")
    adam = dict(lr=traffic["optimizer"]["params"]["lr"], b1=0.9, b2=0.999,
                eps=1e-8, weight_decay=0.0)
    worst = {}
    broken = {"no_bias_correction": {}, "dropped_layer": {}}
    for seed in SEEDS:
        rows = jax.device_count()
        batch = np.random.default_rng([seed, 1]).integers(
            0, 256, size=(rows, traffic["seq_len"])).astype(np.int32)
        ctx = harness.Context(traffic=traffic, devices=jax.devices())
        engine = train._build_engine(ctx, model, tp_rules,
                                     harness.fold_seed(seed), batch)
        w0 = engine.get_fp32_param()
        got = [float(train._step(engine, batch)) for _ in range(3)]
        f32 = lambda: jax.tree_util.tree_map(jnp.asarray, w0)
        want = ref.train_losses(f32(), batch, sizes, steps=2, adam=adam)
        for k, v in train.loss_errors(got, want).items():
            worst[k] = max(worst.get(k, 0.0), v)
        nobc = ref.train_losses(f32(), batch, sizes, steps=2,
                                adam=dict(adam, bias_correction=False))
        drop = ref.train_losses(f32(), batch,
                                dict(sizes, num_hidden_layers=1), steps=2,
                                adam=adam)
        for name, losses in (("no_bias_correction", nobc),
                             ("dropped_layer", drop)):
            for k, v in train.loss_errors(got, losses).items():
                broken[name][k] = min(broken[name].get(k, 1e9), v)
        engine = None
        train._release()
    tol = {k: train.TOL_FACTOR * v for k, v in worst.items()}
    # the tolerances a run uses are set by the same rule from the worst
    # errors the configuration's own file states
    used = train.tolerances(harness.Context(config=config), 2)
    assert set(used) == {"train." + k for k in tol}
    assert used["train.loss1_rel_err"] == pytest.approx(
        train.TOL_FACTOR
        * config["measured_worst"]["train.loss1_rel_err"]["value"])
    for name, errs in broken.items():
        assert any(errs[k] > tol[k] for k in tol), (name, errs, tol)
    assert broken["no_bias_correction"]["drop1_rel_err"] > tol["drop1_rel_err"]
