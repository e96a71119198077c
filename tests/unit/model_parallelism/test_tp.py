"""Tensor-parallel tests (AutoTP analog): TP sharding must not change the
math, and must actually shard the params (reference tests/unit/model_parallelism
intent)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import llama
from deepspeed_tpu.utils import groups


GLOBAL_BATCH = 16


def _run(tp, stage, steps=4, seed=0):
    cfg = llama.llama_tiny(dtype="float32", remat=False)
    model = llama.LlamaModel(cfg)
    dp = 8 // tp
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        tp_rules=llama.tp_rules(cfg),
        config={"train_micro_batch_size_per_gpu": GLOBAL_BATCH // dp,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": stage},
                "mesh": {"tp": tp, "dp": -1}})
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size,
                       size=(GLOBAL_BATCH, 16)).astype(np.int32)
    engine.initialize_parameters(0, ids, ids)
    losses = []
    for _ in range(steps):
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    final = engine.get_fp32_param()
    import deepspeed_tpu.comm as dist
    groups.reset_mesh()
    dist.destroy_process_group()
    return losses, final, engine


def test_tp_matches_no_tp():
    losses_tp, _, _ = _run(tp=2, stage=1)
    losses_ref, _, _ = _run(tp=1, stage=1)
    np.testing.assert_allclose(losses_tp, losses_ref, rtol=2e-4, atol=1e-5)


def test_tp_param_actually_sharded():
    cfg = llama.llama_tiny(dtype="float32", remat=False)
    model = llama.LlamaModel(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, tp_rules=llama.tp_rules(cfg),
        config={"train_micro_batch_size_per_gpu": 2,
                "zero_optimization": {"stage": 0},
                "mesh": {"tp": 4, "dp": -1}})
    ids = np.zeros((2 * engine.dp_world_size, 8), np.int32)
    engine.initialize_parameters(0, ids, ids)
    # find a q_proj kernel leaf and check its sharding spec references "tp"
    found = False
    for kp, leaf in jax.tree_util.tree_leaves_with_path(engine.params):
        from deepspeed_tpu.runtime.zero.partition import path_str
        if path_str(kp).endswith("q_proj/kernel"):
            spec = leaf.sharding.spec
            assert any(ax == "tp" or (isinstance(ax, tuple) and "tp" in ax)
                       for ax in spec if ax is not None), spec
            found = True
    assert found


def test_tp_with_zero3_composes():
    losses, _, engine = _run(tp=2, stage=3)
    assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0]


# ---------------------------------------------------- zero-placeholder rules
def test_zero_placeholder_pins_placement():
    """Rules may pin the ZeRO shard with the 'zero' pseudo-axis; the plan
    must expand it per stage and never add heuristic sharding on top."""
    from jax.sharding import Mesh, PartitionSpec as P
    from deepspeed_tpu.runtime.zero.partition import ZeroPartitionPlan
    devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
    mesh = Mesh(devs, ("dp", "sp", "tp"))
    rules = {"q_proj/kernel": P(None, "tp", "zero"),
             "embed_tokens/embedding": P(("tp", "zero"), None)}
    plan = ZeroPartitionPlan(3, mesh, zero_axes=("dp", "sp"), tp_rules=rules)
    # q/k/v: zero lands on the head dim, not the contracting dim
    assert plan.param_spec((64, 4, 16), "m/q_proj/kernel") == \
        P(None, "tp", ("dp", "sp"))
    # embed: zero composes with tp on the vocab dim
    assert plan.param_spec((256, 64), "m/embed_tokens/embedding") == \
        P(("tp", "dp", "sp"), None)
    # stage-dependent expansion: stage 1 params keep TP only
    plan1 = ZeroPartitionPlan(1, mesh, zero_axes=("dp", "sp"), tp_rules=rules)
    assert plan1.param_spec((64, 4, 16), "m/q_proj/kernel") == \
        P(None, "tp", None)
    assert plan1.master_spec((64, 4, 16), "m/q_proj/kernel") == \
        P(None, "tp", ("dp", "sp"))


def test_zero_placeholder_excludes_claimed_axes():
    """Expansion must not duplicate an axis the rule claims elsewhere (e.g.
    'ep' on expert params) — dup axes make NamedSharding reject the spec."""
    from jax.sharding import Mesh, PartitionSpec as P
    from deepspeed_tpu.runtime.zero.partition import ZeroPartitionPlan
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("dp", "ep"))
    rules = {"experts/*": P("ep"), "gate_proj/kernel": P(None, "zero")}
    plan = ZeroPartitionPlan(3, mesh, zero_axes=("dp", "ep"), tp_rules=rules)
    spec = plan.param_spec((8, 64, 128), "moe/experts/gate_proj/kernel")
    # composed scope rule claims 'ep' on dim0; zero expansion may only use dp
    assert spec == P("ep", None, "dp")


def test_zero_placeholder_divisibility_fallback():
    """If the pinned dim can't take the zero axes, fall back to the heuristic
    instead of silently replicating."""
    from jax.sharding import Mesh, PartitionSpec as P
    from deepspeed_tpu.runtime.zero.partition import ZeroPartitionPlan
    devs = np.array(jax.devices()[:8]).reshape(8)
    mesh = Mesh(devs, ("dp", ))
    rules = {"q_proj/kernel": P(None, None, "zero")}
    plan = ZeroPartitionPlan(3, mesh, zero_axes=("dp", ), tp_rules=rules)
    # head dim 4 % 8 != 0 → pin fails → heuristic shards dim0 (64 % 8 == 0)
    spec = plan.param_spec((64, 2, 4), "m/q_proj/kernel")
    assert spec == P("dp", None, None)
    # partial divisibility: sp-sized factor fits even when the full group
    # doesn't — greedy per-axis placement keeps what divides
    devs2 = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh2 = Mesh(devs2, ("dp", "sp"))
    plan2 = ZeroPartitionPlan(3, mesh2, zero_axes=("dp", "sp"),
                              tp_rules=rules)
    spec2 = plan2.param_spec((64, 4, 2), "m/q_proj/kernel")
    assert spec2 == P(None, None, "sp") or spec2 == P(None, None, ("sp", ))


def _model_rules(name):
    if name == "llama":
        return llama.tp_rules(llama.llama_tiny())
    from deepspeed_tpu.models import smallthinker
    return smallthinker.tp_rules(smallthinker.smallthinker_tiny())


@pytest.mark.parametrize("proj", ["q_proj", "k_proj", "v_proj"])
@pytest.mark.parametrize("model", ["llama", "smallthinker"])
def test_qkv_zero_shard_lands_on_the_heads(model, proj):
    """The models' own rules put the ZeRO shard of a ``[D, H, Dh]`` kernel on
    the HEADS wherever the axis divides them (whole heads = whole lane tiles:
    on four chips the compiler windows the product over the shards and
    writes each piece where the shard sits, PERF.md section 6, PR 57); what
    does not divide the heads falls to the head dimension, and nothing ever
    to dimension 0, the contracting one."""
    from jax.sharding import Mesh, PartitionSpec as P
    from deepspeed_tpu.runtime.zero.partition import ZeroPartitionPlan
    rules = _model_rules(model)
    path = f"layers_0/self_attn/{proj}/kernel"
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4, 1), ("dp", "tp"))
    plan = ZeroPartitionPlan(3, mesh, zero_axes=("dp", ), tp_rules=rules)
    on_heads = P(None, ("tp", "dp"), None)
    for shape in ((64, 32, 128), (64, 8, 128)):
        assert plan.param_spec(shape, path) == on_heads
        assert plan.master_spec(shape, path) == on_heads
        assert plan.grad_spec(shape, path) == on_heads
    # two heads over four chips: the head dimension takes the axis
    assert plan.param_spec((64, 2, 128), path) == P(None, "tp", "dp")
    # two zero axes: the heads take what divides them, Dh the rest
    mesh2 = Mesh(np.array(jax.devices()[:8]).reshape(4, 2, 1),
                 ("dp", "sp", "tp"))
    plan2 = ZeroPartitionPlan(3, mesh2, zero_axes=("dp", "sp"),
                              tp_rules=rules)
    assert plan2.param_spec((64, 4, 16), path) == P(None, ("tp", "dp"), "sp")
    # stages 1 and 2: compute params keep tp alone, master (and from stage 2
    # the gradients) expand as stage 3's
    tp_only = P(None, "tp", None)
    for stage in (1, 2):
        p = ZeroPartitionPlan(stage, mesh, zero_axes=("dp", ),
                              tp_rules=rules)
        assert p.param_spec((64, 8, 128), path) == tp_only
        assert p.master_spec((64, 8, 128), path) == on_heads
        assert p.grad_spec((64, 8, 128), path) == \
            (on_heads if stage == 2 else tp_only)
    # one device: every zero axis has size 1, the placeholder expands to
    # nothing and the spec is what it was before the shard moved
    one = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    plan1 = ZeroPartitionPlan(3, one, zero_axes=("dp", ), tp_rules=rules)
    for shape in ((64, 32, 128), (64, 8, 128), (64, 2, 128)):
        assert plan1.param_spec(shape, path) == tp_only
        assert plan1.master_spec(shape, path) == tp_only


def _first_loss_and_grads(stage):
    from deepspeed_tpu.utils import safe_get_full_grad
    cfg = llama.llama_tiny(dtype="float32", remat=False)
    groups.initialize_mesh(dp=4, devices=jax.devices()[:4])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=llama.LlamaModel(cfg), tp_rules=llama.tp_rules(cfg),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": stage},
                "mesh": {"dp": 4}})
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(8, 16)).astype(np.int32)
    engine.initialize_parameters(0, ids, ids)
    loss = engine(ids, ids)
    engine.backward(loss)
    grads = {n: safe_get_full_grad(engine, n)
             for n in engine.parameter_names()}
    attn = engine.params["layers_0"]["self_attn"]
    specs = {p: attn[p]["kernel"].sharding.spec
             for p in ("q_proj", "k_proj", "v_proj")}
    import deepspeed_tpu.comm as dist
    groups.reset_mesh()
    dist.destroy_process_group()
    return float(loss), grads, specs


def test_zero3_on_the_heads_matches_zero0():
    """A tiny Llama under ZeRO-3 over four devices (q's shard on its 4 heads,
    k's and v's 2 heads do not divide and keep it on Dh): the first loss and
    every gradient equal the unsharded run's."""
    from jax.sharding import PartitionSpec as P
    loss0, grads0, _ = _first_loss_and_grads(0)
    loss3, grads3, specs = _first_loss_and_grads(3)
    assert specs["q_proj"] == P(None, ("tp", "dp"), None)
    assert specs["k_proj"] == specs["v_proj"] == P(None, "tp", "dp")
    np.testing.assert_allclose(loss3, loss0, rtol=2e-4, atol=1e-5)
    assert grads0.keys() == grads3.keys() and len(grads0) > 10
    for name, g in grads0.items():
        np.testing.assert_allclose(grads3[name], g, rtol=2e-4, atol=1e-5,
                                   err_msg=name)


def test_hlo_dump_counts_pieces_and_collectives_by_scope():
    """``tools/train_hlo_dump.py`` reads, under each ``ds.*`` scope of an
    optimised HLO, the ``dynamic-update-slice`` ops that stand OUTSIDE every
    fusion (a product written piece by piece: the four-chip cell's q and k
    before PR 57) and the collectives (an async pair once)."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "train_hlo_dump", os.path.join(
            os.path.dirname(__file__), "..", "..", "..", "tools",
            "train_hlo_dump.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    def meta(scope, proj):
        return ('metadata={op_name="jit(micro)/layers_0/self_attn/'
                f'{scope}/{proj}/dot_general"}}')
    raw = "\n".join([
        "%fused_computation.1 (p0: bf16[1,8,64]) -> bf16[1,8,64] {",
        "  ROOT %dynamic-update-slice.9 = bf16[1,8,64]{2,1,0} "
        "dynamic-update-slice(%p0, %p1, %c, %c, %i), "
        + meta("ds.attn_proj", "o_proj"),
        "}",
        "ENTRY %main (a: bf16[8,4,16]) -> bf16[8,4,16] {",
        "  %fusion.1 = bf16[1,8,64]{2,1,0} fusion(%a), kind=kOutput, "
        "calls=%fused_computation.1, " + meta("ds.attn_proj", "o_proj"),
        "  %collective-permute-start.1 = (bf16[8,4,4], bf16[8,4,4]) "
        "collective-permute-start(%a), " + meta("ds.attn_proj", "q_proj"),
        "  %collective-permute-done.1 = bf16[8,4,4] "
        "collective-permute-done(%collective-permute-start.1), "
        + meta("ds.attn_proj", "q_proj"),
        "  %dynamic-update-slice.1 = bf16[8,4,16]{0,2,1} "
        "dynamic-update-slice(%b, %piece, %c, %c, %i), "
        + meta("ds.attn_proj", "q_proj"),
        "  %all-gather.3 = bf16[8,64]{1,0} all-gather(%w), dimensions={1}, "
        + meta("ds.lm_head_loss", "lm_head"),
        "}"])
    assert tool.scope_counts(raw) == {
        "ds.attn_proj": {"instructions": 5, "dynamic_update_slice": 1,
                         "collectives": 1},
        "ds.lm_head_loss": {"instructions": 1, "dynamic_update_slice": 0,
                            "collectives": 1}}


def test_inference_tp_rules_with_zero_placeholder():
    """init_inference-style sharding must tolerate rules carrying 'zero'."""
    from jax.sharding import Mesh
    from deepspeed_tpu.module_inject.auto_tp import shard_params_for_tp
    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("tp", ))
    cfg = llama.llama_tiny(dtype="float32")
    params = {"embed_tokens": {"embedding": jnp.zeros((256, 64))},
              "layers_0": {"self_attn": {"q_proj": {
                  "kernel": jnp.zeros((64, 4, 16))}}}}
    out = shard_params_for_tp(params, mesh, llama.tp_rules(cfg))
    specs = jax.tree_util.tree_map(lambda x: x.sharding.spec, out)
    assert specs["layers_0"]["self_attn"]["q_proj"]["kernel"] == \
        jax.sharding.PartitionSpec(None, "tp", None)


# ------------------------------------------------------- dataflow TP parser
def test_dataflow_parser_matches_hand_rules():
    """The jaxpr taint parser (reference tp_parser analog) must reproduce the
    hand-written llama rules exactly and classify mixtral experts."""
    from deepspeed_tpu.module_inject.tp_parser import (
        TpParser, derive_tp_rules_from_dataflow)
    from deepspeed_tpu.models import mixtral as mixtral_mod

    cfg = llama.llama_tiny(dtype="float32", remat=False)
    m = llama.LlamaModel(cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0), ids)["params"]
    auto = derive_tp_rules_from_dataflow(
        lambda p, x: m.apply({"params": p}, x), params, ids)
    hand = llama.tp_rules(cfg)
    # the parser still pins a [D, H, Dh] kernel's ZeRO shard on Dh; llama's
    # own rules moved it to the heads in PR 57 (measured on four chips; the
    # parser's pin runs in no cell: ROADMAP.md D14)
    from jax.sharding import PartitionSpec as P
    hand.update({f"{p}_proj/kernel": P(None, "tp", "zero") for p in "qkv"})
    for key, spec in hand.items():
        assert auto.get(key) == spec, (key, auto.get(key), spec)

    cfg2 = mixtral_mod.mixtral_tiny(dtype="float32", remat=False)
    m2 = mixtral_mod.MixtralModel(cfg2)
    params2 = jax.eval_shape(m2.init, jax.random.PRNGKey(0), ids)["params"]
    classes = TpParser().parse(
        lambda p, x: m2.apply({"params": p}, x), params2, ids)
    col = {c.split("/")[-1] for c in classes["expert_column"]}
    row = {c.split("/")[-1] for c in classes["expert_row"]}
    assert col == {"w1", "w3"} and row == {"w2"}
    routers = {c.split("/")[-2] for c in classes["router"]}
    assert routers == {"gate"}


def test_tp_rules_none_auto_derives():
    """tp_rules=None with tp>1: the engine derives rules from dataflow and
    the run matches the hand-rules run (VERDICT round-1 item 6)."""
    cfg = llama.llama_tiny(dtype="float32", remat=False)
    dp = 4
    def run(rules):
        model = llama.LlamaModel(cfg)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, tp_rules=rules,
            config={"train_micro_batch_size_per_gpu": GLOBAL_BATCH // dp,
                    "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                    "zero_optimization": {"stage": 1},
                    "mesh": {"tp": 2, "dp": -1}})
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size,
                           size=(GLOBAL_BATCH, 16)).astype(np.int32)
        engine.initialize_parameters(0, ids, ids)
        assert engine.plan.tp_rules, "no TP rules in effect"
        losses = []
        for _ in range(3):
            loss = engine(ids, ids)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        import deepspeed_tpu.comm as dist
        groups.reset_mesh()
        dist.destroy_process_group()
        return losses

    auto_losses = run(None)
    hand_losses = run(llama.tp_rules(cfg))
    np.testing.assert_allclose(auto_losses, hand_losses, rtol=2e-4, atol=1e-5)
