"""Universal checkpoint + tensor fragment tests — analog of reference
``tests/unit/checkpoint/test_universal_checkpoint.py`` and
``tests/unit/runtime/zero`` fragment tests: convert → resume at a different
topology → trajectory continues identically."""

import os

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.checkpoint import (DeepSpeedCheckpoint, convert_to_universal,
                                      get_fp32_state_dict_from_zero_checkpoint,
                                      load_universal_checkpoint)
from deepspeed_tpu.utils import (safe_get_full_fp32_param, safe_get_full_grad,
                                 safe_get_full_optimizer_state,
                                 safe_set_full_fp32_param)
from tests.unit.simple_model import (batches, make_simple_mlp_params,
                                     random_dataset, simple_mlp_apply)

HIDDEN = 16


def _config(stage=1, mb=4):
    return {
        "train_micro_batch_size_per_gpu": mb,
        "optimizer": {"type": "adam", "params": {"lr": 0.02}},
        "zero_optimization": {"stage": stage},
    }


def _make_engine(stage=1, seed=0):
    params = make_simple_mlp_params(HIDDEN, seed=seed)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=simple_mlp_apply, model_parameters=params,
        config=_config(stage=stage))
    return engine


def _train(engine, data, steps):
    it = iter(data * 100)
    losses = []
    for _ in range(steps):
        x, y = next(it)
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return losses


@pytest.mark.parametrize("src_stage,dst_stage", [(1, 2), (2, 3), (3, 1)])
def test_universal_resume_across_stages(tmp_path, src_stage, dst_stage):
    """Save at one ZeRO stage, convert to universal, resume at another stage
    (= different partitioning topology); training continues bit-identically
    vs an unbroken run."""
    data = batches(random_dataset(64, HIDDEN), 8)

    # unbroken run: 6 steps
    ref = _make_engine(stage=src_stage)
    _train(ref, data, 3)
    ref_losses = _train(ref, data, 3)

    # interrupted run: 3 steps, save, convert, resume at dst_stage
    a = _make_engine(stage=src_stage)
    _train(a, data, 3)
    ckpt = str(tmp_path / "ckpt")
    a.save_checkpoint(ckpt)
    uni = str(tmp_path / "uni")
    convert_to_universal(ckpt, uni)

    b = _make_engine(stage=dst_stage)
    load_universal_checkpoint(b, uni)
    resumed_losses = _train(b, data, 3)

    np.testing.assert_allclose(resumed_losses, ref_losses, rtol=2e-5,
                               err_msg=f"{src_stage}->{dst_stage}")


def test_universal_layout_and_inspection(tmp_path):
    engine = _make_engine(stage=2)
    data = batches(random_dataset(32, HIDDEN), 8)
    _train(engine, data, 2)
    ckpt = str(tmp_path / "ckpt")
    engine.save_checkpoint(ckpt)
    uni = str(tmp_path / "uni")
    convert_to_universal(ckpt, uni)

    # reference layout: zero/{param}/fp32.npy + moments
    assert os.path.exists(os.path.join(uni, "zero", "layer_0", "w", "fp32.npy"))
    assert os.path.exists(os.path.join(uni, "zero", "layer_0", "w", "exp_avg.npy"))
    assert os.path.exists(os.path.join(uni, "zero", "layer_0", "w", "exp_avg_sq.npy"))

    dsc = DeepSpeedCheckpoint(uni)
    assert dsc.is_universal
    assert dsc.get_iteration() == 2
    names = dsc.parameter_names()
    assert "layer_0/w" in names and "layer_1/b" in names
    w = dsc.get_parameter("layer_0/w")
    assert w.shape == (HIDDEN, HIDDEN)
    m = dsc.get_parameter("layer_0/w", key="exp_avg")
    assert np.abs(m).sum() > 0  # moments were trained


def test_zero_to_fp32(tmp_path):
    engine = _make_engine(stage=3)
    data = batches(random_dataset(32, HIDDEN), 8)
    _train(engine, data, 2)
    ckpt = str(tmp_path / "ckpt")
    engine.save_checkpoint(ckpt)

    # recovery script is shipped into the checkpoint dir (reference engine.py:3540)
    assert os.path.exists(os.path.join(ckpt, "zero_to_fp32.py"))

    sd = get_fp32_state_dict_from_zero_checkpoint(ckpt)
    assert "layer_0/w" in sd
    assert sd["layer_0/w"].dtype == np.float32
    # consolidated weights match the live engine master
    live = safe_get_full_fp32_param(engine, "layer_0/w")
    np.testing.assert_allclose(sd["layer_0/w"], live, rtol=1e-6)


def test_tensor_fragment_api():
    engine = _make_engine(stage=2)
    data = batches(random_dataset(32, HIDDEN), 8)
    x, y = data[0]
    loss = engine(x, y)
    engine.backward(loss)

    # grads accessible before step, unscaled
    g = safe_get_full_grad(engine, "layer_0/w")
    assert g is not None and g.shape == (HIDDEN, HIDDEN)
    assert np.abs(g).sum() > 0

    engine.step()
    m = safe_get_full_optimizer_state(engine, "layer_0/w", "exp_avg")
    v = safe_get_full_optimizer_state(engine, "layer_0/w", "exp_avg_sq")
    assert m.shape == (HIDDEN, HIDDEN) and v.shape == (HIDDEN, HIDDEN)
    assert (v >= 0).all()

    # set: overwrite a weight and read it back through both views
    w = safe_get_full_fp32_param(engine, "layer_0/b")
    new = np.full_like(w, 0.5)
    safe_set_full_fp32_param(engine, "layer_0/b", new)
    back = safe_get_full_fp32_param(engine, "layer_0/b")
    np.testing.assert_allclose(back, new)
    assert "layer_0/b" in engine.parameter_names()


def test_universal_resume_adagrad_state(tmp_path):
    """Adagrad's squared-grad accumulator ("sum", torch key) survives the
    universal round-trip — resumed trajectory matches an unbroken run."""
    def make(stage):
        params = make_simple_mlp_params(HIDDEN, seed=0)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=simple_mlp_apply, model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 4,
                    "optimizer": {"type": "adagrad",
                                  "params": {"lr": 0.05}},
                    "zero_optimization": {"stage": stage}})
        return engine

    data = batches(random_dataset(64, HIDDEN), 8)
    ref = make(1)
    _train(ref, data, 3)
    ref_losses = _train(ref, data, 3)

    a = make(1)
    _train(a, data, 3)
    ckpt = str(tmp_path / "ckpt")
    a.save_checkpoint(ckpt)
    uni = str(tmp_path / "uni")
    convert_to_universal(ckpt, uni)

    b = make(2)   # resume at a different stage for good measure
    load_universal_checkpoint(b, uni)
    resumed = _train(b, data, 3)
    np.testing.assert_allclose(resumed, ref_losses, rtol=2e-5)


def test_tensor_fragment_setters_roundtrip():
    """r5 (reference tensor_fragment :171-:320): the remaining setter
    surface — full grad, local fp32/grad/optimizer state — round-trips
    through the matching getters on sharded arrays."""
    from deepspeed_tpu.utils import (safe_get_local_fp32_param,
                                     safe_get_local_grad,
                                     safe_get_local_optimizer_state,
                                     safe_set_full_grad,
                                     safe_set_local_fp32_param,
                                     safe_set_local_grad,
                                     safe_set_local_optimizer_state)

    engine = _make_engine(stage=2)
    data = batches(random_dataset(32, HIDDEN), 8)
    x, y = data[0]
    loss = engine(x, y)
    engine.backward(loss)

    gnew = np.full((HIDDEN, HIDDEN), 0.25, np.float32)
    safe_set_full_grad(engine, "layer_0/w", gnew)
    np.testing.assert_allclose(safe_get_full_grad(engine, "layer_0/w"),
                               gnew, rtol=1e-6)

    gl = safe_get_local_grad(engine, "layer_0/w")
    safe_set_local_grad(engine, "layer_0/w", gl * 2)
    np.testing.assert_allclose(safe_get_local_grad(engine, "layer_0/w"),
                               gl * 2, rtol=1e-6)

    engine.step()
    wl = safe_get_local_fp32_param(engine, "layer_0/b")
    safe_set_local_fp32_param(engine, "layer_0/b", wl + 1.0)
    np.testing.assert_allclose(
        safe_get_local_fp32_param(engine, "layer_0/b"), wl + 1.0,
        rtol=1e-6)

    ml = safe_get_local_optimizer_state(engine, "layer_0/w", "exp_avg")
    safe_set_local_optimizer_state(engine, "layer_0/w", "exp_avg",
                                   np.zeros_like(ml))
    assert np.abs(safe_get_local_optimizer_state(
        engine, "layer_0/w", "exp_avg")).sum() == 0


def test_local_fp32_set_preserves_params_offload():
    """r5: on an engine with a live master, safe_set_local_fp32_param must
    NOT restore the offloaded compute params (re-filling the HBM that
    offload_states() freed) — the boundary apply refreshes them from
    master anyway."""
    from deepspeed_tpu.utils import (safe_get_local_fp32_param,
                                     safe_set_local_fp32_param)

    engine = _make_engine(stage=2)
    data = batches(random_dataset(32, HIDDEN), 8)
    x, y = data[0]
    loss = engine(x, y)
    engine.backward(loss)
    engine.step()
    assert engine.master is not None
    engine.offload_states()
    assert "params" in engine._host_offloaded

    w = safe_get_local_fp32_param(engine, "layer_0/b")
    safe_set_local_fp32_param(engine, "layer_0/b", w + 2.0)
    # master restored and updated; params STILL offloaded
    assert "params" not in (engine._host_offloaded or {}) or \
        engine.master is not None
    assert "params" in engine._host_offloaded, \
        "params were restored although only master was written"
    np.testing.assert_allclose(
        safe_get_local_fp32_param(engine, "layer_0/b"), w + 2.0, rtol=1e-6)


def test_stage3_checkpoint_resumes_across_the_qkv_placement(tmp_path):
    """A stage-3 checkpoint written while q / k / v carried their ZeRO shard
    on the head dimension (the rules before PR 57) loads under the rules that
    put it on the heads, on the same mesh, and the run goes on where it was:
    the same next loss."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.utils import groups
    import deepspeed_tpu.comm as dist

    cfg = llama.llama_tiny(dtype="float32", remat=False)
    new_rules = llama.tp_rules(cfg)
    old_rules = {**new_rules, **{f"{p}_proj/kernel": P(None, "tp", "zero")
                                 for p in "qkv"}}
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(8, 16)).astype(np.int32)

    def engine_with(rules):
        groups.initialize_mesh(dp=4, devices=jax.devices()[:4])
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=llama.LlamaModel(cfg), tp_rules=rules,
            config={**_config(stage=3, mb=2), "mesh": {"dp": 4}})
        engine.initialize_parameters(0, ids, ids)
        return engine

    def q_spec(engine):
        return engine.params["layers_0"]["self_attn"]["q_proj"][
            "kernel"].sharding.spec

    def release():
        groups.reset_mesh()
        dist.destroy_process_group()

    a = engine_with(old_rules)
    assert q_spec(a) == P(None, "tp", "dp")
    _train(a, [(ids, ids)], 2)
    ckpt = str(tmp_path / "ckpt")
    a.save_checkpoint(ckpt)
    expected = _train(a, [(ids, ids)], 2)
    release()

    b = engine_with(new_rules)
    assert q_spec(b) == P(None, ("tp", "dp"), None)
    b.load_checkpoint(ckpt)
    assert q_spec(b) == P(None, ("tp", "dp"), None)
    resumed = _train(b, [(ids, ids)], 2)
    release()
    np.testing.assert_allclose(resumed, expected, rtol=2e-5)
