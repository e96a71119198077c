"""ZeRO++ (qwZ/qgZ/hpZ) + MiCS tests — reference ``tests/unit/runtime/zero/
test_zeropp.py`` style: quantized/hierarchical variants must track plain ZeRO
training trajectories within quantization tolerance."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

import deepspeed_tpu
from deepspeed_tpu.runtime.zero.zeropp import (all_to_all_quant_reduce,
                                               quantized_all_gather,
                                               quantized_weight_gather)
from deepspeed_tpu.utils import groups
from tests.unit.simple_model import (batches, make_simple_mlp_params,
                                     random_dataset, simple_mlp_apply)

HIDDEN = 16


def _config(stage, zero_extra=None, gas=1):
    z = {"stage": stage}
    z.update(zero_extra or {})
    return {
        "train_micro_batch_size_per_gpu": 4,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "adam", "params": {"lr": 0.02}},
        "zero_optimization": z,
    }


def _train(engine, data, steps=15):
    losses = []
    it = iter(data * 50)
    for _ in range(steps):
        for _ in range(engine.gradient_accumulation_steps()):
            x, y = next(it)
            loss = engine(x, y)
            engine.backward(loss)
            engine.step()
        losses.append(float(loss))
    return losses


def _run(stage, zero_extra=None, steps=15):
    params = make_simple_mlp_params(HIDDEN)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=simple_mlp_apply, model_parameters=params,
        config=_config(stage, zero_extra))
    data = batches(random_dataset(64, HIDDEN), 4 * engine.dp_world_size)
    losses = _train(engine, data, steps=steps)
    groups.reset_mesh()
    deepspeed_tpu.comm.destroy_process_group()
    return losses


# ------------------------------------------------------------- collectives
def test_quantized_all_gather_collective():
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp", ))
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))

    fn = shard_map(lambda t: quantized_all_gather(t, ("dp", ), 0),
                   mesh=mesh, in_specs=(P("dp"), ), out_specs=P(),
                   check_vma=False)
    out = fn(x)
    assert out.shape == x.shape
    # int8 groupwise error bound
    assert float(jnp.abs(out - x).max()) <= float(jnp.abs(x).max()) / 127


def test_all_to_all_quant_reduce_collective():
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp", ))
    # per-rank distinct gradients; result must be their mean, scattered
    g = jax.random.normal(jax.random.PRNGKey(1), (8, 64, 32))

    def body(gl):
        # gl: [1, 64, 32] local grad (squeeze rank dim)
        return all_to_all_quant_reduce(gl[0], ("dp", ), 0, 8)

    fn = shard_map(body, mesh=mesh, in_specs=(P("dp", None, None), ),
                   out_specs=P("dp", None), check_vma=False)
    out = fn(g)  # [64, 32]: rank i holds rows i*8:(i+1)*8 of the mean
    ref = jnp.mean(g, axis=0)
    err = jnp.abs(out - ref)
    tol = float(jnp.abs(g).max()) / 127
    assert float(err.max()) <= tol, f"{float(err.max())} > {tol}"


def test_quantized_weight_gather_grads_straight_through():
    """qwZ must not zero gradients (round() has zero slope; bwd is the plain
    reduce-scatter)."""
    groups.initialize_mesh(dp=8)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=simple_mlp_apply,
        model_parameters=make_simple_mlp_params(HIDDEN),
        config=_config(3, {"zero_quantized_weights": True}))

    def loss(params):
        full = quantized_weight_gather(params, engine.plan)
        flat = jax.tree_util.tree_leaves(full)
        return sum(jnp.sum(x.astype(jnp.float32)**2) for x in flat)

    grads = jax.grad(loss)(engine.params)
    total = sum(float(jnp.abs(g).sum())
                for g in jax.tree_util.tree_leaves(grads))
    assert total > 0.0


# ---------------------------------------------------------- training parity
# persistence threshold 0 in the quantized runs: at the default (1e5
# elements) every tensor of this tiny model stays replicated, the qwZ/qgZ
# leaf walkers find no ZeRO-sharded dim, and the "parity" would be trivially
# exact without ever quantizing a byte.
def test_qwz_tracks_plain_zero3():
    ref = _run(3)
    qwz = _run(3, {"zero_quantized_weights": True,
                   "stage3_param_persistence_threshold": 0})
    assert qwz[-1] < qwz[0] * 0.8, f"qwZ diverged: {qwz}"
    assert abs(qwz[-1] - ref[-1]) < 0.25 * abs(ref[0]), (ref, qwz)


def test_qgz_tracks_plain_zero2():
    ref = _run(2)
    qgz = _run(2, {"zero_quantized_gradients": True,
                   "stage3_param_persistence_threshold": 0})
    assert qgz[-1] < qgz[0] * 0.8, f"qgZ diverged: {qgz}"
    assert abs(qgz[-1] - ref[-1]) < 0.25 * abs(ref[0]), (ref, qgz)


def test_qgz_with_qwz_stage3():
    losses = _run(3, {"zero_quantized_gradients": True,
                      "zero_quantized_weights": True,
                      "stage3_param_persistence_threshold": 0})
    assert losses[-1] < losses[0] * 0.8, losses


# --------------------------------------------------------------- hpZ / MiCS
def test_hpz_secondary_partition():
    """hpZ: params shard over the inner zp factor only; trajectory matches
    plain stage 3 exactly (same math, different layout)."""
    ref = _run(3)
    hpz = _run(3, {"zero_hpz_partition_size": 4})
    np.testing.assert_allclose(hpz, ref, rtol=2e-4, atol=2e-4)


def test_hpz_param_sharding_layout():
    params = make_simple_mlp_params(HIDDEN)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=simple_mlp_apply, model_parameters=params,
        config=_config(3, {"zero_hpz_partition_size": 4,
                           "stage3_param_persistence_threshold": 0}))
    st = groups.get_mesh_state()
    assert st.hpz_mesh is not None

    def axes_of(tree):
        leaf = max(jax.tree_util.tree_leaves(tree), key=lambda x: x.size)
        return [a for e in leaf.sharding.spec if e is not None
                for a in (e if isinstance(e, tuple) else (e, ))]

    # a param leaf must be sharded over "zp" (4-way), not full dp (8-way)
    flat_axes = axes_of(engine.params)
    assert "zp" in flat_axes and "dp" not in flat_axes, flat_axes
    # master stays sharded over full dp
    mflat = axes_of(engine.master)
    assert "dp" in mflat or "ep" in mflat, mflat


def test_mics_shard_group():
    """MiCS: all state over the zp shard group; trajectory matches stage 3."""
    ref = _run(3)
    mics = _run(3, {"mics_shard_size": 4})
    np.testing.assert_allclose(mics, ref, rtol=2e-4, atol=2e-4)


def test_mics_state_layout():
    params = make_simple_mlp_params(HIDDEN)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=simple_mlp_apply, model_parameters=params,
        config=_config(3, {"mics_shard_size": 4,
                           "stage3_param_persistence_threshold": 0}))
    for tree in (engine.params, engine.master):
        leaf = max(jax.tree_util.tree_leaves(tree), key=lambda x: x.size)
        flat = [a for e in leaf.sharding.spec if e is not None
                for a in (e if isinstance(e, tuple) else (e, ))]
        assert "zp" in flat and "dp" not in flat, leaf.sharding.spec


def test_qgz_with_hpz():
    """Full ZeRO++ stack: qwZ + qgZ + hpZ together (the canonical config)."""
    losses = _run(3, {"zero_quantized_weights": True,
                      "zero_quantized_gradients": True,
                      "zero_hpz_partition_size": 4})
    assert losses[-1] < losses[0] * 0.8, losses


def test_qgz_with_mics():
    losses = _run(3, {"zero_quantized_gradients": True,
                      "mics_shard_size": 4})
    assert losses[-1] < losses[0] * 0.8, losses


def test_hierarchical_qgz_over_hpz_mesh(monkeypatch):
    """comm_optimizations + hpZ: the manual micro's gradient reduce runs the
    2-hop scheme (fp psum_scatter over intra-host "zp", quantized a2a over
    "zp_outer") from comm/collectives/quantized.py — trajectory must track
    plain stage 3 within quantization tolerance, and the hierarchical
    primitive must actually fire."""
    from deepspeed_tpu.runtime.zero import zeropp
    fired = []
    orig = zeropp.hierarchical_quant_reduce_scatter
    monkeypatch.setattr(
        zeropp, "hierarchical_quant_reduce_scatter",
        lambda *a, **k: fired.append(1) or orig(*a, **k))

    def run(extra):
        params = make_simple_mlp_params(HIDDEN)
        cfg = _config(3, {"zero_hpz_partition_size": 4,
                          "stage3_param_persistence_threshold": 0})
        cfg.update(extra)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=simple_mlp_apply, model_parameters=params, config=cfg)
        data = batches(random_dataset(64, HIDDEN),
                       4 * engine.dp_world_size)
        losses = _train(engine, data, steps=10)
        groups.reset_mesh()
        deepspeed_tpu.comm.destroy_process_group()
        return losses

    ref = run({})
    assert not fired
    hier = run({"comm_optimizations": {"enabled": True,
                                       "quantized_gradients": True,
                                       "quantization_group_size": 128}})
    assert fired, "2-hop reduce never engaged on the zp_outer×zp group"
    assert hier[-1] < hier[0] * 0.8, f"hier qgZ diverged: {hier}"
    assert abs(hier[-1] - ref[-1]) < 0.25 * abs(ref[0]), (ref, hier)


def test_premade_mesh_mismatch_raises():
    groups.initialize_mesh(dp=8)
    with pytest.raises(ValueError, match="zero_partition_size"):
        deepspeed_tpu.initialize(
            model=simple_mlp_apply,
            model_parameters=make_simple_mlp_params(HIDDEN),
            config=_config(3, {"mics_shard_size": 4}))


def test_qgz_on_dp_tp_mesh():
    """qgZ on a dp4×tp2 mesh: the manual micro runs shard_map in
    PARTIAL-manual mode (manual over dp, "tp" left auto so GSPMD keeps
    inserting the tensor-parallel collectives).  Round-2 limit: pure-DP
    meshes only."""
    from deepspeed_tpu.models import llama
    cfg = llama.llama_tiny(dtype="float32", remat=False)
    losses = {}
    for qgz in (False, True):
        model = llama.LlamaModel(cfg)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, tp_rules=llama.tp_rules(cfg),
            config={"train_micro_batch_size_per_gpu": 2,
                    "gradient_accumulation_steps": 1,
                    "optimizer": {"type": "adam", "params": {"lr": 5e-3}},
                    "zero_optimization": {"stage": 2,
                                          "zero_quantized_gradients": qgz},
                    "mesh": {"tp": 2, "dp": -1}})
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, size=(8, 16)).astype(np.int32)
        engine.initialize_parameters(0, ids, ids)
        ls = []
        for _ in range(8):
            loss = engine(ids, ids)
            engine.backward(loss)
            engine.step()
            ls.append(float(loss))
        losses[qgz] = ls
        groups.reset_mesh()
        deepspeed_tpu.comm.destroy_process_group()
    ref, qgz = losses[False], losses[True]
    assert qgz[-1] < qgz[0] * 0.9, f"qgZ×tp diverged: {qgz}"
    # int8-quantized gradient traffic tracks the exact trajectory
    assert abs(qgz[-1] - ref[-1]) < 0.25 * abs(ref[0]), (ref, qgz)


def test_qgz_rejects_sp_mesh():
    """sp/pp meshes still reject loudly with guidance."""
    from deepspeed_tpu.models import llama
    cfg = llama.llama_tiny(dtype="float32", remat=False, use_ulysses=True)
    model = llama.LlamaModel(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2,
                                      "zero_quantized_gradients": True},
                "mesh": {"sp": 2, "dp": -1}})
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(8, 32)).astype(np.int32)
    with pytest.raises(ValueError, match="dp/ep"):
        engine.initialize_parameters(0, ids, ids)
        loss = engine(ids, ids)
    groups.reset_mesh()
    deepspeed_tpu.comm.destroy_process_group()
