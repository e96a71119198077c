"""Expert-parallel MoE engine tests (ISSUE-13): the quantized dispatch
exchange, the MoE-aware ZeRO interplay (per-leaf axes through partition /
zeropp / prefetch), the qgZ manual-micro composition, the noisy-gate rng
threading, routed-token telemetry, and the groups-level ep validation."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.moe import MoE, expert_sharding_rules
from deepspeed_tpu.moe import engine as moe_engine
from deepspeed_tpu.utils import groups

HIDDEN = 32
EXPERTS = 4


class MoEModel(nn.Module):
    hidden: int = HIDDEN
    num_experts: int = EXPERTS
    noisy: str = None
    capacity_factor: float = 2.0

    @nn.compact
    def __call__(self, x, y):
        h = nn.Dense(self.hidden, name="in_proj")(x)
        moe_out, l_aux, _ = MoE(hidden_size=self.hidden,
                                num_experts=self.num_experts, k=1,
                                capacity_factor=self.capacity_factor,
                                noisy_gate_policy=self.noisy,
                                name="moe")(h)
        h = h + moe_out
        out = nn.Dense(self.hidden, name="out_proj")(h)
        return jnp.mean((out - y) ** 2) + 0.01 * l_aux


def _data():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, HIDDEN)).astype(np.float32)
    y = np.tanh(x * 0.5).astype(np.float32)
    return x, y


def _teardown():
    groups.reset_mesh()
    deepspeed_tpu.comm.destroy_process_group()


def _engine(ep=2, stage=2, moe=None, comm=None, noisy=None, model=None):
    _teardown()
    groups.initialize_mesh(ep=ep)
    model = model or MoEModel(noisy=noisy)
    x, y = _data()
    params = jax.tree_util.tree_map(
        np.asarray, model.init(jax.random.PRNGKey(0), x, y)["params"])
    config = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "sgd", "params": {"lr": 0.1}},
        "zero_optimization": {"stage": stage},
        "mesh": {"dp": -1, "ep": ep},
    }
    if moe is not None:
        config["moe"] = moe
    if comm is not None:
        config["comm_optimizations"] = comm
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=config)
    return engine, x, y


def _train(engine, x, y, steps=5):
    losses = []
    for _ in range(steps):
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return losses


# --------------------------------------------------------- exchange algebra
def test_quantized_all_to_all_is_a_permutation():
    """fp32 wire: the dispatch exchange must be an exact permutation —
    concat of per-rank capacity blocks, nothing summed."""
    from deepspeed_tpu.comm.collectives.quantized import quantized_all_to_all
    _teardown()
    groups.initialize_mesh(ep=4)
    mesh = groups.get_global_mesh()
    E, C, D = 8, 4, 16
    x = jnp.arange(8 * E * C * D, dtype=jnp.float32).reshape(8, E, C, D)

    def body(blk):
        return quantized_all_to_all(blk[0], ("ep", ), 0, 1, 4,
                                    wire_format="fp32")

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(("dp", "ep")),
        out_specs=P(("dp", "ep")), check_vma=False))
    out = np.asarray(fn(x))
    # every input element survives exactly once (permutation, no sums)
    assert out.shape == (8 * (E // 4), C * 4, D)
    assert sorted(out.ravel().tolist()) == sorted(
        np.asarray(x).ravel().tolist())
    _teardown()


def test_quantized_all_to_all_int8_roundtrip_close():
    from deepspeed_tpu.comm.collectives.quantized import quantized_all_to_all
    _teardown()
    groups.initialize_mesh(ep=4)
    mesh = groups.get_global_mesh()
    E, C, D = 8, 4, 128
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, E, C, D)), jnp.float32)

    def mk(wire):
        def body(blk):
            return quantized_all_to_all(blk[0], ("ep", ), 0, 1, 4,
                                        wire_format=wire, group_size=128)
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(("dp", "ep")),
            out_specs=P(("dp", "ep")), check_vma=False))

    ref = np.asarray(mk("fp32")(x))
    q = np.asarray(mk("int8")(x))
    err = np.abs(ref - q).max() / (np.abs(ref).max() + 1e-9)
    assert err < 2e-2, err
    _teardown()


# ------------------------------------------------------- ZeRO interplay
def test_leaf_zero_axes_exclude_claimed():
    from deepspeed_tpu.runtime.zero.partition import ZeroPartitionPlan
    _teardown()
    groups.initialize_mesh(ep=2)
    plan = ZeroPartitionPlan(stage=3, mesh=groups.get_global_mesh(),
                             zero_axes=("dp", "ep"),
                             tp_rules=expert_sharding_rules())
    exp = "moe/deepspeed_moe/experts/fc1/kernel"
    assert plan.rule_claimed_axes(exp) == ("ep", )
    assert plan.leaf_zero_axes(exp) == ("dp", )
    assert plan.leaf_zero_axes("in_proj/kernel") == ("dp", "ep")
    _teardown()


def test_gather_shardings_keep_expert_axis():
    """The stage-3 post-gather layout keeps the expert dim sharded over
    "ep" — gathering would reassemble experts across ranks (the prefetch
    marker bug this per-leaf fix removes)."""
    engine, x, y = _engine(ep=2, stage=3, moe={"enabled": True})
    try:
        gs = engine.plan.gather_shardings(engine.params)
        spec = gs["moe"]["deepspeed_moe"]["experts"]["fc1"]["kernel"].spec
        names = spec[0] if isinstance(spec[0], tuple) else (spec[0], )
        assert "ep" in names, spec
        # while the dense leaves lose their ZeRO axes entirely
        dense = gs["in_proj"]["kernel"].spec
        flat_names = [a for e in dense if e is not None
                      for a in (e if isinstance(e, tuple) else (e, ))]
        assert "dp" not in flat_names and "ep" not in flat_names, dense
    finally:
        _teardown()


def test_expert_grad_and_master_shard_over_dp_only():
    engine, x, y = _engine(ep=2, stage=2, moe={"enabled": True})
    try:
        spec = engine.plan.master_spec((EXPERTS, HIDDEN, 4 * HIDDEN),
                                       "moe/deepspeed_moe/experts/fc1/"
                                       "kernel")
        flat = [a for e in spec if e is not None
                for a in (e if isinstance(e, tuple) else (e, ))]
        assert "ep" in flat and "dp" in flat, spec
        # ep claimed on dim 0 by the rule; dp landed elsewhere
        first = spec[0] if isinstance(spec[0], tuple) else (spec[0], )
        assert "ep" in first
    finally:
        _teardown()


@pytest.mark.parametrize("stage", (2, 3))
def test_qgz_manual_micro_with_moe_parity(stage):
    """The qgZ manual micro composes with MoE: expert params stay local
    shards, the dispatcher runs the reference concat-a2a inside the manual
    body, and the trajectory tracks the GSPMD baseline."""
    QGZ = {"enabled": True, "quantized_gradients": True,
           "hierarchical_allreduce": True, "wire_dtype": "int8",
           "quantization_group_size": 128}
    engine, x, y = _engine(ep=2, stage=stage, moe={"enabled": True})
    try:
        ref = _train(engine, x, y)
    finally:
        _teardown()
    engine, x, y = _engine(ep=2, stage=stage, moe={"enabled": True},
                           comm=QGZ)
    try:
        qgz = _train(engine, x, y)
    finally:
        _teardown()
    assert abs(ref[-1] - qgz[-1]) <= 2e-2, (ref, qgz)
    assert qgz[-1] < qgz[0] * 0.9, qgz


def test_qgz_with_quantized_dispatch():
    """qgZ grads + int8 expert dispatch in one run (the manual-context
    branch of the dispatcher)."""
    QGZ = {"enabled": True, "quantized_gradients": True,
           "wire_dtype": "int8", "quantization_group_size": 128}
    engine, x, y = _engine(ep=2, moe={"enabled": True})
    try:
        ref = _train(engine, x, y)
    finally:
        _teardown()
    engine, x, y = _engine(
        ep=2, moe={"enabled": True, "quantized_dispatch": True,
                   "wire_dtype": "int8", "quantization_group_size": 128},
        comm=QGZ)
    try:
        q = _train(engine, x, y)
    finally:
        _teardown()
    assert abs(ref[-1] - q[-1]) <= 2e-2, (ref, q)


# ------------------------------------------------------------- noisy gate
def test_rsample_rng_threaded_and_deterministic():
    """The engine threads a per-step gating rng (the policy used to be a
    silent no-op without hand-plumbed rngs): identical seeds reproduce,
    different gating seeds diverge, and the policy actually changes the
    routing vs the rng-less run."""
    runs = {}
    for name, moe in (("a", {"enabled": True}),
                      ("b", {"enabled": True}),
                      ("seeded", {"enabled": True, "gating_seed": 7}),
                      ("off", {"enabled": False})):
        engine, x, y = _engine(ep=2, moe=moe, noisy="RSample")
        try:
            runs[name] = _train(engine, x, y, steps=4)
        finally:
            _teardown()
    assert runs["a"] == runs["b"], "same seed must reproduce exactly"
    assert runs["a"] != runs["seeded"], "gating_seed must steer the noise"
    assert runs["a"] != runs["off"], (
        "RSample never engaged — the rng thread is dead")


# ------------------------------------------------------------- telemetry
def test_routed_token_accounting_in_step_records(tmp_path):
    engine, x, y = _engine(ep=2, moe={"enabled": True})
    try:
        import json
        import os
        from deepspeed_tpu import telemetry as tel
        # configure telemetry onto a temp dir (the emit sites all guard on
        # the module flag, so flipping it post-bring-up is valid)
        class TC:
            trace_dir = str(tmp_path)
            trace_steps = 0
            fence = False
            metrics = None
        tel.configure(TC())
        try:
            _train(engine, x, y, steps=3)
        finally:
            tel.shutdown()
        with open(os.path.join(str(tmp_path), "steps.jsonl")) as f:
            recs = [json.loads(line) for line in f if line.strip()]
        moe_recs = [r for r in recs if "moe" in r]
        assert moe_recs, "no step record carries the moe section"
        layer = next(iter(moe_recs[0]["moe"]["layers"].values()))
        for key in ("drop_fraction", "overflow_tokens", "load_imbalance",
                    "aux_loss"):
            assert key in layer, layer
        assert 0.0 <= layer["drop_fraction"] <= 1.0
        assert layer["load_imbalance"] >= 1.0 - 1e-6
        assert "drop_fraction_mean" in moe_recs[0]["moe"]
        # per-expert capacity utilization (ISSUE-15 satellite): one
        # occupancy per expert, each a post-drop fraction of capacity
        util = layer["expert_util"]
        assert isinstance(util, list) and len(util) >= 2, util
        assert all(0.0 <= u <= 1.0 + 1e-6 for u in util), util
        assert sum(util) > 0.0, util
    finally:
        _teardown()


# ------------------------------------------------------------ groups/config
def test_ep_must_divide_dp_loudly():
    _teardown()
    with pytest.raises(ValueError, match="ep_size"):
        groups.initialize_mesh(ep=3)  # 8 devices: dp=8, 8 % 3 != 0
    _teardown()


def test_moe_config_rejects_unknown_wire():
    from deepspeed_tpu.runtime.config import (DeepSpeedConfig,
                                              DeepSpeedConfigError)
    with pytest.raises(DeepSpeedConfigError, match="moe.wire_dtype"):
        DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                         "moe": {"enabled": True, "wire_dtype": "int3"}})


def test_dispatch_wire_honors_comm_ladder():
    """The comm_optimizations wire_dtype_by_size ladder steers the expert
    dispatch wire per payload size (the autotuner's per-size choice
    applies to the hardest collective too)."""
    from deepspeed_tpu.moe.engine import MoeOptions

    class CO:
        enabled = True
        intra_node_size = 0
        wire_dtype_by_size = [[1024, "fp8"], [None, "int4"]]

    opts = MoeOptions(enabled=True, quantized_dispatch=True,
                      wire_dtype="int8")
    moe_engine.configure(opts, comm_opts=CO())
    try:
        assert moe_engine.dispatch_wire(512) == "fp8"
        assert moe_engine.dispatch_wire(1 << 20) == "int4"
    finally:
        moe_engine.reset()
    # without a ladder: the moe block's own wire
    moe_engine.configure(opts)
    try:
        assert moe_engine.dispatch_wire(512) == "int8"
    finally:
        moe_engine.reset()


def test_autotuner_space_gains_moe_candidates():
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    base = {"train_micro_batch_size_per_gpu": 1,
            "zero_optimization": {"stage": 2},
            "autotuning": {"enabled": True, "zero_stages": [2]},
            "moe": {"enabled": True}}
    tuner = Autotuner(None, base)
    tuner.probe = lambda: None  # no measurement in a unit test
    tuner.wire_ladders = {}
    exps = tuner.build_comm_space()
    moed = [e for e in exps if "_moed_" in e["name"]]
    assert moed, [e["name"] for e in exps]
    assert any(e["ds_config"]["moe"]["wire_dtype"] == "fp32" for e in moed)
    assert all(e["ds_config"]["moe"]["quantized_dispatch"] for e in moed)
    # no moe block in the base config → no moe candidates
    base2 = {k: v for k, v in base.items() if k != "moe"}
    tuner2 = Autotuner(None, base2)
    tuner2.probe = lambda: None
    tuner2.wire_ladders = {}
    assert not [e for e in tuner2.build_comm_space()
                if "_moed_" in e["name"]]


def test_dispatch_wires_config_sync():
    """runtime/config.py duplicates the accepted-wire tuple (importing the
    moe package there would pull flax into every config parse) — keep the
    two in lockstep."""
    from deepspeed_tpu.comm.collectives import WIRE_FORMATS
    from deepspeed_tpu.moe.engine import DISPATCH_WIRES
    assert DISPATCH_WIRES == ("fp32", ) + tuple(WIRE_FORMATS)


def test_autotuner_trials_restore_moe_dispatcher():
    """A mid-session tune must hand the session's MoE dispatcher state
    back — the last trial's moe block must not keep steering dispatch."""
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.moe.engine import MoeOptions
    _teardown()
    session_opts = MoeOptions(enabled=True, quantized_dispatch=True,
                              wire_dtype="fp8")
    moe_engine.configure(session_opts)
    try:
        tuner = Autotuner(
            None, {"train_micro_batch_size_per_gpu": 1,
                   "autotuning": {"enabled": True}})
        # trial engine bring-up reconfigures the dispatcher...
        tuner._run_experiment({
            "name": "t", "ds_config": {
                "train_micro_batch_size_per_gpu": 1,
                "moe": {"enabled": True, "quantized_dispatch": True,
                        "wire_dtype": "int4"}}})
        # ...and the finally block must restore the session's state even
        # though the trial itself failed (no model)
        assert moe_engine.active_options() is session_opts
    finally:
        moe_engine.reset()
        _teardown()
