"""Forward-direction ZeRO-3 param-gather prefetch
(``runtime/zero/overlap.py`` forward half, docs/overlap.md
forward-prefetch section): forward-order partitioner + persistence
exclusion, max_live window, structural per-bucket all-gather evidence in
the compiled micro-step, loss parity for the GSPMD-marker and pipelined
qwZ flavors, and the ``stage3_prefetch_bucket_size`` arming rules."""

import re

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.runtime.zero import overlap
from deepspeed_tpu.runtime.zero.overlap import (GradBucket, gather_items,
                                                live_window,
                                                partition_prefetch_buckets,
                                                pipelined_gather)
from deepspeed_tpu.utils import groups
from tests.unit.simple_model import (batches, make_simple_mlp_params,
                                     random_dataset, simple_mlp_apply)

HIDDEN = 16
KB = 1 << 10


def _leaf(nbytes):
    return np.zeros((nbytes // 4, ), np.float32)


# ------------------------------------------------------------- partitioner
def test_prefetch_partition_forward_order_and_cover():
    items = [(f"l{i}", _leaf(256)) for i in range(7)]
    buckets = partition_prefetch_buckets(items, 600)
    covered = [i for b in buckets for i in b.indices]
    # exact cover, and concatenated dispatch order IS the forward leaf
    # order (the order the forward pass consumes params)
    assert covered == list(range(7))
    assert [b.index for b in buckets] == list(range(len(buckets)))
    for b in buckets:
        assert b.nbytes <= 600
        assert b.elems == sum(64 for _ in b.indices)


def test_prefetch_partition_oversized_leaf_and_skip():
    items = [("small0", _leaf(128)), ("big", _leaf(4 * KB)),
             ("persist", _leaf(128)), ("small1", _leaf(128))]
    buckets = partition_prefetch_buckets(items, KB, skip={"persist"})
    big = [b for b in buckets if "big" in b.paths]
    assert len(big) == 1 and big[0].paths == ("big", )
    covered = sorted(i for b in buckets for i in b.indices)
    # the skipped (persistent) leaf is in NO bucket; everything else is
    assert covered == [0, 1, 3]
    assert all("persist" not in b.paths for b in buckets)


def test_live_window_clamps_to_max_live_parameters():
    buckets = [GradBucket(i, (i, ), (f"l{i}", ), 4000, elems=1000)
               for i in range(5)]
    # no element bound → the configured max_inflight
    assert live_window(buckets, 0, 4) == 4
    # 2500 elems allow 2 consecutive buckets (2000) but not 3 (3000)
    assert live_window(buckets, 2500, 4) == 2
    # even a single bucket over budget still yields 1 (the bucket being
    # consumed must exist)
    assert live_window(buckets, 500, 4) == 1
    # max_inflight is an upper bound, not a target
    assert live_window(buckets, 10**9, 2) == 2
    assert live_window([], 100, 3) == 3
    # regression: max_inflight wider than the bucket list must still
    # validate the budget (the sliding window otherwise iterates an empty
    # range and over-materializes past max_live)
    two = [GradBucket(i, (i, ), (f"l{i}", ), 4 * 10**6, elems=10**6)
           for i in range(2)]
    assert live_window(two, int(1.5e6), 3) == 1
    assert live_window(two, int(2.5e6), 3) == 2


# ---------------------------------------------- persistence (regression)
def test_persistent_leaves_excluded_from_buckets_and_gather():
    """`stage3_param_persistence_threshold` must be honored PER LEAF by
    the gather paths: replicated leaves appear in no prefetch bucket, no
    live accounting, and pass through the qwZ gather untouched."""
    from jax.sharding import Mesh
    from deepspeed_tpu.runtime.zero.partition import ZeroPartitionPlan
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp", ))
    # min_partition_size 100: b (16 elems) persistent, w (256) sharded
    plan = ZeroPartitionPlan(stage=3, mesh=mesh, zero_axes=("dp", ),
                             min_partition_size=100)
    params = make_simple_mlp_params(HIDDEN, nlayers=2)
    items, persistent = gather_items(params, plan)
    assert persistent == {"layer_0/b", "layer_1/b"}
    buckets = partition_prefetch_buckets(items, 512, skip=persistent)
    bucket_paths = {p for b in buckets for p in b.paths}
    assert bucket_paths == {"layer_0/w", "layer_1/w"}
    # live accounting counts only gathered elements
    assert sum(b.elems for b in buckets) == 2 * HIDDEN * HIDDEN
    # the qwZ gather (pipelined and not) returns persistent leaves as-is
    from deepspeed_tpu.runtime.zero.zeropp import quantized_weight_gather
    from deepspeed_tpu.runtime.zero.overlap import resolve_prefetch

    class _Pf:
        enabled, bucket_mb, max_inflight = True, 0.0005, 2

    out = quantized_weight_gather(
        params, plan, prefetch=resolve_prefetch(_Pf))
    assert out["layer_0"]["b"] is params["layer_0"]["b"]
    assert out["layer_1"]["b"] is params["layer_1"]["b"]
    assert out["layer_0"]["w"] is not params["layer_0"]["w"]


def test_pipelined_gather_math_and_fences():
    grads = {f"l{i}": np.full((64, ), float(i), np.float32)
             for i in range(6)}
    items = [(f"l{i}", grads[f"l{i}"]) for i in range(6)]
    buckets = partition_prefetch_buckets(items, 300)
    assert len(buckets) >= 3

    def run(g):
        return pipelined_gather(g, buckets, lambda p, x: x * 2.0,
                                max_inflight=2)

    out = run({k: jax.numpy.asarray(v) for k, v in grads.items()})
    for i in range(6):
        np.testing.assert_allclose(out[f"l{i}"], np.full((64, ), 2.0 * i))
    # the fence structure is real graph structure, one barrier per fenced
    # bucket pair — and it differentiates (straight-through fence)
    f = lambda g: sum(jax.numpy.sum(v) for v in run(g).values())
    jaxpr = str(jax.make_jaxpr(run)(
        {k: jax.numpy.asarray(v) for k, v in grads.items()}))
    assert jaxpr.count("optimization_barrier") == max(0, len(buckets) - 2)
    grad = jax.grad(f)({k: jax.numpy.asarray(v) for k, v in grads.items()})
    np.testing.assert_allclose(grad["l0"], np.full((64, ), 2.0))


# --------------------------------------------------------- engine plumbing
def _engine(co=None, stage=3, nlayers=4, zero_extra=None):
    params = make_simple_mlp_params(HIDDEN, nlayers=nlayers)
    zo = {"stage": stage, "stage3_param_persistence_threshold": 0}
    if zero_extra:
        zo.update(zero_extra)
    cfg = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "sgd", "params": {"lr": 0.1}},
        "zero_optimization": zo,
    }
    if co:
        cfg["comm_optimizations"] = co
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=simple_mlp_apply, model_parameters=params, config=cfg)
    return engine


def _teardown():
    groups.reset_mesh()
    deepspeed_tpu.comm.destroy_process_group()


PREFETCH = {"overlap": {"prefetch": {"enabled": True, "bucket_mb": 0.0005,
                                     "max_inflight": 2}}}


def _micro_artifacts(engine):
    data = batches(random_dataset(64, HIDDEN), 4 * engine.dp_world_size)
    inputs = engine.shard_batch(*data[0])
    micro = engine._micro_step_fn()
    args = (engine.params, engine.scale_state.scale, inputs)
    jaxpr = jax.make_jaxpr(micro)(*args)
    lowered = jax.jit(micro).lower(*args)
    return jaxpr, lowered


def test_stage3_prefetch_emits_per_bucket_gathers():
    """ISSUE-9 acceptance: with prefetch enabled on a ≥2-device mesh the
    stage-3 forward graph contains ≥2 distinct per-bucket gather groups,
    and the compiled module interleaves all-gathers with layer
    dot_generals — verified structurally from jaxpr and HLO."""
    engine = _engine(PREFETCH)
    try:
        jaxpr, lowered = _micro_artifacts(engine)
        s = str(jaxpr)
        # one barrier per bucket marker (forward side)
        assert s.count("optimization_barrier") >= 2, s.count(
            "optimization_barrier")
        # per-bucket gather constraints reach the lowered module
        stable = lowered.as_text()
        engine2 = _engine(None)
        stable_off = _micro_artifacts(engine2)[1].as_text()
        assert stable.count("sdy.sharding_constraint") > \
            stable_off.count("sdy.sharding_constraint")
        # compiled collective structure: ≥2 distinct all-gathers survive
        # SPMD partitioning, interleaved with the layer dots
        hlo = lowered.compile().as_text()
        if isinstance(hlo, (list, tuple)):
            hlo = "\n".join(hlo)
        n_ag = len(re.findall(r"all-gather", hlo))
        assert n_ag >= 2, n_ag
        assert re.search(r"all-gather.*%dot.*all-gather", hlo, re.S), \
            "no dot between all-gathers: gathers not interleaved"
    finally:
        _teardown()


def test_prefetch_disabled_is_program_identical():
    """Disabled (default) compiles to the exact program of HEAD: same
    jaxpr, no markers, no barriers — the bit-identical contract."""
    engine = _engine({"overlap": {"prefetch": {"enabled": False,
                                               "bucket_mb": 0.0005}}})
    try:
        jaxpr_off, _ = _micro_artifacts(engine)
    finally:
        _teardown()
    engine = _engine(None)
    try:
        jaxpr_none, _ = _micro_artifacts(engine)
    finally:
        _teardown()
    assert "optimization_barrier" not in str(jaxpr_off)
    norm = lambda j: re.sub(r"0x[0-9a-f]+", "0x…", str(j))
    assert norm(jaxpr_off) == norm(jaxpr_none)


def test_all_persistent_leaves_is_program_identical():
    """Regression: a prefetch-armed model whose every leaf sits under the
    persistence threshold has nothing to gather — the program must stay
    untouched (no empty-bucket markers)."""
    # threshold 8000 → min_partition_size 1000 > every leaf of the MLP
    engine = _engine(PREFETCH,
                     zero_extra={"stage3_param_persistence_threshold": 8000})
    try:
        jaxpr_pf, _ = _micro_artifacts(engine)
    finally:
        _teardown()
    engine = _engine(None,
                     zero_extra={"stage3_param_persistence_threshold": 8000})
    try:
        jaxpr_none, _ = _micro_artifacts(engine)
    finally:
        _teardown()
    assert "optimization_barrier" not in str(jaxpr_pf)
    norm = lambda j: re.sub(r"0x[0-9a-f]+", "0x…", str(j))
    assert norm(jaxpr_pf) == norm(jaxpr_none)


def _train(engine, steps=8):
    data = batches(random_dataset(64, HIDDEN), 4 * engine.dp_world_size)
    it = iter(data * 50)
    losses = []
    for _ in range(steps):
        x, y = next(it)
        loss = engine(x, y)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return losses


def test_prefetch_loss_parity_gspmd():
    """Full-precision prefetch gathers each leaf exactly once with
    unchanged per-leaf math — the trajectory must match the unprefetched
    run exactly."""
    engine = _engine(None)
    try:
        ref = _train(engine)
    finally:
        _teardown()
    engine = _engine(PREFETCH)
    try:
        pf = _train(engine)
    finally:
        _teardown()
    np.testing.assert_allclose(pf, ref, rtol=1e-6, atol=1e-6)


def test_prefetch_composes_with_grad_overlap():
    """Both directions armed at once: gather markers in the forward,
    reduce markers in the backward, trajectory still exact."""
    engine = _engine(None)
    try:
        ref = _train(engine)
    finally:
        _teardown()
    both = {"overlap": {"enabled": True, "bucket_mb": 0.0005,
                        "prefetch": {"enabled": True, "bucket_mb": 0.0005}}}
    engine = _engine(both)
    try:
        jaxpr, _ = _micro_artifacts(engine)
        # forward gather markers AND backward reduce markers both present
        assert str(jaxpr).count("optimization_barrier") >= 4
        ov = _train(engine)
    finally:
        _teardown()
    np.testing.assert_allclose(ov, ref, rtol=1e-6, atol=1e-6)


def test_qwz_prefetch_pipeline(monkeypatch):
    """qwZ + prefetch: the gather routes through the pipelined bucket
    gather (fences in the jaxpr), and the trajectory is IDENTICAL to the
    unpipelined qwZ run — the pipeline changes scheduling, not math."""
    fired = []
    orig = overlap.pipelined_gather
    monkeypatch.setattr(
        overlap, "pipelined_gather",
        lambda *a, **k: fired.append(1) or orig(*a, **k))
    qwz = {"enabled": True, "quantized_weights": True,
           "quantization_group_size": 128}
    engine = _engine(qwz)
    try:
        ref = _train(engine)
    finally:
        _teardown()
    assert not fired
    engine = _engine(dict(qwz, **PREFETCH))
    try:
        pf = _train(engine)
    finally:
        _teardown()
    assert fired, "prefetch pipeline never engaged on the qwZ path"
    np.testing.assert_allclose(pf, ref, rtol=1e-6, atol=1e-6)


def test_manual_micro_prefetch(monkeypatch):
    """qgZ manual micro + prefetch: the stage-3 gather inside the manual
    body runs the bucket pipeline and stays at loss parity.  Since
    ISSUE 15 the manual micro is opt-in on pure-dp meshes (the GSPMD-first
    islands micro is the default), so the test forces it."""
    fired = []
    orig = overlap.pipelined_gather
    monkeypatch.setattr(
        overlap, "pipelined_gather",
        lambda *a, **k: fired.append(1) or orig(*a, **k))
    qgz = {"enabled": True, "quantized_gradients": True,
           "quantization_group_size": 128, "zero_mode": "flat_manual"}
    engine = _engine(qgz)
    try:
        ref = _train(engine)
    finally:
        _teardown()
    assert not fired
    engine = _engine(dict(qgz, **PREFETCH))
    try:
        pf = _train(engine)
    finally:
        _teardown()
    assert fired, "prefetch pipeline never engaged on the manual micro"
    assert abs(pf[-1] - ref[-1]) < 0.05 * max(1.0, abs(ref[0])), (ref, pf)


# ------------------------------------------------------- config / describe
def test_stage3_prefetch_bucket_size_knob_arms_prefetch():
    """Reference configs with an explicit ``stage3_prefetch_bucket_size``
    get the gather prefetch (the knob that used to be parsed but
    ignored); 0 keeps it off; below stage 3 it stays off."""
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 4,
        "zero_optimization": {"stage": 3,
                              "stage3_prefetch_bucket_size": 50000}})
    assert cfg.comm_optimizations_config.overlap.prefetch.enabled
    # the knob (an element count) stamps the byte bound: 50000 × 4B fp32
    assert cfg.comm_optimizations_config.overlap.prefetch.bucket_mb == \
        pytest.approx(50000 * 4 / (1 << 20))
    # half-precision compute halves the stamped bound
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 4,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3,
                              "stage3_prefetch_bucket_size": 50000}})
    assert cfg.comm_optimizations_config.overlap.prefetch.bucket_mb == \
        pytest.approx(50000 * 2 / (1 << 20))
    # reference semantics: 0 disables the prefetch
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 4,
        "zero_optimization": {"stage": 3,
                              "stage3_prefetch_bucket_size": 0}})
    assert not cfg.comm_optimizations_config.overlap.prefetch.enabled
    # the default field value (knob absent) must NOT arm it
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 4,
        "zero_optimization": {"stage": 3}})
    assert not cfg.comm_optimizations_config.overlap.prefetch.enabled
    # below stage 3 there is nothing to gather
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 4,
        "zero_optimization": {"stage": 2,
                              "stage3_prefetch_bucket_size": 50000}})
    assert not cfg.comm_optimizations_config.overlap.prefetch.enabled


def test_explicit_prefetch_block_overrides_knob_loudly(monkeypatch):
    """An explicit overlap.prefetch block wins over the stage3 knob, with
    a loud warning (a config carrying both must know which steers)."""
    from deepspeed_tpu.runtime import config as config_mod
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    warned = []
    monkeypatch.setattr(config_mod.logger, "warning",
                        lambda msg, *a, **k: warned.append(msg % a
                                                           if a else msg))
    cfg = DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 4,
        "zero_optimization": {"stage": 3,
                              "stage3_prefetch_bucket_size": 50000},
        "comm_optimizations": {
            "overlap": {"prefetch": {"enabled": False}}}})
    assert not cfg.comm_optimizations_config.overlap.prefetch.enabled
    assert any("overridden" in m for m in warned)
    # no explicit block, no knob → no warning noise
    warned.clear()
    DeepSpeedConfig({
        "train_micro_batch_size_per_gpu": 4,
        "zero_optimization": {"stage": 3}})
    assert not any("overridden" in m for m in warned)


def test_prefetch_bucket_bytes_derivation():
    """An explicit bucket_mb wins; 0 falls back to the 32 MiB default —
    never to the zero_config field's 5e7 default, which would silently
    put small models in one bucket (knob-armed configs arrive with
    bucket_mb stamped by DeepSpeedConfig instead)."""
    from deepspeed_tpu.runtime.zero.overlap import prefetch_bucket_bytes

    class _Pf:
        bucket_mb = 2.0

    assert prefetch_bucket_bytes(_Pf) == 2 << 20
    _Pf.bucket_mb = 0.0
    assert prefetch_bucket_bytes(_Pf) == 32 << 20


def test_plan_describe_reports_prefetch():
    engine = _engine({"overlap": {"prefetch": {"enabled": True,
                                               "bucket_mb": 1.5,
                                               "max_inflight": 3}}})
    try:
        d = engine.plan.describe()
        assert d["prefetch_enabled"] is True
        assert d["prefetch_bucket_mb"] == 1.5
        assert d["prefetch_max_inflight"] == 3
    finally:
        _teardown()
    engine = _engine(None)
    try:
        assert engine.plan.describe()["prefetch_enabled"] is False
    finally:
        _teardown()


def test_prefetch_warns_and_noops_below_stage3(monkeypatch):
    from deepspeed_tpu.runtime import engine as engine_mod
    warned = []
    monkeypatch.setattr(engine_mod.logger, "warning",
                        lambda msg, *a, **k: warned.append(msg % a
                                                           if a else msg))
    engine = _engine(PREFETCH, stage=2)
    try:
        jaxpr, _ = _micro_artifacts(engine)
        assert "optimization_barrier" not in str(jaxpr)
        assert any("prefetch" in m and "stage" in m for m in warned)
    finally:
        _teardown()
