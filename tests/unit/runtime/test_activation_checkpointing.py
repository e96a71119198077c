"""Activation checkpointing tests — analog of reference
``tests/unit/runtime/activation_checkpointing/test_activation_checkpointing.py``:
remat must not change values or gradients."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import llama
from deepspeed_tpu.runtime.activation_checkpointing import (
    RNGStatesTracker, checkpoint, checkpointing, configure, get_policy,
    get_rng_tracker, is_configured, model_parallel_rng_seed,
    non_reentrant_checkpoint, reset, resolve_policy)


@pytest.fixture(autouse=True)
def _reset_cfg():
    yield
    reset()


def _block(w):
    def f(x):
        h = jnp.tanh(x @ w)
        return jnp.sum(h * h)
    return f


def test_checkpoint_preserves_values_and_grads():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)
    f = _block(w)

    ref_val = f(x)
    ref_grad = jax.grad(f)(x)

    ck_val = checkpoint(f, x)
    ck_grad = jax.grad(lambda x_: checkpoint(f, x_))(x)

    np.testing.assert_allclose(ref_val, ck_val, rtol=1e-6)
    np.testing.assert_allclose(ref_grad, ck_grad, rtol=1e-6)

    nr_val = non_reentrant_checkpoint(f, x)
    np.testing.assert_allclose(ref_val, nr_val, rtol=1e-6)


@pytest.mark.parametrize("flags", [
    {"partition_activations": True},
    {"checkpoint_in_cpu": True},
    {"contiguous_checkpointing": True},
])
def test_configured_policies_still_correct(flags):
    configure(**flags)
    assert is_configured()
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)
    f = _block(w)
    np.testing.assert_allclose(f(x), checkpoint(f, x), rtol=1e-6)
    g_ref = jax.grad(f)(x)
    g_ck = jax.grad(lambda x_: checkpoint(f, x_))(x)
    np.testing.assert_allclose(g_ref, g_ck, rtol=1e-6)


def test_checkpoint_inside_jit_and_scan():
    """remat must compose with jit + scan (the PP/long-context path)."""
    w = jnp.eye(8) * 0.5

    def layer(x):
        return jnp.tanh(x @ w)

    @jax.jit
    def stacked(x):
        def body(c, _):
            return checkpoint(layer, c), None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return jnp.sum(out)

    x = jnp.ones((2, 8))
    val = stacked(x)
    g = jax.jit(jax.grad(stacked))(x)
    assert np.isfinite(float(val))
    assert np.isfinite(np.asarray(g)).all()


def test_rng_tracker_fork_deterministic():
    tr = RNGStatesTracker()
    tr.add("model-parallel-rng", 42)
    with tr.fork() as k1:
        a = jax.random.normal(k1, (4, ))
    tr2 = RNGStatesTracker()
    tr2.add("model-parallel-rng", 42)
    with tr2.fork() as k2:
        b = jax.random.normal(k2, (4, ))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # second fork draws a different stream
    with tr.fork() as k3:
        c = jax.random.normal(k3, (4, ))
    assert not np.allclose(np.asarray(a), np.asarray(c))

    with pytest.raises(Exception):
        tr.add("model-parallel-rng", 1)  # duplicate
    with pytest.raises(Exception):
        with tr.fork("missing"):
            pass


def test_model_parallel_rng_seed():
    tr = model_parallel_rng_seed(1234)
    assert tr is get_rng_tracker()
    states = tr.get_states()
    assert "default" in states and "model-parallel-rng" in states


# ---- what a rematerialised block keeps (models' ``remat_policy``)
_TINY = dict(vocab_size=64, hidden_size=64, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, max_position_embeddings=64,
             dtype="float32")


def _op_counts(jaxpr, counts=None):
    """``{name: count}`` of a jaxpr's equations, a Pallas kernel under its
    own name and not looked into, everything else under its primitive's; a
    recomputation lives in a ``checkpoint`` equation's jaxpr."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        kernel = eqn.primitive.name == "pallas_call"
        name = eqn.params["name"] if kernel else eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
        if not kernel:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _op_counts(sub, counts)
    return counts


def _kept(capsys, fn, *args):
    """The residuals ``fn`` keeps for its backward pass that are neither its
    own arguments nor constants of the trace (the rotary tables), as
    ``print_saved_residuals`` words them."""
    import jax.ad_checkpoint
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(fn, *args)
    lines = capsys.readouterr().out.strip().splitlines()
    assert any("from the argument x" in ln for ln in lines)
    return [ln for ln in lines if "from the argument" not in ln
            and "from a constant" not in ln]


def _block_and_args(cfg, batch=4, seq=32):
    block = llama.LlamaBlock(cfg)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (batch, seq, cfg.hidden_size)), jnp.float32)
    params = block.init(jax.random.PRNGKey(0), x)["params"]

    def loss(policy):
        fn = jax.checkpoint(lambda p, x: block.apply({"params": p}, x),
                            policy=resolve_policy(policy))
        return lambda p, x: jnp.sum(fn(p, x))
    return loss, params, x


@pytest.mark.parametrize("policy", [llama.LlamaConfig.remat_policy,
                                    "nothing_saveable"])
@pytest.mark.parametrize("mesh", ["plain", "shard_map"])
def test_remat_block_keeps_the_flash_kernels_residuals(mesh, policy, capsys,
                                                    monkeypatch):
    """A rematerialised Llama block whose attention ran as the flash kernel
    keeps, under the model's default policy, its input plus the kernel's
    five named residuals (output, log-sum-exp, q, k, v) and nothing else,
    and its gradient runs the forward kernel and the q / k / v projections
    once; under ``nothing_saveable`` it keeps the input alone and runs
    them twice.
    Through ``attention_core``'s ``shard_map`` (a mesh of 4) as on one
    device, and to the same gradient bit for bit."""
    from deepspeed_tpu.utils import groups
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")   # interpreted on CPU
    if mesh == "shard_map":
        groups.initialize_mesh(dp=4, devices=jax.devices()[:4])
    cfg = llama.LlamaConfig(**_TINY)
    loss, params, x = _block_and_args(cfg)
    keeps = policy != "nothing_saveable"

    kept = _kept(capsys, loss(policy), params, x)
    B, S = x.shape[:2]
    H = cfg.num_attention_heads
    # o, q and (repeated to the query heads by the model) k, v; and lse
    want = [f"f32[{B},{H},{S},128]"] * 4 + [f"f32[{B},{H},1,{S}]"] \
        if keeps else []
    assert sorted(ln.split(" ")[0] for ln in kept) == sorted(want), kept

    grad = jax.grad(loss(policy), argnums=(0, 1))
    jaxpr = jax.make_jaxpr(grad)(params, x)
    ops = _op_counts(jaxpr.jaxpr)
    assert ("shard_map" in ops) == (mesh == "shard_map")
    assert {k: n for k, n in ops.items() if k.startswith("ds_")} == {
        "ds_flash_fwd": 1 if keeps else 2,
        "ds_flash_bwd_dq": 1, "ds_flash_bwd_dkv": 1}
    # seven matmuls forward, two each backward; recomputed: o, gate and up,
    # and q, k, v only where the kernel's inputs were not kept
    assert ops["dot_general"] == 7 + 14 + (3 if keeps else 6)

    got = jax.jit(grad)(params, x)
    ref = jax.jit(jax.grad(loss("nothing_saveable"), argnums=(0, 1)))(
        params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_remat_default_keeps_nothing_on_the_xla_attention_path(capsys):
    """The names exist only where attention ran as the flash kernel: on the
    XLA path (CPU) the default policy keeps the block's input and nothing
    else, and a ``LlamaConfig(remat=True)`` model trains to the same
    gradient as under ``nothing_saveable``."""
    cfg = llama.LlamaConfig(**_TINY)
    loss, params, x = _block_and_args(cfg)
    assert _kept(capsys, loss(cfg.remat_policy), params, x) == []
    assert "pallas_call" not in str(jax.make_jaxpr(
        jax.grad(loss(cfg.remat_policy)))(params, x))

    ids = jnp.asarray(np.random.default_rng(1).integers(0, 64, (2, 16)))
    grads = []
    for policy in (cfg.remat_policy, "nothing_saveable"):
        model = llama.LlamaModel(llama.LlamaConfig(
            **_TINY, remat=True, remat_policy=policy))
        p = model.init(jax.random.PRNGKey(0), ids, ids)["params"]
        grads.append(jax.grad(
            lambda p: model.apply({"params": p}, ids, ids))(p))
    for a, b in zip(*map(jax.tree_util.tree_leaves, grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", sorted(checkpointing._POLICIES))
def test_every_policy_string_resolves(name):
    policy = resolve_policy(name)
    assert policy is checkpointing._POLICIES[name]
    assert (policy is None) == (name == "none")
    if name in ("nothing_saveable", "dots_saveable"):
        assert policy is getattr(jax.checkpoint_policies, name)
    f = _block(jnp.eye(8) * 0.5)
    x = jnp.ones((2, 8))
    np.testing.assert_allclose(
        jax.grad(jax.checkpoint(f, policy=policy))(x), jax.grad(f)(x),
        rtol=1e-6)


@pytest.mark.parametrize("where", ["resolve_policy", "CheckpointPolicy",
                                   "LlamaConfig"])
def test_unknown_policy_string_raises_by_name(where):
    """A typo used to become ``None`` (jax's full recomputation) through
    ``getattr(jax.checkpoint_policies, name, None)``, with no word."""
    typo = "nothing_savable"
    with pytest.raises(ValueError, match=typo):
        if where == "resolve_policy":
            resolve_policy(typo)
        elif where == "CheckpointPolicy":
            checkpointing.CheckpointPolicy(policy_name=typo).jax_policy()
        else:
            ids = jnp.zeros((1, 8), jnp.int32)
            model = llama.LlamaModel(llama.LlamaConfig(
                **_TINY, remat=True, remat_policy=typo))
            jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)


def test_only_llama_keeps_the_flash_residuals_by_default():
    """One model at a time, each with a cell (ROADMAP D4): the Mixtral
    configuration inherits Llama's fields and states its own default."""
    from deepspeed_tpu.models import evabyte, falcon, mixtral
    assert llama.LlamaConfig().remat_policy == "flash_residuals_saveable"
    for cfg in (mixtral.MixtralConfig(), mixtral.mixtral_tiny(),
                evabyte.EvaByteConfig(), falcon.FalconConfig()):
        assert cfg.remat_policy == "nothing_saveable", type(cfg)
