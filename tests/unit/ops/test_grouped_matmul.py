"""Pallas grouped (MoE expert) matmul — reference FastGen kernel-suite role
(``inference/v2/kernels/cutlass_ops/grouped_gemm``): parity vs XLA's
``lax.ragged_dot`` in interpret mode, including empty groups, non-tile
boundaries and the bf16 wire dtype."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.grouped_matmul import gmm


@pytest.mark.parametrize("sizes", [
    [100, 0, 72, 128],        # empty group + ragged boundaries
    [1, 1, 1, 1],             # tiny groups, heavy padding
    [256, 0, 0, 0],           # one group takes all
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_matches_ragged_dot(sizes, dtype):
    r = np.random.default_rng(0)
    T, K, N, E = sum(sizes), 128, 256, len(sizes)
    x = jnp.asarray(r.standard_normal((T, K)), dtype)
    w = jnp.asarray(r.standard_normal((E, K, N)) * 0.1, dtype)
    gs = jnp.asarray(sizes, jnp.int32)
    y = gmm(x, w, gs)
    ref = jax.lax.ragged_dot(x, w, gs)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_gmm_rejects_untiled_dims():
    with pytest.raises(ValueError, match="block"):
        gmm(jnp.zeros((8, 96)), jnp.zeros((2, 96, 256)),
            jnp.asarray([4, 4], jnp.int32))


def test_the_expert_layers_swiglu_through_the_kernel():
    """``moe/held_experts.grouped_swiglu`` is XLA's ``ragged_dot`` unless its
    caller asks for the Pallas kernel (here in interpret mode), with the same
    result on the rows in a group; a width that no tile divides is one tile."""
    from deepspeed_tpu.moe.held_experts import grouped_swiglu
    r = np.random.default_rng(1)
    T, D, I, E = 64, 128, 192, 4
    sizes = jnp.asarray([20, 0, 30, 8], jnp.int32)      # 6 rows in no group
    x = jnp.asarray(r.standard_normal((T, D)), jnp.float32)
    w1 = jnp.asarray(r.standard_normal((E, D, I)) * 0.1, jnp.float32)
    w2 = jnp.asarray(r.standard_normal((E, I, D)) * 0.1, jnp.float32)
    w3 = jnp.asarray(r.standard_normal((E, D, I)) * 0.1, jnp.float32)
    ref = grouped_swiglu(x, sizes, w1, w2, w3)
    got = grouped_swiglu(x, sizes, w1, w2, w3, kernel=True)
    np.testing.assert_allclose(np.asarray(got)[:58], np.asarray(ref)[:58],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("forward", ["mixtral", "cohere2_moe"])
def test_a_models_forward_keeps_its_gradient_where_kernels_are_on(
        forward, monkeypatch):
    """The kernel has no gradient (a ``pallas_call`` with scalar-prefetch
    operands), so it is the serving step's choice and never the flax
    forward's: with every kernel gate open (as on a TPU) and widths that the
    kernel's tiles divide, ``jax.grad`` through the expert layer of both
    models is what it is with the gates closed."""
    from deepspeed_tpu.models import cohere2_moe as cm
    from deepspeed_tpu.models.mixtral import moe_apply
    r = np.random.default_rng(2)
    T, D, I, E = 24, 128, 128, 4
    arr = lambda *shape: jnp.asarray(r.standard_normal(shape) * 0.1,
                                     jnp.float32)
    x, logits = arr(T, D) * 10, arr(T, E) * 10
    w = {n: arr(E, *s) for n, s in (("w1", (D, I)), ("w2", (I, D)),
                                    ("w3", (D, I)))}
    if forward == "mixtral":
        loss = lambda w: jnp.sum(moe_apply(
            x, logits, w["w1"], w["w2"], w["w3"], 2) ** 2)
    else:
        cfg = cm.cohere2_moe_tiny(hidden_size=D, intermediate_size=I,
                                  num_experts=E, experts_held=E,
                                  num_experts_per_tok=2)
        shared = [arr(2, *s) for s in ((D, I), (I, D), (D, I))]
        loss = lambda w: jnp.sum(cm.moe_layer(
            x, logits, w["w1"], w["w2"], w["w3"], *shared, cfg)[0] ** 2)
    closed = jax.grad(loss)(w)
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    opened = jax.grad(loss)(w)
    for name in w:
        assert float(jnp.max(jnp.abs(closed[name]))) > 0
        np.testing.assert_array_equal(np.asarray(opened[name]),
                                      np.asarray(closed[name]))


@pytest.mark.parametrize("live", [0, 5, 130, 300])
def test_gmm_skips_the_rows_in_no_group(live):
    """Rows past ``sum(group_sizes)`` are in no group: the row tiles past the
    live ones are not computed, and the rows in a group read what they read
    with every row live."""
    r = np.random.default_rng(live)
    T, K, N, E = 300, 128, 256, 3
    x = jnp.asarray(r.standard_normal((T, K)), jnp.float32)
    w = jnp.asarray(r.standard_normal((E, K, N)) * 0.1, jnp.float32)
    cut = sorted(r.integers(0, live + 1, E - 1))
    gs = jnp.asarray(np.diff([0, *cut, live]), jnp.int32)
    y = gmm(x, w, gs)
    ref = jax.lax.ragged_dot(x[:live], w, gs)
    assert y.shape == (T, N)
    np.testing.assert_allclose(np.asarray(y)[:live], np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
