"""The forward of ``moe/held_experts.held_experts_apply`` that may be
differentiated: per-expert padded blocks under batched dense products where
the fullest held expert's copies fit a block, the worst case's buffer under
``lax.ragged_dot`` where they do not.  Both are exact: the blocks are read
against the worst case's branch alone (``tier_rows`` taken away: one buffer
of ``T * k`` rows), forward and in all five gradients."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.moe import held_experts as he

T, K, E, H, D, I = 512, 4, 8, 4, 64, 128
CAP = he.block_rows(T, K, H, E)


def routing(first, full=0, skip=None):
    """``topi [T, K]``: the first ``full`` tokens choose expert ``first``
    and nobody else does; ``skip`` is chosen by nobody; the other choices go
    round the remaining experts evenly.  Neither given: every expert gets
    ``T * K / E`` copies."""
    others = [e for e in range(E) if e not in (first if full else None, skip)]
    topi = np.empty((T, K), np.int32)
    for t in range(T):
        own = [first] if t < full else []
        topi[t] = own + [others[(t * K + i) % len(others)]
                         for i in range(K - len(own))]
    return topi


#: name -> (first_expert, topi, live share, activation)
CASES = {
    "even_reglu": (0, routing(0), 1.0, jax.nn.relu),
    "even_swiglu": (0, routing(0), 1.0, jax.nn.silu),
    "fullest_fills_its_block": (0, routing(0, CAP), 1.0, jax.nn.relu),
    "fullest_one_past_its_block": (0, routing(0, CAP + 1), 1.0, jax.nn.relu),
    "dead_rows": (0, routing(0), 0.7, jax.nn.relu),
    "held_window_from_2": (2, routing(2, CAP - 5), 1.0, jax.nn.silu),
    "an_expert_with_no_copy": (2, routing(2, skip=4), 0.9, jax.nn.relu),
}


def operands(name, dtype):
    first, topi, alive, act = CASES[name]
    r = np.random.default_rng(len(name))
    draw = lambda *shape: r.standard_normal(shape).astype(np.float32)
    topw = np.abs(draw(T, K)) + 0.1
    args = (jnp.asarray(draw(T, D), dtype),
            jnp.asarray(topw / topw.sum(1, keepdims=True)),
            jnp.asarray(draw(H, D, I) / np.sqrt(D), dtype),
            jnp.asarray(draw(H, I, D) / np.sqrt(I), dtype),
            jnp.asarray(draw(H, D, I) / np.sqrt(D), dtype))
    live = None if alive == 1.0 else jnp.asarray(r.random(T) < alive)
    cot = jnp.asarray(draw(T, D))

    def loss(x, topw, w1, w2, w3):
        out, counts = he.held_experts_apply(
            x, jnp.asarray(topi), topw, w1, w2, w3, first_expert=first,
            experts=E, live=live, act=act)
        return jnp.sum(out.astype(jnp.float32) * cot), (out, counts)
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                              has_aux=True), args


def worst_case(fn, args, monkeypatch):
    """What ``fn`` gives with one buffer of ``T * k`` rows on ``ragged_dot``:
    the worst case's branch and nothing else."""
    with monkeypatch.context() as m:
        m.setattr(he, "tier_rows", lambda *a: None)
        return jax.jit(fn)(*args)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", list(CASES))
def test_blocks_agree_with_the_worst_case_forward_and_in_five_gradients(
        name, dtype, monkeypatch):
    fn, args = operands(name, dtype)
    ((_, (out, counts)), grads) = jax.jit(fn)(*args)
    ((_, (want_out, want_counts)), want) = worst_case(fn, args, monkeypatch)
    np.testing.assert_array_equal(counts, want_counts)
    fits = bool(he.in_blocks(counts, T, K, E))
    assert fits == (int(jnp.max(counts)) <= CAP)
    assert fits == (name != "fullest_one_past_its_block")
    if name.startswith("fullest"):
        assert int(counts[0]) == CAP + (0 if fits else 1)
    if name == "an_expert_with_no_copy":
        assert int(counts[2]) == 0 and not np.any(np.asarray(grads[2][2]))
    # float32: the same sums in another order; bfloat16: its rounding (8
    # bits) of the products' results and of the scatter-add's partial sums
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for got, ref in zip((out, ) + grads, (want_out, ) + want):
        ref = np.asarray(ref, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                                   rtol=tol, atol=tol * np.max(np.abs(ref)))


@pytest.mark.parametrize("name", ["fullest_fills_its_block",
                                  "fullest_one_past_its_block"])
def test_the_fullest_experts_count_chooses_the_branch(name, monkeypatch):
    """With ``ragged_dot``'s feed-forward made to return NaN, a step that
    fits its blocks is untouched (it ran none of it), and the step with one
    copy too many is NaN all over: it ran the worst case, where the float32
    test above shows no copy lost."""
    fn, args = operands(name, jnp.float32)
    ((_, (want_out, _)), _) = jax.jit(fn)(*args)
    monkeypatch.setattr(he, "grouped_swiglu", lambda x, *a, **kw: x * jnp.nan)
    fn, _ = operands(name, jnp.float32)         # traced anew
    ((_, (out, _)), grads) = jax.jit(fn)(*args)
    if name == "fullest_fills_its_block":
        np.testing.assert_array_equal(out, want_out)
        assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    else:
        assert np.isnan(np.asarray(out)).any()


def primitives(jaxpr):
    """The names of every primitive in a jaxpr and in the jaxprs it holds."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from primitives(inner)


def test_rows_move_through_the_blocks_by_gathers_alone():
    """Forward and backward, the blocks' branch of each ``cond`` gathers rows
    and scatters none (``to_blocks`` / ``from_blocks`` carry their own
    transposes: a copy has one slot, so the way back is a gather too); the
    worst case's branch keeps its scatter-add."""
    fn, args = operands("even_reglu", jnp.bfloat16)
    conds = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
             if e.primitive.name == "cond"]
    assert len(conds) == 2                      # the forward's, the backward's
    for cond in conds:
        worst, blocks = (set(primitives(b.jaxpr))
                         for b in cond.params["branches"])
        assert "scatter-add" in worst and "gather" in blocks
        assert not {p for p in blocks if p.startswith("scatter")}


def test_block_rows_is_an_experts_share_of_the_tier_in_whole_tiles():
    # the training cell: 15 360 rows over 16 held experts = 960 -> 1024
    assert he.tier_rows(8192, 6, 16, 64) == 15360
    assert he.block_rows(8192, 6, 16, 64) == 1024
    assert (he.tier_rows(T, K, H, E), CAP) == (1280, 384)
    # no tier (every expert held, or a small worst case): no blocks, and
    # no call is counted as one
    assert he.block_rows(8192, 2, 8, 8) is None
    assert he.block_rows(128, 4, 4, 8) is None
    counts = jnp.asarray([[3, 1, 0, 2], [9, 9, 9, 9]], jnp.int32)
    assert not np.any(he.in_blocks(counts, 128, 4, 8))
    np.testing.assert_array_equal(
        he.in_blocks(jnp.asarray([[CAP, 0, 1, 2], [1, CAP + 1, 0, 0]]),
                     T, K, E), [True, False])


def test_a_serving_step_keeps_its_sorted_buffer():
    """``kernel=True`` (the serving steps on the chip) runs no padded block:
    its tier is the Pallas grouped matmul in one sorted buffer, as before."""
    first, topi, _, act = CASES["even_reglu"]
    _, args = operands("even_reglu", jnp.float32)
    x, topw, w1, w2, w3 = args
    apply = lambda kernel: jax.make_jaxpr(
        lambda *a: he.held_experts_apply(
            a[0], jnp.asarray(topi), *a[1:], first_expert=first, experts=E,
            act=act, kernel=kernel))(x, topw, w1, w2, w3)
    served, trained = str(apply(True)), str(apply(False))
    assert "ds_grouped_matmul" in served and "ds_grouped_matmul" not in trained
    # the worst case's three on both; the tier's three are the kernel's there
    # and batched dense products here
    products = lambda text: text.count("= ragged_dot_general[")
    assert products(served) == products(trained) == 3
    block = f"[{H},{CAP},{D}]"
    assert block in trained and block not in served
