"""``ds_gated_delta_slot`` (interpreter mode on the CPU) against the plain XLA
form of the delta rule's one-token step (``models/qwen3_next.
delta_rule_token``) and against the recurrence, in float32: the cell's shape
and a small one over several head blocks; ``fresh`` slots, slots that are not
``live``; sixteen tokens in a row; decays near 1, where a bfloat16 operand
anywhere in the kernel would show."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import ragged_forward as rf
from deepspeed_tpu.models import qwen3_next as qn
from deepspeed_tpu.ops.pallas.gated_delta_rule import (HEAD_BLOCKS,
                                                       SMEM_PAIRS,
                                                       gated_delta_slot,
                                                       head_block)

D = 128


def rows_of(slots, heads, seed=0, tokens=None):
    """A buffer's rows as ``gdn_rule_inputs`` leaves them: unit keys, scaled
    unit queries, ``g`` of the published range (``A`` in 0.5 .. 16 times a
    ``dt`` of 0.001 .. 0.1: a decay of 0.2 .. 0.9995 a token)."""
    lead = (slots, heads) if tokens is None else (tokens, slots, heads)
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    g = -jax.random.uniform(k[3], lead, minval=0.5, maxval=16.0) * jnp.exp(
        jax.random.uniform(k[4], lead, minval=np.log(1e-3),
                           maxval=np.log(1e-1)))
    return (qn.l2_norm(jax.random.normal(k[0], lead + (D, ))) * D ** -0.5,
            qn.l2_norm(jax.random.normal(k[1], lead + (D, ))),
            jax.random.normal(k[2], lead + (D, )), g,
            jax.nn.sigmoid(jax.random.normal(k[5], lead)))


def state_of(slots, heads, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (slots, heads, D, D))


def xla_form(q, k, v, g, beta, state, live, fresh):
    """What ``_rule_slots`` computes off a TPU."""
    return rf._rule_slots(q, k, v, g, beta, state, live, fresh,
                          use_kernel=False)


def some(slots, seed, p):
    return jax.random.bernoulli(jax.random.PRNGKey(seed), p, (slots, ))


@pytest.mark.parametrize("slots, heads, hb", [
    (257, 32, None),                 # the cell's shape, as the cell runs it
    (5, 16, 16), (5, 16, 8), (5, 16, 4), (3, 32, 32)])
def test_the_kernel_is_the_xla_form(slots, heads, hb):
    """At the cell's shape (257 slots of 32 heads; once: 25 s of the CPU's
    time, the interpreter's 514 grid steps over 539 MB buffers; compared by
    jnp, which numpy's ``assert_allclose`` takes 16 s a buffer for) and at a
    small one over the heads of a grid step, some slots fresh and some
    dead."""
    rows, state = rows_of(slots, heads), state_of(slots, heads)
    live, fresh = some(slots, 2, 0.8), some(slots, 3, 0.2)
    want_o, want = xla_form(*rows, state, live, fresh)
    o, new = gated_delta_slot(*rows, jnp.copy(state), live, fresh, hb=hb)
    assert o.dtype == new.dtype == jnp.float32
    for got, ref in ((o, want_o), (new, want)):
        assert float(jnp.max(jnp.abs(got - ref) - 1e-5 * jnp.abs(ref))) <= 1e-5


def test_a_fresh_slot_starts_from_zero_whatever_the_buffer_holds():
    rows = rows_of(4, 8, seed=4)
    fresh = jnp.asarray([False, True, True, False])
    state = state_of(4, 8).at[1].set(jnp.nan).at[2].set(jnp.inf)
    live = jnp.ones((4, ), bool)
    o, new = gated_delta_slot(*rows, jnp.copy(state), live, fresh)
    want_o, want = qn.delta_rule_token(
        *rows, jnp.where(fresh[:, None, None, None], 0, state))
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new, want, rtol=1e-5, atol=1e-5)
    # from zeros: o = (k . q) beta v, the state the outer product k d^T
    q, k, v, _, beta = rows
    np.testing.assert_allclose(
        o[1], jnp.sum(k[1] * q[1], -1, keepdims=True) * beta[1][:, None]
        * v[1], rtol=1e-5, atol=1e-6)


def test_a_slot_that_is_not_live_keeps_its_row_bit_for_bit():
    """Signed zeros, denormals and a NaN among them (the kernel neither
    reads nor writes the row); its ``o`` is zeros, as the XLA form's."""
    rows = rows_of(6, 8, seed=5)
    live = jnp.asarray([False, True, False, True, True, False])
    fresh = jnp.asarray([False, False, True, False, True, False])
    state = state_of(6, 8).at[0, 0, 0, :4].set(
        jnp.asarray([-0.0, 1e-42, jnp.inf, -1e-39]))
    state = state.at[2, 1, 5, 7].set(jnp.nan)
    want_o, want = xla_form(*rows, state, live, fresh)
    o, new = gated_delta_slot(*rows, jnp.copy(state), live, fresh)
    dead = np.flatnonzero(~np.asarray(live))
    assert np.array_equal(np.asarray(new)[dead].view(np.uint32),
                          np.asarray(state)[dead].view(np.uint32))
    np.testing.assert_allclose(new, want, rtol=1e-5, atol=1e-5)
    assert not np.asarray(o)[dead].any() and not np.asarray(want_o)[dead].any()
    np.testing.assert_allclose(np.asarray(o)[[1, 3, 4]],
                               np.asarray(want_o)[[1, 3, 4]], rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("live", [[], [2], [0, 4], [0, 1, 2, 3, 4]],
                         ids=["none", "one", "ends", "all"])
def test_the_grid_walks_the_live_slots_and_stands_still_after_them(live):
    """Whatever the count of live slots (none at all: the buffer comes back
    as it was), two grid steps a slot."""
    rows, state = rows_of(5, 16, seed=10), state_of(5, 16, seed=11)
    live = jnp.zeros((5, ), bool).at[jnp.asarray(live, jnp.int32)].set(True)
    fresh = jnp.asarray([False, True, False, False, False])
    want_o, want = xla_form(*rows, state, live, fresh)
    o, new = gated_delta_slot(*rows, jnp.copy(state), live, fresh, hb=8)
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new, want, rtol=1e-5, atol=1e-5)
    dead = np.flatnonzero(~np.asarray(live))
    assert np.array_equal(np.asarray(new)[dead], np.asarray(state)[dead])


def test_sixteen_tokens_through_the_kernel_are_the_recurrence():
    """A burst: every slot a sequence of its own, the state carried in the
    buffer from call to call; slot 0 never live."""
    slots, heads, tokens = 3, 8, 16
    q, k, v, g, beta = rows_of(slots, heads, seed=6, tokens=tokens)
    start = state_of(slots, heads, seed=7)
    live = jnp.asarray([False, True, True])
    state, got = jnp.copy(start), []
    for t in range(tokens):
        o, state = gated_delta_slot(q[t], k[t], v[t], g[t], beta[t], state,
                                    live, jnp.zeros((slots, ), bool))
        got.append(o)
    got = jnp.stack(got)
    for s in (1, 2):
        want_o, want = qn.delta_rule_recurrence(
            q[:, s], k[:, s], v[:, s], g[:, s], beta[:, s], start[s])
        np.testing.assert_allclose(got[:, s], want_o, rtol=1e-5, atol=2e-5)
        np.testing.assert_allclose(state[s], want, rtol=1e-5, atol=2e-5)
    assert np.array_equal(np.asarray(state[0]), np.asarray(start[0]))


def test_decays_near_one_stay_inside_float32_tolerance():
    """``g`` set by hand at the slow end of the published range (``A`` 0.5,
    ``dt`` 0.001: a decay of 0.9995 a token) over 64 tokens from a state of
    unit size: against the recurrence in FLOAT64 the kernel stays at float32
    rounding, where one operand rounded to bfloat16 (the state's tile, a key
    or the decay: 8 bits of mantissa) is a hundred times off."""
    slots, heads, tokens = 2, 8, 64
    q, k, v, _, beta = rows_of(slots, heads, seed=8, tokens=tokens)
    g = jnp.full((tokens, slots, heads), -0.5 * 1e-3)
    start = state_of(slots, heads, seed=9)
    live, fresh = jnp.ones((slots, ), bool), jnp.zeros((slots, ), bool)

    def through(step, round_to=None):
        state, out = jnp.copy(start), []
        for t in range(tokens):
            o, state = step(q[t], k[t], v[t], g[t], beta[t], state, live,
                            fresh)
            if round_to is not None:
                state = state.astype(round_to).astype(jnp.float32)
            out.append(o)
        return np.asarray(jnp.stack(out)), np.asarray(state)

    def exact():
        f64 = lambda a: np.asarray(a, np.float64)
        s, out = f64(start), []
        for t in range(tokens):
            s = s * np.exp(f64(g[t]))[..., None, None]
            d = f64(beta[t])[..., None] * (f64(v[t]) - np.einsum(
                "shkv,shk->shv", s, f64(k[t])))
            s = s + f64(k[t])[..., :, None] * d[..., None, :]
            out.append(np.einsum("shkv,shk->shv", s, f64(q[t])))
        return np.stack(out), s

    want_o, want = exact()
    err = lambda got: (np.abs(got[0] - want_o).max(),
                       np.abs(got[1] - want).max())
    kernel = err(through(gated_delta_slot))
    rounded = err(through(xla_form, round_to=jnp.bfloat16))
    assert kernel[0] < 2e-5 and kernel[1] < 2e-5, kernel
    assert rounded[0] > 100 * kernel[0] and rounded[1] > 100 * kernel[1]


def test_the_heads_of_a_grid_step():
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    assert head_block(f32(257, 32, 128, 128)) == HEAD_BLOCKS[0] == 16
    assert head_block(f32(3, 8, 128, 128)) == 8
    assert head_block(f32(SMEM_PAIRS // 32, 32, 128, 128)) == 16
    # shapes outside the kernel's rule stay on XLA: heads no block divides,
    # tiles that are not 128 x 128, a state that is not float32, more (slot,
    # head) pairs than SMEM holds e^g and beta of
    assert head_block(f32(3, 4, 128, 128)) is None
    assert head_block(f32(3, 8, 16, 16)) is None
    assert head_block(f32(3, 8, 128, 64)) is None
    assert head_block(jax.ShapeDtypeStruct((3, 8, 128, 128),
                                           jnp.bfloat16)) is None
    assert head_block(f32(SMEM_PAIRS // 32 + 1, 32, 128, 128)) is None


@pytest.mark.parametrize("shape, kernel", [
    ((3, 8, 128, 128), True), ((3, 4, 16, 16), False),
    ((4097, 32, 128, 128), False)],           # an engine of 4 096 sequences
    ids=["wide", "tiny", "past SMEM"])
def test_rule_slots_takes_the_kernel_where_the_gate_and_the_shape_allow(
        monkeypatch, shape, kernel):
    """``DS_TPU_FORCE_PALLAS=1`` (the tests' way to the kernels on the CPU):
    the program of ``_rule_slots`` holds the ``pallas_call`` at a shape of
    the kernel's rule and the XLA form at another; ``use_kernel=False`` holds
    none."""
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    slots, heads, dk, dv = shape
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    args = (f32(slots, heads, dk), f32(slots, heads, dk),
            f32(slots, heads, dv), f32(slots, heads), f32(slots, heads),
            f32(*shape), jax.ShapeDtypeStruct((slots, ), bool),
            jax.ShapeDtypeStruct((slots, ), bool))
    text = str(jax.make_jaxpr(rf._rule_slots)(*args))
    assert ("ds_gated_delta_slot" in text) == kernel
    off = str(jax.make_jaxpr(
        lambda *a: rf._rule_slots(*a, use_kernel=False))(*args))
    assert "pallas_call" not in off
