"""``ds_selective_scan`` (``ops/pallas/selective_scan``, interpret mode)
against a plain ``lax.scan`` over the rows: runs of length 1, a run that
crosses token blocks and 8-row groups, a run from a non-zero state, a run
from position 0 over a dirty row, padding rows to slot 0, and the state rows
of slots without a run left as they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import selective_scan as module
from deepspeed_tpu.ops.pallas.selective_scan import (LOAD, STORE, ZERO,
                                                     channel_tile,
                                                     selective_scan)

S, SLOTS = 16, 6


def _plan(runs, T):
    """``runs``: (slot, first position, rows, first buffer row) each -> the
    kernel's ``slots``, ``flags``, ``n_live``."""
    slots, flags = np.zeros(T, np.int32), np.zeros(T, np.int32)
    n_live = 0
    for slot, p0, n, at in runs:
        slots[at:at + n] = slot
        flags[at] |= ZERO if p0 == 0 else LOAD
        flags[at + n - 1] |= STORE
        n_live = max(n_live, at + n)
    return slots, flags, np.asarray([n_live], np.int32)


def _inputs(T, chans, seed=0, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    # A and dt as published: A = -(1 .. S) by state index, dt log-uniform in
    # 0.001 .. 0.1: a state that remembers hundreds of tokens
    A = -jnp.broadcast_to(jnp.arange(1, S + 1, dtype=jnp.float32)[:, None],
                          (S, chans))
    dt = jnp.exp(jnp.asarray(rng.uniform(np.log(1e-3), np.log(1e-1),
                                         (T, chans)), jnp.float32))
    state = jnp.asarray(rng.standard_normal((SLOTS, S, chans)), dtype)
    return dt, dt * f32(T, chans), f32(T, S), f32(T, S), A, state


def _reference(dt, dtu, B, C, A, state, slots, flags):
    """One row a step; ``h`` float32 inside a run, the state's type where a
    run leaves it."""
    state = np.asarray(state.astype(jnp.float32))
    out_state, y = state.copy(), np.zeros(dt.shape, np.float32)
    h = np.zeros(A.shape, np.float32)
    dt, dtu, B, C, A = (np.asarray(a) for a in (dt, dtu, B, C, A))
    for t, (slot, flag) in enumerate(zip(slots, flags)):
        if not slot:
            continue
        if flag & LOAD:
            h = state[slot].copy()
        if flag & ZERO:
            h = np.zeros_like(h)
        h = np.exp(dt[t][None] * A) * h + dtu[t][None] * B[t][:, None]
        y[t] = (h * C[t][:, None]).sum(0)
        if flag & STORE:
            out_state[slot] = h
    return y, out_state


CASES = {
    # name: (runs, T, channels)
    "decode_rows_of_different_sequences": (
        [(s, 5 * s, 1, s - 1) for s in range(1, 6)], 8, 128),
    "a_run_from_position_zero_over_a_dirty_row": ([(2, 0, 11, 0)], 16, 128),
    "a_run_from_a_non_zero_state": ([(3, 40, 9, 0)], 16, 128),
    "a_run_that_crosses_token_blocks": (
        [(1, 7, 300, 0), (4, 0, 20, 300), (5, 90, 1, 320)], 344, 128),
    "prefill_chunks_decode_rows_then_padding": (
        [(1, 16, 21, 0), (2, 9, 1, 21), (3, 30, 1, 22), (4, 0, 13, 23)], 48,
        256),
    "two_channel_tiles": ([(1, 3, 10, 0), (2, 0, 5, 10)], 24, 1024 + 1024),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_matches_a_scan_over_the_rows(name):
    runs, T, chans = CASES[name]
    slots, flags, n_live = _plan(runs, T)
    dt, dtu, B, C, A, state = _inputs(T, chans)
    want_y, want_state = _reference(dt, dtu, B, C, A, state, slots, flags)
    y, new = selective_scan(dt, dtu, B, C, A, jnp.array(state),
                            jnp.asarray(slots), jnp.asarray(flags),
                            jnp.asarray(n_live))
    live = slots != 0
    # float32 arithmetic in another order; the state rounds to bfloat16 once
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live], rtol=2e-5,
                               atol=2e-5)
    assert not np.asarray(y)[~live].any()       # rows past n_live: zero
    ran = sorted({r[0] for r in runs})
    np.testing.assert_allclose(
        np.asarray(new.astype(jnp.float32))[ran], want_state[ran], rtol=1e-2,
        atol=1e-2)
    idle = [s for s in range(SLOTS) if s not in ran]
    np.testing.assert_array_equal(
        np.asarray(new.astype(jnp.float32))[idle],
        np.asarray(state.astype(jnp.float32))[idle])


def test_state_in_float32_is_the_scan_to_rounding():
    """With a float32 state nothing is rounded: the rows a run leaves are the
    scan's own."""
    runs, T, chans = CASES["prefill_chunks_decode_rows_then_padding"]
    slots, flags, n_live = _plan(runs, T)
    dt, dtu, B, C, A, state = _inputs(T, chans, dtype=jnp.float32)
    _, want_state = _reference(dt, dtu, B, C, A, state, slots, flags)
    _, new = selective_scan(dt, dtu, B, C, A, jnp.array(state),
                            jnp.asarray(slots), jnp.asarray(flags),
                            jnp.asarray(n_live))
    np.testing.assert_allclose(np.asarray(new), want_state, rtol=2e-5,
                               atol=2e-5)


def test_channel_tiles_follow_from_the_channels():
    assert channel_tile(5120) == 1024 and channel_tile(1536) == 512
    assert channel_tile(128) == 128 and channel_tile(96) is None


@pytest.mark.parametrize("slots, tile", [
    (257, 1024), (512, 1024), (513, 512), (1025, 256), (4096, 128),
    (4097, None)])
def test_a_channel_tile_holds_every_slots_state_in_vmem(slots, tile):
    """The kernel keeps ALL slots' state of a channel tile in VMEM, in and
    out, double-buffered: more slots take a narrower tile, and past the
    narrowest the shape stays on the ``lax.scan`` (no engine setting asks
    Mosaic for memory it does not have)."""
    state = jax.ShapeDtypeStruct((slots, 16, 5120), jnp.bfloat16)
    assert module.state_tile(state) == tile
    if tile:
        assert 4 * slots * 16 * tile * 2 <= module._STATE_VMEM_BYTES \
            < module._VMEM_BYTES
    assert module.TOKEN_BLOCK % 8 == 0
