"""``ds_selective_scan``: the Mamba-1 recurrence over the runs of a ragged
buffer, the state rows of the cache in place.

    h_t[:, c] = exp(dt_t[c] A[:, c]) h_{t-1}[:, c] + dt_t[c] u_t[c] B_t
    y_t[c]    = h_t[:, c] . C_t

``A`` differs by channel AND state index, so the recurrence has no matrix
form: it is vector work, ``S x C`` state elements a token.  As plain XLA over
a chunk of ``T`` tokens it materialises ``[T, S, C]`` float32 several times;
here a channel tile's ``[S, tc]`` state stays in registers while the kernel
walks the buffer's rows in order, and ``dt``, ``dt * u`` are read and ``y``
written once.

Layout: the grid is ``(channel tiles, token blocks)``, the token blocks of a
channel tile in order.  A grid step holds ALL slots' state of its channel
tile in VMEM (``[slots, S, tc]``, the cache's own buffer, aliased in and
out): a run (the contiguous rows of one sequence) takes its slot's row at its
first token (zeros where it starts at position 0: nothing is cleared on the
host) and leaves it at its last, by dynamic index; between a run's tokens
``h`` is float32 and never rounded.  The state's ``S`` values lie on the
sublanes and the channels on the lanes, so ``dt`` and ``dt * u`` rows are used as
they lie in ``[T, C]`` (a sublane broadcast) and ``y`` is a sublane sum;
``B_t`` and ``C_t`` have to be constant along the lanes, so the caller hands
them lane-broadcast (``[T, S, 128]`` float32: 8 KB a token, read once a
channel tile).  docs/kernels.md has what a token costs and the v5e readings.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret_mode as _interpret

LANES = 128
#: a run's first row takes its slot's state / starts from zeros; its last row
#: leaves the state (the ``flags`` of :func:`selective_scan`)
LOAD, ZERO, STORE = 1, 2, 4
#: rows of a token block, and the channels of a tile where they divide
TOKEN_BLOCK = 256
CHANNEL_TILES = (1024, 512, 256, 128)
#: the kernel's VMEM: a channel tile's state of every slot, in and out, each
#: double-buffered (4 x 8.4 MB at 257 slots x 16 x 1024 bfloat16), and the
#: token blocks of dt, dt * u, y, B and C; the state's four buffers may take
#: two thirds of it
_VMEM_BYTES = 96 * 1024 * 1024
_STATE_VMEM_BYTES = 64 * 1024 * 1024


def channel_tile(channels, state_row_bytes=0):
    """Channels of a grid step's tile, or None (the shape stays on XLA): the
    widest that divides the channels and whose state block of EVERY slot
    (``state_row_bytes``: slots x states x the type's bytes, of one channel)
    fits the kernel's VMEM four times.  At 16 states in bfloat16: 1024
    channels up to 512 slots, 128 up to 4096."""
    for tc in CHANNEL_TILES:
        if channels % tc == 0 and 4 * tc * state_row_bytes <= _STATE_VMEM_BYTES:
            return tc
    return None


def state_tile(state):
    """:func:`channel_tile` of a state buffer ``[slots, S, C]``."""
    slots, S, chans = state.shape
    return channel_tile(chans, slots * S * jnp.dtype(state.dtype).itemsize)


def _kernel(slots_ref, flags_ref, live_ref, dt_ref, dtu_ref, b_ref, c_ref,
            a_ref, state_in, y_ref, state_ref, h_ref, *, tb, tc):
    j = pl.program_id(1)
    cols = [slice(k * LANES, (k + 1) * LANES) for k in range(tc // LANES)]

    @pl.when(j == 0)
    def _first():
        state_ref[...] = state_in[...]
        h_ref[...] = jnp.zeros_like(h_ref)

    n = jnp.clip(live_ref[0] - j * tb, 0, tb)

    @pl.when(n < tb)
    def _dead_rows():
        y_ref[...] = jnp.zeros_like(y_ref)

    def token(t, h, dt, dtu, B, C):
        """One row: ``dt``, ``dtu`` ``[1, tc]``, ``B``, ``C`` ``[S, LANES]``;
        ``h`` a channel column's ``[S, LANES]`` each.  Returns (h, y)."""
        slot, flag = slots_ref[t], flags_ref[t]
        h = jax.lax.cond(
            (flag & (LOAD | ZERO)) != 0,
            lambda: tuple(jnp.where(
                (flag & ZERO) != 0, 0.0,
                state_ref[slot, :, c].astype(jnp.float32)) for c in cols),
            lambda: h)
        h = tuple(jnp.exp(dt[:, c] * a_ref[:, c]) * hc + dtu[:, c] * B
                  for c, hc in zip(cols, h))

        @pl.when((flag & STORE) != 0)
        def _leave():
            for c, hc in zip(cols, h):
                state_ref[slot, :, c] = hc.astype(state_ref.dtype)
        return h, [jnp.sum(hc * C, axis=0, keepdims=True) for hc in h]

    def rows8(g, h):
        """Eight rows (a float32 sublane tile of ``dt`` and ``y``)."""
        at = pl.ds(pl.multiple_of(g * 8, 8), 8)
        dt8, dtu8 = dt_ref[at, :], dtu_ref[at, :]
        ys = []
        for r in range(8):
            i = g * 8 + r
            h, y = token(j * tb + i, h, dt8[r:r + 1], dtu8[r:r + 1],
                         b_ref[i], c_ref[i])
            ys.append([jnp.where(i < n, yc, 0.0) for yc in y])   # a dead row
        for k, c in enumerate(cols):
            y_ref[at, c] = jnp.concatenate([y[k] for y in ys], axis=0)
        return h

    h = jax.lax.fori_loop(0, (n + 7) // 8, rows8,
                          tuple(h_ref[:, c] for c in cols))
    for c, hc in zip(cols, h):
        h_ref[:, c] = hc


@functools.partial(jax.jit, donate_argnums=(5, ))
def selective_scan(dt, dtu, B, C, A, state, slots, flags, n_live):
    """The recurrence over the rows of a ragged buffer.

    dt and dtu (``dt * u``) ``[T, C]`` float32, B and C ``[T, S]`` float32, A
    ``[S, C]`` float32; state ``[slots, S, C]`` (donated; a row a sequence slot, in
    the cache's type); slots, flags ``[T]`` int32 (``LOAD`` / ``ZERO`` on a
    run's first row, ``STORE`` on its last); n_live ``[1]`` int32: the rows
    past it are not walked (their ``y`` is zero).  Returns ``(y [T, C]
    float32, state)``."""
    T, chans = dt.shape
    S = A.shape[0]
    tc = state_tile(state)
    tb = min(TOKEN_BLOCK, -(-T // 8) * 8)
    pad = -T % tb
    rows = lambda a: jnp.pad(a, ((0, pad), ) + ((0, 0), ) * (a.ndim - 1))
    lanes = lambda a: jnp.broadcast_to(
        rows(a.astype(jnp.float32))[:, :, None], (T + pad, S, LANES))
    token_rows = pl.BlockSpec((tb, tc), lambda c, j, *_: (j, c))
    token_state = pl.BlockSpec((tb, S, LANES), lambda c, j, *_: (j, 0, 0))
    slot_state = pl.BlockSpec((state.shape[0], S, tc),
                              lambda c, j, *_: (0, 0, c))
    y, state = pl.pallas_call(
        functools.partial(_kernel, tb=tb, tc=tc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(chans // tc, (T + pad) // tb),
            in_specs=[token_rows, token_rows, token_state, token_state,
                      pl.BlockSpec((S, tc), lambda c, j, *_: (0, c)),
                      slot_state],
            out_specs=[token_rows, slot_state],
            scratch_shapes=[pltpu.VMEM((S, tc), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((T + pad, chans), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=_interpret(),
        name="ds_selective_scan",
    )(rows(slots.astype(jnp.int32)), rows(flags.astype(jnp.int32)),
      n_live.astype(jnp.int32), rows(dt), rows(dtu), lanes(B), lanes(C), A,
      state)
    return y[:T], state
