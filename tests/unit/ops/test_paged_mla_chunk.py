"""The EXPANDED form's reader of the paged latent cache (``ops/pallas/
paged_attention.paged_mla_chunk_attention``, ``ds_paged_mla_chunk``, interpret
mode): on the rows of every run of ``min_rows`` rows or more it is the dense
expanded form (per-head keys ``(c W_uk ; k_r)`` and values ``c W_uv`` made
from the pages) and the absorbed kernel ``ds_paged_latent`` on the same pages,
zero on every other row; the rule that picks a row's form
(``expanded_min_rows``, ``latent_row_forms``) reads the widths and the rows
alone and gives the host and the device the same rows; and what the batch
builder counts for it (``chunk_page_loads``) is what its loops do."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import paged_attention as paged_module
from deepspeed_tpu.ops.pallas.paged_attention import (
    chunk_page_loads, chunk_tile_rows, chunk_tiled, expanded_min_rows,
    kernel_page_loads, latent_min_rows, latent_row_forms,
    paged_latent_attention, paged_mla_chunk_attention)

RANK, ROPE, ROW, BS, DN, DV = 32, 8, 128, 8, 16, 16
W = DN + ROW - RANK
SCALE = (DN + ROPE) ** -0.5
#: the tests' rule: a run of 10 rows or more is expanded
MIN_ROWS = 10


def _case(heads, runs, T, maxb=24, dtype=jnp.float32, seed=0, max_seqs=8):
    """``runs``: (slot, first position, rows, first buffer row) each; every
    other row is dead.  Returns the query's two parts, the pages, the two
    up-projections, the table and the rows."""
    rng = np.random.default_rng(seed)
    nb = 1 + max_seqs * maxb
    tables = np.zeros((max_seqs, maxb), np.int32)
    perm = rng.permutation(np.arange(1, nb))
    slots, pos = np.zeros(T, np.int32), np.zeros(T, np.int32)
    for slot, p0, n, at in runs:
        used = (p0 + n - 1) // BS + 1
        tables[slot, :used] = perm[slot * maxb:slot * maxb + used]
        slots[at:at + n] = slot
        pos[at:at + n] = np.arange(p0, p0 + n)
    used = np.arange(ROW) < RANK + ROPE          # the row's tail is zeros
    q_n = jnp.asarray(rng.standard_normal((T, heads, DN)), dtype)
    q_r = jnp.asarray(rng.standard_normal((T, heads, ROPE)), dtype)
    pages = jnp.asarray(rng.standard_normal((nb, BS, ROW)) * used, dtype)
    w_uk, w_uv = (jnp.asarray(rng.standard_normal((RANK, heads, d))
                              * RANK ** -0.5, dtype) for d in (DN, DV))
    return q_n, q_r, pages, w_uk, w_uv, jnp.asarray(tables), slots, pos


def _padded(*parts):
    q = jnp.concatenate(parts, axis=-1)
    return jnp.pad(q, ((0, 0), (0, 0), (0, ROW - RANK - ROPE)))


def _expanded(case, min_rows=MIN_ROWS, fn=paged_mla_chunk_attention):
    q_n, q_r, pages, w_uk, w_uv, tables, slots, pos = case
    return np.asarray(fn(
        _padded(q_n, q_r), pages, w_uk, w_uv, tables, jnp.asarray(slots),
        jnp.asarray(pos), rank=RANK, scale=SCALE, min_rows=min_rows),
        np.float32)


def _absorbed(case):
    """The same rows through ``ds_paged_latent``: the query into the latent
    space, the kernel, the output through ``W_uv``."""
    q_n, q_r, pages, w_uk, w_uv, tables, slots, pos = case
    f32 = lambda a: a.astype(jnp.float32)
    q_lat = jnp.einsum("thn,chn->thc", f32(q_n), f32(w_uk))
    o_lat = paged_latent_attention(
        _padded(q_lat.astype(pages.dtype), q_r), pages, tables,
        jnp.asarray(slots), jnp.asarray(pos), rank=RANK, scale=SCALE)
    return np.asarray(jnp.einsum("thc,chv->thv", f32(o_lat), f32(w_uv)))


def _dense(case):
    """The published form, a row and a head at a time, in float64."""
    q_n, q_r, pages, w_uk, w_uv, tables, slots, pos = (
        np.asarray(a, np.float64) if a.dtype != np.int32 else np.asarray(a)
        for a in case)
    out = np.zeros(q_n.shape[:2] + (DV, ))
    for t in np.flatnonzero(slots):
        ctx = pages[tables[slots[t]]].reshape(-1, ROW)[:pos[t] + 1]
        c, k_r = ctx[:, :RANK], ctx[:, RANK:RANK + ROPE]
        for h in range(q_n.shape[1]):
            s = (c @ w_uk[:, h] @ q_n[t, h] + k_r @ q_r[t, h]) * SCALE
            p = np.exp(s - s.max())
            out[t, h] = p / p.sum() @ (c @ w_uv[:, h])
    return out


CASES = {
    # name: (heads, runs, T, kwargs)
    "one_run_with_no_cached_context": (8, [(1, 0, 40, 0)], 48, {}),
    "a_run_after_one_cached_page": (8, [(1, 8, 30, 2)], 32, {}),
    "a_run_that_starts_mid_page_after_many_pages": (
        8, [(1, 101, 40, 3)], 48, {}),
    # the decode rows and the run of 3 are the other form's: dead here
    "two_runs_in_a_tile_beside_rows_of_the_other_form": (
        8, [(2, 17, 1, 0), (1, 100, 40, 1), (3, 5, 35, 41), (4, 9, 3, 76)],
        80, {}),
    "heads_64": (64, [(5, 13, 1, 0), (1, 20, 22, 1)], 24, {}),
    "heads_128": (128, [(1, 20, 24, 0)], 24, {}),
    "bfloat16_cache_16_heads": (
        16, [(1, 20, 30, 1), (2, 4, 1, 31)], 32, {"dtype": jnp.bfloat16}),
}


@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_is_the_dense_expanded_form_and_the_absorbed_kernel(name):
    heads, runs, T, kw = CASES[name]
    case = _case(heads, runs, T, **kw)
    slots, pos = case[-2:]
    out = _expanded(case)
    assert out.shape == (T, heads, DV)
    rows = latent_row_forms(np, slots, pos, MIN_ROWS)
    want = np.zeros(T, bool)
    for slot, _, n, at in runs:
        want[at:at + n] = n >= MIN_ROWS
    np.testing.assert_array_equal(rows, want)
    # every row of the other form, and every dead row, comes back zero
    assert not out[~rows].any()
    # float32: the sums' order differs; bfloat16: the made keys and values
    # and the probabilities are rounded to it, as the published form's are
    tol = 2e-5 if case[2].dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out[rows], _dense(case)[rows], atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(out[rows], _absorbed(case)[rows], atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("sub,tile,keys", [(16, 32, 16), (8, 64, 8),
                                           (16, 16, 64)])
def test_stretches_tiles_and_blocks_of_other_sizes_are_the_same_numbers(
        monkeypatch, sub, tile, keys):
    """A tile of several stretches of rows (a block's keys are made once
    for all of them), a buffer of several tiles (a run is cut at a tile's
    end and each piece is a run), blocks of one or several pages: runs that
    cross a stretch's and a tile's end, interior squares with no mask."""
    runs = [(2, 17, 1, 0), (1, 100, 20, 1), (3, 5, 11, 21), (4, 9, 3, 32),
            (5, 0, 25, 35)]
    case = _case(8, runs, 64)
    slots, pos = case[-2:]
    whole = _expanded(case)
    for name, value in (("_CHUNK_SUB_ROWS", sub), ("_CHUNK_TILE_ROWS", tile),
                        ("_CHUNK_BLOCK_KEYS", keys)):
        monkeypatch.setattr(paged_module, name, value)
    assert chunk_tile_rows(64, MIN_ROWS) == (tile, sub, tile // MIN_ROWS)
    out = _expanded(case, fn=paged_mla_chunk_attention.__wrapped__)
    rows = latent_row_forms(np, slots, pos, MIN_ROWS)
    # tiles of 16: the run of 20 rows from buffer row 1 is a piece of 15 and
    # one of 5 (the other form's), the run of 25 from row 35 one of 13 and
    # one of 12
    assert rows.sum() == {16: 15 + 11 + 13 + 12, 32: 20 + 11 + 25,
                          64: 20 + 11 + 25}[tile]
    assert not out[~rows].any()
    np.testing.assert_allclose(out[rows], _dense(case)[rows], atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(out[rows], whole[rows], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("widths,heads,tokens", [
    ((512, 128, 64, 128), 128, 1024),       # openPangu-Ultra-MoE's step
    ((512, 128, 64, 128), 64, 2048),        # LongCat-Flash's
])
def test_a_run_one_row_under_and_one_over_the_break_even(widths, heads,
                                                         tokens):
    """The rule reads the published widths alone: an absorbed pair costs a
    head 2 x (2 x 512 + 64) operations, an expanded one 2 x (128 + 64 + 128)
    and 2 x 512 x (128 + 128) a context token once a run: even at 170.67
    rows."""
    rank, nope, rope, value = widths
    min_rows = expanded_min_rows(*widths)
    assert min_rows == 171
    absorbed = lambda n: n * 2 * (2 * rank + rope)
    expanded = lambda n: n * 2 * (nope + rope + value) \
        + 2 * rank * (nope + value)
    assert expanded(min_rows) < absorbed(min_rows)
    assert expanded(min_rows - 1) > absorbed(min_rows - 1)
    slots, pos = np.zeros(tokens, np.int32), np.zeros(tokens, np.int32)
    slots[:3], pos[:3] = (1, 2, 3), (900, 17, 4000)        # decode rows
    slots[3:173], pos[3:173] = 4, np.arange(50, 220)       # 170 rows
    slots[173:344], pos[173:344] = 5, np.arange(0, 171)    # 171 rows
    rows = latent_row_forms(np, slots, pos, min_rows)
    assert rows.sum() == 171 and rows[173:344].all()
    # a burst's buffer cannot hold one long run: no row is ever expanded
    cfg = type("Cfg", (), dict(
        kv_lora_rank=rank, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
        v_head_dim=value, num_attention_heads=heads))
    assert latent_min_rows(cfg, 640, jnp.bfloat16, tokens) == 171
    assert latent_min_rows(cfg, 640, jnp.bfloat16, 65) is None
    assert latent_min_rows(cfg, 640, jnp.int8, tokens) is None


def test_the_rule_where_the_absorbed_pair_is_never_dearer():
    assert expanded_min_rows(32, 16, 8, 16) == 33
    assert expanded_min_rows(64, 64, 32, 64) is None
    assert chunk_tiled(32, 16, 16, 128, jnp.float32)       # interpreted
    assert not chunk_tiled(32, 16, 16, 128, jnp.int8)


@pytest.mark.parametrize("seed", range(4))
def test_the_host_and_the_device_pick_the_same_rows(seed):
    """``latent_row_forms`` with numpy (the batch builder) and jax.numpy
    (the step program) on random steps: decode rows first, then chunks of
    every length around the rule, dead rows at the end; a burst's ``[k,
    rows]``."""
    rng = np.random.default_rng(seed)
    T = 96
    slots, pos = np.zeros(T, np.int32), np.zeros(T, np.int32)
    at, slot = 0, 1
    while at < T - 4:
        n = int(rng.choice([1, 1, 2, MIN_ROWS - 1, MIN_ROWS, MIN_ROWS + 1,
                            30]))
        n = min(n, T - 4 - at)
        p0 = int(rng.integers(0, 200))
        slots[at:at + n], pos[at:at + n] = slot, np.arange(p0, p0 + n)
        at, slot = at + n, slot + 1
    host = latent_row_forms(np, slots, pos, MIN_ROWS)
    device = jax.jit(lambda s, p: latent_row_forms(jnp, s, p, MIN_ROWS))(
        slots, pos)
    np.testing.assert_array_equal(host, np.asarray(device))
    assert host.any() and (~host[slots != 0]).any()
    many = np.stack([slots, np.roll(slots, 3)]), np.stack([pos, pos])
    np.testing.assert_array_equal(
        latent_row_forms(np, *many, MIN_ROWS),
        np.asarray(latent_row_forms(jnp, *map(jnp.asarray, many),
                                    MIN_ROWS)))
    assert not latent_row_forms(np, slots, pos, None).any()


def test_the_counts_of_a_mixed_step_add_up():
    """``chunk_page_loads``: the rows, (row, key) pairs and latent pages of
    the expanded kernel's call; with the absorbed kernel's count of the
    other rows they are the step's live rows and pairs."""
    runs = [(2, 17, 1, 0), (1, 100, 40, 1), (3, 5, 35, 41), (4, 9, 3, 76)]
    *_, slots, pos = _case(8, runs, 80)
    took, keys, pages = chunk_page_loads(slots, pos, heads=8, block_size=BS,
                                         min_rows=MIN_ROWS)
    forms = latent_row_forms(np, slots, pos, MIN_ROWS)
    np.testing.assert_array_equal(took[0], forms)
    rows = int(forms.sum())
    assert rows == 40 + 35
    assert keys == sum(range(101, 141)) + sum(range(6, 41))
    # blocks of 1024 keys (128 pages of 8): run 1's context of 140 keys and
    # run 3's of 40 are one block each, brought in once a head
    assert pages == 2 * 128 * 8
    absorbed = (slots != 0) & ~forms
    assert absorbed.sum() == 1 + 3 and rows + absorbed.sum() == 79
    assert keys + int((pos + 1)[absorbed].sum()) \
        == int((pos + 1)[slots != 0].sum())
    grid, *_ = kernel_page_loads(
        np.where(forms, 0, slots), pos, heads=8, kv_heads=1, head_dim=ROW,
        kv_dtype=jnp.float32, block_size=BS, maxb=24, latent=True)
    assert grid == 3 + 2            # positions 17, and 9..11
    # a burst's [k, rows] calls: nothing
    took, keys, pages = chunk_page_loads(
        np.ones((3, 8), np.int32), np.zeros((3, 8)), heads=8, block_size=BS,
        min_rows=None)
    assert not took.any() and (keys, pages) == (0, 0)


def test_a_shape_the_kernel_does_not_take_is_refused():
    case = _case(8, [(1, 0, 12, 0)], 16)
    q_n, q_r, pages, w_uk, w_uv, tables, slots, pos = case
    with pytest.raises(ValueError, match="chunk_tiled"):
        paged_mla_chunk_attention(
            jnp.concatenate([q_n, q_r], -1), pages, w_uk, w_uv, tables,
            jnp.asarray(slots), jnp.asarray(pos), rank=RANK, scale=SCALE,
            min_rows=MIN_ROWS)
