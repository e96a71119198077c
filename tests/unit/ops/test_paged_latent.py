"""The paged LATENT kernel (``ops/pallas/paged_attention.
paged_latent_attention``, ``ds_paged_latent``, interpret mode): equal to the
XLA gather on every row a sequence owns and zero on dead rows, over runs that
fill a tile, runs inside one slab (a decode token's heads), dead rows between
them and contexts that end on and across a page boundary; and the page loads
``kernel_page_loads`` counts for it are the items ``run_plan`` hands the
kernel at the tile ``tile_rows`` picks.  A long run's tile item takes a block
of ``item_pages`` pages through one softmax update: the one-page items'
result to the rounding of a float32 sum taken in another order, and the
pages counted are the same."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.ragged_forward import _latent_attention
from deepspeed_tpu.ops.pallas import paged_attention as paged_module
from deepspeed_tpu.ops.pallas.paged_attention import (
    item_pages, kernel_page_loads, latent_tiled, paged_latent_attention,
    run_plan, slab_rows, tile_rows)

RANK, ROPE, ROW, BS = 32, 8, 128, 8
SCALE = 24 ** -0.5
#: the pages of a block at the tests' page of 8 rows, in either type
P = 8


def _case(heads, runs, T, maxb=8, dtype=jnp.float32, seed=0, max_seqs=8):
    """``runs``: (slot, first position, rows, first buffer row) each; every
    other row is dead (slot 0, position 0)."""
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
    q = jnp.asarray(rng.standard_normal((T, heads, ROW)) * used, dtype)
    pages = jnp.asarray(rng.standard_normal((nb, BS, ROW)) * used, dtype)
    return q, pages, jnp.asarray(tables), slots, pos


CASES = {
    # name: (heads, runs, T, kwargs, expected page loads, of those short[,
    # of those in blocks of P pages: 0 where none is given]).
    # 8 heads: a tile of 1024 query rows is 128 tokens, a slab one token
    "a_prefill_run_and_two_decode_rows": (
        8, [(1, 3, 40, 0), (2, 17, 1, 40), (3, 63, 1, 41)], 48, {},
        6 + 3 + 8, 3 + 8),
    "dead_rows_between_runs": (
        8, [(1, 5, 9, 2), (2, 30, 6, 14)], 32, {}, 2 + 5, 0),
    # position 7 is a page's last row, 8 the next page's first
    "contexts_that_end_on_and_across_a_page_boundary": (
        8, [(1, 7, 1, 0), (2, 8, 1, 1), (3, 0, 8, 2), (4, 0, 9, 10)], 24, {},
        1 + 2 + 1 + 2, 1 + 2),
    "decode_burst_layout": (
        8, [(s, 5 * s, 1, s) for s in (1, 2, 4, 7)], 8, {},
        sum(5 * s // 8 + 1 for s in (1, 2, 4, 7)),
        sum(5 * s // 8 + 1 for s in (1, 2, 4, 7))),
    # 128 heads: a tile is 8 tokens; the run of 20 crosses two tile ends and
    # its last 4 rows are a run of the third tile
    "heads_128_a_run_across_tiles": (
        128, [(1, 10, 20, 0), (2, 33, 1, 20)], 24, {}, 3 + 4 + 4 + 5, 5),
    "bfloat16_cache_16_heads": (
        16, [(1, 3, 40, 0), (2, 20, 1, 40)], 48, {"dtype": jnp.bfloat16},
        6 + 3, 3),
    "tokens_not_a_multiple_of_8": (
        8, [(3, 0, 37, 0), (4, 11, 1, 37)], 43, {}, 5 + 2, 2),
    # ---- runs with whole blocks of P = 8 pages (64 keys)
    # positions 20..147 fill the tile of 128 tokens: pages 0-18 = 2 P + 3
    "a_run_of_two_blocks_and_three_pages_fills_its_tile": (
        8, [(1, 20, 128, 0)], 128, {"maxb": 24}, 19, 0, 16),
    # positions 0..127: two blocks and no rest; the first rows see one key
    "whole_blocks_and_no_rest": (
        8, [(1, 0, 128, 0)], 128, {"maxb": 16}, 16, 0, 16),
    # 128 heads, tiles of 8 tokens: 60..67 (pages 0-8), 68..75 (0-9) and
    # 76..79 (0-9) are three runs of a block and a rest; a decode row
    "heads_128_a_run_with_blocks_crosses_a_tiles_end": (
        128, [(1, 60, 20, 0), (2, 33, 1, 20)], 24, {"maxb": 12},
        9 + 10 + 10 + 5, 5, 24),
    # 100..129 (pages 0-16) between dead rows and three decode rows
    "blocks_beside_decode_rows_and_dead_rows": (
        8, [(2, 17, 1, 2), (1, 100, 30, 5), (3, 63, 1, 40), (4, 64, 1, 41)],
        48, {"maxb": 24}, 3 + 17 + 8 + 9, 3 + 8 + 9, 16),
    # 70..109 (pages 0-13: one block, six pages one by one)
    "bfloat16_cache_16_heads_with_a_block": (
        16, [(1, 70, 40, 0), (2, 20, 1, 40)], 48,
        {"dtype": jnp.bfloat16, "maxb": 16}, 14 + 3, 3, 8),
}


@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_is_the_gather_and_its_loads_are_counted(name):
    heads, runs, T, kw, want_loads, want_short, *want_block = CASES[name]
    q, pages, tables, slots, pos = _case(heads, runs, T, **kw)
    assert latent_tiled(heads, pages.dtype)
    assert item_pages(1, ROW, pages.dtype, BS) == P
    out = paged_latent_attention(q, pages, tables, jnp.asarray(slots),
                                 jnp.asarray(pos), rank=RANK, scale=SCALE)
    ref = _latent_attention(q, pages, tables, jnp.asarray(slots),
                            jnp.asarray(pos), BS, rank=RANK, scale=SCALE,
                            use_kernel=False)
    assert out.shape == (T, heads, RANK) and out.dtype == q.dtype
    live = slots != 0
    # float32: the sums' order differs (a page at a time against all keys at
    # once); bfloat16: the probabilities are rounded to it for the value dot
    tol = 2e-5 if pages.dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               atol=tol, rtol=tol)
    assert not np.asarray(out, np.float32)[~live].any()
    # what is counted is what runs: the items of run_plan at the kernel's tile
    tq = tile_rows(heads, 1, ROW, pages.dtype, T, latent=True)
    assert tq == max(8, 1024 // heads // 8 * 8)
    *_, n_pages, slab, n_blocks = run_plan(np, slots, pos, tq, BS, 0, heads,
                                           P)
    grid, _, short, block = kernel_page_loads(
        slots, pos, heads=heads, kv_heads=1, head_dim=ROW,
        kv_dtype=pages.dtype, block_size=BS, maxb=tables.shape[1],
        latent=True)
    assert grid == int(n_pages.sum()) == want_loads
    assert block == int(n_blocks.sum()) * P == sum(want_block)
    assert short == int(n_pages[slab >= 0].sum()) == want_short
    assert not n_blocks[slab >= 0].any() and block + short <= grid


@pytest.mark.parametrize("name", list(CASES))
def test_block_items_are_one_page_items_with_their_sums_reordered(
        name, monkeypatch):
    """Against the same kernel with the blocks off (every item one page, the
    kernel as it was): a live row's result to the rounding of a float32 sum
    taken in another order, dead rows zero in both; where no run holds a
    block, the same bits; and the pages counted do not depend on the items'
    size."""
    heads, runs, T, kw, want_loads, want_short, *want_block = CASES[name]
    q, pages, tables, slots, pos = _case(heads, runs, T, seed=2, **kw)
    args = (q, pages, tables, jnp.asarray(slots), jnp.asarray(pos))
    shapes = dict(heads=heads, kv_heads=1, head_dim=ROW, kv_dtype=pages.dtype,
                  block_size=BS, maxb=tables.shape[1], latent=True)
    out = paged_latent_attention(*args, rank=RANK, scale=SCALE)
    assert kernel_page_loads(slots, pos, **shapes)[2:] == (
        want_short, sum(want_block))
    monkeypatch.setattr(paged_module, "item_pages", lambda *a: 1)
    one_page = paged_latent_attention.__wrapped__(*args, rank=RANK,
                                                  scale=SCALE)
    assert kernel_page_loads(slots, pos, **shapes) == (
        want_loads, 0, want_short, 0)
    out, one_page = (np.asarray(a, np.float32) for a in (out, one_page))
    assert not out[slots == 0].any() and not one_page[slots == 0].any()
    if not want_block:
        np.testing.assert_array_equal(out.view(np.uint32),
                                      one_page.view(np.uint32))
    # a bfloat16 output: one rounding
    tol = 2e-5 if pages.dtype == jnp.float32 else 8e-3
    np.testing.assert_allclose(out, one_page, atol=tol, rtol=tol)


def test_a_burst_is_k_calls():
    """``[k, rows]`` positions are ``k`` calls of the kernel: the loads add."""
    slots = np.tile(np.array([0, 1, 2, 0, 4], np.int32), (3, 1))
    pos = np.array([0, 14, 7, 0, 30], np.int32)[None] + np.arange(3)[:, None]
    pos = np.where(slots != 0, pos, 0)
    kw = dict(heads=128, kv_heads=1, head_dim=640, kv_dtype=jnp.bfloat16,
              block_size=BS, maxb=8, latent=True)
    each = [kernel_page_loads(slots[i], pos[i], **kw) for i in range(3)]
    assert kernel_page_loads(slots, pos, **kw) == tuple(
        sum(e[i] for e in each) for i in range(4))
    # positions 7 -> 8, 15 -> 16 and 31 -> 32 cross a page: a load more
    assert [e[0] for e in each] == [2 + 1 + 4, 2 + 2 + 4, 3 + 2 + 5]


def test_which_shapes_the_kernel_takes():
    """A token's heads fill whole sublane tiles of the cache's type; the
    shapes it does not take stay on the gather, and the count then is one
    grid row a token (every row times every page of the table)."""
    assert latent_tiled(128, jnp.bfloat16) and latent_tiled(8, jnp.float32)
    assert not latent_tiled(8, jnp.bfloat16)
    assert not latent_tiled(12, jnp.float32)
    assert not latent_tiled(128, jnp.int8)
    assert tile_rows(12, 1, ROW, jnp.float32, 64, latent=True) is None
    assert tile_rows(128, 1, 640, jnp.bfloat16, 1024, latent=True) == 8
    assert tile_rows(128, 1, 640, jnp.bfloat16, 65, latent=True) == 8
    assert slab_rows(128) == 128 and slab_rows(8) == 8
    with pytest.raises(ValueError, match="latent_tiled"):
        q, pages, tables, slots, pos = _case(12, [(1, 0, 4, 0)], 8)
        paged_latent_attention(q, pages, tables, jnp.asarray(slots),
                               jnp.asarray(pos), rank=RANK, scale=SCALE)
    grid, _, short, _ = kernel_page_loads(
        np.array([1, 1, 0]), np.array([8, 9, 0]), heads=12, kv_heads=1,
        head_dim=ROW, kv_dtype=jnp.float32, block_size=BS, maxb=5,
        latent=True)
    assert (grid, short) == (15, 0)
