"""The paged-attention kernels (``ops/pallas/paged_attention.
paged_attention``, interpret mode), run-tiled and per-token: equal to the XLA
gather on every row a sequence owns, zero on dead rows, and the run-tiled
kernel's page loads are the ones ``kernel_page_loads`` counts: the count
``InferenceEngineV2._page_counts`` reports.  An item of the run-tiled kernel
computes one slab of rows where its run lies inside one (a decode token), the
whole tile otherwise: the same bits either way, and the short items the kernel
ran are the ones the host counts.  An item of a LONG run takes a block of
``item_pages`` pages through one softmax update: the one-page items' result to
the rounding of a reordered float32 sum, and every count still one of pages."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2 import ragged_forward
from deepspeed_tpu.inference.v2.ragged_forward import _paged_attention
from deepspeed_tpu.inference.v2.ragged import window_row_positions
from deepspeed_tpu.models import llama
from deepspeed_tpu.ops.pallas import paged_attention as paged_module
from deepspeed_tpu.ops.pallas.paged_attention import (item_pages,
                                                      kernel_page_loads,
                                                      paged_attention,
                                                      run_tiled, slab_rows)

MAX_SEQS = 8


def _case(heads, kv_heads, runs, T, bs=8, maxb=8, window=0,
          dtype=jnp.float32, seed=0, head_dim=128, max_seqs=MAX_SEQS):
    """``runs``: (slot, first position, rows, first buffer row) each; every
    other row is dead (slot 0, position 0)."""
    rng = np.random.default_rng(seed)
    nb = 1 + max_seqs * maxb
    tables = np.zeros((max_seqs, maxb), np.int32)
    perm = rng.permutation(np.arange(1, nb))
    slots, pos = np.zeros(T, np.int32), np.zeros(T, np.int32)
    for slot, p0, n, at in runs:
        # only the pages the sequence has reached are in its table
        used = (p0 + n - 1) // bs + 1
        tables[slot, :used] = perm[slot * maxb:slot * maxb + used]
        slots[at:at + n] = slot
        pos[at:at + n] = np.arange(p0, p0 + n)
    q = jnp.asarray(rng.standard_normal((T, heads, head_dim)), dtype)
    kc, vc = (jnp.asarray(rng.standard_normal((nb, bs, kv_heads, head_dim)),
                          dtype) for _ in range(2))
    if kv_heads == 1 and head_dim == 128 and dtype == jnp.bfloat16:
        # a multi-query page of a 16-bit cache holds two tokens a row: the
        # same bytes (paged_attention.page_row_tokens)
        kc, vc = (c.reshape(nb, bs // 2, 2, head_dim) for c in (kc, vc))
    return q, kc, vc, jnp.asarray(tables), slots, pos


def _bs(kc, kv_heads):
    """The tokens of a page (its rows, but in a cache of token pairs)."""
    return kc.shape[1] * kc.shape[2] // kv_heads


def _assert_is_the_gather(out, q, kc, vc, tables, slots, pos, window,
                          bs=None):
    """``out`` equals the XLA gather on every live row and is zero on dead
    ones."""
    ref = _paged_attention(q, kc, vc, tables, jnp.asarray(slots),
                           jnp.asarray(pos), bs or kc.shape[1],
                           window=window, use_kernel=False)
    live = slots != 0
    tol = 2e-5 if kc.dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               atol=tol, rtol=tol)
    assert not np.asarray(out, np.float32)[~live].any()


def _eva(position, window=32, chunk=4):
    """A position inside EvaByte's block-table row ``[summaries | window]``
    (block 8 = window 32 / chunk 4)."""
    return int(window_row_positions(np.int32(position), window, chunk))


CASES = {
    # name: (heads, kv_heads, runs, T, kwargs, expected page loads, and of
    # those the loads of SHORT items: the run's g * rows query rows lie in
    # one slab of `slab_rows(g)` rows that starts at a multiple of 8)
    # tiles of 32 rows; 40 rows at positions 3..42: pages 0-4, then 0-5
    "gqa_32_8_prefill_crosses_a_tile": (
        32, 8, [(1, 3, 40, 0)], 40, {}, 5 + 6, 0),
    "gqa_4_1": (4, 1, [(1, 0, 20, 0), (2, 17, 1, 20)], 24, {}, 3 + 3, 3),
    # tiles of 64 rows: MHA feeds the MXU one row a token.  The run's last
    # two rows (positions 64, 65: pages 0-8) are a short run of the next tile
    "mha_crosses_a_tile": (
        4, 4, [(1, 0, 66, 0), (2, 9, 1, 66)], 72, {"maxb": 12}, 8 + 9 + 2,
        9 + 2),
    "two_runs_and_dead_rows_in_one_tile": (
        8, 2, [(1, 5, 9, 2), (2, 30, 6, 14)], 32, {}, 2 + 5, 0),
    "decode_rows_of_different_sequences": (
        8, 2, [(s, 7 * s, 1, s - 1) for s in range(1, 8)], 8, {},
        sum(7 * s // 8 + 1 for s in range(1, 8)),
        sum(7 * s // 8 + 1 for s in range(1, 8))),
    "tokens_not_a_multiple_of_the_tile": (
        8, 2, [(3, 0, 37, 0), (4, 11, 1, 37)], 43, {}, 4 + 5 + 2, 2),
    # window 11: positions 28..35 see keys 18..35 = pages 2..4 of 0..4
    "window_kills_leading_pages": (
        8, 2, [(1, 28, 8, 0), (2, 50, 1, 8)], 16, {"window": 11}, 3 + 2, 2),
    "table_with_unused_trailing_entries": (
        8, 2, [(1, 0, 5, 0), (2, 9, 1, 5)], 8, {"maxb": 27}, 1 + 2, 2),
    # decode_burst: row i is slot i, idle slots are dead rows in between
    "decode_burst_layout": (
        8, 2, [(s, 5 * s, 1, s) for s in (1, 2, 4, 7)], MAX_SEQS, {},
        sum(5 * s // 8 + 1 for s in (1, 2, 4, 7)),
        sum(5 * s // 8 + 1 for s in (1, 2, 4, 7))),
    "bfloat16_cache_two_heads_a_word": (
        32, 8, [(1, 3, 40, 0), (2, 20, 1, 40)], 48,
        {"dtype": jnp.bfloat16}, 5 + 6 + 3, 3),
    "bfloat16_cache_one_word_a_row": (
        4, 2, [(1, 3, 10, 0), (2, 20, 1, 10)], 16,
        {"dtype": jnp.bfloat16}, 2 + 3, 3),
    # 16-bit MQA (Falcon, Jamba): ONE KV head, a page of two tokens a row
    "bfloat16_mqa_two_tokens_a_row": (
        8, 1, [(1, 3, 10, 0), (2, 20, 1, 10)], 16,
        {"dtype": jnp.bfloat16}, 2 + 3, 3),
    # Jamba's 20 query heads on the one KV head (slabs of 24 rows): a
    # prefill chunk that crosses a tile of 32 tokens, and decode rows
    "bfloat16_mqa_20_heads_a_kv_head": (
        20, 1, [(1, 3, 40, 0), (2, 20, 1, 40), (3, 9, 1, 41)], 48,
        {"dtype": jnp.bfloat16}, 5 + 6 + 3 + 2, 3 + 2),
    # ---- the rows an item computes (g = 4: two tokens a slab of 8 rows)
    # a burst's 64 rows at the serving cell's heads: every item is short
    "burst_of_64_one_token_runs": (
        32, 8, [(s, (5 * s) % 23, 1, s) for s in range(1, 64) if s % 9], 64,
        {"dtype": jnp.bfloat16, "max_seqs": 64, "maxb": 3},
        sum((5 * s) % 23 // 8 + 1 for s in range(1, 64) if s % 9),
        sum((5 * s) % 23 // 8 + 1 for s in range(1, 64) if s % 9)),
    # as _build_batch lays a step: prefill chunks and decode rows, then dead
    # rows; only the decode rows' items are short
    "mixed_prefill_chunks_and_decode_rows": (
        32, 8, [(1, 16, 21, 0), (2, 9, 1, 21), (3, 30, 1, 22), (4, 7, 1, 23),
                (5, 0, 13, 24), (6, 63, 1, 37)], 48, {},
        5 + 2 + 4 + 1 + (1 + 2) + 8, 2 + 4 + 1 + 8),
    # two tokens of one sequence: one slab from an even row, two from an odd
    "two_token_run_on_an_even_row": (
        32, 8, [(1, 14, 2, 2), (2, 3, 1, 4)], 8, {}, 2 + 1, 2 + 1),
    "two_token_run_on_an_odd_row": (
        32, 8, [(1, 14, 2, 3), (2, 3, 1, 5)], 8, {}, 2 + 1, 1),
    # MHA (slab of 8 tokens): rows 6..9 straddle, rows 16..23 fill one slab
    "a_run_that_straddles_a_slab": (
        4, 4, [(1, 5, 4, 6), (2, 20, 8, 16), (3, 11, 1, 24)], 32, {},
        2 + 4 + 2, 4 + 2),
    # g = 7 (Qwen2's 28 / 4): slabs of 16 rows hold a token wherever it
    # starts, the tile's last token too; two tokens from row 12 do not fit
    "g_7_slabs_of_16_rows": (
        28, 4, [(1, 9, 1, 0), (2, 17, 1, 5), (3, 30, 2, 12), (4, 4, 1, 31)],
        32, {}, 2 + 3 + 4 + 1, 2 + 3 + 1),
    "g_8_one_token_a_slab": (
        16, 2, [(1, 6, 3, 0), (2, 12, 1, 3), (3, 40, 1, 7)], 8, {},
        2 + 2 + 6, 2 + 6),
    # a window that binds on short items: position 50 sees keys 40..50
    "binding_window_on_decode_rows": (
        32, 8, [(1, 50, 1, 0), (2, 23, 1, 1), (3, 8, 1, 2)], 8,
        {"window": 11, "dtype": jnp.bfloat16}, 2 + 2 + 2, 2 + 2 + 2),
    # EvaByte: MHA, rows positioned inside [summaries | window]: a prefill
    # chunk at 70..79 (row positions 22..31: pages 0-3) and decode rows at
    # 33, 64, 95 (row positions 9, 16, 47)
    "evabyte_row_positions": (
        4, 4, [(1, _eva(70), 10, 0), (2, _eva(33), 1, 10),
               (3, _eva(64), 1, 11), (4, _eva(95), 1, 12)], 16,
        {"dtype": jnp.bfloat16}, 4 + 2 + 3 + 6, 2 + 3 + 6),
}


@pytest.mark.parametrize("name", list(CASES))
def test_run_tiled_kernel_matches_the_gather(name):
    heads, kv_heads, runs, T, kw, want_loads, want_short = CASES[name]
    q, kc, vc, tables, slots, pos = _case(heads, kv_heads, runs, T, **kw)
    bs, window = _bs(kc, kv_heads), kw.get("window", 0)
    assert run_tiled(kv_heads, 128, kc.dtype)
    out, loads = paged_attention(q, kc, vc, tables, jnp.asarray(slots),
                                 jnp.asarray(pos), window=window,
                                 count_loads=True, block_size=bs)
    _assert_is_the_gather(out, q, kc, vc, tables, slots, pos, window, bs)
    # what the loops loaded is what the host counts (every one a page that
    # holds a key some row of the run may see); the items that computed one
    # slab of rows are the ones the host calls short
    grid, _, short, _ = kernel_page_loads(
        slots, pos, heads=heads, kv_heads=kv_heads, head_dim=128,
        kv_dtype=kc.dtype, block_size=bs, maxb=tables.shape[1], window=window)
    assert int(loads[:, 0].sum()) == want_loads == grid
    assert int(loads[:, 1].sum()) == want_short == short


@pytest.mark.parametrize("name", list(CASES))
def test_short_items_give_the_bits_of_whole_tiles(name, monkeypatch):
    """The rows an item computes, and the heads that go through the softmax
    together, do not change a live row's bits: the kernel against itself
    with every item on its whole tile, one head at a time (the items as
    they were before either existed)."""
    heads, kv_heads, runs, T, kw, want_loads, _ = CASES[name]
    q, kc, vc, tables, slots, pos = _case(heads, kv_heads, runs, T, seed=1,
                                          **kw)
    args = (q, kc, vc, tables, jnp.asarray(slots), jnp.asarray(pos))
    window = kw.get("window", 0)
    bs = _bs(kc, kv_heads)
    out, loads = paged_attention(*args, window=window, count_loads=True,
                                 block_size=bs)
    monkeypatch.setattr(paged_module, "_run_kernel", functools.partial(
        paged_module._run_kernel, short=False))
    monkeypatch.setattr(paged_module, "_STACK_ROWS", 1)
    whole, whole_loads = paged_attention.__wrapped__(
        *args, window=window, count_loads=True, block_size=bs)
    assert int(whole_loads[:, 0].sum()) == want_loads
    assert not int(whole_loads[:, 1].sum())
    as_bits = lambda a: np.asarray(a.astype(jnp.float32)).view(np.uint32)
    np.testing.assert_array_equal(as_bits(out), as_bits(whole))
    assert np.asarray(loads)[:, 0].tolist() == \
        np.asarray(whole_loads)[:, 0].tolist()


# ------------------------------------------------ blocks of pages (PR 38)
#: pages of 8 tokens are small: every shape of this file takes blocks of 8
P = 8

BLOCK_CASES = {
    # name: (heads, kv_heads, runs, T, kwargs, page loads, of those the short
    # items' and the BLOCK items': whole blocks of P pages of a run whose
    # items compute the tile, counted in pages)
    # 16 rows at positions 48..63: pages 0-7
    "a_run_of_exactly_one_block": (
        8, 2, [(1, 48, 16, 0)], 16, {}, 8, 0, 8),
    # at 56..71: pages 0-8, the ninth through the one-page item
    "one_page_more_than_a_block": (
        8, 2, [(1, 56, 16, 0)], 16, {"maxb": 12}, 9, 0, 8),
    # at 40..55: pages 0-6, no block at all
    "one_page_short_of_a_block": (
        8, 2, [(1, 40, 16, 0)], 16, {}, 7, 0, 0),
    "three_blocks_and_no_rest": (
        8, 2, [(1, 176, 16, 0)], 16, {"maxb": 24}, 24, 0, 24),
    # window 100: position 150 sees keys 51..150, so the run's first page is
    # page 6 and its one block pages 6-13 (the later rows' windows start
    # inside it), then pages 14-20 one by one
    "a_window_whose_first_page_falls_inside_a_block": (
        8, 2, [(1, 150, 16, 0)], 16, {"maxb": 24, "window": 100}, 15, 0, 8),
    # EvaByte: a chunk at 234..243 in its eighth window: row positions
    # 66..75 behind seven windows' summaries, pages 0-9 of the table row
    "evabyte_summaries_and_window_in_one_block": (
        4, 4, [(1, _eva(234), 10, 0), (2, _eva(33), 1, 10)], 16,
        {"maxb": 12, "dtype": jnp.bfloat16}, 10 + 2, 2, 8),
    # behind a decode row (4 short loads), from buffer row 9: 60..79
    "a_run_that_starts_mid_tile": (
        8, 2, [(2, 30, 1, 0), (1, 60, 20, 9)], 32, {"maxb": 12},
        4 + 10, 4, 8),
    # three tiles of 32 rows, the middle one dead: 64..87 (pages 0-10) and
    # 100..129 (pages 0-16: two blocks)
    "a_dead_tile_between_two_runs": (
        8, 2, [(1, 64, 24, 0), (2, 100, 30, 64)], 96, {"maxb": 24},
        11 + 17, 0, 8 + 16),
    # 70..83 (pages 0-10) and 127..142 (pages 0-17) side by side
    "two_runs_in_one_tile": (
        8, 2, [(1, 70, 14, 0), (2, 127, 16, 14)], 32, {"maxb": 24},
        11 + 18, 0, 8 + 16),
    # the serving cells' heads, two to a word: 60..91 in the first tile
    # (pages 0-11), 92..99 in the second (0-12), a decode row at 20
    "bfloat16_prefill_crosses_a_tile": (
        32, 8, [(1, 60, 40, 0), (2, 20, 1, 40)], 48,
        {"maxb": 16, "dtype": jnp.bfloat16}, 12 + 13 + 3, 3, 8 + 8),
}


def _block_case(name, **more):
    heads, kv_heads, runs, T, kw, *want = BLOCK_CASES[name]
    q, kc, vc, tables, slots, pos = _case(heads, kv_heads, runs, T,
                                          **{**kw, **more})
    assert item_pages(kv_heads, 128, kc.dtype, kc.shape[1]) == P
    return (q, kc, vc, tables, jnp.asarray(slots), jnp.asarray(pos)), \
        kw.get("window", 0), want, dict(
            heads=heads, kv_heads=kv_heads, head_dim=128, kv_dtype=kc.dtype,
            block_size=kc.shape[1], maxb=tables.shape[1])


@pytest.mark.parametrize("name", list(BLOCK_CASES))
def test_block_items_match_the_gather_and_the_hosts_counts(name):
    args, window, want, shapes = _block_case(name)
    out, loads = paged_attention(*args, window=window, count_loads=True)
    slots, pos = (np.asarray(a) for a in args[4:])
    _assert_is_the_gather(out, *args[:4], slots, pos, window)
    # what the loops loaded, in PAGES whatever the items: all of it, the
    # short items' part and the block items' part are the host's three
    grid, _, short, block = kernel_page_loads(slots, pos, window=window,
                                              **shapes)
    assert np.asarray(loads).sum(0).tolist() == want == [grid, short, block]
    assert block % P == 0 and block + short <= grid


@pytest.mark.parametrize("name", list(BLOCK_CASES))
def test_block_items_are_one_page_items_with_their_sums_reordered(
        name, monkeypatch):
    """Against the same kernel with the blocks off (every item one page, the
    kernel as it was): the same pages tile by tile, and a live row's result
    to the rounding of a float32 sum taken in another order; where no run
    holds a block, the same bits."""
    args, window, want, shapes = _block_case(name, seed=2)
    out, loads = paged_attention(*args, window=window, count_loads=True)
    monkeypatch.setattr(paged_module, "item_pages", lambda *a: 1)
    paged, paged_loads = paged_attention.__wrapped__(
        *args, window=window, count_loads=True)
    assert np.asarray(paged_loads).sum(0).tolist() == want[:2] + [0]
    assert kernel_page_loads(*(np.asarray(a) for a in args[4:]),
                             window=window, **shapes)[3] == 0
    assert np.asarray(loads)[:, :2].tolist() == \
        np.asarray(paged_loads)[:, :2].tolist()
    out, paged = (np.asarray(a, np.float32) for a in (out, paged))
    if not want[2]:
        np.testing.assert_array_equal(out.view(np.uint32),
                                      paged.view(np.uint32))
    # float32 results a few ulps apart; a bfloat16 output one rounding
    tol = 2e-6 if args[1].dtype == jnp.float32 else 8e-3
    np.testing.assert_allclose(out, paged, atol=tol, rtol=tol)


def test_the_pages_of_a_block_follow_from_the_shapes():
    bf16, f32 = jnp.bfloat16, jnp.float32
    # 512 keys: four pages of 128 at 8 KV heads (Mistral, Command A+) ...
    assert item_pages(8, 128, bf16, 128) == 4
    # ... two where four buffers of four 1 MB pages would pass 8 MB (EvaByte)
    assert item_pages(32, 128, bf16, 128) == 2
    assert item_pages(32, 128, f32, 128) == 1
    # pages of 256 keys: two; of 512 and more: one (every item one page);
    # small pages: no more than the eight that were measured
    assert [item_pages(8, 128, bf16, bs) for bs in (256, 512, 1024)] == \
        [2, 1, 1]
    assert [item_pages(8, 128, bf16, bs) for bs in (8, 16, 64)] == [8, 8, 8]


@pytest.mark.parametrize("heads, kv_heads, T, limit", [
    (32, 8, 768, None),             # Mistral's step: 7 MB of its own
    (32, 32, 768, 64 << 20),        # EvaByte's: 14 MB
    (32, 32, 16, None),             # its burst: a tile of 16 rows
    (128, 8, 2048, 64 << 20),       # Command A+'s: 16 MB
])
def test_the_kernel_asks_for_vmem_only_where_its_buffers_need_it(
        heads, kv_heads, T, limit, monkeypatch):
    """A call that asks for a VMEM limit costs its program 24 us beside the
    kernel (docs/kernels.md): the cells' shapes, pages of 128 keys."""
    asked = []
    params = paged_module.pltpu.CompilerParams
    monkeypatch.setattr(
        paged_module.pltpu, "CompilerParams",
        lambda **kw: asked.append(kw["vmem_limit_bytes"]) or params(**kw))
    sds = jax.ShapeDtypeStruct
    cache = sds((4, 128, kv_heads, 128), jnp.bfloat16)
    jax.eval_shape(paged_attention.__wrapped__,
                   sds((T, heads, 128), jnp.bfloat16), cache, cache,
                   sds((3, 4), jnp.int32), sds((T, ), jnp.int32),
                   sds((T, ), jnp.int32))
    assert asked == [limit]


def test_slab_rows_follow_from_the_group_size():
    # the float32 sublane tile where a token's rows cannot straddle two;
    # 16 rows otherwise, never more than the smallest tile (8 tokens)
    assert [slab_rows(g) for g in (1, 2, 4, 8)] == [8] * 4
    assert [slab_rows(g) for g in (3, 5, 6, 7, 12, 16)] == [16] * 6
    assert all(slab_rows(g) <= 8 * g for g in range(1, 72))


PER_TOKEN_CASES = {
    # name: (heads, kv_heads, head_dim, runs, T, kwargs); each names the
    # fault it would catch
    # positions 28..35 under a window of 11 see keys 18..35: a page wholly
    # before the window is skipped, one it cuts is masked inside
    "window_cuts_a_page": (4, 2, 16, [(1, 28, 8, 0)], 8, {"window": 11}),
    # a prefill run, dead rows, another sequence's run: a row reads ITS
    # table row and ITS position, not its neighbour's
    "rows_of_two_sequences_and_dead_rows_between": (
        4, 2, 16, [(1, 8, 6, 0), (2, 0, 4, 8)], 12, {}),
    # head sizes of the zoo that are not whole lanes (OPT, Phi)
    "head_size_64": (4, 4, 64, [(1, 3, 10, 0), (2, 20, 1, 10)], 12, {}),
    "head_size_80_window": (
        4, 2, 80, [(1, 13, 9, 0), (2, 40, 1, 9)], 12, {"window": 11}),
    # 16-bit MQA at a head size that is not whole lanes: every query head
    # reads the one KV head (at 128 the run-tiled kernel reads it: CASES)
    "mqa_bfloat16_head_64": (8, 1, 64, [(1, 3, 10, 0), (2, 20, 1, 10)], 12,
                     {"dtype": jnp.bfloat16}),
    # decode rows with idle slots between (a burst's rows)
    "decode_rows_and_idle_slots": (
        4, 1, 64, [(s, 6 * s, 1, s) for s in (1, 3, 4, 7)], MAX_SEQS, {}),
}


@pytest.mark.parametrize("name", list(PER_TOKEN_CASES))
def test_per_token_kernel_matches_the_gather(name):
    heads, kv_heads, head_dim, runs, T, kw = PER_TOKEN_CASES[name]
    q, kc, vc, tables, slots, pos = _case(heads, kv_heads, runs, T,
                                          head_dim=head_dim, **kw)
    bs, window = kc.shape[1], kw.get("window", 0)
    assert not run_tiled(kv_heads, head_dim, kc.dtype)
    out = paged_attention(q, kc, vc, tables, jnp.asarray(slots),
                          jnp.asarray(pos), window=window)
    _assert_is_the_gather(out, q, kc, vc, tables, slots, pos, window)
    # one grid row a token: every row streams every page of the table
    maxb = tables.shape[1]
    row_pages = np.where(
        slots != 0, pos // bs + 1 - (np.maximum(pos - window + 1, 0) // bs
                                     if window else 0), 0)
    grid, shared, short, _ = kernel_page_loads(
        slots, pos, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        kv_dtype=kc.dtype, block_size=bs, maxb=maxb, window=window,
        row_pages=row_pages)
    assert grid == T * maxb and short == 0
    # nothing loads together on this kernel: once a row
    assert shared == row_pages.sum() > 0


def test_shapes_the_run_tiled_kernel_leaves_to_the_per_token_one():
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert run_tiled(8, 128, bf16) and run_tiled(32, 128, bf16)
    assert run_tiled(4, 128, bf16) and run_tiled(1, 128, f32)
    # one KV head in 16 bits: its page holds two tokens a row
    assert run_tiled(1, 128, bf16) and not run_tiled(3, 128, bf16)
    # head sizes that are not whole lanes; heads that do not pair up or tile
    for kv_heads, head_dim, dtype in (
            (32, 80, bf16), (12, 64, bf16), (1, 64, bf16), (40, 128, bf16),
            (12, 128, f32), (8, 128, jnp.float16)):
        assert not run_tiled(kv_heads, head_dim, dtype)
    # such a shape still answers, one grid row a token: PER_TOKEN_CASES


# ------------------------------------------------------------- engine level
def _tiny_mistral(budget=48, head_dim=128):
    """Mistral's shape at toy width: 4 query / 2 KV heads of ``head_dim``, a
    sliding window that binds, default engine settings."""
    cfg = llama.llama_tiny(dtype="float32", remat=False,
                           hidden_size=4 * head_dim, sliding_window=24)
    model = llama.LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    sm = dict(max_tracked_sequences=8, max_ragged_batch_size=budget,
              max_ragged_sequence_count=8, max_context=128, block_size=16,
              num_blocks=40)
    return InferenceEngineV2(model, params=params, config=dict(
        dtype="float32", decode_burst=4, state_manager=sm))


def test_batch_is_runs_of_consecutive_positions():
    """What the kernel is fast on, and the counts rest on: the rows a
    sequence gets in one step are contiguous, their positions consecutive;
    dead rows carry slot 0 and position 0."""
    eng = _tiny_mistral()
    assert eng.model_config.head_dim == 128
    rng = np.random.default_rng(0)
    eng.put(range(4), [rng.integers(1, 96, size=n).tolist()
                       for n in (30, 1, 7, 29)])
    steps = 0
    while (batch := eng._build_batch()) is not None:
        toks, pos, slots, *_ = batch
        for s in set(slots[slots != 0].tolist()):
            rows = np.flatnonzero(slots == s)
            assert (np.diff(rows) == 1).all()
            assert (np.diff(pos[rows]) == 1).all()
        assert not pos[slots == 0].any() and not toks[slots == 0].any()
        c = eng.last_step_counts
        bs = eng.kv_cache.block_size
        loads = kernel_page_loads(
            slots, pos, heads=4, kv_heads=2, head_dim=128,
            kv_dtype=jnp.float32, block_size=bs,
            maxb=eng.state_manager.block_table.shape[1], window=24)
        assert c["grid_pages"] == loads[0] and "live_pages" not in c
        assert c["short_pages"] == loads[2] <= c["grid_pages"]
        assert c["block_pages"] == loads[3] == 0    # no run of 8 pages
        assert c["row_pages"] == sum(
            p // bs + 1 - max(p - 24 + 1, 0) // bs for p in pos[slots != 0])
        assert c["row_pages"] >= c["grid_pages"] > 0
        steps += 1
    assert steps == 2       # 48 rows of 67 prompt tokens, then the rest
    eng.flush(range(4))


@pytest.mark.parametrize("head_dim", [128, 16],
                         ids=["run_tiled", "per_token"])
def test_tiny_mistral_streams_the_same_tokens_with_and_without_the_kernel(
        monkeypatch, head_dim):
    """Greedy tokens of an engine at its defaults, ragged steps and decode
    bursts: the kernel ``paged_attention`` picks for the head size
    (interpret mode) against ``use_kernel=False``.  Each engine gets its own
    jit of the step, so the suite's cached programs (traced without the
    kernel gate) are neither used nor replaced."""
    assert run_tiled(2, head_dim, jnp.float32) == (head_dim == 128)
    monkeypatch.setenv("DS_TPU_FORCE_PALLAS", "1")
    inner = ragged_forward.llama_ragged_step.__wrapped__
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 96, size=n).tolist() for n in (41, 9, 2, 23)]
    outs = []
    for use_kernel in (True, False):
        eng = _tiny_mistral(head_dim=head_dim)

        def step(*a, _use=use_kernel, **kw):
            return inner(*a, **{**kw, "use_kernel": _use})

        eng._step_fn = jax.jit(
            step, static_argnames=("cfg", "block_size", "use_kernel",
                                   "kv_dtype"),
            donate_argnums=(1, ))
        outs.append(eng.generate(prompts, max_new_tokens=10))
        eng.flush(range(len(prompts)))
    assert outs[0] == outs[1]
    assert all(len(o) == 10 for o in outs[0])
