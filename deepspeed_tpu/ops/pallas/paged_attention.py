"""Paged (blocked-KV) attention, Pallas TPU — the FastGen blocked-flash
analog (reference ``inference/v2/kernels/ragged_ops/blocked_flash`` +
``linear_blocked_kv_rotary``).

:func:`paged_attention` is the entry.  Its kernel tiles the token buffer by
RUNS: the rows one sequence gets in a step are contiguous and their
positions consecutive (``engine_v2._build_batch``), so every row of a run
attends to a K/V page from ONE load of it.  The grid walks Q tiles of ``TQ``
buffer rows; inside a tile a loop with a dynamic trip count visits only the
(run, page) items that hold a key some live row may see, K/V stay in HBM and
each page arrives by a double-buffered DMA whose block id is read from the
scalar-prefetched block table.  The online-softmax state is per row and lives
across the items of a tile, so a tile of 32 decode rows of 32 sequences is as
exact as one prefill chunk.  GQA is expressed in the index math (no repeated
KV): the wrapper hands the kernel ``q`` as ``[Hkv, TQ * g, Dh]``.  The rows an
item COMPUTES follow the rows of its run: a run that lies inside one slab of
:func:`slab_rows` rows (a decode token, a burst's row) loads, computes and
stores that slab alone, every other item the whole tile; and an item's KV
heads go through the softmax together, their dots back to back.  An item
on the whole tile takes a BLOCK of :func:`item_pages` consecutive pages of
its run through one softmax update, as far as the run has whole blocks.

:func:`paged_attention_per_token` is the older grid of one row times every
page of the table; the shapes the run-tiled kernel does not take
(:func:`run_tiled`) keep it.


:func:`paged_latent_attention` (``ds_paged_latent``) reads a LATENT cache
(multi-head latent attention in its absorbed form): one buffer a layer of
``[num_blocks, bs, L]`` rows ``(c [rank] ; k_r)``, every query head against
the same row, so ONE page load serves the scores (all ``L`` columns) and the
values (its first ``rank``).  It walks the same items of the same
:func:`run_plan`, blocks of :func:`item_pages` pages among them, with all the
heads as one KV head's group.

The XLA fallback (``inference/v2/ragged_forward._paged_attention``) computes
the same math by gather; the kernels replace it on TPU where the gather's
HBM blowup ([T, max_ctx, ...]) matters.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
#: rows of scores the run-tiled kernel takes through the softmax in one
#: piece: an item's KV heads are stacked up to it, so that their dots run
#: back to back and not each behind its own softmax (docs/kernels.md has the
#: v5e readings)
_STACK_ROWS = 1024
#: a block of pages (:func:`item_pages`): as many keys as the flash kernels'
#: key block, at most the 8 pages that were measured, and what the blocks'
#: four K/V buffers (K and V, double-buffered) may hold together
_BLOCK_KEYS = 512
_BLOCK_MAX_PAGES = 8
_BLOCK_BUFFER_BYTES = 8 * 1024 * 1024
#: the run-tiled kernel's VMEM.  What it holds itself (the tile's q and out,
#: the accumulator and softmax state, the blocks' K/V buffers) may take three
#: quarters of the compiler's default of 16 MB, the rest being the compiler's
#: own (a block's score arrays ``[_STACK_ROWS, P * bs]``, 2 MB each); a
#: kernel that holds more (16 MB at 16 query heads a KV head) asks for 64 MB.
#: Only then: a call that asks costs its program 24 us beside the kernel
#: (docs/kernels.md)
_RUNS_VMEM_HELD_BYTES = 12 * 1024 * 1024
_RUNS_VMEM_BYTES = 64 * 1024 * 1024
#: query rows (tokens x heads) of a tile of the latent kernel: 8 tokens of
#: 128 heads, which is what its VMEM holds beside the accumulator
#: (docs/kernels.md)
_LATENT_TILE_ROWS = 1024
#: its VMEM: the tile's q and output (each double-buffered), the float32
#: accumulator and softmax state, two blocks of pages, a block's score arrays
_LATENT_VMEM_BYTES = 64 * 1024 * 1024
#: the EXPANDED form's reader (:func:`paged_mla_chunk_attention`): a tile of
#: at most 2048 buffer rows, whose runs' keys are made once a head; 1024 of
#: its rows against the 1024 keys of a block of pages through one softmax
#: update (the flash kernels' blocks: docs/kernels.md has the v5e sweep);
#: its VMEM (the tile's q and output twice each, a float32 accumulator and
#: softmax state, two blocks of pages, the made keys and values, a
#: stretch's score arrays ``[1024, 1024]``, 4 MB each)
_CHUNK_TILE_ROWS = 2048
_CHUNK_SUB_ROWS = 1024
_CHUNK_BLOCK_KEYS = 1024
_CHUNK_VMEM_BYTES = 96 * 1024 * 1024


from ._common import interpret_mode as _interpret


# ----------------------------------------------------------- run-tiled path
def page_row_tokens(kv_heads, head_dim, kv_dtype):
    """TOKENS a row of a K/V page, the one statement of the page's format: 2
    for ONE KV head (multi-query) of a whole-lane head size in bfloat16, whose
    page is held ``[bs / 2, 2, Dh]`` (the bytes of ``[bs, 1, Dh]``
    row-major): the device tiles a 16-bit array's second-minor axis by 2 at
    least, so a head axis of 1 would be padded to twice the memory and Mosaic
    could not copy such a page.  1 everywhere else: a row a token, ``[bs,
    Hkv, Dh]``.  The cache lays its pages out by it
    (``ragged.BlockedKVCache``), the scatter writes by it
    (``ragged_forward._kv_scatter``), and :func:`run_tiled` takes a bfloat16
    multi-query shape because a page of it is held so."""
    return 2 if (kv_heads == 1 and head_dim % 128 == 0
                 and kv_dtype == jnp.bfloat16) else 1


def page_kv_heads(cache_shape, block_size):
    """The KV heads of a cache ``[blocks, rows, heads a row, Dh]`` whose page
    holds ``block_size`` tokens in ``rows`` rows (:func:`page_row_tokens` a
    row)."""
    return cache_shape[2] * cache_shape[1] // block_size


def run_tiled(kv_heads, head_dim, kv_dtype):
    """Whether the run-tiled kernel takes this shape — by the shape alone
    (docs/kernels.md lists what is left on the per-token kernel).  A K/V page
    ``[bs, Hkv, Dh]`` is read per KV head by a sublane-strided load of its
    ``[bs * Hkv, Dh]`` view; a 16-bit cache packs two heads a sublane, so the
    heads have to pair up and the packed view has to tile.  ONE KV head
    (multi-query) is the page itself, read whole and widened: in bfloat16 a
    page of token pairs (:func:`page_row_tokens`)."""
    if kv_dtype not in (jnp.float32, jnp.bfloat16):
        return False
    if kv_heads == 1:
        return head_dim % 128 == 0
    sublanes, odd = divmod(kv_heads, 4 // jnp.dtype(kv_dtype).itemsize)
    return not odd and head_dim % 128 == 0 and (
        sublanes in (1, 2, 4, 8) or sublanes % 8 == 0)


def latent_tiled(heads, kv_dtype):
    """Whether :func:`paged_latent_attention` takes this shape: a token's
    ``heads`` query rows are sliced out of the tile in the cache's type, so
    they have to fill whole sublane tiles of it (8 rows of 32 bits, 16 of
    16)."""
    if kv_dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return heads % (8 * 4 // jnp.dtype(kv_dtype).itemsize) == 0


def tile_rows(heads, kv_heads, head_dim, kv_dtype, tokens, latent=False):
    """WHICH kernel reads the cache for a call of ``tokens`` rows: the
    run-tiled one with Q tiles of the returned ``TQ`` buffer rows, or (None)
    one grid row a token.  :func:`paged_attention` and the count of its
    loads (:func:`kernel_page_loads`) both ask here; a further reader is
    added here.  ``TQ`` follows from the shapes alone: an item on the whole
    tile pays for all its ``TQ * g`` MXU rows per KV head, so small tiles
    win although they load a long run's pages more often (docs/kernels.md
    has the v5e readings).

    ``latent``: the cache is a latent one (``kv_heads`` 1, ``head_dim`` its
    row's length) and the reader :func:`paged_latent_attention`, whose tile
    holds ``_LATENT_TILE_ROWS`` query rows whatever the heads (None: the
    shape stays on the gather)."""
    if latent:
        if not latent_tiled(heads, kv_dtype):
            return None
        return max(8, _LATENT_TILE_ROWS // heads // 8 * 8)
    if not run_tiled(kv_heads, head_dim, kv_dtype):
        return None
    g = heads // kv_heads
    return min(max(32, 64 // g // 8 * 8), -(-tokens // 8) * 8)


def slab_rows(g):
    """The rows ``R`` a SHORT item computes, from the shape alone: the least
    multiple of 8 (the float32 sublane tile) that holds one token's ``g``
    query rows wherever they start, with the slab's first row a multiple of
    8 — 8 where ``g`` divides 8, 16 for ``g`` in 3, 5, 6, 7 and 9-16."""
    return -(-(8 - math.gcd(g, 8) + g) // 8) * 8


def item_pages(kv_heads, head_dim, kv_dtype, block_size):
    """The pages ``P`` that one item of a LONG run (one whose rows take the
    whole tile) brings and takes through ONE online-softmax update, from the
    shapes alone.  The softmax's state (``m``, ``alpha``, ``l``, the
    accumulator's rescaling) is owed once an update whatever the keys, and
    with one page an update it is more than half of an item's vector work;
    a block's price is VMEM: four buffers of ``P`` pages and score arrays
    ``P`` times as wide.  So: the flash kernels' 512 keys (4 pages of 128),
    fewer where the buffers would pass 8 MB (2 pages of 1 MB: EvaByte's 32
    KV heads).  1: every item is one page.  The latent kernel asks with its
    one row as the one KV head (4 pages of 128 x 640).  docs/kernels.md has
    the v5e sweeps (``P`` 1 / 2 / 4 / 8 at the serving cells' shapes)."""
    page = block_size * kv_heads * head_dim * jnp.dtype(kv_dtype).itemsize
    return int(max(1, min(_BLOCK_KEYS // block_size, _BLOCK_MAX_PAGES,
                          _BLOCK_BUFFER_BYTES // (4 * page))))


def run_plan(xp, seq_slots, positions, tq, block_size, window=0, g=1,
             block=1):
    """The loop bounds of the run-tiled kernel, as arrays — with ``xp`` numpy
    on the host (:func:`kernel_page_loads`) and jax.numpy inside the step
    program, so that what is counted is what runs.

    ``seq_slots``/``positions``: ``[T]`` (or ``[B, T]``: B calls).  A RUN is
    a stretch of live rows (slot != 0) inside one tile with one slot and
    consecutive positions.  Returns, per tile: ``pos``, ``rid [n, tq]`` each
    row's position and run (-1: dead row), and per run ``run_slot``,
    ``first_page``, ``n_pages``, ``slab``, ``n_blocks [n, tq]`` (runs
    compacted to the front, 0 pages past the last run).

    ``slab`` says which rows a run's items compute, of the tile's ``tq * g``
    (a token's ``g`` query rows adjacent): the first row of the one slab of
    :func:`slab_rows` rows that holds all the run's rows (a multiple of 8),
    or -1: no such slab, its items compute the whole tile.  The kernel
    branches on it and :func:`kernel_page_loads` counts by it.

    ``n_blocks``: a run whose items compute the whole tile goes through its
    pages ``block`` (:func:`item_pages`) at a time, one item a BLOCK, as far
    as whole blocks go (``n_pages // block`` of them), and the rest of its
    pages one item each; 0 for a run with a slab, and for every run where
    ``block`` is 1.  A tile's items are ``n_pages - n_blocks * (block - 1)``
    summed over its runs; every other count is of PAGES."""
    T = seq_slots.shape[-1]
    pad = -T % tq
    slots, pos = (xp.pad(a.reshape(-1, T).astype(xp.int32),
                         ((0, 0), (0, pad))).reshape(-1, tq)
                  for a in (seq_slots, positions))
    live = slots != 0
    edge = xp.full((slots.shape[0], 1), -1, xp.int32)
    start = live & ((slots != xp.concatenate([edge, slots[:, :-1]], 1))
                    | (pos != xp.concatenate([edge, pos[:, :-1]], 1) + 1))
    rid = xp.where(live, xp.cumsum(start, axis=1) - 1, -1).astype(xp.int32)
    member = rid[:, None, :] == xp.arange(tq, dtype=xp.int32)[None, :, None]
    first = member & start[:, None, :]
    n_rows = member.sum(-1).astype(xp.int32)
    run_slot = (first * slots[:, None, :]).sum(-1).astype(xp.int32)
    first_pos = (first * pos[:, None, :]).sum(-1).astype(xp.int32)
    first_page = (xp.maximum(first_pos - window + 1, 0) // block_size
                  if window else xp.zeros_like(first_pos))
    n_pages = xp.where(n_rows > 0, (first_pos + n_rows - 1) // block_size
                       + 1 - first_page, 0).astype(xp.int32)
    R = slab_rows(g)
    row0 = (first * xp.arange(tq, dtype=xp.int32)).sum(-1) * g
    slab = xp.minimum(row0 // 8 * 8, tq * g - R)
    slab = xp.where((n_rows > 0) & (row0 + n_rows * g <= slab + R), slab, -1)
    n_blocks = xp.where(slab < 0, n_pages // block, 0) if block > 1 \
        else xp.zeros_like(n_pages)
    return pos, rid, run_slot, first_page.astype(xp.int32), n_pages, \
        slab.astype(xp.int32), n_blocks.astype(xp.int32)


def kernel_page_loads(seq_slots, positions, *, heads, kv_heads, head_dim,
                      kv_dtype, block_size, maxb, window=0, row_pages=None,
                      latent=False):
    """Host-side (numpy) count of the K/V page loads (each brings one K and
    one V page) of the kernel :func:`paged_attention` picks for these rows
    (``[T]``, or ``[B, T]``: B calls) against a ``maxb``-page block table:
    ``(grid, shared, short, block)``.  ``grid``: the loads the kernel's loops
    perform — the run-tiled kernel's (run, page) items, each of which holds
    a key some live row may see, or every row times every page of the
    table.  ``shared``: of ``row_pages`` (a page count a row, equal along a
    run; None: 0) the sum over what loads together — once a run, or once a
    row.  ``short``: of ``grid``, the loads whose item computes one slab of
    rows and not the tile (:func:`run_plan`'s ``slab``; 0 on the per-token
    kernel).  ``block``: of ``grid``, the loads of items that take a block
    of :func:`item_pages` pages through one softmax update
    (:func:`run_plan`'s ``n_blocks``; 0 where an item is one page: the
    per-token kernel).  All four count PAGES.  ``latent``: the loads of
    :func:`paged_latent_attention` (each brings ONE page, scores and values
    both) for ``heads`` query heads on one latent row (``kv_heads`` 1,
    ``head_dim`` the row's length), by :func:`tile_rows`'s branch."""
    slots, pos = (np.atleast_2d(np.asarray(a))
                  for a in (seq_slots, positions))
    if row_pages is not None:
        row_pages = np.where(slots != 0, np.atleast_2d(row_pages), 0)
    T = slots.shape[-1]
    tq = tile_rows(heads, kv_heads, head_dim, kv_dtype, T, latent)
    if tq is None:
        return slots.size * maxb, \
            0 if row_pages is None else int(row_pages.sum()), 0, 0
    P = item_pages(kv_heads, head_dim, kv_dtype, block_size)
    _, rid, _, _, n_pages, slab, n_blocks = run_plan(
        np, slots, pos, tq, block_size, window, heads // kv_heads, P)
    grid, short = int(n_pages.sum()), int(n_pages[slab >= 0].sum())
    block = int(n_blocks.sum()) * P
    if row_pages is None:
        return grid, 0, short, block
    per_row = np.pad(row_pages, ((0, 0), (0, -T % tq))).reshape(-1, tq)
    runs = rid[:, None, :] == np.arange(tq)[None, :, None]
    return grid, int((runs * per_row[:, None, :]).max(-1).sum()), short, block


def _head_pages(buf, kv_heads, keys):
    """The float32 ``[keys, Dh]`` rows of every KV head, from the first
    ``keys`` rows (one page, or a block of pages) of the VMEM buffer ``buf
    [rows, Hkv, Dh]`` — one sublane-strided load a head; a bfloat16 page
    is read as uint32 words that hold two heads each, and a bfloat16 IS the
    high half of its float32."""
    rows = buf.reshape(buf.shape[0] * buf.shape[1], buf.shape[-1])
    if buf.dtype == jnp.float32:
        if kv_heads == 1:
            return [rows[:keys]]
        return [rows[pl.ds(h, keys, stride=kv_heads), :]
                for h in range(kv_heads)]
    if kv_heads == 1:           # multi-query: the page is the head's rows
        return [rows[:keys].astype(jnp.float32)]
    words = rows.bitcast(jnp.uint32)
    out = []
    for j in range(kv_heads // 2):
        w = (words[:keys] if kv_heads == 2 else
             words[pl.ds(j, keys, stride=kv_heads // 2), :])
        out.append(pltpu.bitcast(w << 16, jnp.float32))
        out.append(pltpu.bitcast(w & jnp.uint32(0xFFFF0000), jnp.float32))
    return out


def _run_kernel(tables_ref, slot_ref, first_ref, npages_ref, slab_ref,
                nblocks_ref, total_ref, q_ref, pos_ref, rid_ref, k_hbm,
                v_hbm, o_ref, *rest, tq, block_size, maxb, scale, window,
                count_loads, block, short=True):
    """One Q tile: ``q_ref [1, Hkv, M, Dh]`` (``M = tq * g`` rows, row
    ``t * g + gi``), ``pos_ref``/``rid_ref [1, M, 1]`` each row's position
    and run, against the tile's items.  An item is one page ``p`` of a run
    ``k``, or (``nblocks_ref``: :func:`run_plan`'s ``n_blocks``) a BLOCK of
    ``block`` consecutive pages of it, which go through one softmax update
    together.  An item computes the rows ``slab_ref`` gives its run: one
    slab, or (-1) the tile; ``short=False`` computes the tile in every item
    (tests hold the two to the same bits)."""
    if count_loads:
        loads_ref, *rest = rest
    k_buf, v_buf, q32_ref, acc_ref, m_ref, l_ref, sem = rest
    i = pl.program_id(0)
    base = i * tq
    total = total_ref[i]
    kv_heads, M = acc_ref.shape[:2]
    R = slab_rows(M // tq)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def blocked(k, p):
        """Whether the item at page ``p`` of run ``k`` is a block."""
        return p < nblocks_ref[base + k] * block

    def each_copy(k, p, buf, act):
        """``act`` (start, or wait) on the DMAs of the item at page ``p`` of
        run ``k``: its page, or its block's pages, side by side in ``buf``."""
        page_rows = k_hbm.shape[1]     # block_size, or half (token pairs)

        def pages(js):
            row = slot_ref[base + k] * maxb + first_ref[base + k] + p
            for j in js:
                blk = tables_ref[row + j]
                at = pl.ds(j * page_rows, page_rows)
                act(pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[buf, at],
                                          sem.at[0, buf]))
                act(pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[buf, at],
                                          sem.at[1, buf]))

        pages((0, ))
        if block > 1:
            pl.when(blocked(k, p))(lambda: pages(range(1, block)))

    start, wait = (lambda c: c.start()), (lambda c: c.wait())

    @pl.when(total > 0)
    def _first():
        each_copy(0, 0, 0, start)
        # a 16-bit ref cannot be sliced at 8 rows: widen q once a tile
        for h in range(kv_heads):
            q32_ref[h] = q_ref[0, h].astype(jnp.float32)

    def attend(k, p, buf, rows, pages=1):
        """The item on the rows ``rows`` of the tile (``n`` of its ``M``)
        against ``pages`` consecutive pages from ``p``: ONE online-softmax
        update over their ``pages * bs`` keys.  A row's scores, softmax
        state and accumulation do not depend on which other rows, or heads,
        are computed beside it: as many heads as fill ``_STACK_ROWS`` rows
        go through the softmax as one array, between their q.K dots and
        their P.V dots."""
        pos, rid = pos_ref[0, rows], rid_ref[0, rows]          # [n, 1]
        n = pos.shape[0]
        keys = pages * block_size
        col = (first_ref[base + k] + p) * block_size + \
            jax.lax.broadcasted_iota(jnp.int32, (n, keys), 1)
        mask = jnp.logical_and(rid == k, col <= pos)
        if window:  # sliding window: only the last `window` positions
            mask = jnp.logical_and(mask, col > pos - window)
        k_pages = _head_pages(k_buf.at[buf], kv_heads, keys)
        v_pages = _head_pages(v_buf.at[buf], kv_heads, keys)
        together = max(1, _STACK_ROWS // n)
        for h0 in range(0, kv_heads, together):
            hs = range(h0, min(h0 + together, kv_heads))
            c = len(hs)
            s = jnp.concatenate([jax.lax.dot_general(
                q32_ref[h, rows], k_pages[h], (((1, ), (1, )), ((), ())),
                preferred_element_type=jnp.float32) for h in hs]) \
                * scale                                     # [c * n, keys]
            live = jnp.tile(mask, (c, 1))
            s = jnp.where(live, s, _NEG_INF)
            state = lambda ref: ref[h0:h0 + c, rows].reshape(
                c * n, ref.shape[2])[:, :1]
            m_prev = state(m_ref)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
            e = jnp.where(live, jnp.exp(s - m_safe), 0.0)
            alpha = jnp.where(m_prev == _NEG_INF, 0.0,
                              jnp.exp(m_prev - m_safe))
            l_new = alpha * state(l_ref) + jnp.sum(e, axis=1, keepdims=True)
            for i, h in enumerate(hs):
                part = slice(i * n, (i + 1) * n)
                acc_ref[h, rows] = acc_ref[h, rows] * alpha[part] + jnp.dot(
                    e[part], v_pages[h], preferred_element_type=jnp.float32)
            for ref, new in ((m_ref, m_new), (l_ref, l_new)):
                ref[h0:h0 + c, rows] = jnp.broadcast_to(
                    new, (c * n, ref.shape[2])).reshape(c, n, ref.shape[2])

    def item(it, carry):
        k, p, loaded, n_short, n_block = carry
        buf = it % 2
        slab = slab_ref[base + k] if short else jnp.int32(-1)
        whole = blocked(k, p) if block > 1 else False
        took = jnp.where(whole, block, 1)
        last = p + took == npages_ref[base + k]
        k_next = jnp.where(last, k + 1, k)
        p_next = jnp.where(last, 0, p + took)

        @pl.when(it + 1 < total)
        def _prefetch():
            each_copy(k_next, p_next, 1 - buf, start)

        each_copy(k, p, buf, wait)

        @pl.when(jnp.logical_and(slab < 0, jnp.logical_not(whole)))
        def _tile():
            attend(k, p, buf, slice(None))

        if block > 1:
            @pl.when(whole)
            def _block():
                attend(k, p, buf, slice(None), block)

        if short:
            @pl.when(slab >= 0)
            def _slab():
                attend(k, p, buf, pl.ds(pl.multiple_of(slab, 8), R))

        return k_next, p_next, loaded + took, \
            n_short + (slab >= 0).astype(jnp.int32), \
            n_block + jnp.where(whole, block, 0)

    *_, loaded, n_short, n_block = jax.lax.fori_loop(
        0, total, item, (jnp.int32(0), ) * 5)
    if count_loads:
        loads_ref[0, 0, 0] = loaded
        loads_ref[0, 0, 1] = n_short
        loads_ref[0, 0, 2] = n_block

    for h in range(kv_heads):
        l = l_ref[h, :, :1]
        o_ref[0, h] = (acc_ref[h] / jnp.where(l == 0.0, 1.0, l)) \
            .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "count_loads",
                                             "block_size"))
def paged_attention(q, k_cache, v_cache, block_tables, seq_slots, positions,
                    window=0, count_loads=False, block_size=None):
    """q: [T, H, Dh]; caches: [num_blocks, bs, Hkv, Dh]; block_tables:
    [max_seqs, maxb] int32; seq_slots, positions: [T] int32 → [T, H, Dh].

    Row ``t`` attends to the keys at positions ``<= positions[t]`` (inside
    ``window``, if any) of the sequence in slot ``seq_slots[t]``.  Slot 0 is
    the dead row's: it attends to nothing and comes back zero.  Exact for
    any rows; FAST when the rows of a sequence are contiguous with
    consecutive positions, since a run shares each page load
    (:func:`run_plan`).  ``count_loads=True`` also returns the page loads
    each tile performed and, of those, the short items' and the block
    items' (``[n_tiles, 3]``, all in pages; tests compare
    :func:`kernel_page_loads`).

    ``block_size`` (default: the cache's second axis): the tokens of a page,
    which a cache whose rows hold :func:`page_row_tokens` 2 has to state
    (``[num_blocks, bs / 2, 2, Dh]``).

    A shape :func:`run_tiled` refuses keeps one grid row a token."""
    T, H, Dh = q.shape
    _, page_rows, page_heads, _ = k_cache.shape
    bs = int(block_size or page_rows)
    Hkv = page_kv_heads(k_cache.shape, bs)
    if bs != page_rows * page_row_tokens(Hkv, Dh, k_cache.dtype):
        raise ValueError(
            f"pages of {page_rows} rows x {page_heads} for {bs} tokens of "
            f"{Hkv} KV heads: page_row_tokens states the format")
    tq = tile_rows(H, Hkv, Dh, k_cache.dtype, T)
    if tq is None:
        if count_loads:
            raise ValueError("count_loads needs the run-tiled kernel")
        out = paged_attention_per_token(q, k_cache, v_cache,
                                        block_tables[seq_slots], positions,
                                        window=window)
        return jnp.where((seq_slots != 0)[:, None, None], out, 0)
    maxb = block_tables.shape[1]
    g = H // Hkv
    M = tq * g
    P = item_pages(Hkv, Dh, k_cache.dtype, bs)
    pos, rid, run_slot, first_page, n_pages, slab, n_blocks = run_plan(
        jnp, seq_slots, positions, tq, bs, int(window), g, P)
    n = rid.shape[0]

    def rows(a):            # [n, tq] → [n, M, 1]: a token's g rows adjacent
        return jnp.repeat(a, g, axis=1)[:, :, None]

    qt = jnp.pad(q, ((0, n * tq - T), (0, 0), (0, 0))) \
        .reshape(n, tq, Hkv, g, Dh).transpose(0, 2, 1, 3, 4) \
        .reshape(n, Hkv, M, Dh)
    tile = lambda *block: pl.BlockSpec(
        (1, ) + block, lambda i, *_: (i, ) + (0, ) * len(block))
    out_shape = [jax.ShapeDtypeStruct((n, Hkv, M, Dh), q.dtype)]
    out_specs = [tile(Hkv, M, Dh)]
    if count_loads:
        out_shape.append(jax.ShapeDtypeStruct((n, 1, 3), jnp.int32))
        out_specs.append(pl.BlockSpec((1, 1, 3), lambda i, *_: (i, 0, 0),
                                      memory_space=pltpu.SMEM))
    buffers = [
        pltpu.VMEM((2, P * page_rows, page_heads, Dh), k_cache.dtype),
        pltpu.VMEM((2, P * page_rows, page_heads, Dh), v_cache.dtype),
        pltpu.VMEM((Hkv, M, Dh), jnp.float32),      # q, widened
        pltpu.VMEM((Hkv, M, Dh), jnp.float32),
        pltpu.VMEM((Hkv, M, 128), jnp.float32),
        pltpu.VMEM((Hkv, M, 128), jnp.float32),
    ]
    # the kernel's own VMEM: its buffers, and q and out tiles twice each
    held = sum(math.prod(b.shape) * jnp.dtype(b.dtype).itemsize
               for b in buffers) + 4 * Hkv * M * Dh * q.dtype.itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(n, ),
        in_specs=[tile(Hkv, M, Dh), tile(M, 1), tile(M, 1),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=out_specs,
        scratch_shapes=buffers + [pltpu.SemaphoreType.DMA((2, 2))],
    )
    out, *loads = pl.pallas_call(
        functools.partial(_run_kernel, tq=tq, block_size=bs, maxb=maxb,
                          scale=Dh**-0.5, window=int(window),
                          count_loads=count_loads, block=P),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", ),
            vmem_limit_bytes=None if held <= _RUNS_VMEM_HELD_BYTES
            else _RUNS_VMEM_BYTES),
        interpret=_interpret(),
        name="ds_paged_runs",
    )(block_tables.reshape(-1).astype(jnp.int32), run_slot.reshape(-1),
      first_page.reshape(-1), n_pages.reshape(-1), slab.reshape(-1),
      n_blocks.reshape(-1), (n_pages - n_blocks * (P - 1)).sum(-1), qt,
      rows(pos), rows(rid), k_cache, v_cache)
    out = out.reshape(n, Hkv, tq, g, Dh).transpose(0, 2, 1, 3, 4) \
        .reshape(n * tq, H, Dh)[:T]
    return (out, loads[0][:, 0]) if count_loads else out


# ------------------------------------------------------------- latent path
def _latent_kernel(tables_ref, slot_ref, first_ref, npages_ref, slab_ref,
                   nblocks_ref, long_ref, total_ref, q_ref, pos_ref, rid_ref,
                   c_hbm, o_ref, c_buf, sem, acc_ref, m_ref, l_ref, *, tq,
                   block_size, maxb, scale, rank, block, window=0):
    """One Q tile of the latent cache's reader: ``q_ref [1, M, L]`` (``M =
    tq * heads`` rows, row ``t * heads + h``, each ``(q_lat [rank] ; q_r)``
    in the cache's type), ``pos_ref``/``rid_ref [1, M, 1]``, against the
    tile's items of :func:`run_plan`: one page ``p`` of a run ``k``, or
    (``nblocks_ref``) a BLOCK of ``block`` consecutive pages of it, which go
    through one softmax update together, as :func:`_run_kernel`'s.  A page
    ``[bs, L]`` arrives ONCE and is both the keys (all ``L`` columns) and the
    values (the first ``rank``); the dots take their operands in the cache's
    type and sum in float32, the softmax state is float32.  An item computes
    its run's slab of rows, or (-1) the tile.  A tile none of whose runs
    takes the whole tile (``long_ref``: a burst's every tile) walks its
    items in a loop of its own: beside the block item in one loop body a
    slab item costs 7 % more (docs/kernels.md).  ``window``: a row sees the
    last ``window`` positions alone (0: all of them); its run's items start
    at the first page one of its rows sees (:func:`run_plan`)."""
    i = pl.program_id(0)
    base = i * tq
    total = total_ref[i]
    M = acc_ref.shape[0]
    g = M // tq
    R = slab_rows(g)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def blocked(k, p):
        """Whether the item at page ``p`` of run ``k`` is a block."""
        return p < nblocks_ref[base + k] * block

    def copies(k, p, buf, js, act):
        """``act`` (start, or wait) on the DMAs of the pages ``p + j`` of run
        ``k``, one under the other in ``c_buf[buf]``."""
        row = slot_ref[base + k] * maxb + first_ref[base + k] + p
        for j in js:
            act(pltpu.make_async_copy(
                c_hbm.at[tables_ref[row + j]],
                c_buf.at[buf, pl.ds(j * block_size, block_size)],
                sem.at[buf]))

    def each_copy(k, p, buf, act):
        """``act`` on the DMAs of the item at page ``p`` of run ``k``: its
        page, or its block's pages."""
        copies(k, p, buf, (0, ), act)
        if block > 1:
            pl.when(blocked(k, p))(
                lambda: copies(k, p, buf, range(1, block), act))

    start, wait = (lambda c: c.start()), (lambda c: c.wait())

    @pl.when(total > 0)
    def _first():
        each_copy(0, 0, 0, start)

    def attend(k, p, buf, rows, pages=1):
        """The item on the rows ``rows`` of the tile against ``pages``
        consecutive pages from ``p``: ONE online-softmax update over their
        ``pages * bs`` keys."""
        pos, rid = pos_ref[0, rows], rid_ref[0, rows]          # [n, 1]
        n = pos.shape[0]
        keys = pages * block_size
        col = (first_ref[base + k] + p) * block_size + \
            jax.lax.broadcasted_iota(jnp.int32, (n, keys), 1)
        live = jnp.logical_and(rid == k, col <= pos)
        if window:
            live = jnp.logical_and(live, col > pos - window)
        page = c_buf[buf, :keys]                               # [keys, L]
        s = jax.lax.dot_general(
            q_ref[0, rows], page, (((1, ), (1, )), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [n, keys]
        s = jnp.where(live, s, _NEG_INF)
        m_prev = m_ref[rows, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        e = jnp.where(live, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_new = alpha * l_ref[rows, :1] + jnp.sum(e, axis=1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * alpha + jnp.dot(
            e.astype(page.dtype), page[:, :rank],
            preferred_element_type=jnp.float32)
        m_ref[rows] = jnp.broadcast_to(m_new, (n, m_ref.shape[1]))
        l_ref[rows] = jnp.broadcast_to(l_new, (n, l_ref.shape[1]))

    def attend_slab(k, p, buf):
        # a token's g rows start at a multiple of g: where g is whole
        # sublane tiles the slab IS the token's rows
        attend(k, p, buf, pl.ds(pl.multiple_of(
            slab_ref[base + k], R if g % 8 == 0 else 8), R))

    def item_after(k, p, took):
        """The run and page of the item after the one that took ``took``
        pages from page ``p`` of run ``k``."""
        last = p + took == npages_ref[base + k]
        return jnp.where(last, k + 1, k), jnp.where(last, 0, p + took)

    def item(it, carry):
        k, p = carry
        buf = it % 2
        slab = slab_ref[base + k]
        whole = blocked(k, p) if block > 1 else False
        k_next, p_next = item_after(k, p, jnp.where(whole, block, 1))

        @pl.when(it + 1 < total)
        def _prefetch():
            each_copy(k_next, p_next, 1 - buf, start)

        each_copy(k, p, buf, wait)

        @pl.when(jnp.logical_and(slab < 0, jnp.logical_not(whole)))
        def _tile():
            attend(k, p, buf, slice(None))

        if block > 1:
            @pl.when(whole)
            def _block():
                attend(k, p, buf, slice(None), block)

        @pl.when(slab >= 0)
        def _slab():
            attend_slab(k, p, buf)

        return k_next, p_next

    def slab_item(it, carry):
        """:func:`item` where every run of the tile lies in one slab."""
        k, p = carry
        buf = it % 2
        k_next, p_next = item_after(k, p, 1)

        @pl.when(it + 1 < total)
        def _prefetch():
            copies(k_next, p_next, 1 - buf, (0, ), start)

        copies(k, p, buf, (0, ), wait)
        attend_slab(k, p, buf)
        return k_next, p_next

    long_tile = long_ref[i] > 0

    @pl.when(long_tile)
    def _items():
        jax.lax.fori_loop(0, total, item, (jnp.int32(0), ) * 2)

    @pl.when(jnp.logical_not(long_tile))
    def _slab_items():
        jax.lax.fori_loop(0, total, slab_item, (jnp.int32(0), ) * 2)

    l = l_ref[:, :1]
    o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "window"))
def paged_latent_attention(q, c_cache, block_tables, seq_slots, positions, *,
                           rank, scale, window=0):
    """Multi-head latent attention, absorbed, over a paged latent cache.

    q: ``[T, H, L]``, head ``h`` of row ``t`` as ``(q_n W_uk,h^T [rank] ;
    q_r [L - rank])``; c_cache: ``[num_blocks, bs, L]``, a token's row ``(c
    [rank] ; k_r)``, the same for every head; block_tables ``[max_seqs,
    maxb]``, seq_slots, positions ``[T]`` as :func:`paged_attention`'s.
    Returns ``[T, H, rank]``: ``sum_j softmax_j(q . row_j * scale) c_j`` over
    the keys ``j <= positions[t]`` of the row's sequence, and ``j >
    positions[t] - window`` where the layer has a ``window`` (the caller
    takes it through ``W_uv``).  A dead row (slot 0) comes back zero.  The
    shape has to pass :func:`latent_tiled`."""
    T, H, L = q.shape
    _, bs, _ = c_cache.shape
    tq = tile_rows(H, 1, L, c_cache.dtype, T, latent=True)
    if tq is None:
        raise ValueError(f"ds_paged_latent does not take {H} heads in "
                         f"{c_cache.dtype} (latent_tiled)")
    maxb = block_tables.shape[1]
    M = tq * H
    P = item_pages(1, L, c_cache.dtype, bs)
    pos, rid, run_slot, first_page, n_pages, slab, n_blocks = run_plan(
        jnp, seq_slots, positions, tq, bs, int(window), H, P)
    n = rid.shape[0]
    rows = lambda a: jnp.repeat(a, H, axis=1)[:, :, None]      # [n, M, 1]
    qt = jnp.pad(q.astype(c_cache.dtype), ((0, n * tq - T), (0, 0), (0, 0))) \
        .reshape(n, M, L)
    tile = lambda *block: pl.BlockSpec(
        (1, ) + block, lambda i, *_: (i, ) + (0, ) * len(block))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(n, ),
        in_specs=[tile(M, L), tile(M, 1), tile(M, 1),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile(M, rank),
        scratch_shapes=[
            pltpu.VMEM((2, P * bs, L), c_cache.dtype),
            pltpu.SemaphoreType.DMA((2, )),
            pltpu.VMEM((M, rank), jnp.float32),
            pltpu.VMEM((M, 128), jnp.float32),
            pltpu.VMEM((M, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_kernel, tq=tq, block_size=bs, maxb=maxb,
                          scale=float(scale), rank=int(rank), block=P,
                          window=int(window)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, M, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", ),
            vmem_limit_bytes=_LATENT_VMEM_BYTES),
        interpret=_interpret(),
        name="ds_paged_latent",
    )(block_tables.reshape(-1).astype(jnp.int32), run_slot.reshape(-1),
      first_page.reshape(-1), n_pages.reshape(-1), slab.reshape(-1),
      n_blocks.reshape(-1),
      ((slab < 0) & (n_pages > 0)).sum(-1).astype(jnp.int32),
      (n_pages - n_blocks * (P - 1)).sum(-1), qt,
      rows(pos), rows(rid), c_cache)
    return out.reshape(n * tq, H, rank)[:T]


# ------------------------------------------- latent path, expanded form
def expanded_min_rows(rank, nope, rope, value):
    """The rows a run has to hold for the EXPANDED form of multi-head latent
    attention to cost fewer operations than the absorbed one, from the
    configuration's widths alone.  A (row, key) pair costs a head ``2 (2 rank
    + rope)`` operations absorbed (scores over the latent row, values its
    first ``rank``) and ``2 (nope + rope + value)`` expanded, where a context
    token's key and value are first made from its latent row: ``2 rank (nope
    + value)`` a head, ONCE a run (:func:`paged_mla_chunk_attention` keeps a
    run in one tile).  The least whole ``n`` past the break-even ``rank (nope
    + value) / (2 rank - nope - value)``; None where the absorbed pair is the
    cheaper one and no run is long enough."""
    saved = 2 * rank - nope - value        # (2 rank + rope) - (nope + rope + value)
    if saved <= 0:
        return None
    return rank * (nope + value) // saved + 1


def chunk_tile_rows(tokens, min_rows):
    """``(TQ, SQ, R)`` of :func:`paged_mla_chunk_attention` for a call of
    ``tokens`` rows: the rows of a tile (a run is cut at a tile's end, and
    its keys are made once a tile), the rows that go through one softmax
    update together, and the most runs of ``min_rows`` rows a tile holds."""
    sq = min(_CHUNK_SUB_ROWS, -(-tokens // 8) * 8)
    tq = min(_CHUNK_TILE_ROWS // sq, -(-tokens // sq)) * sq
    return tq, sq, max(1, tq // min_rows)


def chunk_tiled(rank, nope, value, row, kv_dtype):
    """Whether :func:`paged_mla_chunk_attention` takes this shape: every
    slice of a page, of the made keys and of the query is whole lane tiles.
    (Interpreted, off the chip, any shape goes: the tests' widths.)"""
    if kv_dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return _interpret() or not any(
        n % 128 for n in (rank, nope, value, row - rank))


def latent_min_rows(cfg, row, kv_dtype, tokens):
    """``min_rows`` of :func:`latent_row_forms` for a call of ``tokens`` rows
    of a model ``cfg`` (its published widths) on a latent cache of ``row``
    columns: :func:`expanded_min_rows`, or None where every row takes the
    absorbed form: a shape one of the two readers does not take, or a
    buffer too short to hold one long run (a burst's: its program holds no
    call of the second reader)."""
    rank, nope, value = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.v_head_dim)
    min_rows = expanded_min_rows(rank, nope, cfg.qk_rope_head_dim, value)
    if min_rows is None or tokens < min_rows or not (
            latent_tiled(cfg.num_attention_heads, kv_dtype)
            and chunk_tiled(rank, nope, value, row, kv_dtype)):
        return None
    return min_rows


def latent_row_forms(xp, seq_slots, positions, min_rows):
    """WHICH form each row of a step over a latent cache takes: ``[..., T]``
    bool, True the expanded one (:func:`paged_mla_chunk_attention`), False
    the absorbed one (:func:`paged_latent_attention`) or a dead row.  With
    ``xp`` numpy in the batch builder and jax.numpy inside the step program,
    from the same ``seq_slots`` / ``positions``: the two make the same
    choice.  A RUN is a stretch of live rows with one slot and consecutive
    positions inside one tile of :func:`chunk_tile_rows`; it takes the
    expanded form when it holds ``min_rows`` rows
    (:func:`latent_min_rows`; None: no row does) or more.  ``[k, T]`` rows
    are ``k`` calls."""
    if min_rows is None:
        return xp.zeros(seq_slots.shape, bool)
    return _chunk_runs(xp, seq_slots, positions, min_rows)[0]


def _running(xp, a, reverse=False):
    """The running maximum of ``a [n, tq]`` along a tile's rows (``reverse``:
    the running minimum, from the tile's end)."""
    if xp is np:
        return np.minimum.accumulate(a[:, ::-1], 1)[:, ::-1] if reverse \
            else np.maximum.accumulate(a, 1)
    return jax.lax.cummin(a, 1, reverse=True) if reverse \
        else jax.lax.cummax(a, 1)


def _chunk_runs(xp, seq_slots, positions, min_rows):
    """:func:`latent_row_forms` (in ``seq_slots``' shape) and, of the runs
    that take the expanded form, by tile of :func:`chunk_tile_rows` (``[n,
    R]``, a tile's runs compacted to the front): the ``slot``, first buffer
    row ``row0``, rows ``n_rows`` (0: no such run) and first position
    ``pos0``."""
    T = seq_slots.shape[-1]
    tq, _, R = chunk_tile_rows(T, min_rows)
    slots, pos = (xp.pad(a.reshape(-1, T).astype(xp.int32),
                         ((0, 0), (0, -T % tq))).reshape(-1, tq)
                  for a in (seq_slots, positions))
    live = slots != 0
    edge = xp.full((slots.shape[0], 1), -1, xp.int32)
    joins = (slots == xp.concatenate([edge, slots[:, :-1]], 1)) \
        & (pos == xp.concatenate([edge, pos[:, :-1]], 1) + 1)
    start = live & ~joins
    # a run's last row: the next one starts a run, is dead, or is the tile's
    end = live & xp.concatenate([~(live & joins)[:, 1:], edge == -1], 1)
    at = xp.arange(tq, dtype=xp.int32)[None]
    first = _running(xp, xp.where(start, at, 0))
    last = _running(xp, xp.where(end, at, tq - 1), reverse=True)
    expanded = live & (last - first + 1 >= min_rows)
    head = start & expanded
    nth = xp.cumsum(head, axis=1) - 1
    pick = head[:, None, :] & (
        nth[:, None, :] == xp.arange(R, dtype=xp.int32)[None, :, None])
    of = lambda a: (pick * a[:, None, :]).sum(-1).astype(xp.int32)
    return expanded.reshape(seq_slots.shape[:-1] + (-1, ))[..., :T], \
        of(slots), of(xp.broadcast_to(at, slots.shape)), \
        of(last - first + 1), of(pos)


def _chunk_blocks(xp, n_rows, pos0, block_size, window=0):
    """``(P, blocks)``: the pages of a block of the chunk kernel, and the
    blocks each run of :func:`_chunk_runs` walks: its context's, from key 0
    or, under a ``window``, from the block that holds the first key its
    first row sees (:func:`_chunk_first_block`)."""
    P = _CHUNK_BLOCK_KEYS // block_size or 1
    keys = P * block_size
    runs = n_rows > 0
    blocks = (pos0 + n_rows - 1) // keys + 1
    if window:
        blocks = blocks - _chunk_first_block(xp, pos0, keys, window)
    return P, xp.where(runs, blocks, 0)


def _chunk_first_block(xp, pos0, keys, window):
    """The first block of ``keys`` keys that a run whose first row stands at
    ``pos0`` walks under a ``window``: the one that holds position ``pos0 -
    window + 1``."""
    return xp.maximum(pos0 - window + 1, 0) // keys


def seen_keys(positions, window=0):
    """The keys a row at each of ``positions`` (numpy) attends: all up to its
    own, or the last ``window`` of them."""
    return np.minimum(positions + 1, window) if window else positions + 1


def chunk_page_loads(seq_slots, positions, *, heads, block_size, min_rows,
                     window=0):
    """Host-side (numpy) count of what :func:`paged_mla_chunk_attention`
    does for these rows (``[T]``, or ``[B, T]``: B calls): ``(expanded,
    keys, pages)``, the rows that take it (:func:`latent_row_forms`), the
    (row, key) pairs they attend (inside the layer's ``window``, if any),
    and the latent pages its loops bring in: a run's blocks of
    ``_CHUNK_BLOCK_KEYS`` keys, once a head."""
    slots, pos = (np.atleast_2d(np.asarray(a))
                  for a in (seq_slots, positions))
    if min_rows is None:
        return np.zeros(slots.shape, bool), 0, 0
    expanded, _, _, n_rows, pos0 = _chunk_runs(np, slots, pos, min_rows)
    P, blocks = _chunk_blocks(np, n_rows, pos0, block_size, window)
    return expanded, int(seen_keys(pos, window)[expanded].sum()), \
        int(blocks.sum()) * P * heads


def _mla_chunk_kernel(tables_ref, total_ref, slot_ref, row0_ref, nrows_ref,
                      pos0_ref, q_ref, wuk_ref, wuv_ref, c_hbm, o_ref, c_buf,
                      sem, k_ref, v_ref, acc_ref, m_ref, l_ref, *, sq,
                      block_size, pages, maxb, scale, rank, max_runs,
                      window=0):
    """One tile of ``tq`` buffer rows and one head: ``q_ref [tq, W]`` (a row
    ``(q_n [nope] ; q_r ; zeros)``, as long as ``nope`` plus a page row's
    columns past ``rank``), ``wuk_ref [rank, nope]``, ``wuv_ref [rank,
    value]``, against the tile's runs (:func:`_chunk_runs`).  An item is one
    BLOCK of ``pages`` pages of a run: its latent rows arrive by DMA, the
    head's keys ``(c W_uk ; k_r ; zeros)`` and values ``c W_uv`` are made
    from them into VMEM in the cache's type, and every stretch of ``sq``
    rows that holds rows of the run which see the block takes one
    online-softmax update: with a mask only where an edge (the run's first
    or last row, the diagonal) crosses the stretch's square.  ``window``: a
    row sees the last ``window`` positions alone; a run's blocks then start
    at :func:`_chunk_first_block`, and the window's lower edge is one more
    edge that may cross a square."""
    i = pl.program_id(0)
    base = i * max_runs
    tq = acc_ref.shape[0]
    nope = wuk_ref.shape[1]
    keys = pages * block_size
    total = total_ref[i]

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def copies(k, j, buf, act):
        """``act`` (start, or wait) on the DMAs of block ``j`` of run ``k``:
        its pages one under the other in ``c_buf[buf]``.  A page past the
        table's end is its last page again (no row sees it)."""
        row = slot_ref[base + k] * maxb
        for p in range(pages):
            page = jnp.minimum(j * pages + p, maxb - 1)
            act(pltpu.make_async_copy(
                c_hbm.at[tables_ref[row + page]],
                c_buf.at[buf, pl.ds(p * block_size, block_size)],
                sem.at[buf]))

    start, wait = (lambda c: c.start()), (lambda c: c.wait())

    def first_block(k):
        """The first block run ``k`` walks (a run past the tile's last: the
        last run's again; no item of it follows)."""
        if not window:
            return 0
        return _chunk_first_block(
            jnp, pos0_ref[base + jnp.minimum(k, max_runs - 1)], keys, window)

    @pl.when(total > 0)
    def _first():
        copies(0, first_block(0), 0, start)

    def attend(k, j, r0, masked):
        """The rows ``r0 .. r0 + sq`` against the made block; ``masked``: an
        edge crosses the square (not every row is the run's, or not every
        row sees every key)."""
        rows = pl.ds(r0, sq)
        s = jax.lax.dot_general(
            q_ref[rows], k_ref[...], (((1, ), (1, )), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [sq, keys]
        m_prev = m_ref[rows, :1]
        if masked:
            row = r0 - row0_ref[base + k] + jax.lax.broadcasted_iota(
                jnp.int32, (sq, keys), 0)
            col = j * keys + jax.lax.broadcasted_iota(
                jnp.int32, (sq, keys), 1)
            live = (row >= 0) & (row < nrows_ref[base + k]) \
                & (col <= pos0_ref[base + k] + row)
            if window:
                live &= col > pos0_ref[base + k] + row - window
            s = jnp.where(live, s, _NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # a row of another run, or of none, keeps -inf: exp(-inf - 0)
            m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
            alpha = jnp.where(m_prev == _NEG_INF, 0.0,
                              jnp.exp(m_prev - m_safe))
        else:                   # every score counts: m_new is finite
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            m_safe = m_new
            alpha = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_safe)
        l_new = alpha * l_ref[rows, :1] + jnp.sum(e, axis=1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * alpha + jnp.dot(
            e.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_ref[rows] = jnp.broadcast_to(m_new, (sq, m_ref.shape[1]))
        l_ref[rows] = jnp.broadcast_to(l_new, (sq, l_ref.shape[1]))

    def item(it, carry):
        k, j = carry
        buf = it % 2
        row0, n_rows = row0_ref[base + k], nrows_ref[base + k]
        pos0 = pos0_ref[base + k]
        last = j == (pos0 + n_rows - 1) // keys
        k_next = jnp.where(last, k + 1, k)
        j_next = jnp.where(last, first_block(k_next), j + 1)

        @pl.when(it + 1 < total)
        def _prefetch():
            copies(k_next, j_next, 1 - buf, start)

        copies(k, j, buf, wait)
        block = c_buf[buf]                                  # [keys, row]
        made = lambda w: jnp.dot(
            block[:, :rank], w[...],
            preferred_element_type=jnp.float32).astype(k_ref.dtype)
        k_ref[:, :nope] = made(wuk_ref)
        k_ref[:, nope:] = block[:, rank:]
        v_ref[...] = made(wuv_ref)

        for r0 in range(0, tq, sq):
            # the run's rows of this stretch (one past the last); whether
            # the last of them sees the block; whether the stretch lies
            # inside the run and its first row sees all of the block
            lo = jnp.maximum(row0, r0)
            hi = jnp.minimum(row0 + n_rows, r0 + sq)
            sees = (lo < hi) & (j * keys <= pos0 + hi - 1 - row0)
            inner = (row0 <= r0) & (row0 + n_rows >= r0 + sq) \
                & ((j + 1) * keys - 1 <= pos0 + r0 - row0)
            if window:      # the block's last key against the first row's
                # window, its first against the last row's
                sees &= (j + 1) * keys - 1 > pos0 + lo - row0 - window
                inner &= j * keys > pos0 + r0 + sq - 1 - row0 - window
            pl.when(sees & inner)(
                functools.partial(attend, k, j, r0, False))
            pl.when(sees & jnp.logical_not(inner))(
                functools.partial(attend, k, j, r0, True))
        return k_next, j_next

    jax.lax.fori_loop(0, total, item,
                      (jnp.int32(0), jnp.asarray(first_block(0), jnp.int32)))

    l = l_ref[:, :1]
    o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)) \
        .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "min_rows",
                                             "window"))
def paged_mla_chunk_attention(q, c_cache, w_uk, w_uv, block_tables, seq_slots,
                              positions, *, rank, scale, min_rows, window=0):
    """Multi-head latent attention in the EXPANDED form over the paged latent
    cache, for the rows of LONG runs (``ds_paged_mla_chunk``).

    q: ``[T, H, W]``, head ``h`` of row ``t`` as ``(q_n [nope] ; q_r ;
    zeros)`` with ``W = nope + (L - rank)``; c_cache ``[num_blocks, bs, L]``,
    a token's row ``(c [rank] ; k_r ; zeros)``; w_uk ``[rank, G, nope]``,
    w_uv ``[rank, G, value]`` with ``G`` = ``H``, or fewer: K/V GROUPS, each
    read by ``H / G`` adjacent query heads; block_tables, seq_slots,
    positions as
    :func:`paged_latent_attention`'s.  Returns ``[T, H, value]``: for every
    row of a run of ``min_rows`` rows or more (:func:`latent_row_forms`)
    ``sum_j softmax_j(q . (c_j W_uk,h ; k_r,j) * scale) c_j W_uv,h`` over
    the keys ``j <= positions[t]`` of its sequence (and ``j > positions[t]
    - window`` where the layer has a ``window``), the keys and values made
    from the latent pages in VMEM, a block of ``_CHUNK_BLOCK_KEYS`` keys at
    a time, in the cache's type with float32 sums; every other row (a
    shorter run's, a dead one) comes back zero.  The grid is (tile, head):
    a head's step walks the blocks of the tile's runs, so a run's keys are
    made once a head.  The shape has to pass :func:`chunk_tiled`."""
    T, H, W = q.shape
    _, bs, L = c_cache.shape
    nope, value = w_uk.shape[2], w_uv.shape[2]
    group = H // w_uk.shape[1]              # query heads a K/V group
    if W != nope + L - rank or not chunk_tiled(rank, nope, value, L,
                                               c_cache.dtype):
        raise ValueError(
            f"ds_paged_mla_chunk does not take queries of {W} on keys of "
            f"{nope} + {L} - {rank} in {c_cache.dtype} (chunk_tiled)")
    dtype = c_cache.dtype
    tq, sq, R = chunk_tile_rows(T, min_rows)
    maxb = block_tables.shape[1]
    _, run_slot, row0, n_rows, pos0 = _chunk_runs(
        jnp, seq_slots, positions, min_rows)
    n = row0.shape[0]
    P, blocks = _chunk_blocks(jnp, n_rows, pos0, bs, int(window))
    qt = jnp.pad(q.astype(dtype).reshape(T, H * W), ((0, n * tq - T), (0, 0)))
    cols = lambda width: pl.BlockSpec((tq, width), lambda i, h, *_: (i, h))
    weight = lambda width: pl.BlockSpec(
        (rank, width), (lambda i, h, *_: (0, h)) if group == 1
        else (lambda i, h, *_: (0, h // group)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n, H),
        in_specs=[cols(W), weight(nope), weight(value),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=cols(value),
        scratch_shapes=[
            pltpu.VMEM((2, P * bs, L), dtype),
            pltpu.SemaphoreType.DMA((2, )),
            pltpu.VMEM((P * bs, W), dtype),             # the made keys
            pltpu.VMEM((P * bs, value), dtype),         # the made values
            pltpu.VMEM((tq, value), jnp.float32),
            pltpu.VMEM((tq, 128), jnp.float32),
            pltpu.VMEM((tq, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_mla_chunk_kernel, sq=sq, block_size=bs, pages=P,
                          maxb=maxb, scale=float(scale), rank=int(rank),
                          max_runs=R, window=int(window)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n * tq, H * value), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_CHUNK_VMEM_BYTES),
        interpret=_interpret(),
        name="ds_paged_mla_chunk",
    )(block_tables.reshape(-1).astype(jnp.int32), blocks.sum(-1),
      run_slot.reshape(-1), row0.reshape(-1), n_rows.reshape(-1),
      pos0.reshape(-1), qt, w_uk.astype(dtype).reshape(rank, -1),
      w_uv.astype(dtype).reshape(rank, -1), c_cache)
    return out[:T].reshape(T, H, value)


# ------------------------------------------------------- per-token path
def _per_token_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                      acc_ref, m_ref, l_ref, *, block_size, scale, groups,
                      window):
    """Grid ``(token i, page j of its table row)``: ``q_ref [1, H, Dh]``
    against the page ``k_ref``/``v_ref [1, bs, Hkv, Dh]`` the index map took
    from the block table; the online-softmax state lives across ``j``.  A
    page past the token's position (or wholly before its window) is still
    brought in by the pipeline, but not computed on."""
    i, j = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    k_start = j * block_size
    pos = pos_ref[i]
    live = k_start <= pos
    if window:
        live = jnp.logical_and(live,
                               k_start + block_size - 1 > pos - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [H, Dh]
        k = k_ref[0].astype(jnp.float32)               # [bs, Hkv, Dh]
        v = v_ref[0].astype(jnp.float32)
        H, Dh = q.shape
        bs, Hkv, _ = k.shape
        s = jnp.einsum("kmd,bkd->kmb", q.reshape(Hkv, groups, Dh), k,
                       preferred_element_type=jnp.float32) * scale
        col = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        mask = col <= pos
        if window:  # sliding window: only the last `window` positions
            mask = jnp.logical_and(mask, col > pos - window)
        s = jnp.where(mask, s, _NEG_INF)

        s_f = s.reshape(H, bs)
        m_prev = m_ref[:, :1]                          # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s_f, axis=1, keepdims=True))
        m_safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.exp(s_f - m_safe)
        p = jnp.where(s_f == _NEG_INF, 0.0, p)
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.einsum("kmb,bkd->kmd", p.reshape(Hkv, groups, bs), v,
                        preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv.reshape(H, Dh)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nb - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)) \
            .astype(o_ref.dtype)


def paged_attention_per_token(q, k_cache, v_cache, tables_t, positions,
                              window=0):
    """One grid row a token: q ``[T, H, Dh]``, ``tables_t [T, maxb]`` each
    token's block-table row, ``positions [T]`` → ``[T, H, Dh]``.  Every
    token streams every page of its row; the kernel for the shapes
    :func:`run_tiled` refuses."""
    T, H, Dh = q.shape
    _, bs, Hkv, _ = k_cache.shape
    maxb = tables_t.shape[1]
    page = pl.BlockSpec((1, bs, Hkv, Dh),
                        lambda i, j, tb, ps: (tb[i, j], 0, 0, 0))
    row = pl.BlockSpec((1, H, Dh), lambda i, j, tb, ps: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, maxb),
        in_specs=[row, page, page],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((H, Dh), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_per_token_kernel, block_size=bs, scale=Dh**-0.5,
                          groups=H // Hkv, window=int(window)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="ds_paged_decode",
    )(tables_t, positions, q, k_cache, v_cache)
