#!/usr/bin/env python
"""Multi-head latent attention over the paged latent cache, both forms, on the
chip: the ABSORBED form (``ds_paged_latent``: every head's query taken into
the latent space, the cache rows themselves the keys and the values) against
the EXPANDED form, as the kernel that serves a prefill chunk's rows since PR 51
(``ds_paged_mla_chunk``: a block's per-head keys and values made from the
latent pages in VMEM; section ``chunk``) and as PR 35 wrote it in XLA (per-head
keys and values in a step's scratch, ``expanded_run_attention`` below; section
``forms``); the absorbed kernel's chunk with its long runs' tile items taking
a BLOCK of ``P`` pages through one softmax update, ``P`` in 1 / 2 / 4 / 8
(section ``pages``; set from here: the program's ``P`` is
``paged_attention.item_pages``' from the shapes, and has no option); and the
kernel at a decode burst's shape (section ``burst``).

    python tools/mla_forms_bench.py                       # chip only, ~5 min
    python tools/mla_forms_bench.py --sections chunk      # ~2 min

Prints one JSON line a shape: milliseconds a call (one layer), the largest
difference between the two forms' outputs, and what a kernel's time is of its
roofline; in the sweeps the kernel's own time from a device trace of
``--reps`` calls, microseconds a PAGE and a tile item's roofline over that.
Section ``chunk`` puts one run that fills the buffer (``--chunk-shapes``:
heads x rows, the two cells' steps) through both PATHS and sweeps the new
kernel's rows of a softmax update and keys of a block (``--chunk-variants``,
set from here: the program has no such option).  docs/kernels.md and PERF.md
hold the readings that decided which rows take which form in ``_mla_block``
and what ``item_pages`` gives the latent kernel.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from deepspeed_tpu.ops.pallas import paged_attention as paged  # noqa: E402
from deepspeed_tpu.ops.pallas.paged_attention import (  # noqa: E402
    chunk_page_loads, expanded_min_rows, kernel_page_loads,
    paged_latent_attention, paged_mla_chunk_attention)
from paged_block_bench import kernel_ms  # noqa: E402

H, RANK, DN, DR, DV, ROW, BS = 128, 512, 128, 64, 128, 640, 128
SCALE = (DN + DR) ** -0.5
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9          # perfbench/peaks.json, v5e
#: the least time of ONE page on a tile of 1024 query rows: its two dots
TILE_PAGE_US = 1024 * BS * (ROW + RANK) * 2 / PEAK_FLOPS * 1e6


def expanded_run_attention(q_n, q_r, pages, table_row, first_pos, w_uk, w_uv,
                           key_block=512):
    """One run's rows (consecutive positions from ``first_pos`` of the
    sequence whose block-table row is ``table_row``) in the expanded form:
    q_n ``[T, H, dn]``, q_r ``[T, H, dr]`` -> ``[T, H, dv]``.  The context's
    keys and values are made ``key_block`` cache rows at a time (scratch:
    ``[key_block, H, dn + dv]``) and folded into an online softmax, as many
    blocks as the run's last position needs."""
    T = q_n.shape[0]
    pos = first_pos + jnp.arange(T)
    per = key_block // BS
    f32 = jnp.float32

    def block(j, carry):
        m, l, acc = carry
        blks = jax.lax.dynamic_slice_in_dim(table_row, j * per, per)
        rows = pages[blks].reshape(key_block, -1)
        c, k_r = rows[:, :RANK], rows[:, RANK:RANK + DR]
        k_n = jnp.einsum("tc,chn->thn", c, w_uk)
        v = jnp.einsum("tc,chv->thv", c, w_uv)
        s = (jnp.einsum("shn,thn->hst", q_n, k_n, preferred_element_type=f32)
             + jnp.einsum("shr,tr->hst", q_r, k_r,
                          preferred_element_type=f32)) * SCALE
        key_pos = j * key_block + jnp.arange(key_block)
        live = key_pos[None, None, :] <= pos[None, :, None]
        s = jnp.where(live, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        e = jnp.where(live, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hst,thv->hsv", e.astype(v.dtype), v, preferred_element_type=f32)
        return m_new, alpha * l + jnp.sum(e, -1), acc

    n = (first_pos + T - 1) // key_block + 1
    m, l, acc = jax.lax.fori_loop(
        0, n, block, (jnp.full((H, T), -1e30, f32), jnp.zeros((H, T), f32),
                      jnp.zeros((H, T, DV), f32)))
    return (acc / l[..., None]).transpose(1, 0, 2).astype(q_n.dtype)


def timed(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3, out


def roofline_ms(slots, pos, maxb):
    """The least time of the absorbed kernel's call: its page loads and its
    rows' q and output against the (row, key) pairs' operations."""
    grid, *_ = kernel_page_loads(
        slots, pos, heads=H, kv_heads=1, head_dim=ROW,
        kv_dtype=jnp.bfloat16, block_size=BS, maxb=maxb, latent=True)
    live = slots != 0
    keys = int((pos + 1)[live].sum())
    nbytes = (grid * BS * (RANK + DR) + int(live.sum()) * H
              * (2 * RANK + DR)) * 2
    flops = keys * H * (2 * RANK + DR) * 2
    return max(nbytes / PEAK_BYTES, flops / PEAK_FLOPS) * 1e3, grid, keys


def chunk(rng, ctx, T, maxb, nb, lead=0):
    """One sequence's rows that end a context of ``ctx`` tokens: all ``T``
    of a buffer, or all but its first ``lead`` (dead rows, where a step has
    its decode rows: the chunk then fills no stretch of the kernel's)."""
    tables = np.zeros((65, maxb), np.int32)
    tables[1] = rng.permutation(np.arange(1, nb))[:maxb]
    live = np.arange(T) >= lead
    return tables, live.astype(np.int32), np.where(
        live, np.arange(ctx - T, ctx), 0).astype(np.int32)


def compiled_with(P, args, slots, pos, maxb):
    """``ds_paged_latent`` compiled for ``args`` as ``item_pages`` = ``P``
    would make it, and the pages its block items then load."""
    rule, paged.item_pages = paged.item_pages, (lambda *a: P)
    try:
        fn = jax.jit(lambda *a: paged_latent_attention.__wrapped__(
            *a, rank=RANK, scale=SCALE)).lower(*args).compile()
        block = kernel_page_loads(
            slots, pos, heads=H, kv_heads=1, head_dim=ROW,
            kv_dtype=jnp.bfloat16, block_size=BS, maxb=maxb, latent=True)[3]
    finally:
        paged.item_pages = rule
    return fn, block


def chunk_section(opts, dev, pages, rng, maxb, nb):
    """``ds_paged_mla_chunk`` (the expanded form inside one kernel) on one
    run that fills the step's buffer, beside the absorbed PATH on the same
    rows (``q`` into the latent space, ``ds_paged_latent``, the output
    through ``W_uv``): each kernel's own time from a device trace, each
    path's call, and the new kernel's share of ITS roofline (a pair's 2 x
    (nope + rope + value) operations a head, and 2 x rank x (nope + value) a
    head for every context token a tile makes keys for)."""
    bf16 = jnp.bfloat16
    min_rows = expanded_min_rows(RANK, DN, DR, DV)
    for shape in opts.chunk_shapes.split(","):
        heads, T = (int(x) for x in shape.split("x"))
        ks = jax.random.split(jax.random.PRNGKey(heads), 4)
        w_uk = jax.random.normal(ks[0], (RANK, heads, DN), bf16) * RANK ** -0.5
        w_uv = jax.random.normal(ks[1], (RANK, heads, DV), bf16) * RANK ** -0.5
        q_n = jax.random.normal(ks[2], (T, heads, DN), bf16)
        q_r = jax.random.normal(ks[3], (T, heads, DR), bf16)

        def absorbed_path(q_n, q_r, pg, t, s, p):
            q_lat = jnp.einsum("thn,chn->thc", q_n, w_uk)
            q = jnp.pad(jnp.concatenate([q_lat, q_r], -1),
                        ((0, 0), (0, 0), (0, ROW - RANK - DR)))
            o_lat = paged_latent_attention(q, pg, t, s, p, rank=RANK,
                                           scale=SCALE)
            return jnp.einsum("thc,chv->thv", o_lat, w_uv)

        def expanded_path(q_n, q_r, pg, t, s, p):
            q = jnp.pad(jnp.concatenate([q_n, q_r], -1),
                        ((0, 0), (0, 0), (0, ROW - RANK - DR)))
            return paged_mla_chunk_attention.__wrapped__(
                q, pg, w_uk, w_uv, t, s, p, rank=RANK, scale=SCALE,
                min_rows=min_rows)

        for ctx in (int(x) for x in opts.chunk_contexts.split(",")):
            if ctx < T:
                continue
            tables, slots, pos = chunk(rng, ctx, T, maxb, nb,
                                       opts.chunk_lead)
            args = (q_n, q_r, pages, jnp.asarray(tables), jnp.asarray(slots),
                    jnp.asarray(pos))
            fn_a = jax.jit(absorbed_path).lower(*args).compile()
            ms_a, out_a = timed(fn_a, *args)
            own_a, _ = kernel_ms(fn_a, args, opts.reps, "ds_paged_latent")
            out_a = np.asarray(out_a, np.float32)
            for variant in opts.chunk_variants.split(","):
                sub, keys = (int(x) for x in variant.split(":"))
                saved = paged._CHUNK_SUB_ROWS, paged._CHUNK_BLOCK_KEYS
                paged._CHUNK_SUB_ROWS, paged._CHUNK_BLOCK_KEYS = sub, keys
                try:
                    t0 = time.perf_counter()
                    # (a jit of its own: the trace is cached by function)
                    fn_e = jax.jit(lambda *a: expanded_path(*a)).lower(
                        *args).compile()
                    compile_s = time.perf_counter() - t0
                    forms, pairs, loads = chunk_page_loads(
                        slots, pos, heads=heads, block_size=BS,
                        min_rows=min_rows)
                finally:
                    paged._CHUNK_SUB_ROWS, paged._CHUNK_BLOCK_KEYS = saved
                ms_e, _ = timed(fn_e, *args)
                own_e, out_e = kernel_ms(fn_e, args, opts.reps,
                                         "ds_paged_mla_chunk")
                out_e = np.asarray(out_e, np.float32)
                tiles = -(-T // paged.chunk_tile_rows(T, min_rows)[0])
                flops = pairs * heads * 2 * (DN + DR + DV) \
                    + tiles * ctx * heads * 2 * RANK * (DN + DV)
                floor = max(flops / PEAK_FLOPS,
                            loads * BS * ROW * 2 / PEAK_BYTES) * 1e3
                print(json.dumps({
                    "shape": f"chunk of {T - opts.chunk_lead} rows, {heads} "
                    f"heads, context {ctx}", "device": dev.device_kind,
                    "rows_update:keys_block": variant,
                    "absorbed_path_ms": round(ms_a, 3),
                    "ds_paged_latent_ms": round(own_a, 3),
                    "expanded_path_ms": round(ms_e, 3),
                    "ds_paged_mla_chunk_ms": round(own_e, 3),
                    "compile_s": round(compile_s, 2),
                    "expanded_rows": int(forms.sum()), "expanded_keys": pairs,
                    "expanded_pages": loads,
                    "us_a_page": round(1e3 * own_e / loads, 4),
                    "chunk_floor_ms": round(floor, 3),
                    "chunk_roofline_share": round(100 * floor / own_e, 2),
                    "max_abs_diff": float(np.abs(out_a - out_e).max()),
                    "out_abs_max": float(np.abs(out_a).max())}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pages", default="1,2,4,8")
    ap.add_argument("--contexts", default="4096,16384")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--forms", type=int, default=1,
                    help="0: leave the absorbed / expanded comparison out")
    ap.add_argument("--sections", default="forms,pages,burst,chunk",
                    help="which parts run (chunk: ds_paged_mla_chunk)")
    ap.add_argument("--chunk-shapes", default="128x1024,64x2048",
                    help="heads x rows of the chunk kernel's calls")
    ap.add_argument("--chunk-contexts", default="2048,4096,8192,16384")
    ap.add_argument("--chunk-lead", type=int, default=0,
                    help="dead rows before the chunk (a step's decode rows)")
    ap.add_argument("--chunk-variants", default="1024:1024",
                    help="rows of a softmax update : keys of a block, several "
                    "with commas between (set from here; the program has no "
                    "such option)")
    opts = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",")]
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip only; jax found {dev.platform}", file=sys.stderr)
        return 2
    bf16 = jnp.bfloat16
    key = jax.random.PRNGKey(0)
    maxb, nb = 160, 1024                        # 20 k tokens a row; 131 k rows
    ks = jax.random.split(key, 6)
    pages = (jax.random.normal(ks[0], (nb, BS, ROW), bf16)
             * (jnp.arange(ROW) < RANK + DR)).astype(bf16)
    w_uk = jax.random.normal(ks[1], (RANK, H, DN), bf16) * RANK ** -0.5
    w_uv = jax.random.normal(ks[2], (RANK, H, DV), bf16) * RANK ** -0.5
    rng = np.random.default_rng(0)

    absorbed = jax.jit(lambda q, pg, t, s, p: paged_latent_attention(
        q, pg, t, s, p, rank=RANK, scale=SCALE))

    @jax.jit
    def absorbed_whole(q_n, q_r, pg, t, s, p):
        q_lat = jnp.einsum("thn,chn->thc", q_n, w_uk)
        q = jnp.pad(jnp.concatenate([q_lat, q_r], -1),
                    ((0, 0), (0, 0), (0, ROW - RANK - DR)))
        o_lat = paged_latent_attention(q, pg, t, s, p, rank=RANK, scale=SCALE)
        return jnp.einsum("thc,chv->thv", o_lat, w_uv)

    expanded = jax.jit(lambda q_n, q_r, pg, row, p0: expanded_run_attention(
        q_n, q_r, pg, row, p0, w_uk, w_uv))

    # ---- a prefill chunk of 1024 rows against 4 k and 16 k of context
    T = 1024
    q_n = jax.random.normal(ks[3], (T, H, DN), bf16)
    q_r = jax.random.normal(ks[4], (T, H, DR), bf16)
    q_lat = jnp.pad(jnp.concatenate(
        [jnp.einsum("thn,chn->thc", q_n, w_uk), q_r], -1),
        ((0, 0), (0, 0), (0, ROW - RANK - DR)))
    sections = set(opts.sections.split(","))
    if "chunk" in sections:
        chunk_section(opts, dev, pages, rng, maxb, nb)
    for ctx in ints(opts.contexts) if opts.forms and "forms" in sections \
            else ():
        tables, slots, pos = chunk(rng, ctx, T, maxb, nb)
        p0 = ctx - T
        args = (jnp.asarray(tables), jnp.asarray(slots), jnp.asarray(pos))
        ms_a, out_a = timed(absorbed_whole, q_n, q_r, pages, *args)
        ms_k, _ = timed(absorbed, q_lat, pages, *args)
        ms_e, out_e = timed(expanded, q_n, q_r, pages,
                            jnp.asarray(tables[1]), jnp.int32(p0))
        floor, grid, keys = roofline_ms(slots, pos, maxb)
        diff = float(jnp.max(jnp.abs(out_a.astype(jnp.float32)
                                     - out_e.astype(jnp.float32))))
        print(json.dumps({
            "shape": f"chunk of {T} rows, context {ctx}", "device":
            dev.device_kind, "absorbed_ms": ms_a, "ds_paged_latent_ms": ms_k,
            "expanded_ms": ms_e, "max_abs_diff": diff,
            "out_abs_max": float(jnp.max(jnp.abs(out_e.astype(jnp.float32)))),
            "page_loads": grid, "keys": keys, "kernel_floor_ms": floor,
            "kernel_roofline_share": 100 * floor / ms_k}), flush=True)

    # ---- the same chunk, a long run's item on P pages (every item of a
    # one-sequence chunk is a tile item): the kernel's own time
    rule = paged.item_pages(1, ROW, bf16, BS)
    for ctx in ints(opts.contexts) if "pages" in sections else ():
        tables, slots, pos = chunk(rng, ctx, T, maxb, nb)
        args = (q_lat, pages, jnp.asarray(tables), jnp.asarray(slots),
                jnp.asarray(pos))
        floor, grid, keys = roofline_ms(slots, pos, maxb)
        base = None
        for P in ints(opts.pages):
            fn, block = compiled_with(P, args, slots, pos, maxb)
            ms, out = kernel_ms(fn, args, opts.reps, "ds_paged_latent")
            out = np.asarray(out, np.float32)
            base = out if base is None else base
            print(json.dumps({
                "shape": f"chunk of {T} rows, context {ctx}", "P": P,
                "item_pages_rule": rule, "kernel_ms": round(ms, 4),
                "grid_pages": grid, "block_pages": block,
                "us_a_page": round(1e3 * ms / grid, 4),
                "item_roofline_share": round(
                    100 * TILE_PAGE_US / (1e3 * ms / grid), 2),
                "kernel_roofline_share": round(100 * floor / ms, 2),
                "max_abs_diff_from_first": float(np.abs(out - base).max()),
                "out_abs_max": float(np.abs(base).max())}), flush=True)

    # ---- a decode burst's iteration: 64 sequences, one row each
    for ctx in (2048, 6500, 12000) if "burst" in sections else ():
        tables = np.zeros((65, maxb), np.int32)
        for s in range(1, 65):
            tables[s] = rng.integers(1, nb, maxb)
        slots = np.arange(65, dtype=np.int32)
        pos = np.where(slots != 0, ctx + 7 * slots, 0).astype(np.int32)
        q = jax.random.normal(ks[5], (65, H, ROW), bf16)
        args = (q, pages, jnp.asarray(tables), jnp.asarray(slots),
                jnp.asarray(pos))
        ms_k, _ = timed(absorbed, *args, reps=20)
        # every item a slab item: the kernel's own time as the program makes
        # it and with every item one page, which have to read the same
        own = [kernel_ms(compiled_with(P, args, slots, pos, maxb)[0], args,
                         opts.reps, "ds_paged_latent")[0] for P in (rule, 1)]
        floor, grid, keys = roofline_ms(slots, pos, maxb)
        print(json.dumps({
            "shape": f"burst row of 64 sequences, context ~{ctx}",
            "ds_paged_latent_ms": ms_k, "kernel_ms": round(own[0], 4),
            "kernel_ms_with_one_page_items": round(own[1], 4),
            "us_a_page": round(1e3 * own[0] / grid, 4),
            "page_loads": grid, "keys": keys,
            "us_per_item": 1e3 * ms_k / grid, "kernel_floor_ms": floor,
            "kernel_roofline_share": 100 * floor / ms_k}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
