#!/usr/bin/env python
"""Multi-head latent attention over the paged latent cache, both forms, on the
chip: the ABSORBED form (``ds_paged_latent``: every head's query taken into
the latent space, the cache rows themselves the keys and the values) against
the EXPANDED form (per-head keys and values made from the cache rows in a
step's scratch, ``expanded_run_attention`` below) for one prefill chunk of one
sequence; the absorbed kernel's chunk with its long runs' tile items taking a
BLOCK of ``P`` pages through one softmax update, ``P`` in 1 / 2 / 4 / 8 (set
from here: the program's ``P`` is ``paged_attention.item_pages``' from the
shapes, and has no option); and the kernel at a decode burst's shape.

    python tools/mla_forms_bench.py            # chip only, ~3 min

Prints one JSON line a shape: milliseconds a call (one layer), the largest
difference between the two forms' outputs, and what the absorbed kernel's
time is of its roofline; in the sweep the kernel's own time from a device
trace of ``--reps`` calls, microseconds a PAGE and a tile item's roofline
over that.  docs/kernels.md and PERF.md hold the readings that decided which
rows take which form in ``pangu_ultra_moe_ragged_step`` and what
``item_pages`` gives the latent kernel.
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
    kernel_page_loads, paged_latent_attention)
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


def chunk(rng, ctx, T, maxb, nb):
    """One sequence's ``T`` rows that end a context of ``ctx`` tokens."""
    tables = np.zeros((65, maxb), np.int32)
    tables[1] = rng.permutation(np.arange(1, nb))[:maxb]
    return tables, np.ones(T, np.int32), np.arange(ctx - T, ctx,
                                                   dtype=np.int32)


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


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pages", default="1,2,4,8")
    ap.add_argument("--contexts", default="4096,16384")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--forms", type=int, default=1,
                    help="0: leave the absorbed / expanded comparison out")
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
    for ctx in ints(opts.contexts) if opts.forms else ():
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
    for ctx in ints(opts.contexts):
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
    for ctx in (2048, 6500, 12000):
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
