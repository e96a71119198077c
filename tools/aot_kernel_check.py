#!/usr/bin/env python
"""AOT compile-check the Pallas kernel suite for a TPU target — the sandbox's
Mosaic gate.

The CPU test suite runs Pallas kernels in interpreter mode, so a Mosaic-only
lowering error (bad block shape, unsupported op, layout mismatch) would
otherwise surface on the chip.  With libtpu installed,
``jax.experimental.topologies.get_topology_desc`` describes a v5e with no chip
attached, and each kernel compiles ahead of time against it.

It says a kernel COMPILES for ``TPU v5 lite``.  It says nothing about whether
it runs, how fast, or whether the result is right.

Prints one PASS/FAIL line per kernel; exit 0 only if all pass, 3 if no TPU
topology can be described here (no libtpu).  ``tests/unit/ops/
test_aot_kernel_check.py`` runs it in tier-1.

``--ops <kernel>`` (a ``pallas_call`` name, ``ds_paged_runs``; several with
commas between) also counts what Mosaic made of that kernel: it has the
compiler write the kernel after its last pass (``--xla_mosaic_dump_to``, a
temporary directory) and prints, for every check that compiled the kernel, the
histogram of ``llo.*`` operations in the body of the kernel's first loop and
in each branch (``scf.if``) directly inside it — for ``ds_paged_runs`` and
``ds_paged_latent`` the item loop, whose branches it names: the prefetch, the
wait for the rest of a block's pages, the one-page item on the whole tile, the
BLOCK item (``item_pages`` pages through one softmax update: also printed a
page, beside the one-page item's), the item on one slab.
Counts of instructions as written, not of cycles: the place a kernel issue
starts from.
"""

import argparse
import collections
import glob
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# This process compiles for a TPU while its own backend is the CPU: pin the
# host accelerator, and force Mosaic (not interpreted) kernels — the kernel
# modules read the flag when they are imported.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DS_ACCELERATOR"] = "cpu"
os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

NO_TOPOLOGY_RC = 3
TOPOLOGY = "v5e:2x2"


def check(name, fn, *args):
    try:
        jax.jit(fn).lower(*args).compile()
        return name, "PASS", ""
    except Exception as e:   # the report IS the failure, one line per kernel
        return name, "FAIL", f"{type(e).__name__}: {str(e)[:300]}"


def loop_ops(llo_text):
    """``[(region, {llo op: count})]`` of the first ``scf.for`` of a kernel's
    final LLO text: ``"loop"`` (its whole body) and ``"if <n>"`` for each
    ``scf.if`` directly inside it, in order.  A kernel with no loop (the flash
    kernels: a grid step is the whole program) gives its ``@main`` instead,
    under ``"step"``.  An attribute's braces open and close on one line, so a
    region ends on the line after which the count of open braces is what it
    was before the region's first."""
    lines = llo_text.splitlines()
    outer, name = ("scf.for", "loop") if "scf.for" in llo_text else (
        "func.func @main", "step")
    regions, depth = [], 0      # [name, first line, depth before it, last]
    for n, line in enumerate(lines):
        if not regions and outer in line:
            regions.append([name, n, depth, None])
        elif regions and depth == regions[0][2] + 1 and "scf.if" in line:
            regions.append([f"if {len(regions)}", n, depth, None])
        depth += line.count("{") - line.count("}")
        for r in regions:
            if r[3] is None and n > r[1] and depth == r[2]:
                r[3] = n
        if regions and regions[0][3] is not None:
            break
    return [(name, dict(collections.Counter(
        re.findall(r"llo\.[a-z_0-9.]+", "\n".join(lines[lo:hi])))
        .most_common())) for name, lo, _, hi in regions]


#: the item loop of ``ds_paged_runs`` and of ``ds_paged_latent`` and the
#: ``scf.if`` regions in it, in order, where its items take blocks of pages
#: (every shape checked here)
PAGED_REGIONS = ("loop", "prefetch", "wait for a block's other pages",
                 "tile item", "block item", "slab item")


def paged_lines(kernel, check, regions, pages):
    """The ``OPS`` lines of one check that compiled ``kernel`` (one of the
    two above) with blocks of ``pages`` pages: each region under its name,
    the block item's total also divided by its pages."""
    named = len(regions) == len(PAGED_REGIONS)
    for n, (region, counts) in enumerate(regions):
        name = PAGED_REGIONS[n] if named else region
        total = sum(counts.values())
        if name == "block item":
            name = f"block item of {pages} pages: {total // pages} a page"
        yield f"OPS {kernel} | {check} | {name} | {total} | " \
            f"{json.dumps(counts)}"


#: a grid step of ``ds_flash_fwd`` / ``_bwd_dq`` / ``_bwd_dkv`` (every step
#: is a live block: a dead one is no step): what it runs outside the
#: ``pl.when`` regions, then those in order
FLASH_REGIONS = ("every step", "first step of a row", "edge block",
                 "interior block", "last step of a row")


def flash_lines(kernel, check, regions):
    """The ``OPS`` lines of one check that compiled a flash kernel: the
    step's ``scf.if`` regions under their names, before them what is left of
    the step outside them."""
    (_, step), branches = regions[0], regions[1:]
    outside = collections.Counter(step)
    for _, counts in branches:
        outside.subtract(counts)
    regions = [("", dict(+outside))] + branches
    named = len(regions) == len(FLASH_REGIONS)
    for n, (region, counts) in enumerate(regions):
        name = FLASH_REGIONS[n] if named else region or "outside"
        yield f"OPS {kernel} | {check} | {name} | " \
            f"{sum(counts.values())} | {json.dumps(counts)}"


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ops", metavar="KERNEL", help="also print the histogram"
                    " of llo.* ops in KERNEL's first loop (module docstring);"
                    " several kernels with commas between")
    ap.add_argument("--only", metavar="TEXT", help="compile only the checks "
                    "whose name holds TEXT (the others read SKIP)")
    opts = ap.parse_args()
    dump = None
    if opts.ops:        # read by libtpu when it is loaded: before the topology
        dump = tempfile.TemporaryDirectory(prefix="mosaic_dump_")
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(filter(None, (
            os.environ.get("LIBTPU_INIT_ARGS"),
            f"--xla_mosaic_dump_to={dump.name}")))
    ops = []

    def checked(name, fn, *args, pages=1):
        """:func:`check`, and the ops of the ``--ops`` kernel it compiled
        (``pages``: the pages of a block item, if it has one)."""
        if opts.only and opts.only not in name:
            return name, "SKIP", ""
        result = check(name, fn, *args)
        if dump is not None:
            for kernel in opts.ops.split(","):
                for path in sorted(glob.glob(os.path.join(
                        dump.name, f"*-{kernel}-post-finalize-llo.txt"))):
                    with open(path) as f:
                        ops.append((kernel, name, loop_ops(f.read()), pages))
            for path in glob.glob(os.path.join(dump.name, "*")):
                os.remove(path)
        return result

    try:
        topo = topologies.get_topology_desc(TOPOLOGY, platform="tpu")
    except Exception as e:
        print(f"no TPU topology can be described here: "
              f"{type(e).__name__}: {e}")
        return NO_TOPOLOGY_RC
    mesh = Mesh(np.array(topo.devices[:1]), ("x", ))
    kind = topo.devices[0].device_kind

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P()))

    bf16 = jnp.bfloat16
    B, S, H, D = 2, 1024, 8, 128
    results = []

    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    q = sds((B, S, H, D), bf16)
    kv = sds((B, S, 2, D), bf16)
    results.append(checked(
        "flash_attention(MHA causal)",
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, q, q))
    results.append(checked(
        "flash_attention(GQA window)",
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=256),
        q, kv, kv))
    # the training shape: backward (dq; dk+dv) at D=128, blocks 512/512
    q2 = sds((2, 2048, H, D), bf16)
    results.append(checked(
        "flash_attention(grad, S=2048 512/512)",
        jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=512, block_k=512
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2)), q2, q2, q2))

    # Bloom's ALiBi (slopes a head in SMEM), forward and gradients
    slopes = np.linspace(0.05, 0.4, H).astype(np.float32)
    results.append(checked(
        "flash_attention(ALiBi, grad)",
        jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True, alibi_slopes=slopes
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2)), q2, q2, q2))
    # the training cells' shapes (a sequence a chip, bfloat16, blocks of the
    # default): SmallThinker's window and full layers (S 8192, 28 heads) and
    # Mistral-7B's (S 4096, 32 heads, a window that does not bind), forward
    # and all three gradients
    for name, S2, heads, window in (
            ("S 8192, 28 heads, window 4096: the SmallThinker cell", 8192, 28,
             4096),
            ("S 8192, 28 heads, full: the SmallThinker cell", 8192, 28, 0),
            ("S 4096, 32 heads, window 4096: the Mistral cells", 4096, 32,
             4096)):
        qc = sds((1, S2, heads, D), bf16)
        attend = lambda q, k, v, window=window: flash_attention(
            q, k, v, causal=True, window=window)
        results.append(checked(f"flash_attention({name})", attend,
                               qc, qc, qc))
        results.append(checked(
            f"flash_attention(grad, {name})",
            jax.grad(lambda q, k, v, attend=attend: attend(q, k, v).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)), qc, qc, qc))

    from deepspeed_tpu.ops.pallas.flash_bias import flash_attention_bias
    bias = sds((B, H, S, S), bf16)
    results.append(checked(
        "flash_bias(evoformer)",
        lambda q, k, v, b: flash_attention_bias(q, k, v, bias=b),
        q, q, q, bias))

    from deepspeed_tpu.ops.pallas.optimizers import (fused_adam_step,
                                                     fused_lamb_step,
                                                     fused_lion_step)
    p = sds((1 << 16, ), jnp.float32)
    results.append(checked(
        "fused_adam_step",
        lambda g, mst, m, v: fused_adam_step(
            g, mst, m, v, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
            weight_decay=0.0, count=1), p, p, p, p))
    results.append(checked(
        "fused_lamb_step",
        lambda g, mst, m, v: fused_lamb_step(
            g, mst, m, v, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
            weight_decay=0.01, count=1), p, p, p, p))
    results.append(checked(
        "fused_lion_step",
        lambda g, mst, m: fused_lion_step(g, mst, m, lr=1e-4, beta1=0.9,
                                          beta2=0.99, weight_decay=0.0),
        p, p, p))

    from deepspeed_tpu.ops.pallas.quantizer import (dequantize_blockwise,
                                                    quantize_blockwise)

    def qdq(x):
        qv, scales, meta = quantize_blockwise(x, num_bits=8)
        return dequantize_blockwise(qv, scales, meta)

    results.append(checked("quantizer(int8 block)", qdq,
                         sds((4096, 512), jnp.float32)))

    from deepspeed_tpu.ops.pallas.paged_attention import (
        item_pages, paged_attention, paged_attention_per_token)
    # serving shapes at Llama-7B width: 32 heads x 128, 128-token pages
    T, maxb = 64, 5
    pq = sds((T, 32, D), bf16)
    kc = sds((41, 128, 32, D), bf16)
    bt = sds((T, maxb), jnp.int32)
    pos = sds((T, ), jnp.int32)
    results.append(checked("paged_attention_per_token",
                         paged_attention_per_token, pq, kc, kc, bt, pos))
    # the run-tiled kernel with every branch of an item (the tile, a block of
    # pages on the tile, one slab of rows): the serving cells' own shapes (Mistral-7B: 768-token
    # budget, 27-page table, window 4096; EvaByte: 32 / 32 heads, 1 MB pages)
    # and their bursts' (a row a slot), a shape whose slab is 16 rows, and
    # head sizes of the zoo that stay on the per-token kernel
    for name, T, heads, kv_heads, head_dim, maxb, window in (
            ("GQA 32/8, the cell", 768, 32, 8, 128, 27, 4096),
            ("GQA 32/8, the cell's burst", 65, 32, 8, 128, 27, 4096),
            ("MHA 32/32, the EvaByte cell", 768, 32, 32, 128, 27, 0),
            ("MHA 32/32, the EvaByte cell's burst", 17, 32, 32, 128, 27, 0),
            ("GQA 28/4, Qwen2", 256, 28, 4, 128, 16, 0),
            # 16 query heads a KV head (tiles of 512 rows, slabs of 16): the
            # Command A+ cell's window and full layers, and its burst
            ("GQA 128/8, the Command A+ cell, window", 2048, 128, 8, 128,
             137, 4096),
            ("GQA 128/8, the Command A+ cell, full", 2048, 128, 8, 128, 137,
             0),
            ("GQA 128/8, the Command A+ cell's burst", 33, 128, 8, 128, 137,
             0),
            # 20 query heads on ONE KV head (tiles of 640 rows, slabs of 24):
            # the Jamba cell's two attention layers, and its burst
            ("MQA 20/1, the Jamba cell", 2048, 20, 1, 128, 193, 0),
            ("MQA 20/1, the Jamba cell's burst", 257, 20, 1, 128, 193, 0),
            # ONE query head a KV head at 16 heads (tiles of 64 rows, slabs
            # of 8): the Ouro cell's 192 calls a step, and its burst of 9 rows
            ("MHA 16/16, the Ouro cell", 512, 16, 16, 128, 11, 0),
            ("MHA 16/16, the Ouro cell's burst", 9, 16, 16, 128, 11, 0),
            # 8 query heads on each of 2 KV heads of 256 (PR 60): the
            # Qwen3-Next configuration's two gated-attention layers at its
            # engine layout, a step and a burst
            ("GQA 16/2 x 256, Qwen3-Next's step", 2048, 16, 2, 256, 193, 0),
            ("GQA 16/2 x 256, Qwen3-Next's burst", 257, 16, 2, 256, 193, 0),
            ("MHA 32/32 x 80: per token", 64, 32, 32, 80, 16, 0),
            ("MQA 71/1 x 64: per token", 64, 71, 1, 64, 16, 0)):
        # one KV head in 16 bits: a page holds two tokens a row (ragged.py)
        kc = sds((64, 64, 2, head_dim) if kv_heads == 1 and head_dim == 128
                 else (64, 128, kv_heads, head_dim), bf16)
        results.append(checked(
            f"paged_attention({name})",
            lambda q, k, v, t, s, l, window=window: paged_attention(
                q, k, v, t, s, l, window=window, block_size=128),
            sds((T, heads, head_dim), bf16), kc, kc,
            sds((max(65, T if T < 512 else 0), maxb), jnp.int32),
            sds((T, ), jnp.int32), sds((T, ), jnp.int32),
            pages=item_pages(kv_heads, head_dim, bf16, 128)))

    # the variant the tests count page loads and short items with
    kc = sds((64, 128, 8, D), bf16)
    results.append(checked(
        "paged_attention(GQA 32/8, count_loads)",
        lambda q, k, v, t, s, l: paged_attention(q, k, v, t, s, l,
                                                 count_loads=True),
        sds((256, 32, D), bf16), kc, kc, sds((65, 27), jnp.int32),
        sds((256, ), jnp.int32), sds((256, ), jnp.int32),
        pages=item_pages(8, D, bf16, 128)))

    # the latent cache's reader at openPangu-Ultra-MoE's sizes (128 heads on
    # rows of 512 + 64 in 640): a prefill step's buffer and a burst's
    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_latent_attention
    for name, T in (("the cell's step", 1024), ("the cell's burst", 65)):
        results.append(checked(
            f"paged_latent_attention(MLA 128 x 576, {name})",
            lambda q, c, t, s, l: paged_latent_attention(
                q, c, t, s, l, rank=512, scale=192 ** -0.5),
            sds((T, 128, 640), bf16), sds((64, 128, 640), bf16),
            sds((65, 193), jnp.int32), sds((T, ), jnp.int32),
            sds((T, ), jnp.int32), pages=item_pages(1, 640, bf16, 128)))
    # the same reader at LongCat-Flash's 64 heads (a tile of 16 tokens, a
    # decode token's slab of 64 rows): a step of 2048 rows and a burst's
    for name, T in (("the LongCat cell's step", 2048),
                    ("the LongCat cell's burst", 33)):
        results.append(checked(
            f"paged_latent_attention(MLA 64 x 576, {name})",
            lambda q, c, t, s, l: paged_latent_attention(
                q, c, t, s, l, rank=512, scale=192 ** -0.5),
            sds((T, 64, 640), bf16), sds((64, 128, 640), bf16),
            sds((33, 137), jnp.int32), sds((T, ), jnp.int32),
            sds((T, ), jnp.int32), pages=item_pages(1, 640, bf16, 128)))

    # the EXPANDED form's reader of the same pages, at the two cells' steps:
    # a tile of 1024 rows of 128 heads, one of 2048 rows of 64
    from deepspeed_tpu.ops.pallas.paged_attention import (
        expanded_min_rows, paged_mla_chunk_attention)
    for name, T, heads, seqs, maxb in (
            ("the Pangu cell's step", 1024, 128, 65, 193),
            ("the LongCat cell's step", 2048, 64, 33, 137)):
        results.append(checked(
            f"paged_mla_chunk_attention(MLA {heads} x 576, {name})",
            lambda q, c, k, v, t, s, l: paged_mla_chunk_attention(
                q, c, k, v, t, s, l, rank=512, scale=192 ** -0.5,
                min_rows=expanded_min_rows(512, 128, 64, 128)),
            sds((T, heads, 256), bf16), sds((64, 128, 640), bf16),
            sds((512, heads, 128), bf16), sds((512, heads, 128), bf16),
            sds((seqs, maxb), jnp.int32), sds((T, ), jnp.int32),
            sds((T, ), jnp.int32)))

    # both readers at Motif-3's sizes: 80 query heads (a tile of 8 tokens, a
    # decode token's slab of 80 rows) in 16 K/V groups; the absorbed one with
    # a window of 128 (without, it is the kernel above at 80 heads), the
    # expanded one on a window layer and on a full one
    for name, T in (("the Motif cell's step", 1024),
                    ("the Motif cell's burst", 65)):
        results.append(checked(
            f"paged_latent_attention(GDLA 80 x 576, window 128, {name})",
            lambda q, c, t, s, l: paged_latent_attention(
                q, c, t, s, l, rank=512, scale=192 ** -0.5, window=128),
            sds((T, 80, 640), bf16), sds((64, 128, 640), bf16),
            sds((65, 193), jnp.int32), sds((T, ), jnp.int32),
            sds((T, ), jnp.int32), pages=item_pages(1, 640, bf16, 128)))
    for window in (128, 0):
        layer = f"window {window}" if window else "a full layer"
        results.append(checked(
            f"paged_mla_chunk_attention(GDLA 80 in 16 x 576, {layer}, the "
            "Motif cell's step)",
            lambda q, c, k, v, t, s, l, window=window:
            paged_mla_chunk_attention(
                q, c, k, v, t, s, l, rank=512, scale=192 ** -0.5,
                min_rows=expanded_min_rows(512, 128, 64, 128), window=window),
            sds((1024, 80, 256), bf16), sds((64, 128, 640), bf16),
            sds((512, 16, 128), bf16), sds((512, 16, 128), bf16),
            sds((65, 193), jnp.int32), sds((1024, ), jnp.int32),
            sds((1024, ), jnp.int32)))

    # the Mamba-1 recurrence at the Jamba cell's shapes: a 2048-row step over
    # 257 slots' state of 16 x 5120 (bfloat16, aliased in and out)
    from deepspeed_tpu.ops.pallas.selective_scan import selective_scan
    for name, T in (("the Jamba cell's step", 2048), ("a short step", 40)):
        f32 = jnp.float32
        results.append(checked(
            f"selective_scan(16 x 5120, 257 slots, {name})", selective_scan,
            sds((T, 5120), f32), sds((T, 5120), f32), sds((T, 16), f32),
            sds((T, 16), f32), sds((16, 5120), f32),
            sds((257, 16, 5120), bf16), sds((T, ), jnp.int32),
            sds((T, ), jnp.int32), sds((1, ), jnp.int32)))

    # the gated delta rule's one-token form at the Qwen3-Next cell's shape:
    # 257 slots' float32 state of 32 heads x 128 x 128 (aliased in and out),
    # and at the most (slot, head) pairs its shape predicate lets through
    # (e^g and beta of every pair lie in SMEM)
    from deepspeed_tpu.ops.pallas.gated_delta_rule import (SMEM_PAIRS,
                                                           gated_delta_slot)
    for slots, what in ((257, "the Qwen3-Next cell"),
                        (SMEM_PAIRS // 32, "the most SMEM holds")):
        results.append(checked(
            f"gated_delta_slot(32 heads of 128 x 128, {slots} slots, {what})",
            gated_delta_slot,
            *(sds((slots, 32, 128), f32), ) * 3,
            *(sds((slots, 32), f32), ) * 2,
            sds((slots, 32, 128, 128), f32),
            *(sds((slots, ), jnp.bool_), ) * 2))

    from deepspeed_tpu.ops.pallas.grouped_matmul import gmm
    results.append(checked(
        "gmm(moe grouped matmul)", lambda a, b, s: gmm(a, b, s),
        sds((512, 256), bf16), sds((4, 256, 128), bf16),
        sds((4, ), jnp.int32)))
    # the serving path's expert layer: 16 held experts of 4096 x 4096, the
    # short buffer of a 2048-row step, tiles of 256 x 1024 x 1024
    results.append(checked(
        "gmm(16 experts of 4096 x 4096, tiles 256 x 1024 x 1024)",
        lambda a, b, s: gmm(a, b, s, block_m=256, block_n=1024,
                            block_k=1024),
        sds((2560, 4096), bf16), sds((16, 4096, 4096), bf16),
        sds((16, ), jnp.int32)))

    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_flash_attention)
    from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
    blk = 64
    # the layout is static host data (it sizes the kernel's index tables)
    layout = np.asarray(FixedSparsityConfig(num_heads=H,
                                            block=blk).make_layout(S))
    results.append(checked(
        "block_sparse_flash_attention(fixed)",
        lambda q, k, v: block_sparse_flash_attention(q, k, v, layout, blk),
        q, q, q))

    print(f"target: {TOPOLOGY} ({kind}), compile only")
    for name, status, err in results:
        print(f"{status:4s} {name}" + (f"  {err}" if err else ""))
    for kernel, name, regions, pages in ops:
        if kernel in ("ds_paged_runs", "ds_paged_latent"):
            print("\n".join(paged_lines(kernel, name, regions, pages)))
            continue
        if kernel.startswith("ds_flash_") and regions:
            print("\n".join(flash_lines(kernel, name, regions)))
            continue
        for region, counts in regions:
            print(f"OPS {kernel} | {name} | {region} | "
                  f"{sum(counts.values())} | {json.dumps(counts)}")
    return 0 if all(r[1] != "FAIL" for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
