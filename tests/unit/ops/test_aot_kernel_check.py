"""The sandbox's Mosaic gate: every Pallas kernel compiles ahead of time for
``TPU v5 lite`` (``tools/aot_kernel_check.py`` — libtpu describes the
topology with no chip attached).  Compile only: nothing here says a kernel
runs, is right, or is fast.  With ``--ops ds_paged_runs,ds_paged_latent`` the
tool also counts the instructions Mosaic made of those kernels' item loops,
branch by branch: the one-page item on the tile, the block item (a page of it
beside), the slab; with ``ds_flash_fwd,ds_flash_bwd_dq,ds_flash_bwd_dkv`` those
of a grid step of the flash kernels at the training cells' shapes: an edge
block, an interior block, a row's first and last step."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
NO_TOPOLOGY_RC = 3      # tools/aot_kernel_check.NO_TOPOLOGY_RC


@pytest.fixture(scope="module")
def report():
    """One run of the tool for the file's tests.  A subprocess: the tool
    pins Mosaic (not interpreted) kernels through the environment before the
    kernel modules are imported."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "aot_kernel_check.py"),
         "--ops", "ds_paged_runs,ds_paged_latent,"
         "ds_flash_fwd,ds_flash_bwd_dq,ds_flash_bwd_dkv"],
        capture_output=True, text=True, timeout=600)
    if r.returncode == NO_TOPOLOGY_RC:
        pytest.skip("get_topology_desc unavailable: " + r.stdout.strip()[-200:])
    return r


def test_every_kernel_compiles_for_v5e(report):
    r = report
    lines = [ln for ln in r.stdout.splitlines()
             if ln.startswith(("PASS", "FAIL"))]
    assert r.returncode == 0, "\n".join(lines) + r.stderr[-1500:]
    assert len(lines) >= 40 and all(ln.startswith("PASS") for ln in lines)
    assert "TPU v5 lite" in r.stdout
    # the run-tiled paged kernel, every branch of its item (the block of
    # pages too), at the three serving cells' shapes and their bursts': a
    # failure of the kind of PERF.md's fault 1 is met here, before a cell is
    for kernel in ("flash_attention(grad, S=2048",
                   "flash_attention(ALiBi, grad)",
                   *FLASH_CELLS, *(c.replace("(", "(grad, ", 1)
                                   for c in FLASH_CELLS),
                   "paged_attention_per_token",
                   "paged_attention(GQA 32/8, the cell)",
                   "paged_attention(GQA 32/8, the cell's burst)",
                   "paged_attention(MHA 32/32, the EvaByte cell)",
                   "paged_attention(MHA 32/32, the EvaByte cell's burst)",
                   "paged_attention(GQA 28/4, Qwen2)",
                   "paged_attention(GQA 128/8, the Command A+ cell, window)",
                   "paged_attention(GQA 128/8, the Command A+ cell, full)",
                   "paged_attention(GQA 128/8, the Command A+ cell's burst)",
                   "paged_attention(GQA 32/8, count_loads)",
                   # 20 query heads on one KV head, a page of token pairs
                   "paged_attention(MQA 20/1, the Jamba cell)",
                   "paged_attention(MQA 20/1, the Jamba cell's burst)",
                   # one query head a KV head, 16 heads, a burst of 9 rows
                   "paged_attention(MHA 16/16, the Ouro cell)",
                   "paged_attention(MHA 16/16, the Ouro cell's burst)",
                   # 8 query heads on each of 2 KV heads of 256
                   "paged_attention(GQA 16/2 x 256, Qwen3-Next's step)",
                   "paged_attention(GQA 16/2 x 256, Qwen3-Next's burst)",
                   "selective_scan(16 x 5120, 257 slots, the Jamba cell's "
                   "step)",
                   "selective_scan(16 x 5120, 257 slots, a short step)",
                   # the delta rule's one-token form, float32 state in place
                   "gated_delta_slot(32 heads of 128 x 128, 257 slots, the "
                   "Qwen3-Next cell)",
                   "gated_delta_slot(32 heads of 128 x 128, 2048 slots, the "
                   "most SMEM holds)",
                   "paged_latent_attention(MLA 128 x 576, the cell's step)",
                   "paged_latent_attention(MLA 128 x 576, the cell's burst)",
                   "paged_latent_attention(MLA 64 x 576, the LongCat cell's "
                   "step)",
                   "paged_latent_attention(MLA 64 x 576, the LongCat cell's "
                   "burst)",
                   # 80 heads in 16 K/V groups, a window layer and a full one
                   "paged_latent_attention(GDLA 80 x 576, window 128, the "
                   "Motif cell's step)",
                   "paged_latent_attention(GDLA 80 x 576, window 128, the "
                   "Motif cell's burst)",
                   "paged_mla_chunk_attention(GDLA 80 in 16 x 576, window "
                   "128, the Motif cell's step)",
                   "paged_mla_chunk_attention(GDLA 80 in 16 x 576, a full "
                   "layer, the Motif cell's step)",
                   "block_sparse_flash_attention"):
        assert any(kernel in ln for ln in lines), kernel


#: the flash kernels at the shapes the three training cells run (bfloat16, a
#: sequence a chip, blocks of 512): forward, and under ``jax.grad``
FLASH_CELLS = (
    "flash_attention(S 8192, 28 heads, window 4096: the SmallThinker cell)",
    "flash_attention(S 8192, 28 heads, full: the SmallThinker cell)",
    "flash_attention(S 4096, 32 heads, window 4096: the Mistral cells)")
FLASH_REGIONS = ["every step", "first step of a row", "edge block",
                 "interior block", "last step of a row"]

REGIONS = ["loop", "prefetch", "wait for a block's other pages", "tile item",
           "block item", "slab item"]


@pytest.fixture(scope="module")
def ops(report):
    """``OPS <kernel> | <check> | <region> | <ops> | {op: count}`` of the
    item loops of ``ds_paged_runs`` and ``ds_paged_latent`` (a check compiles
    one of them): ``{check: {region: (pages, counts)}}``, the block item under
    ``"block item"`` with the pages its name states."""
    out = {}
    for ln in report.stdout.splitlines():
        if ln.startswith(("OPS ds_paged_runs | ", "OPS ds_paged_latent | ")):
            _, check, region, total, counts = ln.split(" | ", 4)
            counts, pages = json.loads(counts), 1
            assert int(total) == sum(counts.values())
            if region.startswith("block item of "):
                pages = int(region.split()[3])
                assert region == f"block item of {pages} pages: " \
                    f"{int(total) // pages} a page"
                region = "block item"
            out.setdefault(check, {})[region] = (pages, counts)
    return out


CELLS = (("paged_attention(GQA 32/8, the cell)", 128, 4),
         ("paged_attention(MHA 32/32, the EvaByte cell)", 64, 2),
         ("paged_attention(GQA 128/8, the Command A+ cell, full)", 512, 4))


@pytest.mark.parametrize("check, rows, pages", CELLS)
def test_a_short_item_holds_a_fraction_of_the_tiles_matmuls(ops, check, rows,
                                                            pages):
    """The item loop holds the prefetch, the wait for the rest of a block
    and the item's three branches.  The branch on one slab of rows streams
    ``slab_rows(g)`` rows a dot where the branch on the tile streams all of
    them; both latch the same pages."""
    regions = ops[check]
    assert list(regions) == REGIONS
    assert "llo.enqueue_dma" in regions["prefetch"][1]
    assert "llo.dma_done" in regions["wait for a block's other pages"][1]
    tile, slab = regions["tile item"][1], regions["slab item"][1]
    R = 16 if rows == 512 else 8
    assert tile["llo.vmatmul"] * R == slab["llo.vmatmul"] * rows
    assert slab["llo.vmatmul"] * 5 < tile["llo.vmatmul"]
    assert slab["llo.vlatch"] == tile["llo.vlatch"]
    assert slab["llo.vexp.f32"] * 5 < tile["llo.vexp.f32"]
    assert sum(slab.values()) * 2 < sum(tile.values())


@pytest.mark.parametrize("check, rows, pages", CELLS)
def test_a_block_item_pays_the_softmax_state_once_for_all_its_pages(
        ops, check, rows, pages):
    """A block item against ``pages`` one-page tile items: the same dots
    (latches and matmuls a page), the ``exp`` of ``alpha`` and the lane
    broadcasts of the state once and not once a page, one cross-lane
    reduction a row and not one a page."""
    regions = ops[check]
    (n, block), (_, tile) = regions["block item"], regions["tile item"]
    assert n == pages
    for op in ("llo.vlatch", "llo.vmatmul"):
        assert block[op] == pages * tile[op], op
    # a one-page item's vexp are half the scores', half alpha's
    assert block["llo.vexp.f32"] * 2 == (pages + 1) * tile["llo.vexp.f32"]
    assert block["llo.vperm"] <= tile["llo.vperm"] * 1.05
    for op in ("llo.vmax.xlane.f32", "llo.vadd.xlane.f32"):
        assert block[op] == tile[op], op
    assert sum(block.values()) < 0.8 * pages * sum(tile.values())


LATENT = ("paged_latent_attention(MLA 128 x 576, the cell's step)",
          "paged_latent_attention(MLA 128 x 576, the cell's burst)")


@pytest.mark.parametrize("check", LATENT)
def test_the_latent_kernels_block_item_rescales_its_accumulator_once(ops,
                                                                     check):
    """``ds_paged_latent``'s item loop holds the same branches.  Its block
    item of 4 pages against 4 one-page tile items: the same dots, the lane
    broadcasts of the state (``m``, ``alpha``, ``l`` over the accumulator's
    columns) and the accumulator's loads and stores once and not once a page;
    a slab item (a decode token's 128 heads) an eighth of the tile's dots."""
    regions = ops[check]
    assert list(regions) == REGIONS
    assert "llo.enqueue_dma" in regions["prefetch"][1]
    assert "llo.dma_done" in regions["wait for a block's other pages"][1]
    (n, block), (_, tile) = regions["block item"], regions["tile item"]
    slab = regions["slab item"][1]
    assert n == 4
    assert block["llo.vmatmul"] == n * tile["llo.vmatmul"]
    assert tile["llo.vmatmul"] == 8 * slab["llo.vmatmul"]
    assert block["llo.vperm"] <= tile["llo.vperm"] * 1.05
    assert block["llo.vector_store"] < 2 * tile["llo.vector_store"]
    for op in ("llo.vmax.xlane.f32", "llo.vadd.xlane.f32"):
        assert block[op] == tile[op], op
    assert sum(block.values()) < 0.6 * n * sum(tile.values())


@pytest.fixture(scope="module")
def flash_ops(report):
    """``{(kernel, check): {region: counts}}`` of the ``OPS ds_flash_*``
    lines: a grid step's ``pl.when`` regions under their names."""
    out = {}
    for ln in report.stdout.splitlines():
        if ln.startswith("OPS ds_flash_"):
            kernel, check, region, total, counts = ln[4:].split(" | ", 4)
            counts = json.loads(counts)
            assert int(total) == sum(counts.values())
            out.setdefault((kernel, check), {})[region] = counts
    return out


def _count(counts, prefix):
    return sum(n for op, n in counts.items() if op.startswith(prefix))


@pytest.mark.parametrize("kernel", ["ds_flash_fwd", "ds_flash_bwd_dq",
                                    "ds_flash_bwd_dkv"])
@pytest.mark.parametrize("check", FLASH_CELLS)
def test_a_flash_step_does_only_what_its_block_needs(flash_ops, check,
                                                     kernel):
    """At the cells' shapes (blocks of 1024) a grid step of each kernel is a
    live block (a dead one is no step): outside its regions scalar work
    alone; on an INTERIOR block no compare, no select and no mask ``and``; on
    an EDGE block the mask over all 1024 vregs of the tile; the same dots on
    both (1024 ``vmatmul`` a 1024 x 1024 x 128 product of float32 operands);
    no transpose and no identity in a block of the backward; ``lse`` /
    ``delta`` packed and unpacked by the XLU once a row, with no identity
    product."""
    regions = flash_ops[(kernel, check.replace("(", "(grad, ", 1))]
    assert list(regions) == FLASH_REGIONS
    if kernel == "ds_flash_fwd":        # the forward alone: the same step
        assert flash_ops[(kernel, check)] == regions
    every, first, edge, interior, last = regions.values()
    assert not any(op.startswith("llo.v") for op in every) and \
        sum(every.values()) < 400
    for op in ("llo.vcmp", "llo.vselect", "llo.vmand"):
        assert _count(interior, op) == 0, op
    assert edge["llo.vmand"] >= 1024 and edge["llo.vselect"] >= 1024
    dots = {"ds_flash_fwd": 2, "ds_flash_bwd_dq": 3, "ds_flash_bwd_dkv": 4}
    for block in (edge, interior):
        assert block["llo.vmatmul"] == 1024 * dots[kernel]
        assert block["llo.vexp.f32"] <= 1024 + 128
        if kernel != "ds_flash_fwd":
            assert "llo.vxpose" not in block
    for region in regions.values():     # no identity is built anywhere
        assert "llo.vcmp.eq.s32" not in region
    assert sum(interior.values()) < 0.92 * sum(edge.values())
    if kernel == "ds_flash_fwd":        # lse to its packed row
        assert last["llo.vxpose"] == 128 and "llo.vmatmul" not in last
    if kernel == "ds_flash_bwd_dq":     # lse / delta to columns once a row
        assert first["llo.vxpose"] == 256 and "llo.vmatmul" not in first
