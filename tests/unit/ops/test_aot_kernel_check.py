"""The sandbox's Mosaic gate: every Pallas kernel compiles ahead of time for
``TPU v5 lite`` (``tools/aot_kernel_check.py`` — libtpu describes the
topology with no chip attached).  Compile only: nothing here says a kernel
runs, is right, or is fast.  With ``--ops ds_paged_runs`` the tool also counts
the instructions Mosaic made of that kernel's item loop."""

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
         "--ops", "ds_paged_runs"],
        capture_output=True, text=True, timeout=600)
    if r.returncode == NO_TOPOLOGY_RC:
        pytest.skip("get_topology_desc unavailable: " + r.stdout.strip()[-200:])
    return r


def test_every_kernel_compiles_for_v5e(report):
    r = report
    lines = [ln for ln in r.stdout.splitlines()
             if ln.startswith(("PASS", "FAIL"))]
    assert r.returncode == 0, "\n".join(lines) + r.stderr[-1500:]
    assert len(lines) >= 21 and all(ln.startswith("PASS") for ln in lines)
    assert "TPU v5 lite" in r.stdout
    # the run-tiled paged kernel, both branches of its item, at the two
    # serving cells' shapes and their bursts': a failure of the kind of
    # PERF.md's fault 1 is met here, before a cell meets it
    for kernel in ("flash_attention(grad", "paged_attention_per_token",
                   "paged_attention(GQA 32/8, the cell)",
                   "paged_attention(GQA 32/8, the cell's burst)",
                   "paged_attention(MHA 32/32, the EvaByte cell)",
                   "paged_attention(MHA 32/32, the EvaByte cell's burst)",
                   "paged_attention(GQA 28/4, Qwen2)",
                   "paged_attention(GQA 32/8, count_loads)",
                   "paged_latent_attention(MLA 128 x 576, the cell's step)",
                   "paged_latent_attention(MLA 128 x 576, the cell's burst)",
                   "block_sparse_flash_attention"):
        assert any(kernel in ln for ln in lines), kernel


def test_a_short_item_holds_a_fraction_of_the_tiles_matmuls(report):
    """``OPS <kernel> | <check> | <region> | <ops> | {op: count}``: the item
    loop of ``ds_paged_runs`` holds the prefetch and the item's two
    branches.  The branch on one slab of rows streams 8 rows a dot where
    the branch on the tile streams all of them; both latch the same pages."""
    ops = {}
    for ln in report.stdout.splitlines():
        if ln.startswith("OPS ds_paged_runs | "):
            _, check, region, _, counts = ln.split(" | ", 4)
            ops.setdefault(check, {})[region] = json.loads(counts)
    for check, rows in (("paged_attention(GQA 32/8, the cell)", 128),
                        ("paged_attention(MHA 32/32, the EvaByte cell)", 64)):
        regions = ops[check]
        assert list(regions) == ["loop", "if 1", "if 2", "if 3"]
        assert "llo.enqueue_dma" in regions["if 1"]
        tile, slab = regions["if 2"], regions["if 3"]
        assert tile["llo.vmatmul"] * 8 == slab["llo.vmatmul"] * rows
        assert slab["llo.vmatmul"] * 5 < tile["llo.vmatmul"]
        assert slab["llo.vlatch"] == tile["llo.vlatch"]
        assert slab["llo.vexp.f32"] * 5 < tile["llo.vexp.f32"]
        assert sum(slab.values()) * 2 < sum(tile.values())
