"""The sandbox's Mosaic gate: every Pallas kernel compiles ahead of time for
``TPU v5 lite`` (``tools/aot_kernel_check.py`` — libtpu describes the
topology with no chip attached).  Compile only: nothing here says a kernel
runs, is right, or is fast."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
NO_TOPOLOGY_RC = 3      # tools/aot_kernel_check.NO_TOPOLOGY_RC


def test_every_kernel_compiles_for_v5e():
    # a subprocess: the tool pins Mosaic (not interpreted) kernels through
    # the environment before the kernel modules are imported
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "aot_kernel_check.py")],
        capture_output=True, text=True, timeout=600)
    if r.returncode == NO_TOPOLOGY_RC:
        pytest.skip("get_topology_desc unavailable: " + r.stdout.strip()[-200:])
    lines = [ln for ln in r.stdout.splitlines()
             if ln.startswith(("PASS", "FAIL"))]
    assert r.returncode == 0, "\n".join(lines) + r.stderr[-1500:]
    assert len(lines) >= 16 and all(ln.startswith("PASS") for ln in lines)
    assert "TPU v5 lite" in r.stdout
    # the run-tiled paged kernel at the serving cell's grouped-query shape
    # and at an MHA shape: a failure of the kind of PERF.md's fault 1 is
    # met here, before a cell meets it
    for kernel in ("flash_attention(grad", "paged_attention_per_token",
                   "paged_attention(GQA 32/8, the cell)",
                   "paged_attention(MHA 32/32)",
                   "block_sparse_flash_attention"):
        assert any(kernel in ln for ln in lines), kernel
