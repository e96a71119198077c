"""ISSUE-5 acceptance gate: with ``comm_optimizations`` enabled, a ZeRO-2
smoke train reaches loss parity (≤1e-2) with the flat path while the
gradient wire payload shrinks.  Drives ``tools/comm_smoke.py`` in-process
(loaded via importlib)."""

import importlib.util
import os

spec = importlib.util.spec_from_file_location(
    "comm_smoke", os.path.join(os.path.dirname(__file__), "..", "..", "..",
                               "tools", "comm_smoke.py"))
comm_smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(comm_smoke)


def test_overlap_loss_parity_gate(monkeypatch):
    """ISSUE-8 acceptance: overlap-off is bit-identical, overlap-on stays
    within parity bounds — and both overlap flavors actually engage (the
    GSPMD bucket markers and the manual qgZ pipeline)."""
    from deepspeed_tpu.runtime.zero import overlap
    marked, piped = [], []
    orig_mark = overlap.mark_tree
    orig_pipe = overlap.pipelined_bucket_reduce
    monkeypatch.setattr(overlap, "mark_tree",
                        lambda *a, **k: marked.append(1) or orig_mark(*a, **k))
    monkeypatch.setattr(
        overlap, "pipelined_bucket_reduce",
        lambda *a, **k: piped.append(1) or orig_pipe(*a, **k))
    r = comm_smoke.run_overlap_smoke(steps=6)
    assert marked, "GSPMD bucket markers never engaged"
    assert piped, "manual qgZ bucket pipeline never engaged"
    assert r["disabled_bit_identical"], (
        r["flat_losses"], r["disabled_losses"])
    assert r["fp_overlap_max_delta"] <= 1e-6, r["overlap_losses"]
    assert r["quant_final_delta"] <= r["tolerance"], (
        r["flat_losses"], r["quant_overlap_losses"])
    assert r["converged"] and r["pass"]


def test_gather_prefetch_parity_gate(monkeypatch):
    """ISSUE-9 acceptance: prefetch-off is bit-identical at stage 3,
    fp prefetch is bit-close (≤1e-6), int8 qwZ prefetch stays within the
    quantized tolerance and converges — and both prefetch flavors
    actually engage (the GSPMD gather markers and the pipelined qwZ
    gather)."""
    from deepspeed_tpu.runtime.zero import overlap
    marked, piped = [], []
    orig_mark = overlap.mark_gather_tree
    orig_pipe = overlap.pipelined_gather
    monkeypatch.setattr(
        overlap, "mark_gather_tree",
        lambda *a, **k: marked.append(1) or orig_mark(*a, **k))
    monkeypatch.setattr(
        overlap, "pipelined_gather",
        lambda *a, **k: piped.append(1) or orig_pipe(*a, **k))
    r = comm_smoke.run_gather_prefetch_smoke(steps=6)
    assert marked, "GSPMD gather markers never engaged"
    assert piped, "pipelined qwZ gather never engaged"
    assert r["disabled_bit_identical"], (
        r["flat_losses"], r["disabled_losses"])
    assert r["fp_prefetch_max_delta"] <= 1e-6, r["prefetch_losses"]
    assert r["quant_final_delta"] <= r["tolerance"], (
        r["flat_losses"], r["quant_prefetch_losses"])
    assert r["converged"] and r["pass"]


def test_zero2_loss_parity_with_comm_optimizations(monkeypatch):
    # prove the quantized micro actually engages for the comm-opts run
    # (parity against an accidentally-flat run would be vacuous) — and
    # that the DEFAULT is the GSPMD-first islands micro, not the legacy
    # full-manual one (ISSUE 15)
    from deepspeed_tpu.runtime.zero import gspmd, zeropp
    islands, manual = [], []
    orig = gspmd.build_gspmd_quantized_micro
    monkeypatch.setattr(gspmd, "build_gspmd_quantized_micro",
                        lambda e: islands.append(1) or orig(e))
    monkeypatch.setattr(zeropp, "build_manual_dp_micro",
                        lambda e: manual.append(1))
    r = comm_smoke.run_smoke(steps=6)
    assert len(islands) == 1  # exactly the quantized run, not the flat one
    assert not manual, "flat-manual micro built on the GSPMD-first default"
    assert r["converged"], r["quant_losses"]
    assert r["final_delta"] <= r["tolerance"], (
        r["flat_losses"], r["quant_losses"])
    assert r["wire_reduced"], r
    assert r["pass"]


def test_zero_mode_flat_manual_matches_islands_bitwise():
    """The two qgZ micro architectures are the SAME numerics: zero_mode:
    "flat_manual" (the legacy full-manual micro) and the GSPMD-first
    islands default produce bitwise-identical loss trajectories on a pure
    dp mesh — the ISSUE-15 island-shrink contract."""
    flat_manual = dict(comm_smoke.COMM_OPTS, zero_mode="flat_manual")
    manual = comm_smoke._one_run(flat_manual, 6, 0.2)
    islands = comm_smoke._one_run(comm_smoke.COMM_OPTS, 6, 0.2)
    assert manual == islands, (manual, islands)
