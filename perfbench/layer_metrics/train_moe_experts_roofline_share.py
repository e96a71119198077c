"""Per-layer metric ``train_moe_experts_roofline_share``: how near the held
experts' part of a TRAINING step, forward and backward, comes to the chip's
roofline.

What the part MUST move and compute, from the step's own counts (not what an
implementation happens to move): every copy costs nine products of ``D x I``
(gate, up and down in the forward; for each of the three, the rows' gradient
and the matrix's in the backward); every held expert with at least one copy
has its three matrices read in the forward, read again in the backward and
their gradients written, in the compute type (2 bytes).  The copies' own rows
are left out of the bytes, so the floor reads lower for it and never higher.
``D`` and ``I`` are the ``hidden_size`` and ``moe_ffn_hidden_size`` of the
configuration whose trace is read; the peaks are ``peaks.json``'s.
"""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "train_moe_experts_ms_per_step.py"))
BYTES = 2                       # bfloat16


def must_move_bytes(active, hidden, width):
    """Bytes the held experts of a micro-step must move: ``active`` experts'
    three matrices read twice and their gradients written once."""
    return active * 3 * hidden * width * 3 * BYTES


def must_compute_flops(copies, hidden, width):
    """Operations the held experts of a micro-step must perform: nine
    products of ``hidden x width`` a copy."""
    return copies * 9 * 2 * hidden * width


def floor_seconds(counts, hidden, width, peaks):
    """The least time the chip could take for the counted micro-steps' held
    experts: the larger of bytes over bandwidth and operations over peak, of
    the SUMMED counts (the max of a sum is at most the sum of the maxes: no
    higher than step by step)."""
    return max(
        must_move_bytes(counts["expert_active"], hidden, width)
        / peaks["hbm_bytes_per_s"],
        must_compute_flops(counts["expert_copies"], hidden, width)
        / peaks["bf16_flops_per_s"])


def read(record):
    """The floor of the counted micro-steps over the measured time under
    ``ds.moe_experts`` of as many traced steps, in %.  None without the
    scope, the counts or the configuration's two widths."""
    got = _experts.scope_ms(record, "SCOPE_MOE_EXPERTS")
    config, peaks = _experts.traced_config(record), record.get("peaks")
    if not got or not config or not peaks:
        return None
    ms_per_step, t = got
    counts = _experts.counted(t)
    if not counts or not counts.get("micro_steps_covered") or \
            "moe_ffn_hidden_size" not in config:
        return None
    floor_s = floor_seconds(counts, config["hidden_size"],
                            config["moe_ffn_hidden_size"], peaks)
    return 100.0 * floor_s / (
        ms_per_step / 1e3 * counts["micro_steps_covered"])
