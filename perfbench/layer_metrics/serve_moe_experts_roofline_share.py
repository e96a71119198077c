"""Per-layer metric ``serve_moe_experts_roofline_share``: how near the held
experts' part of a step comes to the chip's roofline.

What the part MUST move and compute, from the step's own counts (not what a
kernel happens to move): every held expert with at least one copy is read
once, three matrices of ``D x I`` in the serving type; every copy is read
twice and written twice as a row of ``D`` (gathered in, its two products'
activation out and in again, its result out); every copy costs three
products of ``D x I``.  ``D`` and ``I`` are the ``hidden_size`` and ``intermediate_size`` of the
configuration whose trace is read, 2 bytes an element; the peaks are
``peaks.json``'s.
"""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))
BYTES = 2                       # bfloat16


def must_move_bytes(active, copies, hidden, width):
    """Bytes a step's held experts must move: ``active`` experts' three
    matrices once, four rows of ``hidden`` a copy."""
    return (active * 3 * hidden * width + copies * 4 * hidden) * BYTES


def must_compute_flops(copies, hidden, width):
    """Operations a step's held experts must perform: three products of
    ``hidden x width`` a copy."""
    return copies * 6 * hidden * width


def read(record):
    """Over the traced steps that carry the counts: sum of max(bytes / HBM
    bandwidth, flops / peak) over the measured time under ``ds.moe_experts``
    of ALL the traced steps, in %.  (A step that fetches nothing leaves its
    counts on the device, and the next step that fetches carries both steps'
    sum: the max of a sum is at most the sum of the maxes, so the share reads
    no higher for it.)  None without the scope, the counts or the
    configuration's two widths."""
    got = _experts.scope_ms(record, "SCOPE_MOE_EXPERTS")
    config, peaks = _experts.traced_config(record), record.get("peaks")
    if not got or not config or not peaks:
        return None
    ms, steps = got
    steps = [c for c in steps if "expert_copies" in c]
    if not steps or not ms:
        return None
    hidden, width = config["hidden_size"], config["intermediate_size"]
    floor_s = sum(max(
        must_move_bytes(int(c["expert_active"]), int(c["expert_copies"]),
                        hidden, width) / peaks["hbm_bytes_per_s"],
        must_compute_flops(int(c["expert_copies"]), hidden, width)
        / peaks["bf16_flops_per_s"]) for c in steps)
    return 100.0 * floor_s / (ms / 1e3)
