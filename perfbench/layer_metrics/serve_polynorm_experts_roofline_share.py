"""Per-layer metric ``serve_polynorm_experts_roofline_share``: how near the
held experts' part of a step comes to the chip's roofline, for a
configuration that states an expert's width as ``moe_intermediate_size`` and
whose gate is a row-wise activation between the grouped products.

``serve_moe_experts_roofline_share`` takes ``intermediate_size`` for the
width (here a leading dense layer's) and ``serve_held_experts_roofline_share``
``expert_ffn_hidden_size``.  The operations and bytes are the first reader's
own two functions, loaded from its file: an activation's four coefficients an
expert and its three mean squares a copy add nothing that counts beside three
matrices of ``D x I`` an expert and three products of ``D x I`` a copy.
"""

import os

from perfbench.loader import load_file

_here = os.path.dirname(os.path.abspath(__file__))
_roofline = load_file(os.path.join(_here,
                                   "serve_moe_experts_roofline_share.py"))
_experts = load_file(os.path.join(_here, "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Over the traced steps that carry the counts: sum of max(bytes / HBM
    bandwidth, flops / peak) over the measured time under ``ds.moe_experts``
    (the activation's ops under ``ds.polynorm`` are inside it) of ALL the
    traced steps, in % (``serve_moe_experts_roofline_share`` says why a step
    without counts reads no higher for it).  None without the scope, the
    counts or the configuration's two widths."""
    got = _experts.scope_ms(record, "SCOPE_MOE_EXPERTS")
    config, peaks = _experts.traced_config(record), record.get("peaks")
    if not got or not config or not peaks:
        return None
    ms, steps = got
    steps = [c for c in steps if "expert_copies" in c]
    if not steps or not ms or "moe_intermediate_size" not in config:
        return None
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    floor_s = sum(max(
        _roofline.must_move_bytes(int(c["expert_active"]),
                                  int(c["expert_copies"]), hidden, width)
        / peaks["hbm_bytes_per_s"],
        _roofline.must_compute_flops(int(c["expert_copies"]), hidden, width)
        / peaks["bf16_flops_per_s"]) for c in steps)
    return 100.0 * floor_s / (ms / 1e3)
