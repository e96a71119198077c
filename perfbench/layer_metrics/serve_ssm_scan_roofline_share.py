"""Per-layer metric ``serve_ssm_scan_roofline_share``: how near the Mamba
layers' recurrence (the scope ``ds.ssm_scan``) comes to the chip's roofline.

What the scope MUST move and compute, from the step's own counts (not from
what a kernel or a fusion happens to move, so the share reads the same work
whatever implements it).  With ``C`` inner channels (``mamba_expand x
hidden_size``) and ``S`` states a channel (``mamba_d_state``), summed over the
state-space layers by the counts themselves:

* a run that starts past position 0 reads its slot's ``h`` (``state_rows_read``)
  and every run writes it (``state_rows_written``): ``S x C`` values of 2 bytes;
* a row of a scan (``scan_tokens``) reads ``x`` (2 bytes a channel) and ``dt``
  (4: the recurrence is float32), ``B`` and ``C`` (4 bytes a state each) and
  writes ``y`` (4 bytes a channel): ``10 C + 8 S`` bytes;
* and computes, a state element, the plain recurrence's ``dt A``, ``exp``,
  ``* h``, ``+ (dt x) B``, ``* C`` and its part of the sum over ``S``: 7
  operations on ``S x C`` elements.

``peaks.json`` lists no vector peak, so the floor is ``max(bytes / HBM
bandwidth, operations / the bf16 matrix peak)`` and the operations' side is
far below what the vector unit can do: the share reads LOW against what
bounds the scan in truth (PERF.md section 5 says what that is), and cannot
pass 100 %.
"""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))
STATE_BYTES = 2                 # h between steps: bfloat16
ROW_BYTES_A_CHANNEL = 2 + 4 + 4     # x in, dt in, y out
ROW_BYTES_A_STATE = 4 + 4           # B, C in
OPS_AN_ELEMENT = 7


def must_move_bytes(rows_read, rows_written, tokens, channels, states):
    """Bytes the recurrence must move: a slot's ``h`` in and out a run, a
    row's inputs and its output."""
    return (rows_read + rows_written) * states * channels * STATE_BYTES \
        + tokens * (ROW_BYTES_A_CHANNEL * channels
                    + ROW_BYTES_A_STATE * states)


def must_compute_ops(tokens, channels, states):
    """Operations of the plain recurrence: ``OPS_AN_ELEMENT`` a state
    element a row."""
    return tokens * channels * states * OPS_AN_ELEMENT


def read(record):
    """Over the traced steps that carry the counts: sum of max(bytes / HBM
    bandwidth, operations / peak) over the measured time under
    ``ds.ssm_scan`` of all the traced steps, in %.  None without the scope,
    the counts or the configuration's widths."""
    got = _experts.scope_ms(record, "SCOPE_SSM_SCAN")
    config, peaks = _experts.traced_config(record), record.get("peaks")
    if not got or not config or not peaks or "mamba_d_state" not in config:
        return None
    ms, steps = got
    steps = [c for c in steps if "scan_tokens" in c]
    if not steps or not ms:
        return None
    channels = config["mamba_expand"] * config["hidden_size"]
    states = config["mamba_d_state"]
    floor_s = sum(max(
        must_move_bytes(int(c["state_rows_read"]),
                        int(c["state_rows_written"]), int(c["scan_tokens"]),
                        channels, states) / peaks["hbm_bytes_per_s"],
        must_compute_ops(int(c["scan_tokens"]), channels, states)
        / peaks["bf16_flops_per_s"]) for c in steps)
    return 100.0 * floor_s / (ms / 1e3)
