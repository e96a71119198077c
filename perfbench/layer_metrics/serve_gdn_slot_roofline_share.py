"""Per-layer metric ``serve_gdn_slot_roofline_share``: how near the delta
rule's ONE-TOKEN form (the scope ``ds.gdn_slot`` inside ``ds.gdn_rule``: every
live row of a burst, a decode row beside a chunk) comes to the chip's
roofline.

What the FORM must move whatever implements it, from the step's own counts:
a token-row (``rule_slot_tokens``, summed over the Gated DeltaNet layers by
the count itself) reads its slot's state once and writes it once,
``linear_num_value_heads x linear_key_head_dim x linear_value_head_dim``
float32 values each way (2 MiB at the published widths); the row's q, k, v
and its output are a thousandth of that and are left out, so the share reads
a little LOW and cannot pass 100 %.  Its arithmetic (six operations a state
element) is far under the matrix peak and bounds nothing.
"""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))
STATE_BYTES = 4                 # the state between steps: float32


def state_row_bytes(config):
    """Bytes of ONE layer's state row of one sequence."""
    return (config["linear_num_value_heads"] * config["linear_key_head_dim"]
            * config["linear_value_head_dim"] * STATE_BYTES)


def must_move_bytes(slot_tokens, row_bytes):
    """A token-row reads its state row once and writes it once."""
    return slot_tokens * 2 * row_bytes


def read(record):
    """Over the traced steps that carry ``rule_slot_tokens``: the bytes the
    form must move at the HBM bandwidth over the measured time under
    ``ds.gdn_slot`` of all the traced steps, in %.  None without the scope,
    the count or the configuration's widths."""
    got = _experts.scope_ms(record, "SCOPE_GDN_SLOT")
    config, peaks = _experts.traced_config(record), record.get("peaks")
    if not got or not config or not peaks \
            or "linear_num_value_heads" not in config:
        return None
    ms, steps = got
    tokens = sum(int(c["rule_slot_tokens"]) for c in steps
                 if "rule_slot_tokens" in c)
    if not tokens or not ms:
        return None
    floor_s = must_move_bytes(tokens, state_row_bytes(config)) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (ms / 1e3)
