"""Per-layer metric ``serve_attn_gate_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.attn_gate`` scope (a gated
    attention's own parts: the per-head zero-centred norms of q and k before
    the rotary, and the sigmoid gate on the attention's output before
    ``o_proj``) per traced ``ds:serve.step``.  None for an untraced run and
    for a program without the scope."""
    got = _experts.scope_ms(record, "SCOPE_ATTN_GATE")
    return got and got[0] / len(got[1])
