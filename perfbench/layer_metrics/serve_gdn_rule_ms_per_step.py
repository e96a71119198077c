"""Per-layer metric ``serve_gdn_rule_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.gdn_rule`` scope (a Gated
    DeltaNet layer's delta rule in BOTH forms: a burst's or a lone decode
    row's one update of its slot's float32 state row, and the loop over a
    step's chunks of 64 rows with the state's read and write around it) per
    traced ``ds:serve.step``.  None for an untraced run and for a program
    without the scope."""
    got = _experts.scope_ms(record, "SCOPE_GDN_RULE")
    return got and got[0] / len(got[1])
