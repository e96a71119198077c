"""Per-layer metric ``serve_moe_zero_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.moe_zero`` scope (the
    identity experts' part of an expert branch: the chosen identity experts'
    weights summed a row, times the branch's input) per traced
    ``ds:serve.step``."""
    got = _experts.scope_ms(record, "SCOPE_MOE_ZERO")
    return got and got[0] / len(got[1])
