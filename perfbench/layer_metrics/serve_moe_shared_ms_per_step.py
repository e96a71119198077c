"""Per-layer metric ``serve_moe_shared_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.moe_shared`` scope (the
    shared experts over every row of the buffer, and their mean) per traced
    ``ds:serve.step``."""
    got = _experts.scope_ms(record, "SCOPE_MOE_SHARED")
    return got and got[0] / len(got[1])
