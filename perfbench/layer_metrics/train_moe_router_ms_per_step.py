"""Per-layer metric ``train_moe_router_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "train_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.moe_router`` scope (the
    router's product, softmax and top-k, forward and backward) per traced
    step."""
    got = _experts.scope_ms(record, "SCOPE_MOE_ROUTER")
    return got and got[0]
