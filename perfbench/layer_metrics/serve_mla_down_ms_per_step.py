"""Per-layer metric ``serve_mla_down_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.mla_down`` scope (multi-head
    latent attention's low-rank half: the q and kv down-projections, their
    norms, the q up-projection and the rotary of q_r and k_r)
    per traced ``ds:serve.step``."""
    got = _experts.scope_ms(record, "SCOPE_MLA_DOWN")
    return got and got[0] / len(got[1])
