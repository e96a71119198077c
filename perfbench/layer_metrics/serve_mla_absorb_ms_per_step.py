"""Per-layer metric ``serve_mla_absorb_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.mla_absorb`` scope (multi-head
    latent attention's absorbed products: every head's query into the latent
    space through W_uk, the latent output out of it through W_uv)
    per traced ``ds:serve.step``."""
    got = _experts.scope_ms(record, "SCOPE_MLA_ABSORB")
    return got and got[0] / len(got[1])
