"""Per-layer metric ``serve_gdn_proj_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.gdn_proj`` scope (a Gated
    DeltaNet mixer's two input products, the rule's inputs, the gated norm
    and ``out_proj``) and under ``ds.gdn_conv`` (the causal convolution over
    a run's rows and its slot's last rows, and their write-back) per traced
    ``ds:serve.step``.  None without either scope."""
    got = [_experts.scope_ms(record, scope)
           for scope in ("SCOPE_GDN_PROJ", "SCOPE_GDN_CONV")]
    got = [g for g in got if g]
    return sum(g[0] for g in got) / len(got[0][1]) if got else None
