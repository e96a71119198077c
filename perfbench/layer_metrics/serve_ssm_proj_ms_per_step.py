"""Per-layer metric ``serve_ssm_proj_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.ssm_proj`` scope (a Mamba
    mixer's matrix products and what lies between them: ``in_proj``,
    ``x_proj``, the three inner norms, ``dt_proj`` with its softplus, the
    gate and ``out_proj``) per traced ``ds:serve.step``.  None without the
    scope."""
    got = _experts.scope_ms(record, "SCOPE_SSM_PROJ")
    return got and got[0] / len(got[1])
