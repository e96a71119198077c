"""Per-layer metric ``serve_diff_combine_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.diff_attn`` scope
    (grouped differential attention's own parts: lambda's projection and
    sigmoid, the noise heads' outputs subtracted from the signal heads',
    the element-wise output gate's projection and product)
    per traced ``ds:serve.step``.  None for an untraced run and for a
    program without the scope."""
    got = _experts.scope_ms(record, "SCOPE_DIFF_ATTN")
    return got and got[0] / len(got[1])
