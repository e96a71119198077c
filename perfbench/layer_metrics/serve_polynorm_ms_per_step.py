"""Per-layer metric ``serve_polynorm_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.polynorm`` scope
    (PolyNorm on the gate of every feed-forward: the dense layers', the
    shared expert's and, between the grouped products, the held experts'
    with each copy's own expert's coefficients)
    per traced ``ds:serve.step``.  None for an untraced run and for a
    program without the scope."""
    got = _experts.scope_ms(record, "SCOPE_POLYNORM")
    return got and got[0] / len(got[1])
