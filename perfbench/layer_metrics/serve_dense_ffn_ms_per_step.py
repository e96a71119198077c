"""Per-layer metric ``serve_dense_ffn_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.dense_ffn`` scope (the
    dense feed-forwards of a layer whose expert branch runs BESIDE them, on a
    shortcut: two SwiGLUs a layer over every row of the buffer) per traced
    ``ds:serve.step``."""
    got = _experts.scope_ms(record, "SCOPE_DENSE_FFN")
    return got and got[0] / len(got[1])
