"""Per-layer metric ``serve_mhc_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.mhc`` scope
    (a multi-stream residual's own work (manifold-constrained hyper-
    connections): each sublayer's three mappings from the normed streams,
    the Sinkhorn sweeps, the read of the streams and the write back to
    them)
    per traced ``ds:serve.step``.  None for an untraced run and for a
    program without the scope."""
    got = _experts.scope_ms(record, "SCOPE_MHC")
    return got and got[0] / len(got[1])
