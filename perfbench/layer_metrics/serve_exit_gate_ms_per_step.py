"""Per-layer metric ``serve_exit_gate_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.exit_gate`` scope (a
    looped model's exit gate on every pass's output but the last, a product
    of ``[rows, hidden] x [hidden, 1]``, and the exit distribution's
    bookkeeping) per traced ``ds:serve.step``.  None without the scope."""
    got = _experts.scope_ms(record, "SCOPE_EXIT_GATE")
    return got and got[0] / len(got[1])
