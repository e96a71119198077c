"""Per-layer metric ``serve_ut_pass_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.ut_pass`` scope (the
    passes of a LOOPED model's stack: every layer of every pass, and the
    final norm between two passes, ``ds.ut_norm``, inside it) per traced
    ``ds:serve.step``, ragged steps and bursts alike: all of a step but the
    embedding, the exit gate and the head.  None without the scope (a model
    that runs its stack once, a parent before PR 54)."""
    got = _experts.scope_ms(record, "SCOPE_UT_PASS")
    return got and got[0] / len(got[1])
