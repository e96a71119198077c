"""Per-layer metric ``serve_ssm_scan_ms_per_step``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Time of the first chip's ops under the ``ds.ssm_scan`` scope (the
    Mamba layers' recurrence of either kind of step: the kernel
    ``ds_selective_scan`` with the lane-broadcast of its ``B`` and ``C``, or a
    burst's elementwise update of every slot, and the state rows' read and
    write) per traced ``ds:serve.step``.  None without the scope (a parent
    before PR 41, a model with no state-space layer)."""
    got = _experts.scope_ms(record, "SCOPE_SSM_SCAN")
    return got and got[0] / len(got[1])
