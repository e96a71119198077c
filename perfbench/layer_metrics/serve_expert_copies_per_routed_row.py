"""Per-layer metric ``serve_expert_copies_per_routed_row``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """(row, expert) copies that landed on a held expert per live row and
    ROUTED layer, for a configuration whose first ``n_dense_first_layers``
    layers have no router (``serve_expert_copies_per_row`` divides by the
    whole depth): over the traced ``ds:serve.step`` spans, ragged steps and
    bursts, sum ``expert_copies`` / (sum ``live_tokens`` x routed layers).  A
    chip that holds ``h`` of the router's ``E`` experts reads ``k h / E`` at
    even routing: 0.5 at 8 a token and 24 of 384; a row tile of the grouped
    product is full at ``tile / (live rows x this / h)`` of the rows it is
    sized for.  The rows are ALL the traced steps' (that reader says why).
    None where no traced step carries the count or the configuration states
    no leading dense layers."""
    from perfbench import serve_trace
    t = serve_trace.traced(record)
    config = _experts.traced_config(record)
    if not t or not config or "n_dense_first_layers" not in config:
        return None
    routed = config["depth"] - int(config["n_dense_first_layers"])
    steps = t["steps"]
    copies = [int(c["expert_copies"]) for c in steps if "expert_copies" in c]
    rows = sum(int(c.get("live_tokens", 0)) for c in steps)
    if not copies or not rows or routed <= 0:
        return None
    return sum(copies) / (rows * routed)
