"""Per-layer metric ``serve_expert_copies_per_row``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """(row, expert) copies that landed on a held expert per live row and
    layer: over the traced ``ds:serve.step`` spans, ragged steps and bursts,
    sum ``expert_copies`` / (sum ``live_tokens`` x layers).  A chip that holds
    ``h`` of the router's ``E`` experts reads ``k h / E`` at even routing:
    1.0 at 8 a token and 16 of 128.  The count is made on the device and
    comes back with the tokens a request waits for: a step that fetches
    nothing (a prompt's middle chunk) carries no count, and the next step
    that fetches carries both steps' sum, so the rows are ALL the traced
    steps'.  None where no traced step carries the count."""
    from perfbench import serve_trace
    t = serve_trace.traced(record)
    config = _experts.traced_config(record)
    layers = config and config["depth"]
    steps = t["steps"] if t else []
    copies = [int(c["expert_copies"]) for c in steps if "expert_copies" in c]
    rows = sum(int(c.get("live_tokens", 0)) for c in steps)
    if not copies or not rows or not layers:
        return None
    return sum(copies) / (rows * layers)
