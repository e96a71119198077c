"""Per-layer metric ``serve_zero_expert_copy_share``."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "serve_moe_experts_ms_per_step.py"))


def read(record):
    """Of a router's choices, the share that fell on an IDENTITY expert (one
    without weights: the weighted copy of the layer's input, no product):
    over the traced ``ds:serve.step`` spans, ragged steps and bursts, sum
    ``zero_expert_copies`` / (sum ``live_tokens`` x ``moe_topk`` x layers),
    in %.  A third of LongCat-Flash's router is identity experts, so even
    routing reads 33 %: the rest is what a token pays real experts for (so
``better`` is higher: a choice that costs no product; with a seeded router it
is the DRAW's number, which the program does not move).  The
    count is made on the device and arrives as ``expert_copies`` does
    (``serve_expert_copies_per_row``): the rows are ALL the traced steps'.
    None where no traced step carries the count or the configuration states
    no ``moe_topk``."""
    from perfbench import serve_trace
    t = serve_trace.traced(record)
    config = _experts.traced_config(record)
    steps = t["steps"] if t else []
    copies = [int(c["zero_expert_copies"]) for c in steps
              if "zero_expert_copies" in c]
    rows = sum(int(c.get("live_tokens", 0)) for c in steps)
    if not copies or not rows or not config or "moe_topk" not in config:
        return None
    return 100.0 * sum(copies) / (rows * config["moe_topk"] * config["depth"])
