"""Per-layer metric ``train_expert_padded_call_share``: how often a routed
training layer runs its copies in per-expert padded blocks (batched dense
products) and not in the worst case's buffer (``lax.ragged_dot``)."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "train_moe_experts_ms_per_step.py"))


def share(counts, layers):
    """``expert_padded_calls`` (the layer-calls of the counted micro-steps
    whose fullest held expert fitted its block) over the layer-calls there
    were (``layers`` a micro-step), in %."""
    calls = layers * counts.get("micro_steps_covered", 0)
    if not calls:
        return None
    return 100.0 * counts["expert_padded_calls"] / calls


def read(record):
    """From the counts the traced ``ds:train.micro`` spans carry and the
    depth the traced cell's configuration trains (``num_hidden_layers``'s
    ``train``: every layer is routed).  None without them: an untraced run,
    a program that does not count the calls (a parent of PR 50), a cell with
    no expert layer."""
    t = _experts.traced(record)
    counts = t and _experts.counted(t)
    config = _experts.traced_config(record)
    layers = config and config.get("num_hidden_layers")
    if not counts or "expert_padded_calls" not in counts or \
            not isinstance(layers, dict) or "train" not in layers:
        return None
    return share(counts, layers["train"])
