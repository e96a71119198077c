"""Per-layer metric ``train_expert_rows_max_share``: the load imbalance the
grouped products of a training step wait on."""

import os

from perfbench.loader import load_file

_experts = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "train_moe_experts_ms_per_step.py"))


def share(counts, held):
    """``expert_rows_max`` (the fullest held expert's copies, summed over
    layers) over the mean copies a held expert (``expert_copies / held``,
    which is the layers' means summed), in %: 100 is an even routing."""
    if not counts.get("expert_copies"):
        return None
    return 100.0 * counts["expert_rows_max"] * held / counts["expert_copies"]


def read(record):
    """From the counts the traced ``ds:train.micro`` spans carry and the
    experts the traced cell's configuration holds
    (``moe_num_primary_experts`` of a file that states a ``share``).  None
    without them."""
    t = _experts.traced(record)
    counts = t and _experts.counted(t)
    config = _experts.traced_config(record)
    if not counts or not config or "expert_rows_max" not in counts or \
            "moe_num_primary_experts" not in config:
        return None
    return share(counts, config["moe_num_primary_experts"])
