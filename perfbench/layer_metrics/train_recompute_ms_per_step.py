"""Per-layer metric ``train_recompute_ms_per_step``."""


def read(record):
    """Time of the first chip's ops whose scope path carries JAX's
    ``rematted_computation`` marker (the forward pass run again inside the
    backward pass: activation checkpointing) per traced step."""
    from perfbench import program_trace
    return program_trace.per_train_step(record, lambda s: s["recompute_ms"])
