"""Per-layer metric ``train_micro_step_device_ms``."""


def read(record):
    """Mean duration of the micro-step program's executions
    (``ds_micro_<variant>``) on the first chip's ``XLA Modules`` line, over
    the whole steps of the traced stretch (``perfbench/train_step_trace.py``):
    the inside twin of the step's time, with ``train_optimizer_ms_per_step``
    for ``ds_apply_update``."""
    from perfbench import train_step_trace
    t = train_step_trace.traced(record)
    return t and train_step_trace.micro_step_device_ms(t)
