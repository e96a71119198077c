"""Shared Pallas helpers."""

import functools
import os

import jax


@functools.cache
def interpret_mode() -> bool:
    """THE "am I off a TPU" predicate of the kernel layer: True → Pallas
    kernels run in interpreter mode and dispatch sites keep their XLA
    formulations; False → Mosaic kernels compile for the TPU.

    Decided by the default device's platform.  A backend that fails to come
    up raises here — interpreting the kernels on what should have been a
    TPU would silently destroy performance.

    ``DS_TPU_PALLAS_INTERPRET=0|1`` is the explicit override for tests and
    for AOT compile checks (``tools/aot_kernel_check.py`` targets a TPU
    topology while the default backend is the CPU).
    """
    forced = os.environ.get("DS_TPU_PALLAS_INTERPRET")
    if forced is not None:
        return forced not in ("0", "false", "False")
    return jax.devices()[0].platform != "tpu"
