"""Accelerator selection.

Analog of reference ``accelerator/real_accelerator.py:51`` (``get_accelerator``):
explicit override via ``DS_ACCELERATOR`` env var, else auto-detect (TPU if jax
sees TPU devices, else CPU).
"""

import os

from ..utils.logging import logger

_accelerator = None

_ACCELERATOR_NAMES = ("tpu", "cpu")


def _validate_accelerator_name(name):
    if name not in _ACCELERATOR_NAMES:
        raise ValueError(
            f"DS_ACCELERATOR must be one of {_ACCELERATOR_NAMES}, got {name!r}")


def get_accelerator():
    global _accelerator
    if _accelerator is not None:
        return _accelerator

    name = os.environ.get("DS_ACCELERATOR")
    if name is not None:
        _validate_accelerator_name(name)
    else:
        # Auto-detect: TPU when jax is on a TPU platform.  JAX_PLATFORMS is
        # honored implicitly because jax.devices() reflects it; a backend
        # that fails to come up is an error here, never a quiet "cpu".
        import jax
        platforms = {d.platform for d in jax.devices()}
        name = "tpu" if "tpu" in platforms else "cpu"

    set_accelerator_name(name)
    return _accelerator


def set_accelerator_name(name):
    """Install the accelerator singleton by name (test hook)."""
    global _accelerator
    _validate_accelerator_name(name)
    if name == "tpu":
        from .tpu_accelerator import TPU_Accelerator
        _accelerator = TPU_Accelerator()
    else:
        from .cpu_accelerator import CPU_Accelerator
        _accelerator = CPU_Accelerator()
    logger.debug(f"Setting accelerator to {name}")
    return _accelerator


def set_accelerator(accel):
    global _accelerator
    _accelerator = accel
    return _accelerator
