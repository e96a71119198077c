"""THE should-we-run-Pallas gate of every kernel dispatch site (attention,
paged attention, block-sparse, evoformer)."""

import os


def use_pallas_kernels() -> bool:
    """True on a TPU (``pallas/_common.interpret_mode`` is False) unless the
    fleet-wide kill switch ``DS_TPU_DISABLE_PALLAS_ATTN`` is set.
    ``DS_TPU_FORCE_PALLAS=1`` forces True (tests drive the kernels in
    interpret mode on CPU)."""
    if os.environ.get("DS_TPU_DISABLE_PALLAS_ATTN"):
        return False
    if os.environ.get("DS_TPU_FORCE_PALLAS") == "1":
        return True
    from .pallas._common import interpret_mode
    return not interpret_mode()
