"""``ds_report`` — environment / op-compatibility report.

Reference ``deepspeed/env_report.py`` prints a torch/cuda/nccl version matrix
and per-op_builder compatibility.  TPU version reports the JAX stack, device
inventory, and the native-op availability (Pallas kernels, C++ extensions).
"""

import importlib
import os
import sys

GREEN = "\033[92m"
RED = "\033[91m"
YELLOW = "\033[93m"
END = "\033[0m"
OKAY = f"{GREEN}[OKAY]{END}"
WARNING = f"{YELLOW}[WARNING]{END}"
NO = f"{RED}[NO]{END}"


def _version(mod_name):
    try:
        mod = importlib.import_module(mod_name)
        return getattr(mod, "__version__", "unknown")
    except Exception:
        return None


def op_report():
    """Native/kernel op availability (op_builder analog)."""
    rows = []
    from .ops.op_builder import ALL_OPS
    for name, builder in sorted(ALL_OPS.items()):
        try:
            compatible = builder().is_compatible()
        except Exception:
            compatible = False
        rows.append((name, compatible))
    return rows


def debug_report():
    import deepspeed_tpu
    rows = [
        ("deepspeed_tpu version", deepspeed_tpu.__version__),
        ("python version", sys.version.split()[0]),
        ("python platform", sys.platform),
    ]
    # aio engine probe (reference async_io report role).  Report-only: a
    # cold cache must NOT trigger the g++ JIT build mid-report (this tool
    # must never hang), and a setup probe is reported as such — the real
    # resolution happens at AIOHandle construction.
    try:
        from .ops.aio import AsyncIOBuilder, uring_available
        if not os.path.exists(AsyncIOBuilder().lib_path()):
            rows.append(("aio engine (auto)",
                         "not built yet (first AIOHandle builds it)"))
        elif uring_available():
            rows.append(("aio engine (auto)", "io_uring (setup probe ok)"))
        else:
            rows.append(("aio engine (auto)",
                         "thread-pool (io_uring setup refused)"))
    except Exception as e:
        rows.append(("aio engine (auto)", f"unavailable: {e}"))
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "numpy"):
        v = _version(mod)
        rows.append((f"{mod} version", v if v else "not installed"))
    # a report tool reports a missing backend instead of dying on it
    try:
        import jax
        dev = jax.devices()
        rows.append(("jax backend", jax.default_backend()))
        rows.append(("device kind", dev[0].device_kind))
        rows.append(("device count", len(dev)))
        rows.append(("devices", ", ".join(str(d) for d in dev[:8])))
    except RuntimeError as e:
        rows.append(("jax backend", f"unavailable ({e})"))
    rows.append(("DS_ACCELERATOR", os.environ.get("DS_ACCELERATOR", "auto")))
    return rows


def main(hide_operator_status=False, hide_errors_and_warnings=False):
    if not hide_operator_status:
        print("-" * 70)
        print("DeepSpeed-TPU op compatibility")
        print("-" * 70)
        for name, ok in op_report():
            print(f"{name:.<40} {OKAY if ok else NO}")
    print("-" * 70)
    print("DeepSpeed-TPU general environment info:")
    print("-" * 70)
    for key, val in debug_report():
        print(f"{key:.<32} {val}")
    return 0


def cli_main():
    sys.exit(main())


if __name__ == "__main__":
    main()
