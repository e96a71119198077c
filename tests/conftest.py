"""Test harness configuration.

TPU analog of the reference's distributed test strategy (SURVEY.md §4): instead
of spawning N processes with a FileStore rendezvous (reference
``tests/unit/common.py:326``), we run single-process JAX with a *virtual
8-device CPU mesh* (``--xla_force_host_platform_device_count=8``) so every
mesh-axis collective (dp/sp/pp/tp/ep) executes with real SPMD semantics.
"""

import os

# torch is imported at collection time (test_torch_migration) and its OpenMP
# pool coexists badly with XLA's Eigen + tensorstore threads on small CPU
# boxes — intermittent suite-wide segfaults mid-jit-execution.  Pin OpenMP
# to one thread BEFORE anything native loads; the suite's torch work is a
# handful of tiny tensor saves, XLA does not use OpenMP.
os.environ.setdefault("OMP_NUM_THREADS", "1")

# Must be set before jax is imported anywhere: the suite runs on the CPU
# platform whatever the ambient environment selects.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("DS_ACCELERATOR", "cpu")

import jax  # noqa: E402

# XLA compilation cache — PER-SESSION by default, cross-run only by opt-in.
# (CPU-only test plumbing: entry points use utils/compile_cache.py.)
#
# The disk cache matters even within a single pytest process: each test's
# engine makes fresh jit objects, so the in-memory cache (keyed by function
# identity) misses, while the disk cache (keyed by HLO hash) dedupes the
# recompiles — worth ~40% of suite wall time.
#
# It does not persist across runs by default: on jax 0.4.3x, CPU
# executables deserialized from a cache written by a PREVIOUS process
# mishandled donated buffers (warm-cache runs NaN'd the engine
# offload/reload tests and intermittently segfaulted pytest, while
# identical cold runs passed).  Not re-examined on the installed jax
# 0.9.0, so the safe default stays: a fresh per-session directory keeps
# the in-run speedup and makes cross-run poisoning structurally impossible.
#
# DS_TPU_TEST_CACHE opts into a shared cross-run cache: the dir is
# namespaced by jax/jaxlib version (a different build's entries segfault
# on deserialize) and self-heals — a dirty marker held for the session
# means a crashed run, whose entries may be truncated mid-write, wipes the
# dir on next start.
import tempfile  # noqa: E402

_cache_opt_in = os.environ.get("DS_TPU_TEST_CACHE")
if os.environ.get("DS_TPU_TEST_NO_DISK_CACHE"):
    # Debugging escape hatch: no disk cache at all — no executable ever
    # takes the deserialization path.  Slower suite-wide; use to rule the
    # cache in/out when chasing native crashes.
    _cache_dir = None

    def pytest_sessionfinish(session, exitstatus):
        pass
elif _cache_opt_in:
    import jaxlib

    _cache_dir = os.path.join(_cache_opt_in,
                              f"{jax.__version__}-{jaxlib.__version__}")
    _dirty_marker = os.path.join(_cache_dir, ".session_dirty")
    if os.path.exists(_dirty_marker):
        import shutil
        shutil.rmtree(_cache_dir, ignore_errors=True)
    os.makedirs(_cache_dir, exist_ok=True)
    with open(_dirty_marker, "w") as _f:
        _f.write(str(os.getpid()))

    def pytest_sessionfinish(session, exitstatus):
        """Clean exit → this session's cache entries are trustworthy."""
        try:
            os.remove(_dirty_marker)
        except OSError:
            pass
else:
    _cache_dir = tempfile.mkdtemp(prefix="ds_tpu_jax_cache_")

    def pytest_sessionfinish(session, exitstatus):
        """The per-session cache is garbage once the process exits."""
        import shutil
        shutil.rmtree(_cache_dir, ignore_errors=True)

if _cache_dir is not None:
    jax.config.update("jax_compilation_cache_dir", _cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import pytest  # noqa: E402


@pytest.fixture(scope="module")
def no_disk_cache():
    """For a module that wraps an engine's step (a recording step, a step
    around other storage): its burst programs have the HLO of another's under
    another static ``step_fn``, so the later one would be READ from the
    suite's disk cache, and a CPU executable deserialized from it mishandles
    the donated cache (the history is above; seen as a wrong stream in one
    run of ten).  ``pytestmark = pytest.mark.usefixtures("no_disk_cache")``."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def _reset_global_state():
    """Each test gets a fresh mesh/comm world (analog of per-test process
    groups in the reference's DistributedTest)."""
    yield
    from deepspeed_tpu.utils import groups
    from deepspeed_tpu import comm as dist
    groups.reset_mesh()
    dist.destroy_process_group()
