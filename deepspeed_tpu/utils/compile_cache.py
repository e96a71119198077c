"""The one place an entry point turns on JAX's persistent compilation cache.

The cache key includes the directory path, so a directory that moves (a temp
name, a pid, a timestamp) never hits.  Policy:

* ``JAX_COMPILATION_CACHE_DIR`` set → jax reads it itself; no directory is
  set in code;
* otherwise → one fixed, git-ignored path inside the checkout
  (:data:`CHECKOUT_CACHE_DIR`).

Library code never calls this — only ``__main__`` entry points do
(``chip_smoke.py``, ``perfbench/``, the examples, ``__graft_entry__.py``).
"""

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — the parent of the ``deepspeed_tpu`` package
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache():
    """Enable the persistent cache for this process; returns the directory
    in use.  Every program is cached, however quick its compile — a second
    process must find all of them."""
    import jax
    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir
