"""Rank-aware logging.

TPU-native analog of the reference's ``deepspeed/utils/logging.py`` (rank-aware
``log_dist`` / ``logger``).  Process identity comes from JAX's distributed runtime
rather than torch.distributed.
"""

import logging
import os
import sys
from functools import lru_cache

LOG_LEVELS = {
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warning": logging.WARNING,
    "error": logging.ERROR,
    "critical": logging.CRITICAL,
}


@lru_cache(None)
def _create_logger(name="DeepSpeedTPU", level=logging.INFO):
    logger_ = logging.getLogger(name)
    logger_.setLevel(level)
    logger_.propagate = False
    if not logger_.handlers:
        handler = logging.StreamHandler(stream=sys.stdout)
        handler.setFormatter(
            logging.Formatter(
                "[%(asctime)s] [%(levelname)s] [%(name)s] %(message)s"))
        logger_.addHandler(handler)
    return logger_


logger = _create_logger(
    level=LOG_LEVELS.get(os.environ.get("DS_TPU_LOG_LEVEL", "info").lower(), logging.INFO))


def _get_rank():
    """This process's rank WITHOUT bringing up a JAX backend: a launcher
    parent that logs must not take the chip from the worker it is about to
    start.  The launcher exports RANK to workers; a process started any
    other way is rank 0 until it has joined ``jax.distributed``."""
    rank = os.environ.get("RANK")
    if rank is not None:
        return int(rank)
    import jax
    return jax.process_index() if jax.distributed.is_initialized() else 0


def log_dist(message, ranks=None, level=logging.INFO):
    """Log ``message`` only on the given ranks (None or [-1] = all ranks).

    Mirrors the behavior of the reference's ``log_dist``
    (``deepspeed/utils/logging.py``).
    """
    my_rank = _get_rank()
    if ranks is None or len(ranks) == 0 or -1 in ranks or my_rank in ranks:
        logger.log(level, f"[Rank {my_rank}] {message}")


def warning_once(message):
    _warn_once_cache = getattr(warning_once, "_cache", None)
    if _warn_once_cache is None:
        _warn_once_cache = set()
        warning_once._cache = _warn_once_cache
    if message not in _warn_once_cache:
        _warn_once_cache.add(message)
        logger.warning(message)


def print_json_dist(message, ranks=None, path=None):
    """Print/append a json message on selected ranks (autotuning metric dump)."""
    import json
    my_rank = _get_rank()
    if ranks is None or len(ranks) == 0 or -1 in ranks or my_rank in ranks:
        message["rank"] = my_rank
        if path is None:
            print(json.dumps(message))
        else:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            with open(path, "a") as f:
                f.write(json.dumps(message) + "\n")
