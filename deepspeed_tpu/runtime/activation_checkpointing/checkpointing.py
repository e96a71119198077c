"""Activation checkpointing — TPU rebuild of reference
``runtime/activation_checkpointing/checkpointing.py``.

The reference re-implements torch checkpointing (``CheckpointFunction`` :488,
``checkpoint()`` :948) with four extras: partitioning activations across TP
ranks (:377), CPU checkpointing, contiguous checkpoint buffers, and a CUDA RNG
state tracker (:124) so dropout inside the recomputed segment replays
identically.

On TPU every one of those maps onto ``jax.checkpoint`` (remat) policies:

* plain checkpointing       → ``jax.checkpoint(fn, policy=nothing_saveable)``
* selective ("contiguous
  memory" tradeoff)         → ``dots_saveable`` / ``dots_with_no_batch_dims``
  — keep the matmul outputs (the expensive recompute), rematerialize the
  cheap elementwise tail; this is the XLA-native analog of the reference's
  "checkpoint only what's costly to keep" knob.
* partition_activations     → saved residuals carry a sharding constraint on
  the ("sp","tp") axes so each rank stores 1/tp of every checkpoint
  (reference :377 slices the tensor; GSPMD does it by layout).
* cpu_checkpointing         → ``save_and_offload_only_these_names`` /
  offload-to-host policy: saved residuals live in pinned host memory.
* RNG replay                → free: jax PRNG keys are values, so recompute
  replays dropout bit-exactly with no state juggling.  The
  ``RNGStatesTracker`` below exists for Megatron-style model code that wants
  named per-TP-rank streams (reference ``CudaRNGStatesTracker`` :124).
"""

import contextlib
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ...ops.pallas.flash_attention import RESIDUAL_NAMES as FLASH_RESIDUALS
from ...utils.logging import logger

# THE table of jax.checkpoint policies by name (reference deepspeed_config_
# activation checkpointing knobs → remat policies): the models'
# ``remat_policy`` and ``CheckpointPolicy.policy_name`` both resolve here,
# through :func:`resolve_policy`.
_POLICIES = {
    "none": None,  # jax.checkpoint's own default: recompute everything
    "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
    # keep the flash kernel's residuals: its output and log-sum-exp, which
    # only the kernel can make, and q / k / v as it received them, so that a
    # recomputed block runs neither the forward kernel nor the projections,
    # rotary and transposes that feed it a second time.  Where attention did
    # not run as that kernel the names do not exist and this is
    # nothing_saveable.
    "flash_residuals_saveable": jax.checkpoint_policies.save_only_these_names(
        *FLASH_RESIDUALS),
    "everything_saveable": jax.checkpoint_policies.everything_saveable,
    "dots_saveable": jax.checkpoint_policies.dots_saveable,
    "checkpoint_dots": jax.checkpoint_policies.dots_saveable,
    "dots_with_no_batch_dims_saveable":
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "checkpoint_dots_with_no_batch_dims":
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


def resolve_policy(name):
    """The ``jax.checkpoint`` policy a configuration string names; an unknown
    string raises by name (a typo must not become full recomputation)."""
    try:
        return _POLICIES[name]
    except KeyError:
        raise ValueError(f"unknown remat policy {name!r}: one of "
                         f"{sorted(_POLICIES)}") from None


@dataclass
class CheckpointPolicy:
    """Resolved activation-checkpointing behavior (from the
    ``activation_checkpointing`` config block, reference
    ``runtime/activation_checkpointing/config.py``)."""
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    policy_name: str = "nothing_saveable"

    def jax_policy(self):
        if self.cpu_checkpointing:
            # offload saved residuals to pinned host memory (reference CPU
            # checkpointing :377 area) — offload everything remat would save
            try:
                return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
                    "device", "pinned_host")
            except Exception:  # older jax: fall back to device-saved dots
                logger.warning("offload remat policy unavailable; "
                               "falling back to dots_saveable")
                return jax.checkpoint_policies.dots_saveable
        if self.contiguous_memory_optimization:
            # keep matmul outputs (the contiguous big buffers) — closest
            # XLA-native analog of the reference's contiguous buffer reuse
            return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return resolve_policy(self.policy_name)


_config: Optional[CheckpointPolicy] = None


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None):
    """Reference ``checkpointing.configure()`` signature; accepts either a
    DeepSpeedConfig or explicit flags."""
    global _config
    cfg = CheckpointPolicy()
    if deepspeed_config is not None:
        ac = getattr(deepspeed_config, "activation_checkpointing_config", None)
        if ac is not None:
            cfg.partition_activations = ac.partition_activations
            cfg.cpu_checkpointing = ac.cpu_checkpointing
            cfg.contiguous_memory_optimization = getattr(
                ac, "contiguous_memory_optimization", False)
            cfg.number_checkpoints = ac.number_checkpoints
    if partition_activations is not None:
        cfg.partition_activations = partition_activations
    if contiguous_checkpointing is not None:
        cfg.contiguous_memory_optimization = contiguous_checkpointing
    if num_checkpoints is not None:
        cfg.number_checkpoints = num_checkpoints
    if checkpoint_in_cpu is not None:
        cfg.cpu_checkpointing = checkpoint_in_cpu
    _config = cfg
    return cfg


def is_configured():
    return _config is not None


def reset():
    global _config
    _config = None


def get_policy():
    return _config or CheckpointPolicy()


def _partition_constraint(x):
    """Shard saved residuals over the model-parallel axes so each rank keeps
    1/tp of every activation (reference partition_activations :377)."""
    from ...utils import groups
    mesh = groups.get_global_mesh()
    if mesh is None or x.ndim == 0:
        return x
    from ..zero.partition import shard_spec
    spec = shard_spec(x.shape, mesh, ("tp", "sp"))
    try:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, spec))
    except Exception:
        return x


def checkpoint(function, *args, policy=None, prevent_cse=True, **kwargs):
    """Megatron-compatible ``checkpoint(fn, *args)`` (reference :948):
    activations inside ``function`` are rematerialized on the backward pass.

    Unlike the reference this composes with jit/scan — it is a trace-time
    transform, not an autograd.Function."""
    cfg = get_policy()
    jp = (policy.jax_policy() if isinstance(policy, CheckpointPolicy)
          else policy if policy is not None else cfg.jax_policy())

    wrapped = function
    if cfg.partition_activations:
        inner = function

        def wrapped(*a, **kw):
            out = inner(*a, **kw)
            return jax.tree_util.tree_map(_partition_constraint, out)

    fn = jax.checkpoint(wrapped, policy=jp, prevent_cse=prevent_cse)
    return fn(*args, **kwargs)


def non_reentrant_checkpoint(function, *args, **kwargs):
    """Reference non-reentrant variant (:704) — identical under jax (there is
    no reentrant autograd engine); kept for API parity."""
    return checkpoint(function, *args, **kwargs)


def checkpoint_wrapper(function, policy=None):
    """Return a remat-wrapped callable (for scan-over-layers use)."""
    cfg = get_policy()
    jp = (policy.jax_policy() if isinstance(policy, CheckpointPolicy)
          else policy if policy is not None else cfg.jax_policy())
    return jax.checkpoint(function, policy=jp)


# --------------------------------------------------------------------- RNG
class RNGStatesTracker:
    """Named PRNG streams (reference ``CudaRNGStatesTracker`` :124).

    jax keys are values, so "states" here are keys; ``fork`` yields a
    sub-key derived per entry so model-parallel regions can draw
    rank-correlated or rank-independent randomness explicitly."""

    def __init__(self):
        self._keys = {}
        self._use_count = {}

    def reset(self):
        self._keys.clear()
        self._use_count.clear()

    def get_states(self):
        return dict(self._keys)

    def set_states(self, states):
        self._keys = dict(states)

    def add(self, name, seed):
        if name in self._keys:
            raise Exception(f"rng state {name} already exists")
        self._keys[name] = jax.random.key(seed)
        self._use_count[name] = 0

    @contextlib.contextmanager
    def fork(self, name="model-parallel-rng"):
        if name not in self._keys:
            raise Exception(f"rng state {name} not added")
        self._use_count[name] += 1
        yield jax.random.fold_in(self._keys[name], self._use_count[name])


_RNG_TRACKER = RNGStatesTracker()


def get_rng_tracker():
    return _RNG_TRACKER


def model_parallel_rng_seed(seed):
    """Reference ``model_parallel_cuda_manual_seed`` (:201): default stream
    shares ``seed`` across TP ranks; the model-parallel stream folds in the
    TP rank so dropout differs per shard."""
    _RNG_TRACKER.reset()
    _RNG_TRACKER.add("default", seed)
    # under SPMD all processes trace the same program; the model-parallel
    # stream is distinguished inside the traced fn via axis_index, so at the
    # host level we fold in only the process index
    _RNG_TRACKER.add("model-parallel-rng", seed + 2718 + jax.process_index())
    return _RNG_TRACKER
