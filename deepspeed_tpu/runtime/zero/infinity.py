"""ZeRO-Infinity parameter streaming — host/NVMe-resident parameters fed to
the chip one transformer block at a time.

Reference: ``deepspeed/runtime/swap_tensor/partitioned_param_swapper.py:37``
(``AsyncPartitionedParameterSwapper``) + the fetch/release coordinator
``deepspeed/runtime/zero/partitioned_param_coordinator.py:276`` + host-side
optimization ``csrc/adam/cpu_adam_impl.cpp``.

TPU-native shape (NOT a hook translation): the model exposes itself as
``embed → L homogeneous blocks → head`` (:class:`StreamingSpec`); the engine
drives per-block *jitted* calls while this module keeps every block's state
host-resident:

* fp32 master + optimizer moments + a wire-dtype (bf16) parameter cache live
  in host RAM — or on NVMe via the aio thread pool — as ONE flat contiguous
  vector per (block, kind), so a block's optimizer update is a single native
  SIMD kernel call (``ops/cpu_optimizers.py``) and a block's NVMe swap is one
  file stream.
* ``start_fetch``/``finish_fetch`` double-buffer: NVMe→RAM via async aio
  reads, RAM→HBM via (async) ``jax.device_put`` of zero-copy views into the
  flat vector.
* gradients arrive as device arrays per block; ``accumulate_grads`` copies
  them into a host stash (wire dtype at gas=1, fp32 when accumulating), and
  ``optimizer_sweep`` runs the host Adam/Adagrad/Lion kernel block-by-block —
  emitting the updated bf16 cache in the same pass (``bf16_out``), so updated
  params never round-trip through HBM.

HBM never holds more than the executor's working set of blocks (the
:class:`~deepspeed_tpu.runtime.infinity_engine.InfinityEngine` keeps ≤ 3:
current + prefetch, tracked and asserted in tests).
"""

import os
import tempfile
from typing import Callable, NamedTuple

import numpy as np
import ml_dtypes

import jax

from ...utils.logging import log_dist

BF16 = ml_dtypes.bfloat16


class StreamingSpec(NamedTuple):
    """How a model exposes its block structure to the streaming executor.

    ``block_keys``   ordered top-level parameter-tree keys, one per block —
                     every block must share one pytree structure so a single
                     compiled ``block_apply`` serves all of them.
    ``resident_keys``  top-level keys of the embed/norm/head group (fetched
                     once per step, resident for the whole step).
    ``embed_apply``  ``(resident_params, *batch) -> activations``
    ``block_apply``  ``(block_params, activations) -> activations``
    ``head_apply``   ``(resident_params, activations, *batch) -> loss`` (or
                     logits when the batch carries no labels)
    ``init_block``   ``(rng, key, activations) -> host block params``
    ``init_resident``  ``(rng, *batch) -> host resident params``
    """
    block_keys: tuple
    resident_keys: tuple
    embed_apply: Callable
    block_apply: Callable
    head_apply: Callable
    init_block: Callable
    init_resident: Callable


def _flatten_f32(tree):
    """Host pytree → (one C-contiguous fp32 vector, leaf metadata)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    arrs = [np.asarray(l, dtype=np.float32) for l in leaves]
    sizes = [a.size for a in arrs]
    flat = np.empty(sum(sizes), np.float32)
    off = 0
    shapes = []
    for a in arrs:
        flat[off:off + a.size] = a.ravel()
        shapes.append(a.shape)
        off += a.size
    return flat, (treedef, shapes, sizes)


def _views(flat, meta):
    """Zero-copy pytree view of a flat vector."""
    treedef, shapes, sizes = meta
    out, off = [], 0
    for shape, n in zip(shapes, sizes):
        out.append(flat[off:off + n].reshape(shape))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


class _FetchHandle:
    """In-flight block fetch: optional aio read → device_put."""

    def __init__(self, key):
        self.key = key
        self.aio_handle = None
        self.device_tree = None


class BlockStore:
    """Host/NVMe residency manager for per-block parameters and optimizer
    state (flat-vector layout, see module docstring).

    ``param_device`` / ``state_device``: "cpu" (host RAM) or "nvme".
    ``optimizer``: adam | adamw | fusedadam | adagrad | lion — mapped onto
    the native host kernels.
    """

    KINDS = {"adam": ("m", "v"), "adamw": ("m", "v"), "fusedadam": ("m", "v"),
             "adagrad": ("sum", ), "lion": ("m", )}

    def __init__(self, param_device="cpu", state_device="cpu", nvme_path=None,
                 optimizer="adam", opt_params=None, wire_dtype=BF16,
                 grad_accum_fp32=False):
        if optimizer not in self.KINDS:
            raise ValueError(
                f"host optimizer {optimizer!r} is not supported for "
                f"ZeRO-Infinity param streaming (have: "
                f"{sorted(self.KINDS)}); the native LAMB has no host kernel")
        self.param_device = param_device
        self.state_device = state_device
        self.optimizer = optimizer
        p = dict(opt_params or {})
        self.lr = p.get("lr", 1e-3)
        self.betas = tuple(p.get("betas", (0.9, 0.999) if "adam" in optimizer
                                 else (0.9, 0.99)))
        self.eps = p.get("eps", 1e-8)
        self.weight_decay = p.get("weight_decay", 0.0)
        self.adamw_mode = optimizer in ("adamw", "fusedadam") or \
            p.get("adam_w_mode", False)
        self.wire_dtype = np.dtype(wire_dtype)
        self.grad_accum_fp32 = grad_accum_fp32
        self.step_count = 0
        self._kernels = None

        self._meta = {}      # key → (treedef, shapes, sizes)
        self._master = {}    # key → flat fp32 (cpu mode)
        self._state = {}     # key → {kind: flat fp32} (cpu mode)
        self._cache = {}     # key → flat wire-dtype param cache (cpu mode)
        self._grads = {}     # key → flat stash (allocated on first arrival)
        self._swapper = None
        if "nvme" in (param_device, state_device):
            from ..swap_tensor import AsyncTensorSwapper
            base = nvme_path or os.path.join(tempfile.gettempdir(),
                                             "ds_tpu_infinity")
            swap_dir = os.path.join(str(base), "param_stream",
                                    f"rank{jax.process_index()}")
            self._swapper = AsyncTensorSwapper(swap_dir)
            log_dist(f"ZeRO-Infinity param streaming → {swap_dir}", ranks=[0])

    # ------------------------------------------------------------ install
    def install_group(self, key, host_tree):
        """Adopt a block's fp32 params; allocates moments + wire cache."""
        flat, meta = _flatten_f32(host_tree)
        self._meta[key] = meta
        cache = flat.astype(self.wire_dtype) \
            if self.wire_dtype != np.float32 else flat
        state = {k: np.zeros_like(flat) for k in self.KINDS[self.optimizer]}
        if self.state_device == "nvme":
            self._swapper.swap_out(f"{key}:master", flat)
            for k, s in state.items():
                self._swapper.swap_out(f"{key}:{k}", s)
        else:
            self._master[key] = flat
            self._state[key] = state
        if self.param_device == "nvme":
            self._swapper.swap_out(f"{key}:cache", cache)
            if self.wire_dtype == np.float32:
                # cache aliases master in RAM mode only; on NVMe they are
                # separate files, so nothing further to do
                pass
        else:
            self._cache[key] = cache

    def keys(self):
        return tuple(self._meta)

    def param_bytes(self, key):
        return sum(self._meta[key][2]) * self.wire_dtype.itemsize

    # ------------------------------------------------------------ fetch
    def start_fetch(self, key):
        h = _FetchHandle(key)
        if self.param_device == "nvme":
            h.aio_handle = self._swapper.swap_in(f"{key}:cache")
        return h

    def finish_fetch(self, handle, sharding=None):
        """Complete a fetch: host flat vector → device pytree (async put).
        ``sharding``: one jax Sharding applied to every leaf (the executor
        passes mesh-replicated so multi-device steps don't re-broadcast the
        block on every use).  Multi-process meshes assemble through
        ``make_array_from_callback`` — every host holds the same store
        bytes, so each process serves its addressable shards locally."""
        key = handle.key
        if handle.device_tree is not None:
            return handle.device_tree
        flat = (handle.aio_handle.wait() if handle.aio_handle is not None
                else self._cache[key])
        views = _views(flat, self._meta[key])
        if sharding is None:
            put = jax.device_put
        elif jax.process_count() > 1:
            put = (lambda v: jax.make_array_from_callback(
                v.shape, sharding, lambda idx: v[idx]))
        else:
            put = (lambda v: jax.device_put(v, sharding))
        tree = jax.tree_util.tree_map(put, views)
        handle.device_tree = tree
        return tree

    # ------------------------------------------------------------ grads
    def accumulate_grads(self, key, dev_grads):
        """Device grad pytree → host stash (one flat vector per block).
        Multi-process: grads are replicated post-GSPMD-reduce, but each
        process only addresses its shard of the replication — allgather
        them to full host values so every host steps identically."""
        if jax.process_count() > 1 and any(
                not getattr(l, "is_fully_replicated", True)
                for l in jax.tree_util.tree_leaves(dev_grads)):
            # GSPMD normally leaves block grads fully replicated (directly
            # addressable); anything else must gather to full host values
            from jax.experimental import multihost_utils
            dev_grads = multihost_utils.process_allgather(dev_grads)
        leaves = jax.tree_util.tree_leaves(dev_grads)
        for l in leaves:   # start all D2H copies before blocking on any
            if hasattr(l, "copy_to_host_async"):
                l.copy_to_host_async()
        treedef, shapes, sizes = self._meta[key]
        stash = self._grads.get(key)
        first = stash is None
        if first:
            dt = np.float32 if self.grad_accum_fp32 else self.wire_dtype
            stash = self._grads[key] = np.empty(sum(sizes), dt)
        off = 0
        for l, n in zip(leaves, sizes):
            host = np.asarray(l).ravel()
            if first:
                stash[off:off + n] = host
            else:
                # accumulate in the stash dtype (fp32 when gas > 1)
                stash[off:off + n] += host.astype(stash.dtype)
            off += n

    def grad_sq_norm(self):
        """Σ ‖g‖² over every stash (native kernel on an fp32 transient)."""
        from ...ops.cpu_optimizers import cpu_sq_norm
        total = 0.0
        for key, stash in self._grads.items():
            g = stash if stash.dtype == np.float32 else \
                np.ascontiguousarray(stash, dtype=np.float32)
            total += cpu_sq_norm(g)
        return total

    # ------------------------------------------------------------ step
    def _get_kernels(self):
        if self._kernels is None:
            from ...ops import cpu_optimizers as k
            if self.optimizer == "adagrad":
                self._kernels = k.DeepSpeedCPUAdagrad(
                    lr=self.lr, eps=self.eps, weight_decay=self.weight_decay)
            elif self.optimizer == "lion":
                self._kernels = k.DeepSpeedCPULion(
                    lr=self.lr, betas=self.betas,
                    weight_decay=self.weight_decay)
            else:
                self._kernels = k.DeepSpeedCPUAdam(
                    lr=self.lr, betas=self.betas, eps=self.eps,
                    weight_decay=self.weight_decay,
                    adamw_mode=self.adamw_mode)
        return self._kernels

    def optimizer_sweep(self, lr=None, grad_scale=None):
        """One host optimizer step over every block that received gradients.

        ``grad_scale``: optional multiplier folded into the grads (global-norm
        clip coefficient and/or 1/gas averaging).  Updates the wire-dtype
        cache in the same kernel pass (``bf16_out``) — the next device fetch
        streams the new weights without any HBM round-trip.
        """
        kern = self._get_kernels()
        self.step_count += 1
        for key in list(self._grads):
            stash = self._grads.pop(key)
            grad = stash if stash.dtype == np.float32 else \
                np.ascontiguousarray(stash, dtype=np.float32)
            if grad_scale is not None and grad_scale != 1.0:
                grad *= np.float32(grad_scale)
            if self.state_device == "nvme":
                master = self._swapper.swap_in(f"{key}:master",
                                               async_op=False).wait()
                state = {k: self._swapper.swap_in(f"{key}:{k}",
                                                  async_op=False).wait()
                         for k in self.KINDS[self.optimizer]}
            else:
                master, state = self._master[key], self._state[key]
            if self.wire_dtype == BF16:
                if self.param_device == "nvme":
                    cache = np.empty(master.size, BF16)
                else:
                    cache = self._cache[key]
                out = cache.view(np.uint16)
            else:
                cache, out = master, None   # fp32 wire: cache aliases master
            # the kernel wrapper auto-increments per CALL; every block of one
            # sweep must share ONE bias-correction step
            kern.step_count = self.step_count - 1
            if self.optimizer == "adagrad":
                kern.step(master, grad, state["sum"], bf16_out=out, lr=lr)
            elif self.optimizer == "lion":
                kern.step(master, grad, state["m"], bf16_out=out, lr=lr)
            else:
                kern.step(master, grad, state["m"], state["v"], bf16_out=out,
                          lr=lr)
            if self.state_device == "nvme":
                self._swapper.swap_out(f"{key}:master", master)
                for k, s in state.items():
                    self._swapper.swap_out(f"{key}:{k}", s)
            if self.param_device == "nvme":
                if self.wire_dtype == np.float32:
                    cache = master
                self._swapper.swap_out(f"{key}:cache", cache)
            elif self.wire_dtype == np.float32 and \
                    master is not self._cache.get(key):
                # fp32 wire + RAM param cache + NVMe state: the kernel
                # updated the freshly-swapped-in master, not the RAM cache
                # the next fetch reads — copy it back or training silently
                # freezes the device weights
                self._cache[key][:] = master
        if self._swapper is not None:
            # writes must be durable before the next step's reads
            self._swapper.synchronize()

    # ------------------------------------------------- checkpoint interface
    def export_master(self):
        """{key: fp32 host pytree} — consumed by checkpointing."""
        out = {}
        for key, meta in self._meta.items():
            if self.state_device == "nvme":
                flat = self._swapper.swap_in(f"{key}:master",
                                             async_op=False).wait()
            else:
                flat = self._master[key]
            out[key] = jax.tree_util.tree_map(np.copy, _views(flat, meta))
        return out

    def export_state(self):
        out = {"step_count": self.step_count, "kinds": {}}
        for key, meta in self._meta.items():
            if self.state_device == "nvme":
                st = {k: self._swapper.swap_in(f"{key}:{k}",
                                               async_op=False).wait()
                      for k in self.KINDS[self.optimizer]}
            else:
                st = self._state[key]
            out["kinds"][key] = {k: np.copy(v) for k, v in st.items()}
        return out

    def import_master(self, trees):
        for key, tree in trees.items():
            flat, meta = _flatten_f32(tree)
            self._meta[key] = meta
            cache = flat.astype(self.wire_dtype) \
                if self.wire_dtype != np.float32 else flat
            if self.state_device == "nvme":
                self._swapper.swap_out(f"{key}:master", flat)
            else:
                self._master[key] = flat
            if self.param_device == "nvme":
                self._swapper.swap_out(f"{key}:cache", cache)
            else:
                self._cache[key] = cache
        if self._swapper is not None:
            self._swapper.synchronize()

    def import_state(self, state):
        self.step_count = int(state["step_count"])
        for key, kinds in state["kinds"].items():
            flat_state = {k: np.ascontiguousarray(v, dtype=np.float32).ravel()
                          for k, v in kinds.items()}
            if self.state_device == "nvme":
                for k, v in flat_state.items():
                    self._swapper.swap_out(f"{key}:{k}", v)
            else:
                self._state[key] = flat_state
        if self._swapper is not None:
            self._swapper.synchronize()
