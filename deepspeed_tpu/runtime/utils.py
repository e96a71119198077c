"""Runtime utilities — analog of reference ``runtime/utils.py:1103``
(clip_grad_norm_, see_memory_usage, partition helpers)."""

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.logging import logger


def global_grad_norm(grads):
    """L2 norm over a gradient pytree.  Under pjit, sharded leaves still
    produce the *global* norm (GSPMD reduces across shards) — this replaces
    the reference's mpu-aware ``clip_grad_norm_`` (runtime/utils.py)."""
    leaves = [g for g in jax.tree_util.tree_leaves(grads) if g is not None]
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves))


def clip_grads_by_global_norm(grads, max_norm, norm=None):
    """Scale grads so that global norm ≤ max_norm; returns (grads, norm).
    Non-finite norms leave grads unscaled (overflow path handles skipping)."""
    if norm is None:
        norm = global_grad_norm(grads)
    clip_coef = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    clip_coef = jnp.where(jnp.isfinite(clip_coef), clip_coef, 1.0)
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * clip_coef).astype(g.dtype), grads), norm


def partition_uniform(num_items, num_parts):
    """Reference ``partition_uniform``: balanced contiguous split boundaries."""
    parts = [0] * (num_parts + 1)
    chunk = num_items // num_parts
    residual = num_items % num_parts
    for p in range(num_parts):
        parts[p + 1] = parts[p] + chunk + (1 if p < residual else 0)
    return parts


def partition_balanced(weights, num_parts):
    """Reference ``partition_balanced``: split so max part weight is minimized
    (prefix-sum + binary search).  Weights should be positive integers (the
    limit search is integral) — scale float weights up first."""
    n = len(weights)
    prefix = np.concatenate([[0], np.cumsum(weights)])

    def can(limit):
        parts, last, count = [0], 0, 0
        for i in range(1, n + 1):
            if prefix[i] - prefix[last] > limit:
                if i - 1 == last:
                    return None
                parts.append(i - 1)
                last = i - 1
                count += 1
                if count >= num_parts:
                    return None
        parts.append(n)
        return parts if len(parts) <= num_parts + 1 else None

    lo = max(weights) if n else 0
    hi = int(prefix[-1]) or 1
    best = None
    while lo <= hi:
        mid = (lo + hi) // 2
        p = can(mid)
        if p is not None:
            best = p
            hi = mid - 1
        else:
            lo = mid + 1
    if best is None:
        return partition_uniform(n, num_parts)
    # pad to exactly num_parts+1 boundaries
    while len(best) < num_parts + 1:
        best.append(n)
    if n >= num_parts:
        # The greedy packer may use fewer parts than requested, leaving
        # empty trailing parts (repeated boundaries) — an empty PIPELINE
        # STAGE downstream.  Borrow one item from the left neighbor for
        # each empty part, back to front: the shrunken neighbor can only
        # get lighter and the new 1-item part weighs ≤ max(weights) ≤ the
        # found bottleneck, so optimality is preserved.
        for i in range(num_parts - 1, 0, -1):
            if best[i] >= best[i + 1]:
                best[i] = best[i + 1] - 1
    return best


def memory_usage_snapshot():
    """The accelerator ``memory_stats()`` dict distilled to the figures
    the HBM accounting reports everywhere (step records, gauges,
    :func:`see_memory_usage`): live/peak/limit bytes plus a fragmentation
    estimate — 1 − largest_free_block / free when the backend exposes the
    largest contiguous block (XLA's BFC allocator does), else None."""
    from ..accelerator import get_accelerator
    stats = get_accelerator().memory_stats() or {}
    live = int(stats.get("bytes_in_use", 0))
    peak = int(stats.get("peak_bytes_in_use", live))
    limit = int(stats.get("bytes_limit", 0))
    free = max(0, limit - live)
    largest = stats.get("largest_free_block_bytes")
    frag = None
    if largest is not None and free > 0:
        frag = max(0.0, 1.0 - float(largest) / free)
    return {"live_bytes": live, "peak_bytes": peak, "limit_bytes": limit,
            "free_bytes": free, "fragmentation": frag}


def see_memory_usage(message, force=False):
    """Reference ``see_memory_usage``: device memory snapshot — live,
    peak, limit and fragmentation (bytes_in_use vs bytes_limit via the
    largest free block) from the accelerator ``memory_stats()`` dict, not
    just the two raw allocation fields.  Routed through the telemetry
    metrics registry when the spine is enabled."""
    if not force:
        return
    snap = memory_usage_snapshot()
    gib = 1024**3
    frag = (f" frag: {snap['fragmentation']:.1%}"
            if snap["fragmentation"] is not None else "")
    limit = (f" limit: {snap['limit_bytes'] / gib:.2f}GB "
             f"free: {snap['free_bytes'] / gib:.2f}GB"
             if snap["limit_bytes"] else "")
    logger.info(f"{message} | device alloc: {snap['live_bytes'] / gib:.2f}GB "
                f"peak: {snap['peak_bytes'] / gib:.2f}GB{limit}{frag}")
    from .. import telemetry
    if telemetry.enabled:
        for key in ("live_bytes", "peak_bytes", "limit_bytes"):
            g = telemetry.gauge(f"hbm/{key}",
                                help="see_memory_usage device snapshot")
            if g is not None:
                g.set(snap[key])
        if snap["fragmentation"] is not None:
            g = telemetry.gauge("hbm/fragmentation",
                                help="1 - largest_free_block / free")
            if g is not None:
                g.set(snap["fragmentation"])
    return snap


def count_parameters(params):
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


def ensure_directory_exists(filename):
    import os
    os.makedirs(os.path.dirname(os.path.abspath(filename)), exist_ok=True)


def make_scaled_loss_fn(apply_fn, gas, device_counts=False):
    """The one loss-scaling convention shared by every micro-step variant
    (GSPMD, qgZ manual-SPMD, 1-bit local-grad): scale for fp16, divide by GAS
    (reference engine.backward :2023), return (scaled, raw) for has_aux.
    ``device_counts``: the model returns ``(loss, counts)`` and the counts
    leave beside the loss, as ``(scaled, (raw, counts))``."""

    def loss_fn(params, scale, inputs):
        out = apply_fn(params, *inputs)
        loss = out[0] if isinstance(out, (tuple, list)) else out
        aux = (loss, out[1]) if device_counts else loss
        return loss.astype(jnp.float32) * scale / gas, aux

    return loss_fn


def batch_input_specs(inputs, axes, n_replicated_tail=0):
    """shard_map in_specs for a micro-step's batch inputs: leading dim
    sharded over the dp ``axes``, except the last ``n_replicated_tail``
    inputs which are REPLICATED (engine-appended extras that aren't
    per-sample data — e.g. PLD's theta scalar and rng key)."""
    from jax.sharding import PartitionSpec as P
    n = len(inputs)
    return tuple(
        P() if i >= n - n_replicated_tail
        else P(*([axes] + [None] * (x.ndim - 1)))
        for i, x in enumerate(inputs))


def load_16bit_npz(path):
    """Reload a :meth:`DeepSpeedEngine.save_16bit_model` export: bf16 leaves
    (stored as uint16 raw views, names under ``__bf16__``) come back as
    ml_dtypes.bfloat16 arrays; everything else as saved."""
    import ml_dtypes
    import numpy as onp
    with onp.load(path) as data:
        bf16 = (set(str(n) for n in data["__bf16__"])
                if "__bf16__" in data.files else set())
        return {n: (data[n].view(ml_dtypes.bfloat16) if n in bf16
                    else data[n])
                for n in data.files if n != "__bf16__"}
