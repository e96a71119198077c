"""Attention core — dispatch layer for the attention kernels.

Role of the reference's fused attention kernels (``csrc/transformer/inference``
softmax/attention ops and the FastGen blocked flash, SURVEY.md §2.2): a single
entry point the models call; on TPU it routes to the Pallas flash-attention
kernel, elsewhere (CPU tests) to a plain XLA implementation that compiles to
the same math.  The choice is made from the platform, never from an
``except``.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _xla_attention(q, k, v, causal=True, softmax_scale=None, window=0,
                   alibi_slopes=None):
    """Reference XLA path [B, S, H, D] (fp32 softmax accumulation)."""
    B, S, H, D = q.shape
    scale = softmax_scale if softmax_scale is not None else D**-0.5
    logits = jnp.einsum("bshd,bthd->bhst", q, k) * scale
    if alibi_slopes is not None:
        # ALiBi (softmax-invariant form: + slope_h * key_pos) in fp32 —
        # bf16 quantizes slope*position to useless resolution past ~256
        # (and the decode path computes it in fp32; they must agree).
        # Slopes are positional constants, never trained (matches the
        # flash kernel's stop_gradient).
        logits = logits.astype(jnp.float32)
        sl = jax.lax.stop_gradient(jnp.asarray(alibi_slopes, jnp.float32))
        logits = logits + sl[None, :, None, None] \
            * jnp.arange(k.shape[1], dtype=jnp.float32)[None, None, None, :]
    if causal:
        Sk = k.shape[1]
        mask = jnp.tril(jnp.ones((S, Sk), dtype=bool), k=Sk - S)
        if window:
            # sliding window: each query sees only the last `window` keys
            mask &= ~jnp.tril(jnp.ones((S, Sk), dtype=bool),
                              k=Sk - S - window)
        logits = jnp.where(mask[None, None], logits,
                           jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


def _per_device(kernel, q, k, v, split_heads=True):
    """Run ``kernel(q, k, v)`` ([B, S, H, D] operands) as a per-device
    program under whatever mesh is in force.

    A Mosaic kernel is a single-device program: XLA cannot partition it, and
    lowering one inside a multi-device GSPMD ``jit`` raises ("Mosaic kernels
    cannot be automatically partitioned").  So under the global mesh the
    kernel runs inside a ``shard_map`` that is manual over EVERY mesh axis
    (Mosaic accepts nothing less): batch split over the data-parallel axes,
    heads over ``tp`` (unless ``split_heads`` is off — per-head constants
    closed over by the kernel), each only where it divides; an axis that
    does not divide replicates the work instead of failing.  Inside a
    region that is already manual over some axes (the fused pipeline
    program) the nested ``shard_map`` targets the context mesh and takes
    the remaining axes; inside a fully manual region the call is
    per-device already."""
    from ..utils import groups
    cur = jax.sharding.get_abstract_mesh()
    manual = frozenset(cur.manual_axes)
    if manual:
        mesh = cur
    elif groups.mesh_is_initialized():
        mesh = groups.get_global_mesh()
    else:
        return kernel(q, k, v)
    remaining = frozenset(mesh.axis_names) - manual
    if mesh.size == 1 or not remaining:
        return kernel(q, k, v)

    def axes_dividing(axes, *dims):
        axes = tuple(a for a in axes if a in remaining)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return axes if n > 1 and all(d % n == 0 for d in dims) else None

    spec = P(axes_dividing(groups.dp_axes(), q.shape[0]), None,
             axes_dividing((groups.TP_AXIS, ), q.shape[2], k.shape[2])
             if split_heads else None, None)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=remaining,
                         check_vma=False)(q, k, v)


def attention_core(q, k, v, causal=True, softmax_scale=None, window=0,
                   alibi_slopes=None):
    """[B, S, H, D] attention; flash kernel on TPU, XLA elsewhere.
    ``window`` > 0 = sliding-window causal attention (Mistral)."""
    if window and not causal:
        # validate BEFORE dispatch: the flash path rejects this combination
        # and the XLA path used to silently ignore the window — both
        # backends must fail identically (round-2 advisor finding)
        raise ValueError("window > 0 requires causal=True (sliding-window "
                         "attention is defined over causal positions)")
    from ._use_kernels import use_pallas_kernels
    if use_pallas_kernels():
        # a kernel that fails to build or compile RAISES: trading the flash
        # kernel for O(S²)-memory XLA attention behind a log line is how a
        # chip run ends up measuring the wrong program
        from .pallas.flash_attention import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q,
                                             flash_attention)
        return _per_device(partial(
            flash_attention, causal=causal, softmax_scale=softmax_scale,
            window=window, alibi_slopes=alibi_slopes,
            block_q=int(os.environ.get("DS_TPU_FLASH_BLOCK_Q",
                                       DEFAULT_BLOCK_Q)),
            block_k=int(os.environ.get("DS_TPU_FLASH_BLOCK_K",
                                       DEFAULT_BLOCK_K))), q, k, v,
            split_heads=alibi_slopes is None)
    return _xla_attention(q, k, v, causal=causal, softmax_scale=softmax_scale,
                          window=window, alibi_slopes=alibi_slopes)
