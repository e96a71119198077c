"""Diffusers/CLIP attention injection — TPU analog of the reference's
``generic_injection`` (``module_inject/replace_module.py:88``).

The reference swaps torch-diffusers ``CrossAttention`` /
``BasicTransformerBlock`` instances for fused CUDA modules
(``DeepSpeedDiffusersAttention``) and wraps the CLIP text encoder
(``DSClipEncoder``) for stable-diffusion inference.  Flax modules are
immutable, so the TPU mechanism is an **interceptor** instead of a module
swap: ``flax.linen.intercept_methods`` redirects matching modules'
``__call__`` to a fused path that runs q/k/v/out through the module's own
Dense submodules and the attention math through ``ops.attention_core``
(Pallas flash on TPU) — same weights, fused kernel, no tree surgery.

Matched out of the box (by class name + submodule layout):

* ``FlaxAttention`` / ``FlaxCrossAttention`` — flax-diffusers UNet/VAE
  attention (``query``/``key``/``value``/``proj_attn``);
* ``FlaxCLIPAttention`` — transformers' Flax CLIP text/vision encoder
  (``q_proj``/``k_proj``/``v_proj``/``out_proj``, causal for text).

Out-of-scope and deliberately NOT faked: the torch-diffusers pipeline
path (torch in this stack is CPU-only — a torch module swap would not
touch the TPU), and CUDA-graph wrapping (XLA jit covers whole-program
capture).

Usage::

    with generic_injection():              # or fused_attention()
        out = flax_pipe(...)               # matching attentions run fused
"""

import contextlib

import numpy as np

import jax.numpy as jnp

from ..ops.attention import attention_core
from ..utils.logging import logger

# class name → submodule layout of the attention to fuse.  ``arg1`` names
# the meaning of the second POSITIONAL argument (diffusers passes the
# cross-attention ``context`` there; transformers passes the padding mask).
DEFAULT_POLICIES = {
    # "scale": attribute names to probe for the softmax scale, part of the
    # per-class policy (ADVICE r3) — a class whose scale lives under another
    # name must say so here rather than silently computing with D**-0.5
    "FlaxAttention": dict(q="query", k="key", v="value", out="proj_attn",
                          heads=("heads", ), returns_tuple=False,
                          arg1="context", scale=("scale", )),
    "FlaxCrossAttention": dict(q="query", k="key", v="value",
                               out="proj_attn", heads=("heads", ),
                               returns_tuple=False, arg1="context",
                               scale=("scale", )),
    "FlaxCLIPAttention": dict(q="q_proj", k="k_proj", v="v_proj",
                              out="out_proj",
                              heads=("num_heads", "heads"),
                              returns_tuple=True, arg1="attention_mask",
                              scale=("scale", )),
}

# any of these kwargs being non-None means cross-attention / kv-from-
# elsewhere — always the module's own implementation
_CROSS_KWARGS = ("context", "encoder_hidden_states", "key_value_states")


def _fused_call(mod, pol, hidden, counter):
    B, S, _ = hidden.shape
    heads = None
    for attr in pol["heads"]:
        heads = getattr(mod, attr, None)
        if heads is not None:
            break
    q = getattr(mod, pol["q"])(hidden)
    k = getattr(mod, pol["k"])(hidden)
    v = getattr(mod, pol["v"])(hidden)
    Dh = q.shape[-1] // heads
    q = q.reshape(B, S, heads, Dh)
    k = k.reshape(B, S, heads, Dh)
    v = v.reshape(B, S, heads, Dh)
    causal = bool(getattr(mod, "causal", False))
    scale = None
    for attr in pol.get("scale", ("scale", )):
        scale = getattr(mod, attr, None)
        if scale is not None:
            break
    out = attention_core(q, k, v, causal=causal, softmax_scale=scale)
    out = out.reshape(B, S, heads * Dh)
    out = getattr(mod, pol["out"])(out)
    if counter is not None:
        counter[0] += 1
    return (out, ) if pol["returns_tuple"] else out


def make_interceptor(policies=None, counter=None, assume_full_mask=False):
    """A flax method interceptor routing matching attention modules through
    the fused path.  Falls back to the original implementation when the
    call is cross-attention (``context``/``encoder_hidden_states`` present,
    positionally or by kwarg), asks for attention weights (flash never
    materializes them), or carries a padding mask that is not provably a
    no-op.

    ``assume_full_mask``: treat ANY provided padding mask as all-ones.
    Under ``jax.jit`` the mask is a tracer whose values can't be inspected,
    so the safe default falls back — callers who know their batches carry
    no padding set this to keep the fused path inside jit."""
    policies = dict(DEFAULT_POLICIES if policies is None else policies)

    def _mask_blocks_fusion(mask):
        """True → fall back.  A concrete all-ones padding mask is a no-op
        (the transformers default); anything else — real padding, a traced
        mask whose values we can't inspect, an additive bias — keeps the
        module's own implementation (unless assume_full_mask)."""
        if mask is None:
            return False
        if assume_full_mask:
            return False
        try:
            return not bool((np.asarray(mask) == 1).all())
        except Exception:  # traced / non-concrete
            return True

    def interceptor(next_fun, args, kwargs, context):
        pol = policies.get(type(context.module).__name__)
        if pol is None or context.method_name != "__call__":
            return next_fun(*args, **kwargs)
        if any(kwargs.get(kw) is not None for kw in _CROSS_KWARGS):
            return next_fun(*args, **kwargs)  # cross-attention
        arg1 = args[1] if len(args) > 1 else None
        if pol["arg1"] == "context":
            if arg1 is not None:
                return next_fun(*args, **kwargs)  # positional context
            mask = None
        else:
            mask = arg1 if arg1 is not None else kwargs.get("attention_mask")
        if kwargs.get("output_attentions") or _mask_blocks_fusion(mask):
            return next_fun(*args, **kwargs)
        # training-mode attention dropout lives in the module's own path —
        # the fused kernel has none, so non-deterministic calls with a
        # nonzero rate keep the original implementation
        rate = getattr(context.module, "dropout", 0.0)
        det = args[2] if len(args) > 2 else kwargs.get("deterministic", True)
        if isinstance(rate, (int, float)) and rate > 0 and not det:
            return next_fun(*args, **kwargs)
        hidden = args[0] if args else kwargs.get("hidden_states")
        if hidden is None:
            return next_fun(*args, **kwargs)
        try:
            return _fused_call(context.module, pol, hidden, counter)
        except Exception as e:  # unexpected layout → original path, loudly
            logger.warning(
                "fused attention injection failed for %s (%s: %s) — "
                "running the module's own implementation",
                type(context.module).__name__, type(e).__name__, e)
            return next_fun(*args, **kwargs)

    return interceptor


@contextlib.contextmanager
def fused_attention(policies=None, counter=None, assume_full_mask=False):
    """Context manager: flax applies inside run matching attentions fused.
    Set ``assume_full_mask=True`` to keep the fused path under ``jax.jit``
    when batches carry no padding (traced masks can't be inspected)."""
    import flax.linen as nn
    with nn.intercept_methods(
            make_interceptor(policies, counter, assume_full_mask)):
        yield


def generic_injection(module=None, dtype=None, enable_cuda_graph=None,
                      policies=None, assume_full_mask=False):
    """Reference-parity entry (``replace_module.py:88``).  Returns the
    :func:`fused_attention` context manager — flax pipelines are applied
    *inside* it (immutability forbids the reference's in-place swap).
    ``module``/``enable_cuda_graph`` are accepted for signature parity;
    whole-program capture is XLA jit's job on TPU."""
    if dtype is not None and jnp.dtype(dtype) not in (jnp.dtype(jnp.float16),
                                                      jnp.dtype(jnp.bfloat16),
                                                      jnp.dtype(jnp.float32)):
        raise ValueError(f"unsupported dtype {dtype}")
    return fused_attention(policies, assume_full_mask=assume_full_mask)
