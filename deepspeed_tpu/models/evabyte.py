"""EvaByte: a byte-level language model (vocabulary 320) whose attention is
exact inside a window and reads chunk summaries beyond it (EVA: Zheng et al.,
"Efficient Attention via Control Variates", ICLR 2023; the EvaByte release).

The layer, per head, with ``W = window_size``, ``C = chunk_size``,
``s = head_dim ** -0.5``:

* ``h = RMSNorm(x)`` with weight ``1 + g`` (``norm_add_unit_offset``);
  ``q_t, k_t = RoPE_t(h_t Wq), RoPE_t(h_t Wk)``, ``v_t = h_t Wv``;
* every COMPLETE chunk ``j`` (positions ``jC .. jC + C - 1``) is summarised by
  two learned vectors ``phi, mu [heads, head_dim]``: ``a = softmax_m(k_m .
  phi)`` over the chunk's positions, ``k~_j = sum_m a_m k_m + mu``,
  ``v~_j = sum_m a_m v_m``;
* the query at ``t`` (window ``w = t // W``) reads, under ONE softmax, the
  exact keys ``wW <= m <= t`` of its own window and the summaries of every
  chunk of EARLIER windows.  Windows do not slide; inside the first window
  this is plain causal attention;
* SwiGLU MLP; after the last layer RMSNorm and ONE matrix ``[hidden,
  num_pred_heads * vocab]``: columns ``vocab * i ..`` are head ``i``, which
  predicts byte ``t + 1 + i``.  Head 0 is the next-byte distribution.

``EvaByteModel`` is the dense forward (the two kinds of key as masks over one
score matrix): training at small size and the tests.  Serving is
``inference/v2/ragged_forward.evabyte_ragged_step`` over the paged cache
(``inference/v2/ragged.py`` holds the window-plus-summary layout).  The
residual stream, the softmax and the logits are float32 (the published
``fp32_skip_add``, ``mixedp_attn``, ``fp32_logits``).  The model's own
multi-byte self-speculative decoding is a decoding strategy, not part of the
forward pass, and is not here (ROADMAP.md).
"""

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..runtime.activation_checkpointing import resolve_policy
from ..telemetry import names as _names
from .llama import LlamaMLP, _rope_freqs, apply_rotary


@dataclass(frozen=True)
class EvaByteConfig:
    """The keys of the published ``config.json`` by their own names."""
    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    tie_word_embeddings: bool = False
    attention_class: str = "eva"
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    norm_add_unit_offset: bool = True
    hidden_act: str = "silu"
    attention_bias: bool = False
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing_saveable"

    def __post_init__(self):
        if self.attention_class != "eva" or self.hidden_act != "silu" \
                or self.attention_bias \
                or self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "EvaByteConfig: attention_class 'eva', silu, no attention "
                "bias and as many key/value heads as heads are what this "
                "model implements")
        if self.window_size % self.chunk_size:
            raise ValueError("window_size is whole chunks")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    rope_scaling = None
    sliding_window = 0     # windows do not slide: the cache layout holds them


def evabyte_tiny(**overrides):
    """Test-scale config: 4 windows of 8 chunks fit in 128 positions."""
    return EvaByteConfig(**{**dict(
        vocab_size=64, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=256, window_size=32, chunk_size=4,
        num_pred_heads=2), **overrides})


class OffsetRMSNorm(nn.Module):
    """RMSNorm whose weight is ``1 + g`` (``norm_add_unit_offset``).  The
    offset ``g`` is kept as a column ``[D, 1]``: a trained ``g`` lies near 0,
    and a generator of seeded weights that sets every vector to ones and
    draws every matrix with std 1 / sqrt(rows) (``perfbench/weights.py``)
    then gives ``1 + g`` near 1 instead of 2 (a norm that doubles its input
    makes the attention scores' spread 4 and the attention nearly one-hot,
    which no checkpoint does: PERF.md section 6, PR 27)."""
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    unit_offset: bool = True

    @nn.compact
    def __call__(self, x):
        g = self.param("weight", nn.initializers.zeros if self.unit_offset
                       else nn.initializers.ones, (x.shape[-1], 1))[:, 0]
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        w = 1.0 + g if self.unit_offset else g
        return (x32 * jax.lax.rsqrt(var + self.eps) * w).astype(self.dtype)


def chunk_summaries(k, v, phi, mu, chunk):
    """Summaries of the complete chunks of ``k, v [..., S, H, Dh]`` (keys
    after rotary): ``[..., S // chunk, H, Dh]`` each, float32."""
    *lead, s, h, dh = k.shape
    n = s // chunk
    kc = k[..., :n * chunk, :, :].reshape(*lead, n, chunk, h, dh) \
        .astype(jnp.float32)
    vc = v[..., :n * chunk, :, :].reshape(*lead, n, chunk, h, dh) \
        .astype(jnp.float32)
    a = jax.nn.softmax(
        jnp.einsum("...nchd,hd->...nch", kc, phi.astype(jnp.float32)),
        axis=-2)
    ks = jnp.einsum("...nch,...nchd->...nhd", a, kc) + mu.astype(jnp.float32)
    vs = jnp.einsum("...nch,...nchd->...nhd", a, vc)
    return ks, vs


def eva_attention(q, k, v, phi, mu, window, chunk):
    """q, k, v: [B, S, H, Dh] (after rotary) -> [B, S, H, Dh] float32: one
    softmax over the exact keys of the query's own window up to itself and
    the summaries of every chunk of earlier windows."""
    s = q.shape[1]
    scale = q.shape[-1] ** -0.5
    q32 = q.astype(jnp.float32)
    t = jnp.arange(s)
    exact = jnp.einsum("bqhd,bkhd->bhqk", q32, k.astype(jnp.float32)) * scale
    see = (t[None, :] <= t[:, None]) & \
        (t[None, :] // window == t[:, None] // window)
    exact = jnp.where(see[None, None], exact, -jnp.inf)
    ks, vs = chunk_summaries(k, v, phi, mu, chunk)
    n = ks.shape[1]
    if n:
        far = jnp.einsum("bqhd,bnhd->bhqn", q32, ks) * scale
        closed = (jnp.arange(n)[None, :] * chunk) // window < \
            t[:, None] // window
        far = jnp.where(closed[None, None], far, -jnp.inf)
        scores = jnp.concatenate([far, exact], axis=-1)
        values = jnp.concatenate([vs, v.astype(jnp.float32)], axis=1)
    else:
        scores, values = exact, v.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, values)


class EvaAttention(nn.Module):
    config: EvaByteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        H, Dh = cfg.num_attention_heads, cfg.head_dim
        qkv = partial(nn.DenseGeneral, features=(H, Dh), use_bias=False,
                      dtype=dtype, param_dtype=jnp.float32)
        q, k, v = (qkv(name=n)(x) for n in ("q_proj", "k_proj", "v_proj"))
        vec = partial(self.param, shape=(H, Dh), dtype=jnp.float32)
        phi = vec("eva_phi", nn.initializers.normal(Dh ** -0.5))
        mu = vec("eva_mu", nn.initializers.normal(Dh ** -0.5))
        cos, sin = _rope_freqs(Dh, cfg.max_position_embeddings,
                               cfg.rope_theta)
        cos, sin = jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)
        q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
        out = eva_attention(q, k, v, phi, mu, cfg.window_size,
                            cfg.chunk_size).astype(dtype)
        return nn.DenseGeneral(features=D, axis=-1, use_bias=False,
                               dtype=dtype, param_dtype=jnp.float32,
                               name="o_proj")(out.reshape(B, S, H * Dh))


class EvaByteBlock(nn.Module):
    config: EvaByteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = partial(OffsetRMSNorm, cfg.rms_norm_eps, jnp.dtype(cfg.dtype),
                       cfg.norm_add_unit_offset)
        # the residual stream stays float32 (fp32_skip_add)
        x = x + EvaAttention(cfg, name="self_attn")(
            norm(name="input_layernorm")(x)).astype(jnp.float32)
        return x + LlamaMLP(cfg, name="mlp")(
            norm(name="post_attention_layernorm")(x)).astype(jnp.float32)


def multi_byte_loss(logits, labels, attention_mask=None):
    """Mean over the prediction heads of the cross-entropy of head ``i``
    against byte ``t + 1 + i``.  logits: [B, S, heads, V]."""
    from ..sequence.cross_entropy import softmax_cross_entropy_with_logits
    s, heads = logits.shape[1], logits.shape[2]
    total = 0.0
    for i in range(min(heads, s - 1)):
        loss = softmax_cross_entropy_with_logits(
            logits[:, :s - 1 - i, i], labels[:, 1 + i:])
        if attention_mask is not None:
            m = attention_mask[:, 1 + i:].astype(jnp.float32)
            total += jnp.sum(loss * m) / jnp.maximum(jnp.sum(m), 1.0)
        else:
            total += jnp.mean(loss)
    return total / min(heads, s - 1)


class EvaByteModel(nn.Module):
    """``__call__(input_ids, labels=None)`` -> the multi-byte loss if labels
    are given, else the logits ``[B, S, num_pred_heads, vocab]`` (float32)."""
    config: EvaByteConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        with jax.named_scope(_names.SCOPE_EMBED):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, dtype=dtype,
                         name="embed_tokens")(input_ids).astype(jnp.float32)
        block = EvaByteBlock
        if cfg.remat:
            block = nn.remat(EvaByteBlock, policy=resolve_policy(
                cfg.remat_policy))
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, name=f"layers_{i}")(x)
        x = OffsetRMSNorm(cfg.rms_norm_eps, dtype, cfg.norm_add_unit_offset,
                          name="norm")(x)
        with jax.named_scope(_names.SCOPE_LM_HEAD_LOSS):
            logits = nn.Dense(cfg.num_pred_heads * cfg.vocab_size,
                              use_bias=False, dtype=jnp.float32,
                              param_dtype=jnp.float32, name="lm_head")(
                                  x.astype(jnp.float32))
            logits = logits.reshape(*logits.shape[:-1], cfg.num_pred_heads,
                                    cfg.vocab_size)
            if labels is None:
                return logits
            return multi_byte_loss(logits, labels, attention_mask)


def tp_rules(config: Optional[EvaByteConfig] = None):
    """AutoTP-style sharding rules (``models/llama.tp_rules``), plus the two
    EVA vectors on their head dimension."""
    from .llama import tp_rules as llama_rules
    rules = dict(llama_rules(config))
    rules["eva_phi"] = P("tp", None)
    rules["eva_mu"] = P("tp", None)
    return rules
