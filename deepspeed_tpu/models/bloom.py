"""Bloom — ALiBi-attention causal LM (reference ``module_inject/containers/
bloom.py`` serves it via v1 kernel injection; Bloom is NOT in the FastGen
model list, so here too it serves through the v1 ``init_inference`` engine).

Layout notes (HF ``modeling_bloom``):
* fused ``query_key_value`` projects to head-interleaved ``[H, 3, Dh]`` —
  the flax module keeps exactly that layout so checkpoint ingest is a plain
  transpose;
* ALiBi replaces positional embeddings: per-head slope × key position added
  to the attention scores (the softmax-invariant form of −slope·distance);
* embeddings pass through a LayerNorm, and the LM head is always tied.
"""

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..runtime.activation_checkpointing import resolve_policy


@dataclass(frozen=True)
class BloomConfig:
    vocab_size: int = 250880
    hidden_size: int = 64
    num_hidden_layers: int = 2
    num_attention_heads: int = 8
    layer_norm_epsilon: float = 1e-5
    apply_residual_connection_post_layernorm: bool = False
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing_saveable"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def bloom_tiny(**overrides):
    return BloomConfig(**{**dict(vocab_size=256, hidden_size=64,
                                 num_hidden_layers=2,
                                 num_attention_heads=4), **overrides})


def alibi_slopes(n_heads):
    """Per-head ALiBi slopes (the published recipe: powers of
    2^(−8/n) for the closest power of two, interleaved extras beyond)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if np.log2(n_heads).is_integer():
        return np.asarray(pow2_slopes(n_heads), np.float32)
    closest = 2 ** int(np.floor(np.log2(n_heads)))
    extra = pow2_slopes(2 * closest)[0::2][:n_heads - closest]
    return np.asarray(pow2_slopes(closest) + extra, np.float32)


class BloomBlock(nn.Module):
    config: BloomConfig

    @nn.compact
    def __call__(self, x, decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        H, Dh = cfg.num_attention_heads, cfg.head_dim
        ln = partial(nn.LayerNorm, epsilon=cfg.layer_norm_epsilon,
                     dtype=dtype, param_dtype=jnp.float32)
        dense = partial(nn.Dense, dtype=dtype, param_dtype=jnp.float32)
        slopes = jnp.asarray(alibi_slopes(H))

        h = ln(name="input_layernorm")(x)
        qkv = dense(3 * D, name="query_key_value")(h)
        qkv = qkv.reshape(B, S, H, 3, Dh)          # HF head-interleaved
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]

        if decode:
            from .cache import decode_attention, kv_cache_update
            k, v, start = kv_cache_update(self, k, v)
            attn = decode_attention(q, k, v, start, alibi_slopes=slopes)
        else:
            from ..ops.attention import attention_core
            attn = attention_core(q, k, v, causal=True,
                                  alibi_slopes=slopes)
        attn_out = dense(D, name="dense")(attn.reshape(B, S, D))

        residual = h if cfg.apply_residual_connection_post_layernorm else x
        x = residual + attn_out

        h2 = ln(name="post_attention_layernorm")(x)
        mlp = dense(D, name="dense_4h_to_h")(
            nn.gelu(dense(4 * D, name="dense_h_to_4h")(h2)))
        residual2 = h2 if cfg.apply_residual_connection_post_layernorm else x
        return residual2 + mlp


class BloomModel(nn.Module):
    """Causal LM.  ``__call__(input_ids, labels=None)`` → loss if labels
    given else logits (tied LM head — Bloom checkpoints never carry one)."""
    config: BloomConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None,
                 decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, dtype=dtype,
                         name="word_embeddings")
        x = embed(input_ids)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=dtype,
                         param_dtype=jnp.float32,
                         name="word_embeddings_layernorm")(x)
        block = BloomBlock
        if cfg.remat and not decode:
            policy = resolve_policy(cfg.remat_policy)
            block = nn.remat(BloomBlock, policy=policy, static_argnums=(2, ))
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, name=f"h_{i}")(x, decode)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=dtype,
                         param_dtype=jnp.float32, name="ln_f")(x)
        logits = embed.attend(x.astype(jnp.float32))
        if labels is None:
            return logits
        from ..sequence.cross_entropy import softmax_cross_entropy_with_logits
        loss = softmax_cross_entropy_with_logits(logits[:, :-1], labels[:, 1:])
        if attention_mask is not None:
            m = attention_mask[:, 1:].astype(jnp.float32)
            return jnp.sum(loss * m) / jnp.maximum(jnp.sum(m), 1.0)
        return jnp.mean(loss)


def tp_rules(config: BloomConfig):
    return {
        "query_key_value/kernel": P(None, ("tp", "zero")),
        "dense/kernel": P(("tp", "zero"), None),
        "dense_h_to_4h/kernel": P(None, ("tp", "zero")),
        "dense_4h_to_h/kernel": P(("tp", "zero"), None),
        "word_embeddings/embedding": P(("tp", "zero"), None),
    }
