"""Falcon-family model (TPU-first flax implementation).

Covers the reference's Falcon support (FastGen impl
``inference/v2/model_implementations/falcon/``): the architecture differs
from Llama in load-bearing ways —

* **parallel block** (falcon-7b ``parallel_attn``): attention and MLP both
  read the SAME layernormed input and their outputs add into the residual
  together (one LN per block; the 40b "new decoder architecture" uses two
  parallel LNs ``ln_attn``/``ln_mlp``);
* LayerNorm (with bias), not RMSNorm;
* fused ``query_key_value`` projection with three layouts (interleaved
  per-head / multi-query / grouped) — handled at checkpoint ingest;
* MLP is a plain GELU 4× expansion (no gating).

Rotary is NeoX-style (same convention as :mod:`deepspeed_tpu.models.llama`);
alibi variants are not supported (rejected at ingest).
"""

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..runtime.activation_checkpointing import resolve_policy
from .llama import _rope_freqs, apply_rotary


@dataclass(frozen=True)
class FalconConfig:
    vocab_size: int = 65024
    hidden_size: int = 4544
    num_hidden_layers: int = 32
    num_attention_heads: int = 71
    num_kv_heads: int = 1          # multi-query default (falcon-7b)
    ffn_hidden_size: int = None    # None → 4*hidden
    max_position_embeddings: int = 2048
    layer_norm_epsilon: float = 1e-5
    rope_theta: float = 10000.0
    new_decoder_architecture: bool = False  # 40b: parallel ln_attn/ln_mlp
    parallel_attn: bool = True
    bias: bool = False             # linear-layer biases (older variants)
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "nothing_saveable"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def num_key_value_heads(self):
        """Llama-family naming alias (the v2 engine sizes the paged KV cache
        through this)."""
        return self.num_kv_heads

    @property
    def ffn_size(self):
        return self.ffn_hidden_size or 4 * self.hidden_size


def falcon_tiny(**overrides):
    return FalconConfig(**{**dict(vocab_size=256, hidden_size=64,
                                  num_hidden_layers=2,
                                  num_attention_heads=4, num_kv_heads=1,
                                  max_position_embeddings=128),
                           **overrides})


class FalconBlock(nn.Module):
    config: FalconConfig

    @nn.compact
    def __call__(self, x, decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        H, Hkv, Dh = (cfg.num_attention_heads, cfg.num_kv_heads,
                      cfg.head_dim)
        ln = partial(nn.LayerNorm, epsilon=cfg.layer_norm_epsilon,
                     dtype=dtype, param_dtype=jnp.float32)
        dense = partial(nn.DenseGeneral, use_bias=cfg.bias, dtype=dtype,
                        param_dtype=jnp.float32)

        if cfg.new_decoder_architecture:
            h_attn = ln(name="ln_attn")(x)
            h_mlp = ln(name="ln_mlp")(x)
        else:
            h_attn = h_mlp = ln(name="input_layernorm")(x)

        # ---- attention (NeoX rotary, GQA/MQA)
        q = dense(features=(H, Dh), name="q_proj")(h_attn)
        k = dense(features=(Hkv, Dh), name="k_proj")(h_attn)
        v = dense(features=(Hkv, Dh), name="v_proj")(h_attn)
        cos, sin = _rope_freqs(Dh, cfg.max_position_embeddings,
                               cfg.rope_theta)
        cos, sin = jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
        if Hkv != H:
            k = jnp.repeat(k, H // Hkv, axis=2)
            v = jnp.repeat(v, H // Hkv, axis=2)
        from ..ops.attention import attention_core
        attn = attention_core(q, k, v, causal=True)
        attn = dense(features=D, axis=-1,
                     name="dense")(attn.reshape(B, S, H * Dh))

        # ---- MLP (plain GELU 4x)
        mlp_in = h_mlp if cfg.parallel_attn else ln(name="post_attention_layernorm")(
            x + attn)
        h4 = nn.gelu(dense(features=cfg.ffn_size,
                           name="dense_h_to_4h")(mlp_in))
        mlp = dense(features=D, name="dense_4h_to_h")(h4)

        # sequential vs parallel differ only in mlp_in above; the residual
        # sum is the same either way
        return x + attn + mlp


class FalconModel(nn.Module):
    """Causal-LM.  ``__call__(input_ids, labels=None)`` → loss if labels
    given else logits."""
    config: FalconConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None,
                 decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, dtype=dtype,
                         name="word_embeddings")
        x = embed(input_ids)
        block = FalconBlock
        if cfg.remat and not decode:
            policy = resolve_policy(cfg.remat_policy)
            block = nn.remat(FalconBlock, policy=policy, static_argnums=(2, ))
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, name=f"h_{i}")(x, decode)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=dtype,
                         param_dtype=jnp.float32, name="ln_f")(x)
        if cfg.tie_word_embeddings:
            logits = embed.attend(x.astype(jnp.float32))
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False,
                              dtype=jnp.float32, param_dtype=jnp.float32,
                              name="lm_head")(x.astype(jnp.float32))
        if labels is None:
            return logits
        from ..sequence.cross_entropy import softmax_cross_entropy_with_logits
        loss = softmax_cross_entropy_with_logits(logits[:, :-1], labels[:, 1:])
        if attention_mask is not None:
            m = attention_mask[:, 1:].astype(jnp.float32)
            return jnp.sum(loss * m) / jnp.maximum(jnp.sum(m), 1.0)
        return jnp.mean(loss)


def tp_rules(config: FalconConfig):
    """Column-parallel q/k/v and h_to_4h, row-parallel dense/4h_to_h,
    vocab-sharded embeddings (same scheme the dataflow parser derives)."""
    return {
        "q_proj/kernel": P(None, "tp", "zero"),
        "k_proj/kernel": P(None, "tp", "zero"),
        "v_proj/kernel": P(None, "tp", "zero"),
        "dense/kernel": P("tp", "zero"),
        "dense_h_to_4h/kernel": P(None, ("tp", "zero")),
        "dense_4h_to_h/kernel": P("tp", "zero"),
        "word_embeddings/embedding": P(("tp", "zero"), None),
        "lm_head/kernel": P(None, ("tp", "zero")),
    }
