"""Phi-2 family model (TPU-first flax implementation).

Covers the reference's phi support (FastGen impl
``inference/v2/model_implementations/phi/``).  Distinctives vs Llama:

* **parallel block**: attention and the GELU MLP both read the same
  layernormed input; ``x + attn + mlp`` closes the residual;
* **partial rotary**: only the first ``partial_rotary_factor·head_dim``
  channels rotate, the rest pass through;
* LayerNorm with bias; every linear has a bias (including ``lm_head``).
"""

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..runtime.activation_checkpointing import resolve_policy
from .llama import _rope_freqs, apply_rotary


def apply_partial_rotary(x, cos, sin, rotary_dim, positions=None):
    """Rotate the first ``rotary_dim`` channels of [.., Dh]; pass the rest."""
    if rotary_dim == x.shape[-1]:
        return apply_rotary(x, cos, sin, positions=positions)
    x_rot, x_pass = x[..., :rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [apply_rotary(x_rot, cos, sin, positions=positions), x_pass], axis=-1)


@dataclass(frozen=True)
class PhiConfig:
    vocab_size: int = 51200
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 2048
    layer_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.4
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "nothing_saveable"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def rotary_dim(self):
        # HF floors to an even channel count
        return int(self.partial_rotary_factor * self.head_dim) // 2 * 2


def phi_tiny(**overrides):
    return PhiConfig(**{**dict(vocab_size=256, hidden_size=64,
                               intermediate_size=128, num_hidden_layers=2,
                               num_attention_heads=4, num_key_value_heads=4,
                               max_position_embeddings=128,
                               partial_rotary_factor=0.5),
                        **overrides})


class PhiBlock(nn.Module):
    config: PhiConfig

    @nn.compact
    def __call__(self, x, decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        H, Hkv, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        dense = partial(nn.DenseGeneral, use_bias=True, dtype=dtype,
                        param_dtype=jnp.float32)

        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=dtype,
                         param_dtype=jnp.float32, name="input_layernorm")(x)
        q = dense(features=(H, Dh), name="q_proj")(h)
        k = dense(features=(Hkv, Dh), name="k_proj")(h)
        v = dense(features=(Hkv, Dh), name="v_proj")(h)
        rd = cfg.rotary_dim
        cos, sin = _rope_freqs(rd, cfg.max_position_embeddings,
                               cfg.rope_theta)
        cos, sin = jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)
        q = apply_partial_rotary(q, cos, sin, rd)
        k = apply_partial_rotary(k, cos, sin, rd)
        if Hkv != H:
            k = jnp.repeat(k, H // Hkv, axis=2)
            v = jnp.repeat(v, H // Hkv, axis=2)
        from ..ops.attention import attention_core
        attn = attention_core(q, k, v, causal=True)
        attn = dense(features=D, axis=-1,
                     name="dense")(attn.reshape(B, S, H * Dh))

        mlp = dense(features=D, name="fc2")(
            nn.gelu(dense(features=cfg.intermediate_size, name="fc1")(h)))
        return x + attn + mlp  # parallel residual


class PhiModel(nn.Module):
    """Causal-LM.  ``__call__(input_ids, labels=None)`` → loss if labels
    given else logits."""
    config: PhiConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None,
                 decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, dtype=dtype,
                         name="embed_tokens")
        x = embed(input_ids)
        block = PhiBlock
        if cfg.remat and not decode:
            policy = resolve_policy(cfg.remat_policy)
            block = nn.remat(PhiBlock, policy=policy, static_argnums=(2, ))
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, name=f"layers_{i}")(x, decode)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=dtype,
                         param_dtype=jnp.float32, name="final_layernorm")(x)
        if cfg.tie_word_embeddings:
            # HF ties only the weight; the lm_head bias stays a live param
            bias = self.param("lm_head_bias", nn.initializers.zeros,
                              (cfg.vocab_size,), jnp.float32)
            logits = embed.attend(x.astype(jnp.float32)) + bias
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=True,
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


def tp_rules(config: PhiConfig):
    return {
        "q_proj/kernel": P(None, "tp", "zero"),
        "k_proj/kernel": P(None, "tp", "zero"),
        "v_proj/kernel": P(None, "tp", "zero"),
        "dense/kernel": P("tp", "zero"),
        "fc1/kernel": P(None, ("tp", "zero")),
        "fc2/kernel": P("tp", "zero"),
        "embed_tokens/embedding": P(("tp", "zero"), None),
        "lm_head/kernel": P(None, ("tp", "zero")),
    }
