"""OPT-family model (TPU-first flax implementation).

Covers the reference's OPT support (FastGen impl
``inference/v2/model_implementations/opt/``).  Architecturally distinct from
the Llama family:

* learned positional embeddings with the OPT quirk of a +2 offset
  (``embed_positions`` row i serves position i-2);
* LayerNorm (with bias) in pre-norm placement (``do_layer_norm_before``);
* plain ReLU 4× MLP; every linear carries a bias;
* no rotary — positions enter only through the embedding.
"""

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..runtime.activation_checkpointing import resolve_policy

OPT_POSITION_OFFSET = 2


@dataclass(frozen=True)
class OPTConfig:
    vocab_size: int = 50272
    hidden_size: int = 768
    ffn_dim: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 2048
    layer_norm_eps: float = 1e-5
    do_layer_norm_before: bool = True
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"
    remat: bool = True
    remat_policy: str = "nothing_saveable"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def num_key_value_heads(self):
        return self.num_attention_heads


def opt_tiny(**overrides):
    return OPTConfig(**{**dict(vocab_size=256, hidden_size=64, ffn_dim=128,
                               num_hidden_layers=2, num_attention_heads=4,
                               max_position_embeddings=128),
                        **overrides})


class OPTBlock(nn.Module):
    config: OPTConfig

    @nn.compact
    def __call__(self, x, positions=None, decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        H, Dh = cfg.num_attention_heads, cfg.head_dim
        ln = partial(nn.LayerNorm, epsilon=cfg.layer_norm_eps, dtype=dtype,
                     param_dtype=jnp.float32)
        dense = partial(nn.DenseGeneral, use_bias=True, dtype=dtype,
                        param_dtype=jnp.float32)

        res = x
        h = ln(name="self_attn_layer_norm")(x) if cfg.do_layer_norm_before \
            else x
        q = dense(features=(H, Dh), name="q_proj")(h)
        k = dense(features=(H, Dh), name="k_proj")(h)
        v = dense(features=(H, Dh), name="v_proj")(h)
        from ..ops.attention import attention_core
        out = attention_core(q, k, v, causal=True)
        x = res + dense(features=D, axis=-1,
                        name="out_proj")(out.reshape(B, S, H * Dh))
        if not cfg.do_layer_norm_before:
            x = ln(name="self_attn_layer_norm")(x)

        res = x
        h = ln(name="final_layer_norm")(x) if cfg.do_layer_norm_before else x
        h = nn.relu(dense(features=cfg.ffn_dim, name="fc1")(h))
        x = res + dense(features=D, name="fc2")(h)
        if not cfg.do_layer_norm_before:
            x = ln(name="final_layer_norm")(x)
        return x


class OPTModel(nn.Module):
    """Causal-LM.  ``__call__(input_ids, labels=None)`` → loss if labels
    given else logits."""
    config: OPTConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None,
                 decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S = input_ids.shape
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, dtype=dtype,
                         name="embed_tokens")
        pos_embed = nn.Embed(
            cfg.max_position_embeddings + OPT_POSITION_OFFSET,
            cfg.hidden_size, param_dtype=jnp.float32, dtype=dtype,
            name="embed_positions")
        x = embed(input_ids) + pos_embed(
            jnp.arange(S)[None, :] + OPT_POSITION_OFFSET)

        block = OPTBlock
        if cfg.remat and not decode:
            policy = resolve_policy(cfg.remat_policy)
            block = nn.remat(OPTBlock, policy=policy, static_argnums=(3, ))
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, name=f"layers_{i}")(x, None, decode)

        if cfg.do_layer_norm_before:
            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=dtype,
                             param_dtype=jnp.float32,
                             name="final_layer_norm")(x)
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


def tp_rules(config: OPTConfig):
    return {
        "q_proj/kernel": P(None, "tp", "zero"),
        "k_proj/kernel": P(None, "tp", "zero"),
        "v_proj/kernel": P(None, "tp", "zero"),
        "out_proj/kernel": P("tp", "zero"),
        "fc1/kernel": P(None, ("tp", "zero")),
        "fc2/kernel": P("tp", "zero"),
        "embed_tokens/embedding": P(("tp", "zero"), None),
        "lm_head/kernel": P(None, ("tp", "zero")),
    }
