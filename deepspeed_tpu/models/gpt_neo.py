"""GPT-Neo — reference ``module_inject/containers/gptneo.py`` (v1
injection family; serves through ``init_inference``).

Layout notes (HF ``modeling_gpt_neo``):
* learned positions (``wpe``), gpt2-style sequential residual;
* alternating per-layer attention types: "global" (full causal) and
  "local" (sliding window of ``window_size`` keys) — the window reuses the
  same Pallas flash block-skip path Mistral does;
* **unscaled** attention scores (GPT-Neo skips the 1/sqrt(Dh) factor);
* unbiased q/k/v, biased out_proj/mlp, tied LM head.
"""

from dataclasses import dataclass, field
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..runtime.activation_checkpointing import resolve_policy


@dataclass(frozen=True)
class GPTNeoConfig:
    vocab_size: int = 50257
    hidden_size: int = 64
    num_hidden_layers: int = 2
    num_attention_heads: int = 4
    intermediate_size: int = 256
    max_position_embeddings: int = 2048
    window_size: int = 256
    attention_layers: Tuple[str, ...] = ("global", "local")
    layer_norm_epsilon: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing_saveable"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def gpt_neo_tiny(**overrides):
    return GPTNeoConfig(**{**dict(vocab_size=256, hidden_size=64,
                                  num_hidden_layers=2,
                                  num_attention_heads=4,
                                  intermediate_size=128,
                                  max_position_embeddings=128,
                                  window_size=8), **overrides})


class GPTNeoBlock(nn.Module):
    config: GPTNeoConfig
    attention_type: str = "global"

    @nn.compact
    def __call__(self, x, decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        H, Dh = cfg.num_attention_heads, cfg.head_dim
        window = cfg.window_size if self.attention_type == "local" else 0
        ln = partial(nn.LayerNorm, epsilon=cfg.layer_norm_epsilon,
                     dtype=dtype, param_dtype=jnp.float32)
        qkv = partial(nn.DenseGeneral, use_bias=False, dtype=dtype,
                      param_dtype=jnp.float32)

        h = ln(name="ln_1")(x)
        q = qkv(features=(H, Dh), name="q_proj")(h)
        k = qkv(features=(H, Dh), name="k_proj")(h)
        v = qkv(features=(H, Dh), name="v_proj")(h)

        if decode:
            from .cache import decode_attention, kv_cache_update
            k, v, start = kv_cache_update(self, k, v)
            attn = decode_attention(q, k, v, start, softmax_scale=1.0,
                                    window=window)
        else:
            from ..ops.attention import attention_core
            # GPT-Neo does NOT scale scores by 1/sqrt(Dh)
            attn = attention_core(q, k, v, causal=True, softmax_scale=1.0,
                                  window=window)
        attn_out = nn.Dense(D, dtype=dtype, param_dtype=jnp.float32,
                            name="out_proj")(attn.reshape(B, S, H * Dh))
        x = x + attn_out

        h2 = ln(name="ln_2")(x)
        mlp = nn.Dense(D, dtype=dtype, param_dtype=jnp.float32,
                       name="c_proj")(
            nn.gelu(nn.Dense(cfg.intermediate_size, dtype=dtype,
                             param_dtype=jnp.float32, name="c_fc")(h2)))
        return x + mlp


class GPTNeoModel(nn.Module):
    """Causal-LM.  ``__call__(input_ids, labels=None)`` → loss if labels
    given else logits (tied head)."""
    config: GPTNeoConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None,
                 decode=False, positions=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S = input_ids.shape
        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                       param_dtype=jnp.float32, dtype=dtype, name="wte")
        wpe = nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                       param_dtype=jnp.float32, dtype=dtype, name="wpe")
        if positions is None:
            positions = jnp.arange(S)[None, :]
        x = wte(input_ids) + wpe(positions)

        block = GPTNeoBlock
        if cfg.remat and not decode:
            policy = resolve_policy(cfg.remat_policy)
            block = nn.remat(GPTNeoBlock, policy=policy, static_argnums=(2, ))
        at = cfg.attention_layers
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, attention_type=at[i % len(at)],
                      name=f"h_{i}")(x, decode)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=dtype,
                         param_dtype=jnp.float32, name="ln_f")(x)
        logits = wte.attend(x.astype(jnp.float32))
        if labels is None:
            return logits
        from ..sequence.cross_entropy import softmax_cross_entropy_with_logits
        loss = softmax_cross_entropy_with_logits(logits[:, :-1], labels[:, 1:])
        if attention_mask is not None:
            m = attention_mask[:, 1:].astype(jnp.float32)
            return jnp.sum(loss * m) / jnp.maximum(jnp.sum(m), 1.0)
        return jnp.mean(loss)


def tp_rules(config: GPTNeoConfig):
    return {
        "q_proj/kernel": P(None, "tp", "zero"),
        "k_proj/kernel": P(None, "tp", "zero"),
        "v_proj/kernel": P(None, "tp", "zero"),
        "out_proj/kernel": P("tp", "zero"),
        "c_fc/kernel": P(None, ("tp", "zero")),
        "c_proj/kernel": P(("tp", "zero"), None),
        "wte/embedding": P(("tp", "zero"), None),
    }
