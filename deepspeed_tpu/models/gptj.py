"""GPT-J — reference ``module_inject/containers/gptj.py`` (v1 injection
family; serves through ``init_inference``).

Layout notes (HF ``modeling_gptj``):
* separate UNBIASED q/k/v/out projections;
* INTERLEAVED rotary over the first ``rotary_dim`` dims (GPT-J convention:
  rotate-every-two — NOT the llama/neox half-split);
* one shared LayerNorm feeds both attention and the MLP (parallel
  residual: ``x + attn(ln(x)) + mlp(ln(x))``);
* untied ``lm_head`` WITH bias.
"""

from dataclasses import dataclass
from functools import partial


import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..runtime.activation_checkpointing import resolve_policy
from .llama import _rope_freqs


@dataclass(frozen=True)
class GPTJConfig:
    vocab_size: int = 50400
    hidden_size: int = 64
    num_hidden_layers: int = 2
    num_attention_heads: int = 4
    rotary_dim: int = 16
    intermediate_size: int = 256
    max_position_embeddings: int = 2048
    layer_norm_epsilon: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing_saveable"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def gptj_tiny(**overrides):
    return GPTJConfig(**{**dict(vocab_size=256, hidden_size=64,
                                num_hidden_layers=2, num_attention_heads=4,
                                rotary_dim=8, intermediate_size=128,
                                max_position_embeddings=128), **overrides})


def apply_rotary_interleaved(x, cos, sin, rd, positions=None):
    """GPT-J rotary: rotate-every-two over the first ``rd`` dims.
    x: [B, S, H, Dh]; cos/sin: [Smax, rd/2]."""
    S = x.shape[1]
    if positions is None:
        c = cos[:S][None, :, None, :]
        s = sin[:S][None, :, None, :]
    else:
        c = cos[positions][:, :, None, :]
        s = sin[positions][:, :, None, :]
    xr, xp = x[..., :rd], x[..., rd:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    out = out.reshape(*xr.shape)
    return jnp.concatenate([out, xp], axis=-1).astype(x.dtype)


class GPTJBlock(nn.Module):
    config: GPTJConfig

    @nn.compact
    def __call__(self, x, decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        H, Dh = cfg.num_attention_heads, cfg.head_dim
        rd = cfg.rotary_dim
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=dtype,
                        param_dtype=jnp.float32)
        cos, sin = _rope_freqs(rd, cfg.max_position_embeddings, 10000.0)
        cos = jnp.asarray(cos, jnp.float32)
        sin = jnp.asarray(sin, jnp.float32)

        h = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=dtype,
                         param_dtype=jnp.float32, name="ln_1")(x)
        q = dense(features=(H, Dh), name="q_proj")(h)
        k = dense(features=(H, Dh), name="k_proj")(h)
        v = dense(features=(H, Dh), name="v_proj")(h)

        if decode:
            from .cache import decode_attention, kv_cache_update

            def rotate_k(kk, start):
                pos = start + jnp.arange(kk.shape[1])[None, :]
                return apply_rotary_interleaved(kk, cos, sin, rd,
                                                positions=pos)

            k, v, start = kv_cache_update(self, k, v, rotate_fn=rotate_k)
            q = apply_rotary_interleaved(
                q, cos, sin, rd, positions=start + jnp.arange(S)[None, :])
            attn = decode_attention(q, k, v, start, softmax_scale=Dh**-0.5)
        else:
            q = apply_rotary_interleaved(q, cos, sin, rd)
            k = apply_rotary_interleaved(k, cos, sin, rd)
            from ..ops.attention import attention_core
            attn = attention_core(q, k, v, causal=True)
        attn_out = nn.Dense(D, use_bias=False, dtype=dtype,
                            param_dtype=jnp.float32,
                            name="out_proj")(attn.reshape(B, S, H * Dh))

        mlp = nn.Dense(D, dtype=dtype, param_dtype=jnp.float32,
                       name="fc_out")(
            nn.gelu(nn.Dense(cfg.intermediate_size, dtype=dtype,
                             param_dtype=jnp.float32, name="fc_in")(h)))
        return x + attn_out + mlp  # parallel residual off ONE shared ln


class GPTJModel(nn.Module):
    """Causal-LM.  ``__call__(input_ids, labels=None)`` → loss if labels
    given else logits (untied biased ``lm_head``)."""
    config: GPTJConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None,
                 decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                     param_dtype=jnp.float32, dtype=dtype,
                     name="wte")(input_ids)
        block = GPTJBlock
        if cfg.remat and not decode:
            policy = resolve_policy(cfg.remat_policy)
            block = nn.remat(GPTJBlock, policy=policy, static_argnums=(2, ))
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, name=f"h_{i}")(x, decode)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=dtype,
                         param_dtype=jnp.float32, name="ln_f")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=True, dtype=jnp.float32,
                          param_dtype=jnp.float32,
                          name="lm_head")(x.astype(jnp.float32))
        if labels is None:
            return logits
        from ..sequence.cross_entropy import softmax_cross_entropy_with_logits
        loss = softmax_cross_entropy_with_logits(logits[:, :-1], labels[:, 1:])
        if attention_mask is not None:
            m = attention_mask[:, 1:].astype(jnp.float32)
            return jnp.sum(loss * m) / jnp.maximum(jnp.sum(m), 1.0)
        return jnp.mean(loss)


def tp_rules(config: GPTJConfig):
    return {
        "q_proj/kernel": P(None, "tp", "zero"),
        "k_proj/kernel": P(None, "tp", "zero"),
        "v_proj/kernel": P(None, "tp", "zero"),
        "out_proj/kernel": P("tp", "zero"),
        "fc_in/kernel": P(None, ("tp", "zero")),
        "fc_out/kernel": P(("tp", "zero"), None),
        "wte/embedding": P(("tp", "zero"), None),
        "lm_head/kernel": P(None, ("tp", "zero")),
    }
