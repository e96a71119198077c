"""GPT-NeoX / Pythia — reference ``module_inject/containers/gptneox.py``
(v1 kernel-injection family; not in the FastGen model list, so serving goes
through ``init_inference`` like the reference).

Layout notes (HF ``modeling_gpt_neox``):
* fused ``query_key_value`` projects head-interleaved ``[H, 3·Dh]`` (q
  first within each head) — kept as-is so ingest is a plain transpose;
* partial rotary (``rotary_pct`` of the head dim, NeoX rotate-half
  convention — the same one llama uses);
* ``use_parallel_residual=True`` (default): attention and MLP both read
  their own layernorm of x and add into the residual together;
* untied LM head (``embed_out``).
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
class GPTNeoXConfig:
    vocab_size: int = 50432
    hidden_size: int = 64
    intermediate_size: int = 256
    num_hidden_layers: int = 2
    num_attention_heads: int = 8
    max_position_embeddings: int = 2048
    rotary_pct: float = 0.25
    rotary_emb_base: float = 10000.0
    layer_norm_eps: float = 1e-5
    use_parallel_residual: bool = True
    hidden_act: str = "gelu"
    dtype: str = "bfloat16"
    remat: bool = False
    remat_policy: str = "nothing_saveable"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def rotary_dim(self):
        # HF truncates: int(head_dim * rotary_pct)
        return int(self.head_dim * self.rotary_pct)


def gpt_neox_tiny(**overrides):
    return GPTNeoXConfig(**{**dict(vocab_size=256, hidden_size=64,
                                   intermediate_size=128,
                                   num_hidden_layers=2,
                                   num_attention_heads=4,
                                   max_position_embeddings=128,
                                   rotary_pct=0.5), **overrides})


def _partial_rotary(x, cos, sin, rd, positions=None):
    if rd >= x.shape[-1]:
        return apply_rotary(x, cos, sin, positions=positions)
    return jnp.concatenate(
        [apply_rotary(x[..., :rd], cos, sin, positions=positions),
         x[..., rd:]], axis=-1)


class GPTNeoXBlock(nn.Module):
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, x, decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        H, Dh = cfg.num_attention_heads, cfg.head_dim
        rd = cfg.rotary_dim
        ln = partial(nn.LayerNorm, epsilon=cfg.layer_norm_eps, dtype=dtype,
                     param_dtype=jnp.float32)
        dense = partial(nn.Dense, dtype=dtype, param_dtype=jnp.float32)
        cos, sin = _rope_freqs(rd, cfg.max_position_embeddings,
                               cfg.rotary_emb_base)
        cos = jnp.asarray(cos, jnp.float32)
        sin = jnp.asarray(sin, jnp.float32)

        h = ln(name="input_layernorm")(x)
        qkv = dense(3 * D, name="query_key_value")(h)
        qkv = qkv.reshape(B, S, H, 3, Dh)          # per-head [q; k; v]
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]

        if decode:
            from .cache import decode_attention, kv_cache_update

            def rotate_k(kk, start):
                pos = start + jnp.arange(kk.shape[1])[None, :]
                return _partial_rotary(kk, cos, sin, rd, positions=pos)

            k, v, start = kv_cache_update(self, k, v, rotate_fn=rotate_k)
            q = _partial_rotary(q, cos, sin, rd,
                                positions=start + jnp.arange(S)[None, :])
            attn = decode_attention(q, k, v, start)
        else:
            q = _partial_rotary(q, cos, sin, rd)
            k = _partial_rotary(k, cos, sin, rd)
            from ..ops.attention import attention_core
            attn = attention_core(q, k, v, causal=True)
        attn_out = dense(D, name="dense")(attn.reshape(B, S, D))

        # HF default hidden_act="gelu" is the EXACT erf gelu (ACT2FN);
        # the tanh approximation is a different function
        act = {"gelu": partial(nn.gelu, approximate=False),
               "gelu_new": nn.gelu, "gelu_fast": nn.gelu,
               "gelu_pytorch_tanh": nn.gelu, "relu": nn.relu}.get(
                   cfg.hidden_act)
        if act is None:
            raise ValueError(f"unsupported hidden_act {cfg.hidden_act!r}")

        def mlp(h):
            return dense(D, name="dense_4h_to_h")(
                act(dense(cfg.intermediate_size,
                          name="dense_h_to_4h")(h)))

        if cfg.use_parallel_residual:
            # x + attn(ln1(x)) + mlp(ln2(x))
            return x + attn_out + mlp(ln(name="post_attention_layernorm")(x))
        x = x + attn_out
        return x + mlp(ln(name="post_attention_layernorm")(x))


class GPTNeoXModel(nn.Module):
    """Causal-LM.  ``__call__(input_ids, labels=None)`` → loss if labels
    given else logits (untied ``embed_out`` head)."""
    config: GPTNeoXConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None,
                 decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                     param_dtype=jnp.float32, dtype=dtype,
                     name="embed_in")(input_ids)
        block = GPTNeoXBlock
        if cfg.remat and not decode:
            policy = resolve_policy(cfg.remat_policy)
            block = nn.remat(GPTNeoXBlock, policy=policy,
                             static_argnums=(2, ))
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, name=f"layers_{i}")(x, decode)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=dtype,
                         param_dtype=jnp.float32,
                         name="final_layer_norm")(x)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                          param_dtype=jnp.float32,
                          name="embed_out")(x.astype(jnp.float32))
        if labels is None:
            return logits
        from ..sequence.cross_entropy import softmax_cross_entropy_with_logits
        loss = softmax_cross_entropy_with_logits(logits[:, :-1], labels[:, 1:])
        if attention_mask is not None:
            m = attention_mask[:, 1:].astype(jnp.float32)
            return jnp.sum(loss * m) / jnp.maximum(jnp.sum(m), 1.0)
        return jnp.mean(loss)


def tp_rules(config: GPTNeoXConfig):
    return {
        "query_key_value/kernel": P(None, ("tp", "zero")),
        "dense/kernel": P(("tp", "zero"), None),
        "dense_h_to_4h/kernel": P(None, ("tp", "zero")),
        "dense_4h_to_h/kernel": P(("tp", "zero"), None),
        "embed_in/embedding": P(("tp", "zero"), None),
        "embed_out/kernel": P(None, ("tp", "zero")),
    }
