"""Mixtral-family model (sparse-MoE Llama) — TPU-first flax implementation.

Covers the reference's Mixtral support (FastGen impl
``inference/v2/model_implementations/mixtral/`` and the MoE containers) as a
*training-capable* module:

* attention/norm/rope identical to :mod:`deepspeed_tpu.models.llama` (Mixtral
  is a Llama arch with the MLP replaced by a top-2 router over E experts);
* expert weights are STACKED arrays ``w1/w3: [E, D, I]``, ``w2: [E, I, D]``
  — one array per projection, so expert-parallel sharding is a single
  ``P("ep", ...)`` spec and the grouped matmul maps onto the MXU;
* the expert compute is ``jax.lax.ragged_dot`` over tokens sorted by expert
  (megablocks-style, no token dropping — exact Mixtral semantics), which XLA
  lowers to the TPU grouped-matmul path (``moe/held_experts.py``, the layer
  Cohere2-MoE shares; docs/kernels.md has the chip's readings against the
  Pallas ``ds_grouped_matmul``);
* training adds the standard load-balance aux loss
  (``router_aux_loss_coef``, reference ``sharded_moe.py`` aux-loss algebra).

HF weight layout (``MixtralForCausalLM``) maps 1:1 onto this tree — see
``inference/v2/checkpoint/huggingface_engine.py``.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..moe.held_experts import held_experts_apply, route
from ..runtime.activation_checkpointing import resolve_policy
from .llama import LlamaAttention, LlamaConfig, RMSNorm


@dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    router_aux_loss_coef: float = 0.02
    # qwen2-moe extensions: a dense "shared expert" runs for every token,
    # mixed in via a sigmoid gate; norm_topk_prob=False keeps raw top-k
    # routing probs (mixtral renormalizes)
    shared_expert_intermediate_size: int = 0  # 0 → no shared expert
    norm_topk_prob: bool = True
    # LlamaConfig's default keeps the flash kernel's residuals; THIS block's
    # memory has not been measured against that (the cell that trains a
    # routed block, smallthinker_21b_train_8k, runs models/smallthinker.py)
    remat_policy: str = "nothing_saveable"


def mixtral_tiny(**overrides):
    return MixtralConfig(**{**dict(vocab_size=256, hidden_size=64,
                                   intermediate_size=128, num_hidden_layers=2,
                                   num_attention_heads=4, num_key_value_heads=2,
                                   max_position_embeddings=128,
                                   num_local_experts=4, num_experts_per_tok=2),
                            **overrides})


def moe_apply(x, router_logits, w1, w2, w3, k, norm_topk=True, live=None):
    """Exact (no-drop) top-k MoE: route, sort token-copies by expert, grouped
    matmul, weighted scatter-add back (``moe/held_experts.py``, every expert
    held).  x: [T, D] → [T, D]; ``live [T]``: the rows that are routed at
    all (None: every row).
    """
    topi, topw = route(router_logits, k, "softmax", norm_topk)
    return held_experts_apply(x, topi, topw, w1, w2, w3, live=live)[0]


def load_balance_aux_loss(router_logits, k):
    """Switch/GShard aux loss over a batch of router logits [T, E]."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    E = probs.shape[-1]
    _, topi = jax.lax.top_k(probs, k)
    counts = jnp.sum(jax.nn.one_hot(topi, E), axis=(0, 1))  # [E]
    frac_tokens = counts / jnp.maximum(jnp.sum(counts), 1.0)
    frac_probs = jnp.mean(probs, axis=0)
    return jnp.sum(frac_tokens * frac_probs) * E


class MixtralSparseMoeBlock(nn.Module):
    """Top-k router + stacked experts (HF ``block_sparse_moe`` analog)."""
    config: MixtralConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        E, I = cfg.num_local_experts, cfg.intermediate_size
        tokens = x.reshape(-1, D)

        gate = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                        param_dtype=jnp.float32, name="gate")
        router_logits = gate(tokens.astype(jnp.float32))  # [T, E]

        init = nn.initializers.lecun_normal()
        w1 = self.param("w1", init, (E, D, I), jnp.float32)
        w3 = self.param("w3", init, (E, D, I), jnp.float32)
        w2 = self.param("w2", init, (E, I, D), jnp.float32)
        out = moe_apply(tokens, router_logits,
                        w1.astype(dtype), w2.astype(dtype), w3.astype(dtype),
                        cfg.num_experts_per_tok,
                        norm_topk=cfg.norm_topk_prob)
        if cfg.shared_expert_intermediate_size:
            # qwen2-moe shared expert: dense SwiGLU on every token, mixed in
            # through a per-token sigmoid gate
            Is = cfg.shared_expert_intermediate_size
            dense = lambda f, name: nn.Dense(f, use_bias=False, dtype=dtype,
                                             param_dtype=jnp.float32,
                                             name=name)
            gate_s = dense(Is, "shared_gate_proj")(tokens)
            up_s = dense(Is, "shared_up_proj")(tokens)
            shared = dense(D, "shared_down_proj")(nn.silu(gate_s) * up_s)
            mix = nn.Dense(1, use_bias=False, dtype=jnp.float32,
                           param_dtype=jnp.float32,
                           name="shared_expert_gate")(
                               tokens.astype(jnp.float32))
            out = out + (jax.nn.sigmoid(mix) * shared.astype(
                jnp.float32)).astype(out.dtype)
        self.sow("intermediates", "router_logits", router_logits)
        return out.reshape(B, S, D)


class MixtralBlock(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, x, attention_mask=None, decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        h = x + LlamaAttention(cfg, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, dtype, name="input_layernorm")(x),
            attention_mask, decode=decode)
        return h + MixtralSparseMoeBlock(cfg, name="moe")(
            RMSNorm(cfg.rms_norm_eps, dtype,
                    name="post_attention_layernorm")(h))


class MixtralModel(nn.Module):
    """Causal-LM.  ``__call__(input_ids, labels=None)`` → loss (+aux) if
    labels given else logits."""
    config: MixtralConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None,
                 decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, dtype=dtype,
                         name="embed_tokens")
        x = embed(input_ids)

        block = MixtralBlock
        if cfg.remat and not decode:
            policy = resolve_policy(cfg.remat_policy)
            block = nn.remat(MixtralBlock, policy=policy, static_argnums=(3, ))
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, name=f"layers_{i}")(x, attention_mask, decode)

        x = RMSNorm(cfg.rms_norm_eps, dtype, name="norm")(x)
        if cfg.loss_chunk_vocab and labels is not None and not decode:
            # fused chunked head+loss (models/llama.py loss_chunk_vocab):
            # no [B, S, V] logits in either pass
            from .llama import _lm_loss_chunked
            if cfg.tie_word_embeddings:
                w = embed.variables["params"]["embedding"].T
            else:
                head = nn.Dense(cfg.vocab_size, use_bias=False,
                                dtype=jnp.float32, param_dtype=jnp.float32,
                                name="lm_head")
                head(x[:, :1].astype(jnp.float32))  # bind; dead code to XLA
                w = head.variables["params"]["kernel"]
            loss = _lm_loss_chunked(x.astype(jnp.float32), w, labels,
                                    attention_mask, cfg.loss_chunk_vocab,
                                    jnp.float32)
            return loss
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
            loss = jnp.sum(loss * m) / jnp.maximum(jnp.sum(m), 1.0)
        else:
            loss = jnp.mean(loss)
        # load-balance aux loss from each layer's sown router logits is not
        # reachable inside @nn.compact without a variable pass; recompute is
        # avoided by sowing — the engine adds it when it applies the model
        # with mutable=["intermediates"].  Standalone callers get the plain
        # LM loss plus the coefficient-weighted aux via aux_loss_from_vars.
        return loss


def aux_loss_from_vars(variables, k, coef):
    """Sum the load-balance aux loss over all layers' sown router logits."""
    inter = variables.get("intermediates", {})
    total = 0.0
    n = 0
    for layer in inter.values():
        moe = layer.get("moe") if isinstance(layer, dict) else None
        if moe and "router_logits" in moe:
            for rl in moe["router_logits"]:
                total = total + load_balance_aux_loss(rl, k)
                n += 1
    return coef * total / max(n, 1)


def tp_rules(config: MixtralConfig):
    """Sharding rules: attention like Llama; experts sharded over "ep" on the
    expert axis (+ ZeRO pinned on a non-contracting dim)."""
    from .llama import tp_rules as llama_rules
    rules = dict(llama_rules(config))
    rules.pop("gate_proj/kernel", None)
    rules.pop("up_proj/kernel", None)
    rules.pop("down_proj/kernel", None)
    rules.update({
        "moe/gate/kernel": P(None, None),
        "moe/w1": P("ep", None, ("tp", "zero")),
        "moe/w3": P("ep", None, ("tp", "zero")),
        "moe/w2": P("ep", ("tp", "zero"), None),
    })
    return rules


def param_count(config: MixtralConfig):
    D, I, V, L, E = (config.hidden_size, config.intermediate_size,
                     config.vocab_size, config.num_hidden_layers,
                     config.num_local_experts)
    H, Hkv, Dh = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim)
    per_layer = (D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D) \
        + E * 3 * D * I + D * E + 2 * D
    total = V * D + L * per_layer + D
    if not config.tie_word_embeddings:
        total += D * V
    return total
