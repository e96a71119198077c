"""Cohere2-MoE (``model_type: cohere2_moe``, Command A+): a Cohere PARALLEL
block whose feed-forward part is a sigmoid-routed expert layer beside averaged
shared experts, with window and full attention layers interleaved.

The layer ``l`` of kind ``layer_types[l]``, with ``D`` hidden, ``E`` the
router's width, ``k`` experts a token:

    h   = LN(x) = (x - mean(x)) / sqrt(var(x) + eps) * g     (no bias)
    q, k, v = h Wq, h Wk, h Wv                  (grouped-query, no qk-norm)
    sliding layer: q, k turned by rotary on INTERLEAVED pairs (x[2i], x[2i+1])
                   (``rope_gptj``); causal mask with 0 <= i - j < window
    full layer:    no rotary, no positions at all; causal mask only
    a   = softmax(q k^T / sqrt(head_dim)) v Wo
    s   = sigmoid(h Wr) [E];  S = top-k of s;  w_e = s_e / sum_{e' in S} s_e'
    r   = sum_{e in S} w_e W2_e (silu(W1_e h) * W3_e h)
    c   = 1/n sum_{i<n} V2_i (silu(V1_i h) * V3_i h)         (shared, averaged)
    x'  = x + a + r + c                                (ONE norm, no second)
    logits = LN_f(x_L) Emb^T * logit_scale             (tied embedding)

**One chip's share.**  ``num_experts`` is the ROUTER'S width; ``experts_held``
(default: all) and ``first_expert`` say which experts' stacks this model
holds.  ``S`` and ``w_e`` are taken over the full width, ``r`` sums over ``S``
∩ held only, nothing stands in for the rest, and that partial ``x'`` goes on
to the next layer (``moe/held_experts.py``).

``Cohere2MoeModel`` is the dense forward (the tests, ``param_shapes``);
serving is ``inference/v2/ragged_forward.cohere2_moe_ragged_step`` over the
paged cache.  The expert stacks are 3-D leaves ``moe/w1, w3 [H, D, I]``,
``moe/w2 [H, I, D]`` and ``moe/shared_w1, shared_w3 [n, D, I]``,
``moe/shared_w2 [n, I, D]``; ``param_dtype`` makes them in the serving type
on the device.  The tied embedding is the leaf ``embed_tokens/weight [V, D]``,
drawn at ``1 / sqrt(V)``: a table of unit rows would put a token's own
embedding back on top of its logits (``|e|^2 = D`` against a spread of
``sqrt(D)``), and a model of seeded weights would repeat its input whatever
its layers compute (PERF.md section 6, PR 33).
"""

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..moe.held_experts import held_experts_apply, route
from ..telemetry import names as _names

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class Cohere2MoeConfig:
    """The keys of the published ``config.json`` by their own names, and what
    a chip holds of a layer (``experts_held``, ``first_expert``)."""
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096          # one expert's, routed and shared
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 200000
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    sliding_window: int = 4096
    layer_types: Optional[Tuple[str, ...]] = None   # None: 3 sliding, 1 full
    num_experts: int = 128                 # the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    experts_held: Optional[int] = None     # None: all of them
    first_expert: int = 0
    norm_topk_prob: bool = True
    expert_selection_fn: str = "sigmoid"
    shared_expert_combination_strategy: str = "average"
    position_embedding_type: str = "rope_gptj"
    use_parallel_block: bool = True
    use_qk_norm: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    logit_scale: float = 1.0
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self):
        if (self.expert_selection_fn != "sigmoid"
                or self.shared_expert_combination_strategy != "average"
                or self.position_embedding_type != "rope_gptj"
                or not self.use_parallel_block or self.use_qk_norm
                or self.attention_bias or self.hidden_act != "silu"
                or not self.tie_word_embeddings):
            raise ValueError(
                "Cohere2MoeConfig: sigmoid routing, averaged shared experts, "
                "rope_gptj, the parallel block, no qk-norm, no attention "
                "bias, silu and a tied embedding are what this model "
                "implements")
        kinds = self.kinds
        if len(kinds) != self.num_hidden_layers or \
                set(kinds) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {kinds!r} is not one of "
                             f"{SLIDING!r} / {FULL!r} a layer")
        if not 0 <= self.first_expert <= self.num_experts - self.held:
            raise ValueError("the held experts lie outside the router")

    @property
    def kinds(self):
        """Each layer's kind."""
        if self.layer_types is not None:
            return tuple(self.layer_types)
        return tuple(FULL if i % 4 == 3 else SLIDING
                     for i in range(self.num_hidden_layers))

    @property
    def layer_windows(self):
        """Each layer's window in tokens; 0: full attention, which is also
        the layer with no positions."""
        return tuple(self.sliding_window if kind == SLIDING else 0
                     for kind in self.kinds)

    @property
    def held(self):
        return self.num_experts if self.experts_held is None \
            else self.experts_held


def cohere2_moe_tiny(**overrides):
    """Test-scale config: 16 experts of which 8 are held, 2 a token, 2 shared,
    a window of 16 on three layers of four."""
    return Cohere2MoeConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=512, sliding_window=16,
        num_experts=16, num_experts_per_tok=2, num_shared_experts=2,
        experts_held=8, dtype="float32"), **overrides})


def layer_norm(x, weight, eps):
    """Cohere's LayerNorm: the mean subtracted, no bias; float32 inside."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)
            * weight.astype(jnp.float32)).astype(x.dtype)


def rotary_pairs(x, positions, theta):
    """x: [..., T, heads, Dh] turned by ``positions [..., T]`` on interleaved
    pairs ``(x[2i], x[2i + 1])`` (``rope_gptj``), the angles made here in
    float32 (no table: 200 000 positions would be 100 MB of constants)."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (dh // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1) \
        .reshape(x.shape).astype(x.dtype)


def shared_experts(h, w1, w2, w3):
    """The mean of the ``n`` shared experts' SwiGLU over every row: ``h [T,
    D]``, ``w1/w3 [n, D, I]``, ``w2 [n, I, D]``.  The ``n`` down-projections
    are ONE product over the ``n * I`` activations (``[n, I, D]`` is ``[n *
    I, D]`` as it lies in memory)."""
    n, _, width = w1.shape
    act = jnp.concatenate(
        [jax.nn.silu(h @ w1[i]) * (h @ w3[i]) for i in range(n)], axis=-1)
    out = act @ w2.reshape(n * width, w2.shape[-1])
    return (out.astype(jnp.float32) / n).astype(h.dtype)


class _Table(nn.Module):
    """The tied embedding as the leaf ``<name>/weight [rows, width]``."""
    rows: int
    width: int
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self):
        return self.param(
            "weight", nn.initializers.normal(self.rows ** -0.5),
            (self.rows, self.width), self.param_dtype)


class _Weight(nn.Module):
    """A LayerNorm's scale as the leaf ``<name>/weight [D]``."""
    @nn.compact
    def __call__(self, x, eps):
        g = self.param("weight", nn.initializers.ones, (x.shape[-1], ))
        return layer_norm(x, g, eps)


class Cohere2Attention(nn.Module):
    config: Cohere2MoeConfig
    window: int

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        B, S, _ = h.shape
        H, Hkv, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        proj = lambda heads, name: nn.DenseGeneral(
            features=(heads, Dh), use_bias=False, dtype=dtype,
            param_dtype=pdtype, name=name)(h)
        q, k, v = proj(H, "q_proj"), proj(Hkv, "k_proj"), proj(Hkv, "v_proj")
        pos = jnp.arange(S)
        if self.window:
            q = rotary_pairs(q, pos[None], cfg.rope_theta)
            k = rotary_pairs(k, pos[None], cfg.rope_theta)
        g = H // Hkv
        scores = jnp.einsum(
            "bskgd,btkd->bkgst", q.reshape(B, S, Hkv, g, Dh).astype(
                jnp.float32), k.astype(jnp.float32)) * Dh**-0.5
        dist = pos[:, None] - pos[None, :]
        mask = dist >= 0
        if self.window:
            mask &= dist < self.window
        probs = jax.nn.softmax(
            jnp.where(mask, scores, jnp.finfo(jnp.float32).min), axis=-1)
        out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
        return nn.Dense(cfg.hidden_size, use_bias=False, dtype=dtype,
                        param_dtype=pdtype, name="o_proj")(
                            out.reshape(B, S, H * Dh).astype(dtype))


class Cohere2MoeBlock(nn.Module):
    """Router, the held experts' stacks and the shared experts (``moe``)."""
    config: Cohere2MoeConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        B, S, D = h.shape
        I, n = cfg.intermediate_size, cfg.num_shared_experts
        rows = h.reshape(-1, D)
        router_logits = nn.Dense(
            cfg.num_experts, use_bias=False, dtype=jnp.float32,
            param_dtype=pdtype, name="gate")(rows.astype(jnp.float32))
        init = nn.initializers.lecun_normal(in_axis=1, out_axis=2,
                                            batch_axis=0)
        stack = lambda name, *shape: self.param(
            name, init, shape, pdtype).astype(dtype)
        out, _ = moe_layer(
            rows, router_logits, stack("w1", cfg.held, D, I),
            stack("w2", cfg.held, I, D), stack("w3", cfg.held, D, I),
            stack("shared_w1", n, D, I), stack("shared_w2", n, I, D),
            stack("shared_w3", n, D, I), cfg)
        return out.reshape(B, S, D)


def moe_layer(h, router_logits, w1, w2, w3, s1, s2, s3, cfg, live=None,
              kernel=False):
    """``(r + c [T, D], counts [held])``: the held experts' part of the
    routed sum plus the averaged shared experts, for rows ``h [T, D]``
    (``live [T]``: the rows that are routed at all; None: every row;
    ``kernel``: ``held_experts_apply``'s, the serving step's choice), and the
    copies that landed on each held expert."""
    with jax.named_scope(_names.SCOPE_MOE_ROUTER):
        topi, topw = route(router_logits, cfg.num_experts_per_tok,
                           cfg.expert_selection_fn, cfg.norm_topk_prob)
    with jax.named_scope(_names.SCOPE_MOE_EXPERTS):
        routed, counts = held_experts_apply(
            h, topi, topw, w1, w2, w3, first_expert=cfg.first_expert,
            experts=cfg.num_experts, live=live, kernel=kernel)
    with jax.named_scope(_names.SCOPE_MOE_SHARED):
        return routed + shared_experts(h, s1, s2, s3), counts


class Cohere2MoeLayer(nn.Module):
    config: Cohere2MoeConfig
    window: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = _Weight(name="input_layernorm")(x, cfg.layer_norm_eps)
        return x + Cohere2Attention(cfg, self.window, name="self_attn")(h) \
            + Cohere2MoeBlock(cfg, name="moe")(h)


class Cohere2MoeModel(nn.Module):
    """Causal LM, dense forward: ``__call__(input_ids)`` -> float32 logits
    ``[B, S, vocab]``."""
    config: Cohere2MoeConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        table = _Table(cfg.vocab_size, cfg.hidden_size,
                       jnp.dtype(cfg.param_dtype), name="embed_tokens")()
        x = table[input_ids].astype(jnp.dtype(cfg.dtype))
        for i, window in enumerate(cfg.layer_windows):
            x = Cohere2MoeLayer(cfg, window, name=f"layers_{i}")(x)
        x = _Weight(name="norm")(x, cfg.layer_norm_eps)
        return x.astype(jnp.float32) @ table.T.astype(jnp.float32) \
            * cfg.logit_scale


def tp_rules(config: Cohere2MoeConfig):
    """Sharding rules: attention like Llama's; the experts over "ep" on the
    expert axis, the shared experts over "tp" on their width."""
    tp = "tp"
    return {
        "q_proj/kernel": P(None, tp, None),
        "k_proj/kernel": P(None, tp, None),
        "v_proj/kernel": P(None, tp, None),
        "o_proj/kernel": P(tp, None),
        "moe/gate/kernel": P(None, None),
        "moe/w1": P("ep", None, tp),
        "moe/w3": P("ep", None, tp),
        "moe/w2": P("ep", tp, None),
        "moe/shared_w1": P(None, None, tp),
        "moe/shared_w3": P(None, None, tp),
        "moe/shared_w2": P(None, tp, None),
        "embed_tokens/weight": P(tp, None),
    }
