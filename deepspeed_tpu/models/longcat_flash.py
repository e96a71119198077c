"""LongCat-Flash-Chat (``attention_method: MLA``, ``zero_expert_type:
identity``): a layer with TWO latent-attention sublayers and two dense
feed-forwards, its routed experts on a SHORTCUT beside them (ScMoE), a
softmax router some of whose outputs are identity experts without weights.

Layer ``l``, with ``N`` an RMSNorm (``rms_norm_eps``), ``D`` hidden, ``H``
heads, ``r`` = ``kv_lora_rank``, ``dn`` / ``dr`` / ``dv`` the nope, rope and
value head sizes, ``E`` = ``n_routed_experts`` real experts, ``Z`` =
``zero_expert_num`` identity ones, ``k`` = ``moe_topk``:

    a1 = x  + MLA_0(N_in0(x))
    h1 = N_post0(a1)
    s  = MoE(h1)                         # the shortcut: kept, added at the end
    d1 = a1 + FFN_0(h1)                  # SwiGLU of ``ffn_hidden_size``
    a2 = d1 + MLA_1(N_in1(d1))
    h2 = N_post1(a2)
    x' = a2 + FFN_1(h2) + s

    MLA_i(h): c_q = N(h W_qa) [q_lora_rank]
            (q_n [dn] ; q_r [dr]) = (c_q W_qb) * sqrt(D / q_lora_rank)   a head
            (c [r] ; k_r [dr]) = h W_kva;  c' = N(c) * sqrt(D / r)       (k_r not scaled)
            q_r, k_r <- rope (half-split, theta ``rope_theta``), k_r ONE head for all
            k_i = (c' W_uk,i [dn] ; k_r),  v_i = c' W_uv,i [dv]
            p_i = causal softmax( q_i . k_i / sqrt(dn + dr) );  out = concat_i(p_i v_i) W_o
            (its own weights, and its own cache of rows (c' ; k_r))
    MoE(h): p = softmax(h W_r) in float32 over E + Z
            the k largest of p + b (``e_score_correction_bias``)
            w_e = routed_scaling_factor * p_e of the chosen, NOT renormalised
            sum over chosen e < E of w_e SwiGLU_e(h)   (``expert_ffn_hidden_size``)
            + (sum over chosen e >= E of w_e) h        (identity experts)
    logits = N_f(x_L) W_head                              (untied head)

The absorbed form of ``models/pangu_ultra_moe.py`` holds as it stands: the
two factors sit on ``c_q`` and ``c'``, before anything the forms differ in.
``LongcatFlashModel`` is the dense forward in the expanded form (the tests,
``param_shapes``); serving is
``inference/v2/ragged_forward.longcat_flash_ragged_step`` in the absorbed form
over the paged latent cache, which holds ``kv_cache_entries`` = TWO entries a
layer.

**One chip's share.**  ``n_routed_experts`` is the count of real experts the
ROUTER spans; ``experts_held`` (default: all) and ``first_expert`` say which
experts' stacks this model holds (``moe/held_experts.py``).  An identity
expert is neither here nor elsewhere: its part is computed where the token
lives, for every token, by every chip alike.

Leaves: ``self_attn_{0,1}/...`` as ``pangu_ultra_moe``'s ``self_attn``
(``k_b_proj [r, H, dn]`` and ``v_b_proj [r, H, dv]``, the two halves of the
published ``kv_b_proj`` as the absorbed form contracts them every step) but
for ``q_b_proj``, which is held ``[H (dn + dr), q_lora_rank]``: ``[out, in]``,
and ``mla_weights`` gives ``mla_down`` its ``[q_lora_rank, H, dn + dr]`` view.
The program has no use of its own for that one departure: it is made for the
benchmark's seeded weights (``perfbench/configs/longcat_flash_1chip.json``,
``assumed.up_projection_layout`` has the arithmetic and the chip's readings)
and goes when the generator has a rule for a scaled low-rank path.
``mlp_{0,1}/{gate,up,down}_proj``; ``{input,post_attention}_layernorm_{0,1}``;
``moe/gate [D, E + Z]``, ``moe/e_score_correction_bias [E + Z]``,
``moe/{w1,w3} [held, D, I]``, ``moe/w2 [held, I, D]``.
"""

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..moe.held_experts import held_experts_apply, route
from ..telemetry import names as _names
from .pangu_ultra_moe import _leaves, mla_expanded, rms_norm, swiglu


@dataclass(frozen=True)
class LongcatFlashConfig:
    """The keys of the published ``config.json`` by their own names, and what
    a chip holds of a layer's experts (``experts_held``, ``first_expert``)."""
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288           # each of a layer's two dense FFNs
    expert_ffn_hidden_size: int = 2048     # one routed expert's
    num_layers: int = 28                   # each with two attentions
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512            # the real experts the router spans
    zero_expert_num: int = 256             # identity experts behind them
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    experts_held: Optional[int] = None     # None: all of them
    first_expert: int = 0
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    max_position_embeddings: int = 131072
    attention_bias: bool = False
    attention_method: str = "MLA"
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self):
        if (self.attention_bias or self.attention_method != "MLA"
                or self.zero_expert_type != "identity"):
            raise ValueError(
                "LongcatFlashConfig: latent attention without bias and "
                "identity zero-experts are what this model implements")
        if not 0 <= self.first_expert <= self.n_routed_experts - self.held:
            raise ValueError("the held experts lie outside the router")

    @property
    def held(self):
        return self.n_routed_experts if self.experts_held is None \
            else self.experts_held

    @property
    def router_width(self):
        return self.n_routed_experts + self.zero_expert_num

    @property
    def num_hidden_layers(self):
        return self.num_layers

    @property
    def num_key_value_heads(self):
        """Carried for readers of a config: the cache is latent."""
        return self.num_attention_heads

    @property
    def kv_latent_dim(self):
        """What a cache entry keeps of a token: ``(c' ; k_r)``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def kv_cache_entries(self):
        """How many entries the paged cache holds: one an ATTENTION, two a
        layer (``inference/v2/engine_v2``: a model that does not say has one
        a layer)."""
        return 2 * self.num_layers

    @property
    def softmax_scale(self):
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def q_scale(self):
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self):
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1.0


def longcat_flash_tiny(**overrides):
    """Test-scale config: four layers (eight attentions), a router over 32
    real experts, 8 held, and 16 identity ones, 4 a token, 8 heads on a
    latent row of 32 + 8."""
    return LongcatFlashConfig(**{**dict(
        vocab_size=256, hidden_size=64, ffn_hidden_size=96,
        expert_ffn_hidden_size=32, num_layers=4, num_attention_heads=8,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=32,
        zero_expert_num=16, moe_topk=4, experts_held=8, rope_theta=100.0,
        max_position_embeddings=512, dtype="float32"), **overrides})


def moe_branch(h, moe, cfg, live=None, kernel=False):
    """``(MoE(h) [T, D], counts [held], zero copies)`` of the shortcut branch
    for rows ``h [T, D]``: the held experts' part of the routed sum
    (``live [T]``: the rows that are routed at all; ``kernel``:
    ``held_experts_apply``'s) plus the identity experts' weighted copy of
    ``h``, the copies that landed on each held expert, and the (live row,
    chosen identity expert) pairs."""
    dtype = h.dtype
    with jax.named_scope(_names.SCOPE_MOE_ROUTER):
        router_logits = h.astype(jnp.float32) \
            @ moe["gate"]["kernel"].astype(jnp.float32)
        topi, topw = route(router_logits, cfg.moe_topk, "softmax",
                           norm_topk=False, scale=cfg.routed_scaling_factor,
                           bias=moe["e_score_correction_bias"])
    with jax.named_scope(_names.SCOPE_MOE_EXPERTS):
        routed, counts = held_experts_apply(
            h, topi, topw, moe["w1"].astype(dtype), moe["w2"].astype(dtype),
            moe["w3"].astype(dtype), first_expert=cfg.first_expert,
            experts=cfg.router_width, live=live, kernel=kernel)
    with jax.named_scope(_names.SCOPE_MOE_ZERO):
        identity = topi >= cfg.n_routed_experts
        weight = jnp.sum(jnp.where(identity, topw, 0.0), axis=-1)
        zero = (h.astype(jnp.float32) * weight[:, None]).astype(dtype)
        if live is not None:
            identity &= live[:, None]
    return routed + zero, counts, jnp.sum(identity, dtype=jnp.int32)


def mla_weights(attn, cfg):
    """An attention's leaves as ``mla_down`` and the absorbed form read them:
    ``q_b_proj [H (dn + dr), q_lora_rank]`` as its ``[q_lora_rank, H, dn +
    dr]`` view; the rest as they are."""
    w = attn["q_b_proj"]["kernel"]
    view = w.reshape(cfg.num_attention_heads, -1, w.shape[-1])
    return {**attn, "q_b_proj": {"kernel": view.transpose(2, 0, 1)}}


def _attn_leaves(cfg):
    D, H, r = cfg.hidden_size, cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return mla_weights(_leaves(
        jnp.dtype(cfg.param_dtype),
        kernels=(("q_a_proj", (D, cfg.q_lora_rank)),
                 ("q_b_proj", (H * (dn + dr), cfg.q_lora_rank)),
                 ("kv_a_proj", (D, r + dr)),
                 ("k_b_proj", (r, H, dn)), ("v_b_proj", (r, H, dv)),
                 ("o_proj", (H * dv, D))),
        weights=(("q_a_layernorm", (cfg.q_lora_rank, )),
                 ("kv_a_layernorm", (r, )))), cfg)


class LongcatAttention(nn.Module):
    """One of a layer's two MLAs in the EXPANDED form."""
    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        return mla_expanded(h, _attn_leaves(cfg), cfg, cfg.q_scale,
                            cfg.kv_scale)


class LongcatMLP(nn.Module):
    """One of a layer's two dense SwiGLUs."""
    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        D, I = cfg.hidden_size, cfg.ffn_hidden_size
        mlp = _leaves(jnp.dtype(cfg.param_dtype), kernels=(
            ("gate_proj", (D, I)), ("up_proj", (D, I)),
            ("down_proj", (I, D))))
        return swiglu(h, *(mlp[f"{n}_proj"]["kernel"].astype(h.dtype)
                           for n in ("gate", "up", "down")))


class LongcatMoeBlock(nn.Module):
    """Router, its choice bias and the held experts' stacks (``moe``)."""
    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        B, S, D = h.shape
        I = cfg.expert_ffn_hidden_size
        moe = _leaves(pdtype, kernels=(("gate", (D, cfg.router_width)), ))
        init = nn.initializers.lecun_normal(in_axis=1, out_axis=2,
                                            batch_axis=0)
        moe.update(
            e_score_correction_bias=self.param(
                "e_score_correction_bias", nn.initializers.zeros,
                (cfg.router_width, ), pdtype),
            w1=self.param("w1", init, (cfg.held, D, I), pdtype),
            w2=self.param("w2", init, (cfg.held, I, D), pdtype),
            w3=self.param("w3", init, (cfg.held, D, I), pdtype))
        out, _, _ = moe_branch(h.reshape(-1, D), moe, cfg)
        return out.reshape(B, S, D)


class LongcatFlashLayer(nn.Module):
    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norms = _leaves(jnp.dtype(cfg.param_dtype), weights=tuple(
            (f"{name}_{i}", (cfg.hidden_size, )) for i in (0, 1)
            for name in ("input_layernorm", "post_attention_layernorm")))
        norm = lambda y, name: rms_norm(y, norms[name]["weight"],
                                        cfg.rms_norm_eps)
        shortcut = None
        for i in (0, 1):
            x = x + LongcatAttention(cfg, name=f"self_attn_{i}")(
                norm(x, f"input_layernorm_{i}"))
            h = norm(x, f"post_attention_layernorm_{i}")
            if i == 0:
                shortcut = LongcatMoeBlock(cfg, name="moe")(h)
            x = x + LongcatMLP(cfg, name=f"mlp_{i}")(h)
        return x + shortcut


class LongcatFlashModel(nn.Module):
    """Causal LM, dense forward: ``__call__(input_ids)`` -> float32 logits
    ``[B, S, vocab]``."""
    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype,
                     param_dtype=pdtype, name="embed_tokens")(input_ids)
        for i in range(cfg.num_layers):
            x = LongcatFlashLayer(cfg, name=f"layers_{i}")(x)
        top = _leaves(pdtype, kernels=(
            ("lm_head", (cfg.hidden_size, cfg.vocab_size)), ),
            weights=(("norm", (cfg.hidden_size, )), ))
        x = rms_norm(x, top["norm"]["weight"], cfg.rms_norm_eps)
        return x.astype(jnp.float32) \
            @ top["lm_head"]["kernel"].astype(jnp.float32)


def tp_rules(config: LongcatFlashConfig):
    """Sharding rules: the per-head projections over "tp" on the heads, the
    low-rank ones replicated; the experts over "ep" on the expert axis."""
    tp = "tp"
    return {
        "q_a_proj/kernel": P(None, None),
        "kv_a_proj/kernel": P(None, None),
        "q_b_proj/kernel": P(tp, None),
        "k_b_proj/kernel": P(None, tp, None),
        "v_b_proj/kernel": P(None, tp, None),
        "o_proj/kernel": P(tp, None),
        "gate_proj/kernel": P(None, tp),
        "up_proj/kernel": P(None, tp),
        "down_proj/kernel": P(tp, None),
        "moe/gate/kernel": P(None, None),
        "moe/e_score_correction_bias": P(None),
        "moe/w1": P("ep", None, tp),
        "moe/w3": P("ep", None, tp),
        "moe/w2": P("ep", tp, None),
        "embed_tokens/embedding": P(tp, None),
        "lm_head/kernel": P(None, tp),
    }
