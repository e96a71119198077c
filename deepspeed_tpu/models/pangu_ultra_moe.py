"""openPangu-Ultra-MoE (``model_type: pangu_ultra_moe``): multi-head LATENT
attention, sandwich norms, leading dense layers and then sigmoid-routed
experts beside one shared expert.

Layer ``l``, with ``N`` an RMSNorm (``rms_norm_eps``), ``D`` hidden, ``H``
heads, ``r`` = ``kv_lora_rank``, ``dn`` / ``dr`` / ``dv`` the nope, rope and
value head sizes, ``E`` the router's width, ``k`` experts a token:

    a   = N_post_attn( MLA( N_in(x) ) );          x'  = x  + a    (sandwich)
    m   = N_post_mlp ( F_l( N_pre_mlp(x') ) );    x'' = x' + m
    F_l = SwiGLU of ``intermediate_size``            for l < first_k_dense_replace
    F_l(h) = SwiGLU_shared(h) + s * sum_{e in top-k} w_e SwiGLU_e(h)  otherwise
             sc = sigmoid(h W_r) in float32 over all E; top k of sc;
             w_e = sc_e / sum_top-k sc (norm_topk_prob); s = routed_scaling_factor
    MLA(h): c_q = N(h W_dq) [q_lora_rank];  q = c_q W_uq -> H x (q_n [dn] ; q_r [dr])
            (c_kv [r] ; k_r [dr]) = h W_dkv;  c = N(c_kv)
            q_r, k_r <- rope (half-split, theta ``rope_theta``), k_r ONE head for all
            k_i = (c W_uk,i [dn] ; k_r),  v_i = c W_uv,i [dv]
            p_i = causal softmax( q_i . k_i / sqrt(dn + dr) )
            out = concat_i(p_i v_i) W_o
    absorbed, the same numbers:  qlat_i = q_n,i W_uk,i^T [r]
            score = (qlat_i . c + q_r,i . k_r) / sqrt(dn + dr)
            olat_i = sum_j p_ij c_j [r];  o_i = olat_i W_uv,i
    logits = N_f(x_L) W_head                              (untied head)

What a cache has to keep of a token is the LATENT ROW ``(c [r] ; k_r [dr])``,
the same for every head (``kv_latent_dim``): ``PanguUltraMoeModel`` is the
dense forward in the expanded form (the tests, ``param_shapes``); serving is
``inference/v2/ragged_forward.pangu_ultra_moe_ragged_step`` in the absorbed
form over the paged latent cache.

**One chip's share.**  ``n_routed_experts`` is the ROUTER'S width;
``experts_held`` (default: all) and ``first_expert`` say which experts' stacks
this model holds (``moe/held_experts.py``): the routed sum runs over top-k ∩
held only, nothing stands in for the rest.

Leaves: ``self_attn/{q_a_proj [D, q_lora_rank], q_a_layernorm, q_b_proj
[q_lora_rank, H, dn + dr], kv_a_proj [D, r + dr], kv_a_layernorm [r],
k_b_proj [r, H, dn], v_b_proj [r, H, dv], o_proj [H * dv, D]}`` (the
published ``kv_b_proj`` as its two halves, which is how the absorbed form
reads it); ``mlp/{gate,up,down}_proj`` in a dense layer; ``moe/gate [D, E]``,
``moe/{w1,w3} [held, D, I]``, ``moe/w2 [held, I, D]`` and
``moe/shared_{gate,up,down}_proj`` in a routed one.
``num_nextn_predict_layers`` (the multi-token module after the last layer)
and ``num_key_value_heads`` are carried and used by nothing here.
"""

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..moe.held_experts import held_experts_apply, route
from ..telemetry import names as _names


@dataclass(frozen=True)
class PanguUltraMoeConfig:
    """The keys of the published ``config.json`` by their own names, and what
    a chip holds of a routed layer (``experts_held``, ``first_expert``)."""
    vocab_size: int = 153600
    hidden_size: int = 7680
    intermediate_size: int = 18432         # a leading dense layer's width
    moe_intermediate_size: int = 2048      # one expert's, routed and shared
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    num_key_value_heads: int = 128         # carried: the cache is latent
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256            # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    experts_held: Optional[int] = None     # None: all of them
    first_expert: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    sandwich_norm: bool = True
    num_nextn_predict_layers: int = 1      # carried and used by nothing
    rms_norm_eps: float = 1e-5
    rope_theta: float = 25600000.0
    max_position_embeddings: int = 131072
    attention_bias: bool = False
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self):
        if (not self.sandwich_norm or self.attention_bias
                or self.hidden_act != "silu" or self.tie_word_embeddings):
            raise ValueError(
                "PanguUltraMoeConfig: sandwich norms, no attention bias, "
                "silu and an untied head are what this model implements")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace lies outside the layers")
        if not 0 <= self.first_expert <= self.n_routed_experts - self.held:
            raise ValueError("the held experts lie outside the router")

    @property
    def held(self):
        return self.n_routed_experts if self.experts_held is None \
            else self.experts_held

    @property
    def kv_latent_dim(self):
        """What a cache keeps of a token in a layer: ``(c ; k_r)``.  The
        statement a paged cache lays its buffers out by
        (``inference/v2/ragged.BlockedKVCache``)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self):
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def routed(self, layer):
        return layer >= self.first_k_dense_replace


def pangu_ultra_moe_tiny(**overrides):
    """Test-scale config: one leading dense layer and four routed ones, 16
    experts of which 8 are held, 2 a token, 8 heads on a latent row of 32 +
    8."""
    return PanguUltraMoeConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5,
        first_k_dense_replace=1, num_attention_heads=8,
        num_key_value_heads=8, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=16, num_experts_per_tok=2, experts_held=8,
        rope_theta=100.0, max_position_embeddings=512, dtype="float32"),
        **overrides})


def rms_norm(x, weight, eps, scale=1.0):
    """RMSNorm, float32 inside, back in ``x``'s type; ``scale``: a factor on
    what comes out, applied before the rounding."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return (y if scale == 1.0 else y * scale).astype(x.dtype)


def rope_half(x, positions, theta):
    """``x [..., T, (heads,) d]`` turned by ``positions [..., T]`` in the
    half-split form (``x[i]`` with ``x[i + d/2]``), the angles made here in
    float32 (no table: 131 072 positions)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv
    if x.ndim == ang.ndim + 1:                 # a heads axis before d
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def mla_down(h, attn, positions, cfg, q_scale=1.0, kv_scale=1.0):
    """The low-rank half of MLA for rows ``h [..., T, D]`` at ``positions``:
    ``(q_n [..., T, H, dn], q_r [..., T, H, dr], latent [..., T, r + dr])``
    with both norms applied, ``q_r`` and the latent row's ``k_r`` turned.
    ``q_scale``: a factor on every head's query, both parts (it is linear in
    ``c_q``, and is applied there, inside the norm's float32);
    ``kv_scale``: one on the normed ``c`` (``k_r`` is not scaled)."""
    dtype, eps = h.dtype, cfg.rms_norm_eps
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    c_q = rms_norm(h @ attn["q_a_proj"]["kernel"].astype(dtype),
                   attn["q_a_layernorm"]["weight"], eps, q_scale)
    q = jnp.einsum("...tq,qhe->...the", c_q,
                   attn["q_b_proj"]["kernel"].astype(dtype))
    ckv = h @ attn["kv_a_proj"]["kernel"].astype(dtype)
    c = rms_norm(ckv[..., :r], attn["kv_a_layernorm"]["weight"], eps,
                 kv_scale)
    k_r = rope_half(ckv[..., r:], positions, cfg.rope_theta)
    q_r = rope_half(q[..., dn:], positions, cfg.rope_theta)
    return q[..., :dn], q_r, jnp.concatenate([c, k_r], axis=-1)


def mla_expanded(h, attn, cfg, q_scale=1.0, kv_scale=1.0):
    """MLA of ``h [B, S, D]`` in the EXPANDED form over the leaves ``attn``:
    per-head keys and values made from the latent rows, one causal softmax a
    head in float32, the output through ``o_proj``.  ``q_scale`` /
    ``kv_scale``: :func:`mla_down`'s."""
    dtype = h.dtype
    B, S, _ = h.shape
    r = cfg.kv_lora_rank
    pos = jnp.arange(S)
    q_n, q_r, latent = mla_down(h, attn, pos[None], cfg, q_scale, kv_scale)
    c, k_r = latent[..., :r], latent[..., r:]
    k_n = jnp.einsum("btc,chn->bthn", c,
                     attn["k_b_proj"]["kernel"].astype(dtype))
    v = jnp.einsum("btc,chv->bthv", c,
                   attn["v_b_proj"]["kernel"].astype(dtype))
    f32 = lambda x: x.astype(jnp.float32)
    scores = (jnp.einsum("bshn,bthn->bhst", f32(q_n), f32(k_n))
              + jnp.einsum("bshr,btr->bhst", f32(q_r), f32(k_r))) \
        * cfg.softmax_scale
    mask = pos[:, None] >= pos[None, :]
    probs = jax.nn.softmax(
        jnp.where(mask, scores, jnp.finfo(jnp.float32).min), axis=-1)
    out = jnp.einsum("bhst,bthv->bshv", probs, f32(v))
    return out.reshape(B, S, -1).astype(dtype) \
        @ attn["o_proj"]["kernel"].astype(dtype)


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def moe_layer(h, router_logits, moe, cfg, live=None, kernel=False):
    """``(F_l(h) [T, D], counts [held])`` of a routed layer for rows ``h [T,
    D]``: the shared expert plus the held experts' part of the scaled routed
    sum (``live [T]``: the rows that are routed at all; ``kernel``:
    ``held_experts_apply``'s), and the copies that landed on each held
    expert."""
    dtype = h.dtype
    with jax.named_scope(_names.SCOPE_MOE_ROUTER):
        topi, topw = route(router_logits, cfg.num_experts_per_tok, "sigmoid",
                           cfg.norm_topk_prob,
                           scale=cfg.routed_scaling_factor)
    with jax.named_scope(_names.SCOPE_MOE_EXPERTS):
        routed, counts = held_experts_apply(
            h, topi, topw, moe["w1"].astype(dtype), moe["w2"].astype(dtype),
            moe["w3"].astype(dtype), first_expert=cfg.first_expert,
            experts=cfg.n_routed_experts, live=live, kernel=kernel)
    with jax.named_scope(_names.SCOPE_MOE_SHARED):
        shared = swiglu(h, *(moe[f"shared_{n}_proj"]["kernel"].astype(dtype)
                             for n in ("gate", "up", "down")))
    return routed + shared, counts


class _Leaf(nn.Module):
    """One leaf ``<name>/<leaf>`` of ``shape``: a norm's scale (1-D, ones) or
    a projection's matrix ``[in, ...out]``."""
    leaf: str
    shape: tuple
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self):
        init = nn.initializers.ones if len(self.shape) == 1 else \
            nn.initializers.lecun_normal(
                in_axis=0, out_axis=tuple(range(1, len(self.shape))))
        return self.param(self.leaf, init, self.shape, self.param_dtype)


def _leaves(pdtype, kernels=(), weights=()):
    """``{name: {"kernel" | "weight": leaf}}`` made in the calling module."""
    out = {name: {"kernel": _Leaf("kernel", shape, pdtype, name=name)()}
           for name, shape in kernels}
    out.update({name: {"weight": _Leaf("weight", shape, pdtype, name=name)()}
                for name, shape in weights})
    return out


class PanguAttention(nn.Module):
    """MLA in the EXPANDED form: per-head keys and values made from the
    latent row, one causal softmax a head."""
    config: PanguUltraMoeConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        D = h.shape[-1]
        H, r = cfg.num_attention_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        attn = _leaves(
            jnp.dtype(cfg.param_dtype),
            kernels=(("q_a_proj", (D, cfg.q_lora_rank)),
                     ("q_b_proj", (cfg.q_lora_rank, H, dn + dr)),
                     ("kv_a_proj", (D, r + dr)), ("k_b_proj", (r, H, dn)),
                     ("v_b_proj", (r, H, dv)), ("o_proj", (H * dv, D))),
            weights=(("q_a_layernorm", (cfg.q_lora_rank, )),
                     ("kv_a_layernorm", (r, ))))
        return mla_expanded(h, attn, cfg)


class PanguMLP(nn.Module):
    """A leading dense layer's SwiGLU (``mlp``)."""
    config: PanguUltraMoeConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        D, I = cfg.hidden_size, cfg.intermediate_size
        mlp = _leaves(jnp.dtype(cfg.param_dtype), kernels=(
            ("gate_proj", (D, I)), ("up_proj", (D, I)),
            ("down_proj", (I, D))))
        return swiglu(h, *(mlp[f"{n}_proj"]["kernel"].astype(h.dtype)
                           for n in ("gate", "up", "down")))


class PanguMoeBlock(nn.Module):
    """Router, the held experts' stacks and the shared expert (``moe``)."""
    config: PanguUltraMoeConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        B, S, D = h.shape
        I = cfg.moe_intermediate_size
        Is = I * cfg.n_shared_experts
        rows = h.reshape(-1, D)
        moe = _leaves(pdtype, kernels=(
            ("gate", (D, cfg.n_routed_experts)),
            ("shared_gate_proj", (D, Is)), ("shared_up_proj", (D, Is)),
            ("shared_down_proj", (Is, D))))
        init = nn.initializers.lecun_normal(in_axis=1, out_axis=2,
                                            batch_axis=0)
        moe.update(w1=self.param("w1", init, (cfg.held, D, I), pdtype),
                   w2=self.param("w2", init, (cfg.held, I, D), pdtype),
                   w3=self.param("w3", init, (cfg.held, D, I), pdtype))
        router_logits = rows.astype(jnp.float32) \
            @ moe["gate"]["kernel"].astype(jnp.float32)
        out, _ = moe_layer(rows, router_logits, moe, cfg)
        return out.reshape(B, S, D)


class PanguUltraMoeLayer(nn.Module):
    config: PanguUltraMoeConfig
    routed: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        norms = _leaves(pdtype, weights=tuple(
            (name, (cfg.hidden_size, )) for name in (
                "input_layernorm", "post_attention_layernorm",
                "pre_mlp_layernorm", "post_mlp_layernorm")))
        norm = lambda y, name: rms_norm(y, norms[name]["weight"],
                                        cfg.rms_norm_eps)
        a = PanguAttention(cfg, name="self_attn")(norm(x, "input_layernorm"))
        x = x + norm(a, "post_attention_layernorm")
        h = norm(x, "pre_mlp_layernorm")
        m = PanguMoeBlock(cfg, name="moe")(h) if self.routed \
            else PanguMLP(cfg, name="mlp")(h)
        return x + norm(m, "post_mlp_layernorm")


class PanguUltraMoeModel(nn.Module):
    """Causal LM, dense forward: ``__call__(input_ids)`` -> float32 logits
    ``[B, S, vocab]``."""
    config: PanguUltraMoeConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype,
                     param_dtype=pdtype, name="embed_tokens")(input_ids)
        for i in range(cfg.num_hidden_layers):
            x = PanguUltraMoeLayer(cfg, cfg.routed(i), name=f"layers_{i}")(x)
        top = _leaves(pdtype, kernels=(
            ("lm_head", (cfg.hidden_size, cfg.vocab_size)), ),
            weights=(("norm", (cfg.hidden_size, )), ))
        x = rms_norm(x, top["norm"]["weight"], cfg.rms_norm_eps)
        return x.astype(jnp.float32) \
            @ top["lm_head"]["kernel"].astype(jnp.float32)


def tp_rules(config: PanguUltraMoeConfig):
    """Sharding rules: the per-head projections over "tp" on the heads, the
    low-rank ones replicated; the experts over "ep" on the expert axis."""
    tp = "tp"
    return {
        "q_a_proj/kernel": P(None, None),
        "kv_a_proj/kernel": P(None, None),
        "q_b_proj/kernel": P(None, tp, None),
        "k_b_proj/kernel": P(None, tp, None),
        "v_b_proj/kernel": P(None, tp, None),
        "o_proj/kernel": P(tp, None),
        "mlp/gate_proj/kernel": P(None, tp),
        "mlp/up_proj/kernel": P(None, tp),
        "mlp/down_proj/kernel": P(tp, None),
        "moe/gate/kernel": P(None, None),
        "moe/w1": P("ep", None, tp),
        "moe/w3": P("ep", None, tp),
        "moe/w2": P("ep", tp, None),
        "moe/shared_gate_proj/kernel": P(None, tp),
        "moe/shared_up_proj/kernel": P(None, tp),
        "moe/shared_down_proj/kernel": P(tp, None),
        "embed_tokens/embedding": P(tp, None),
        "lm_head/kernel": P(None, tp),
    }
