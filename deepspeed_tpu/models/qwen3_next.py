"""Qwen3-Next (``model_type: qwen3_next``; Qwen3-Next-80B-A3B-Instruct): three
Gated DeltaNet layers in four beside one gated softmax-attention layer, an
expert layer with a gated shared expert in every layer.

Layer ``i`` (0-based) is full attention iff ``(i + 1) %
full_attention_interval == 0``, else linear attention; every layer ``x = x +
mixer(N(x))``, ``x = x + moe(N(x))``.  ``N`` is the ZERO-CENTRED RMSNorm in
float32, ``x rsqrt(mean(x^2) + eps) (1 + w)`` (input, post-attention, final,
and a head's q / k):

    Gated DeltaNet (``Hk`` key heads, ``Hv`` value heads, both of 128; K taps):
        [q | k | v | z] = h W_qkvz              (D -> 2 Hk 128 + 2 Hv 128)
        [b | a]         = h W_ba                (D -> 2 Hv)
        (q | k | v) = silu( conv_K(q | k | v) ) causal, depthwise, NO bias
        q = l2(q) / sqrt(128);  k = l2(k)       (eps 1e-6); value heads 2j and
                                                2j + 1 read key head j
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   (float32)
        a head's state S [128 (key), 128 (value)], float32, a token:
            S  = exp(g_t) S
            d  = beta_t (v_t - S^T k_t)
            S  = S + k_t d^T
            o_t = S^T q_t
        out = ( w o rsqrt(mean(o^2) + eps) silu(z) ) W_out     (a plain ``w``)
    gated attention (``H`` query heads on ``Hkv`` KV heads of 256):
        [query | gate] a head = h W_q;  k = h W_k;  v = h W_v
        query, k = N_head(query), N_head(k), then a rotary on the FIRST
        ``partial_rotary_factor x 256`` values of a head (half-rotation, theta
        ``rope_theta``, no scaling); causal softmax / sqrt(256), K and V paged
        out = ( attn * sigmoid(gate) ) W_o                      (no biases)
    expert layer (``E`` the router's width, ``k`` a token):
        p = softmax(h W_r) in float32;  S = top-k;  w_e = p_e / sum_S p
        r = sum_{e in S, held} w_e W2_e (silu(W1_e h) * W3_e h)
        c = sigmoid(h . w_g) V2 (silu(V1 h) * V3 h)             (every layer)
    logits = N_f(x_L) W_head                                    (untied)

**The chunk form** of the delta rule (:func:`delta_rule_chunk`; it EQUALS the
recurrence, ``tests/unit/inference/test_qwen3_next.py``): over a chunk of ``C``
tokens with ``G_i = sum_{j <= i} g_j``,

    A  = -tril_strict( (beta k) k^T * exp(G_i - G_j) )
    T  = (I - A)^-1          (A is nilpotent: T = (I + A)(I + A^2)(I + A^4) ...,
                              the same matrix forward substitution gives)
    u  = T (beta v);  w = T (beta k exp(G))
    v' = u - w S
    o  = (q exp(G)) S + tril( q k^T * exp(G_i - G_j) ) v'
    S  = exp(G_C) S + (k exp(G_C - G))^T v'

``GDN_CHUNK`` (64) is the implementation's, not the model's.  Its products
read float32 at the HIGHEST precision: a run's state is carried from chunk to
chunk in float32 and never rounded inside a run.

What a sequence carries from token to token in a Gated DeltaNet layer is
FIXED in size: the state ``[Hv, 128, 128]`` in FLOAT32 (the published
implementation returns its last state in float32) and the convolution's last
``K - 1`` inputs in the model's dtype: ``Qwen3NextConfig.recurrent_state`` is
the statement a cache lays those layers' entries out by, shapes AND types
(``inference/v2/ragged.BlockedKVCache``).

**One chip's share.**  ``num_experts`` is the router's width, ``experts_held``
/ ``first_expert`` what this chip holds of a layer (``moe/held_experts.py``);
the mixers, the router, the shared expert and its gate are whole on every
chip.  The published config names no key for its multi-token-prediction
module: it is not built here.

``Qwen3NextModel`` is the dense forward (the tests, ``param_shapes``): the
recurrence as written above; serving is
``inference/v2/ragged_forward.qwen3_next_ragged_step``.

Leaves (which way a matrix is held changes no number of a trained model):
``embed_tokens/embedding [V, D]``, ``lm_head/kernel [D, V]``, ``norm/weight
[D, 1]``; ``layers_<i>/{input_layernorm,post_attention_layernorm}/weight [D,
1]``; ``linear_attn/{in_proj_qkvz/kernel [D, 2 Hk 128 + 2 Hv 128]`` (q | k | v
| z as leaves' columns of their own, each head-major: the published
interleaving by key-head group is bookkeeping), ``in_proj_ba/kernel [D, 2
Hv]`` (b | a), ``conv1d/weight [K, 2 Hk 128 + Hv 128]`` (tap-major: row ``K -
1`` multiplies the current token), ``A_log [1, Hv]``, ``dt_bias [1, Hv]``,
``norm/weight [128]``, ``out_proj/kernel [Hv 128, D]}``; ``self_attn/{q_proj/
kernel [D, H, 2 x 256]`` (a head's query | gate), ``{k,v}_proj/kernel [D, Hkv,
256]``, ``{q,k}_norm/weight [256]``, ``o_proj/kernel [H 256, D]}``;
``moe/{gate/kernel [D, E], w1, w3 [held, D, I], w2 [held, I, D], shared_w1,
shared_w3 [1, D, Is], shared_w2 [1, Is, D], shared_gate/kernel [D, 1]}``.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..moe.held_experts import held_experts_apply, route
from ..telemetry import names as _names
from .jamba import _Leaves, _leaf

#: tokens of a chunk of the delta rule's chunk form (the implementation's)
GDN_CHUNK = 64
L2_EPS = 1e-6
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Qwen3NextConfig:
    """The keys of the published ``config.json`` by their own names, and
    what one chip holds of a layer's experts."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 5120          # carried: no layer is dense
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rope_scaling: object = None
    max_position_embeddings: int = 262144
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    num_experts: int = 512                 # the router's width
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    experts_held: int = 0                  # 0: every expert
    first_expert: int = 0
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self):
        if (self.decoder_sparse_step != 1 or tuple(self.mlp_only_layers)
                or self.hidden_act != "silu" or self.tie_word_embeddings
                or self.use_sliding_window or self.rope_scaling):
            raise ValueError(
                "Qwen3NextConfig: an expert layer in every layer, silu, an "
                "untied head, no sliding window and no rotary scaling are "
                "what this model implements")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.linear_num_value_heads % self.linear_num_key_heads \
                or self.rotary_dim % 2:
            raise ValueError("heads do not divide")
        if self.first_expert + self.held > self.num_experts:
            raise ValueError("the held experts lie past the router's width")

    @property
    def held(self):
        return self.experts_held or self.num_experts

    @property
    def rotary_dim(self):
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_dim(self):
        """Channels the convolution passes: ``(q | k | v)``."""
        return 2 * self.linear_num_key_heads * self.linear_key_head_dim \
            + self.value_dim

    @property
    def value_dim(self):
        return self.linear_num_value_heads * self.linear_value_head_dim

    def is_attention(self, layer):
        return (layer + 1) % self.full_attention_interval == 0

    @property
    def layer_kinds(self):
        """``"pages"`` (a full-attention layer: K/V a token) or ``"state"``
        (a Gated DeltaNet layer: a fixed row a sequence), a layer."""
        return tuple("pages" if self.is_attention(i) else "state"
                     for i in range(self.num_hidden_layers))

    @property
    def recurrent_state(self):
        """What a cache keeps of a SEQUENCE in a ``"state"`` layer, as the
        shapes AND types of one sequence's row: the convolution's last ``K -
        1`` inputs in the model's dtype and the delta rule's matrix state a
        value head in float32; ``forms``: the layer walks a run of several
        tokens in chunks and a run of one as one update of its row, and the
        engine counts the tokens by form."""
        return {"kinds": self.layer_kinds,
                "conv": (self.linear_conv_kernel_dim - 1, self.conv_dim),
                "ssm": (self.linear_num_value_heads,
                        self.linear_key_head_dim, self.linear_value_head_dim),
                "dtypes": {"ssm": "float32"},
                "forms": ("chunk", "slot")}


def qwen3_next_tiny(**overrides):
    """Test-scale config: two periods of 4 layers, 2 key and 4 value heads of
    16, 4 query heads on 2 KV heads of 16 (8 of them turned), 16 experts, 4 a
    token, 8 held."""
    return Qwen3NextConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, partial_rotary_factor=0.5, rope_theta=10000.0,
        linear_key_head_dim=16, linear_value_head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=4, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, experts_held=8,
        max_position_embeddings=512, dtype="float32"), **overrides})


# ------------------------------------------------------------------ norms
def rms_norm(x, weight, eps):
    """The zero-centred RMSNorm over the last axis, ``(1 + w)``, float32
    inside, in ``x``'s type; ``weight [n, 1]`` or ``[n]``."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * (1.0 + weight.astype(jnp.float32).reshape(-1))).astype(x.dtype)


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


# --------------------------------------------------------- Gated DeltaNet
def gdn_in_proj(h, mp, cfg):
    """``(mixed [..., conv_dim], z [..., value_dim])`` in the model's dtype
    and ``(b, a) [..., Hv]`` float32, of normed rows ``h``."""
    dtype = jnp.dtype(cfg.dtype)
    h = h.astype(dtype)
    qkvz = jnp.dot(h, mp["in_proj_qkvz"]["kernel"].astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)
    ba = jnp.dot(h, mp["in_proj_ba"]["kernel"].astype(dtype),
                 preferred_element_type=jnp.float32)
    hv = cfg.linear_num_value_heads
    return (qkvz[..., :cfg.conv_dim], qkvz[..., cfg.conv_dim:],
            ba[..., :hv], ba[..., hv:])


def gdn_conv_out(acc, cfg):
    """``silu`` of the convolution's float32 sums, in the model's dtype."""
    return jax.nn.silu(acc).astype(jnp.dtype(cfg.dtype))


def gdn_rule_inputs(u, b, a, mp, cfg):
    """What the delta rule reads of the convolved rows ``u [..., conv_dim]``,
    all float32: ``q, k [..., Hv, 128]`` (L2-normalised, ``q`` scaled, a key
    head repeated to its value heads), ``v [..., Hv, 128]``, ``g`` and
    ``beta [..., Hv]``."""
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    u = u.astype(jnp.float32)
    lead = u.shape[:-1]
    q = u[..., :hk * dk].reshape(lead + (hk, dk))
    k = u[..., hk * dk:2 * hk * dk].reshape(lead + (hk, dk))
    v = u[..., 2 * hk * dk:].reshape(lead + (hv, dv))
    q = jnp.repeat(l2_norm(q) * dk ** -0.5, hv // hk, axis=-2)
    k = jnp.repeat(l2_norm(k), hv // hk, axis=-2)
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    g = -jnp.exp(mp["A_log"].astype(jnp.float32)[0]) * jax.nn.softplus(
        a.astype(jnp.float32) + mp["dt_bias"].astype(jnp.float32)[0])
    return q, k, v, g, beta


def delta_rule_token(q, k, v, g, beta, state):
    """ONE token of the recurrence for every leading index alike: ``q, k, v
    [..., 128]``, ``g, beta [...]``, ``state [..., 128 (key), 128 (value)]``
    float32 -> ``(o [..., 128], new state)``.  Element-wise products and sums
    over the key axis (no matrix unit: the form is bound by the state's
    bytes); both sums read the old state, so that it is read once for them
    and once for its update."""
    decay = jnp.exp(g)[..., None]
    sk = decay * jnp.sum(state * k[..., :, None], axis=-2)
    sq = decay * jnp.sum(state * q[..., :, None], axis=-2)
    d = beta[..., None] * (v - sk)
    o = sq + jnp.sum(k * q, axis=-1, keepdims=True) * d
    return o, decay[..., None] * state + k[..., :, None] * d[..., None, :]


def delta_rule_recurrence(q, k, v, g, beta, state):
    """The recurrence as written in the module docstring over the tokens of
    ONE sequence: ``q, k, v [S, Hv, 128]``, ``g, beta [S, Hv]``, ``state [Hv,
    128, 128]`` -> ``(o [S, Hv, 128], last state)``."""
    def token(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = s * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                                             precision=_HIGHEST))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=_HIGHEST)

    state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
    return o, state


def delta_rule_chunk(q, k, v, g, beta, state):
    """The chunk form over ONE chunk: ``q, k, v [C, Hv, 128]``, ``g, beta [C,
    Hv]``, ``state [Hv, 128, 128]`` float32 -> ``(o [C, Hv, 128], state after
    the chunk)``; matrix products in float32 at the highest precision.  A row
    with ``k = v = beta = g = 0`` moves nothing (padding past a run's end)."""
    C = q.shape[0]
    mm = lambda spec, a, b: jnp.einsum(spec, a, b, precision=_HIGHEST)
    G = jnp.cumsum(g, axis=0)                                  # [C, Hv]
    i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    # exp(G_i - G_j) a head where i >= j (it is at most 1), 0 above
    decay = jnp.exp(jnp.where((i >= j)[None], G.T[:, :, None]
                              - G.T[:, None, :], -jnp.inf))   # [Hv, C, C]
    kb, vb = k * beta[..., None], v * beta[..., None]
    A = -mm("ihd,jhd->hij", kb, k) * jnp.where(i > j, decay, 0)
    # (I - A)^-1 of a strictly lower triangular A: the product of (I + A^(2^n))
    T, Pw = jnp.eye(C, dtype=A.dtype) + A, A
    for _ in range(max(C - 1, 1).bit_length() - 1):
        Pw = mm("hij,hjk->hik", Pw, Pw)
        T = T + mm("hij,hjk->hik", T, Pw)
    eG = jnp.exp(G)[..., None]                                 # [C, Hv, 1]
    u = mm("hij,jhd->ihd", T, vb)
    w = mm("hij,jhd->ihd", T, kb * eG)
    vp = u - mm("ihk,hkv->ihv", w, state)
    attn = mm("ihd,jhd->hij", q, k) * decay
    o = mm("ihk,hkv->ihv", q * eG, state) + mm("hij,jhv->ihv", attn, vp)
    last = G[-1]                                               # [Hv]
    state = jnp.exp(last)[:, None, None] * state + mm(
        "ihk,ihv->hkv", k * jnp.exp(last[None] - G)[..., None], vp)
    return o, state


def gdn_gate_out(o, z, mp, cfg):
    """``( w o rsqrt(mean(o^2) + eps) silu(z) ) W_out`` of the rule's outputs
    ``o [..., Hv, 128]`` (float32), in the model's dtype."""
    dtype = jnp.dtype(cfg.dtype)
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = mp["norm"]["weight"].astype(jnp.float32) \
        * (o * jax.lax.rsqrt(var + cfg.rms_norm_eps))
    o = o * jax.nn.silu(z.astype(jnp.float32).reshape(o.shape))
    o = o.reshape(o.shape[:-2] + (cfg.value_dim, )).astype(dtype)
    return jnp.dot(o, mp["out_proj"]["kernel"].astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)


def gdn_mixer(h, mp, cfg):
    """The Gated DeltaNet mixer over whole sequences ``h [B, T, D]`` from a
    zero state: the dense forward (the recurrence, a token at a time)."""
    K = cfg.linear_conv_kernel_dim
    mixed, z, b, a = gdn_in_proj(h, mp, cfg)
    w = mp["conv1d"]["weight"].astype(jnp.float32)             # [K, C]
    xp = jnp.pad(mixed, ((0, 0), (K - 1, 0), (0, 0))).astype(jnp.float32)
    T = h.shape[1]
    u = gdn_conv_out(sum(w[t] * xp[:, t:t + T] for t in range(K)), cfg)
    q, k, v, g, beta = gdn_rule_inputs(u, b, a, mp, cfg)
    zero = jnp.zeros((cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim), jnp.float32)
    o = jax.vmap(lambda *rows: delta_rule_recurrence(*rows, zero)[0])(
        q, k, v, g, beta)
    return gdn_gate_out(o, z, mp, cfg)


# -------------------------------------------------------- gated attention
def rotary_half(x, positions, theta, rotary_dim):
    """``x [..., heads, Dh]`` with the FIRST ``rotary_dim`` values of a head
    turned by ``positions [...]``, half-rotation (value ``i`` with value ``i +
    rotary_dim / 2``); float32 inside, no table."""
    half = rotary_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                           / rotary_dim))
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:rotary_dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x32[..., rotary_dim:]], axis=-1).astype(x.dtype)


def head_norms(ap, cfg):
    """``(q, k) -> (N_head(q), N_head(k))`` of an attention layer's leaves
    (what ``ragged_forward._ragged_attention_block`` takes as ``qk_norm``)."""
    return lambda q, k: (
        rms_norm(q, ap["q_norm"]["weight"], cfg.rms_norm_eps),
        rms_norm(k, ap["k_norm"]["weight"], cfg.rms_norm_eps))


def attention_mixer(h, ap, cfg):
    """The gated attention over whole sequences ``h [B, T, D]``: dense."""
    dtype = jnp.dtype(cfg.dtype)
    H, Hkv, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    B, T, _ = h.shape
    proj = lambda name: jnp.einsum(
        "btd,dhk->bthk", h.astype(dtype), ap[name]["kernel"].astype(dtype),
        preferred_element_type=jnp.float32).astype(dtype)
    qg, k, v = proj("q_proj"), proj("k_proj"), proj("v_proj")
    q, gate = qg[..., :Dh], qg[..., Dh:]
    q, k = head_norms(ap, cfg)(q, k)
    pos = jnp.arange(T)[None]
    q = rotary_half(q, pos, cfg.rope_theta, cfg.rotary_dim)
    k = rotary_half(k, pos, cfg.rope_theta, cfg.rotary_dim)
    rep = H // Hkv
    s = jnp.einsum("btkgd,bskd->bkgts",
                   q.reshape(B, T, Hkv, rep, Dh).astype(jnp.float32),
                   k.astype(jnp.float32), precision=_HIGHEST) * Dh ** -0.5
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(mask, s, jnp.finfo(jnp.float32).min), -1)
    o = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32),
                   precision=_HIGHEST).reshape(B, T, H, Dh).astype(dtype)
    o = (o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dtype)) \
        .reshape(B, T, H * Dh)
    return jnp.dot(o, ap["o_proj"]["kernel"].astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)


# ----------------------------------------------------------- expert layer
def gated_shared_expert(h, moe, dtype):
    """``sigmoid(h . w_g) V2 (silu(V1 h) * V3 h)`` of rows ``h [T, D]``."""
    s = lambda name: moe[name][0].astype(dtype)
    act = jax.nn.silu(h @ s("shared_w1")) * (h @ s("shared_w3"))
    gate = jax.nn.sigmoid(jnp.dot(
        h, moe["shared_gate"]["kernel"].astype(dtype),
        preferred_element_type=jnp.float32))
    return ((act @ s("shared_w2")).astype(jnp.float32) * gate).astype(dtype)


def moe_layer(h, moe, cfg, live=None, kernel=False):
    """``(r + c [T, D], counts [held])``: the held experts' part of the routed
    sum plus the gated shared expert, for rows ``h [T, D]`` (``live [T]``:
    the rows that are routed at all; ``kernel``: ``held_experts_apply``'s, the
    serving step's choice), and the copies that landed on each held expert."""
    dtype = jnp.dtype(cfg.dtype)
    stack = lambda name: moe[name].astype(dtype)
    with jax.named_scope(_names.SCOPE_MOE_ROUTER):
        router_logits = h.astype(jnp.float32) \
            @ moe["gate"]["kernel"].astype(jnp.float32)
        topi, topw = route(router_logits, cfg.num_experts_per_tok, "softmax",
                           cfg.norm_topk_prob)
    with jax.named_scope(_names.SCOPE_MOE_EXPERTS):
        routed, counts = held_experts_apply(
            h, topi, topw, stack("w1"), stack("w2"), stack("w3"),
            first_expert=cfg.first_expert, experts=cfg.num_experts,
            live=live, kernel=kernel)
    with jax.named_scope(_names.SCOPE_MOE_SHARED):
        return routed + gated_shared_expert(h, moe, dtype), counts


# ---------------------------------------------------------- dense forward
class Qwen3NextGdn(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        D, Hv, K = (cfg.hidden_size, cfg.linear_num_value_heads,
                    cfg.linear_conv_kernel_dim)
        pd = jnp.dtype(cfg.param_dtype)
        mod = lambda name, **shapes: _Leaves(tuple(shapes.items()), pd,
                                             name=name)()
        mp = {"in_proj_qkvz": mod("in_proj_qkvz", kernel=(
                  D, cfg.conv_dim + cfg.value_dim)),
              "in_proj_ba": mod("in_proj_ba", kernel=(D, 2 * Hv)),
              "conv1d": mod("conv1d", weight=(K, cfg.conv_dim)),
              "norm": mod("norm", weight=(cfg.linear_value_head_dim, )),
              "out_proj": mod("out_proj", kernel=(cfg.value_dim, D))}
        # drawn wide (fan-in 1): a trained A and dt span decades
        for name in ("A_log", "dt_bias"):
            mp[name] = self.param(name, nn.initializers.normal(1.0),
                                  (1, Hv), pd)
        return gdn_mixer(h, mp, cfg)


class Qwen3NextAttention(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        D, H, Hkv, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                         cfg.num_key_value_heads, cfg.head_dim)
        pd = jnp.dtype(cfg.param_dtype)
        ap = {name: {"kernel": _leaf(pd, name, shape)} for name, shape in (
            ("q_proj", (D, H, 2 * Dh)), ("k_proj", (D, Hkv, Dh)),
            ("v_proj", (D, Hkv, Dh)), ("o_proj", (H * Dh, D)))}
        for name in ("q_norm", "k_norm"):
            ap[name] = {"weight": _leaf(pd, name, (Dh, ), "weight")}
        return attention_mixer(h, ap, cfg)


class Qwen3NextMoe(nn.Module):
    """Router, the held experts' stacks, the shared expert and its gate."""
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        pd = jnp.dtype(cfg.param_dtype)
        D, I, Is = (cfg.hidden_size, cfg.moe_intermediate_size,
                    cfg.shared_expert_intermediate_size)
        init = nn.initializers.lecun_normal(in_axis=1, out_axis=2,
                                            batch_axis=0)
        stack = lambda name, *shape: self.param(name, init, shape, pd)
        moe = {"gate": {"kernel": _leaf(pd, "gate", (D, cfg.num_experts))},
               "w1": stack("w1", cfg.held, D, I),
               "w2": stack("w2", cfg.held, I, D),
               "w3": stack("w3", cfg.held, D, I),
               "shared_w1": stack("shared_w1", 1, D, Is),
               "shared_w2": stack("shared_w2", 1, Is, D),
               "shared_w3": stack("shared_w3", 1, D, Is),
               "shared_gate": {"kernel": _leaf(pd, "shared_gate", (D, 1))}}
        out, _ = moe_layer(h.reshape(-1, D), moe, cfg)
        return out.reshape(h.shape)


class _Table(nn.Module):
    """The token table as the leaf ``<name>/embedding [rows, width]``, drawn
    at std 1 (the benchmark's generator draws that name so too)."""
    rows: int
    width: int
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self):
        return self.param("embedding", nn.initializers.normal(1.0),
                          (self.rows, self.width), self.param_dtype)


class Qwen3NextLayer(nn.Module):
    config: Qwen3NextConfig
    attention: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        pd, dtype = jnp.dtype(cfg.param_dtype), jnp.dtype(cfg.dtype)
        norm = lambda y, name: rms_norm(
            y, _leaf(pd, name, (cfg.hidden_size, 1), "weight"),
            cfg.rms_norm_eps)
        h = norm(x, "input_layernorm").astype(dtype)
        x = x + (Qwen3NextAttention(cfg, name="self_attn")(h)
                 if self.attention
                 else Qwen3NextGdn(cfg, name="linear_attn")(h)).astype(x.dtype)
        h = norm(x, "post_attention_layernorm").astype(dtype)
        return x + Qwen3NextMoe(cfg, name="moe")(h).astype(x.dtype)


class Qwen3NextModel(nn.Module):
    """Causal LM, dense forward: ``__call__(input_ids)`` -> float32 logits
    ``[B, T, vocab]``."""
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        dtype, pd = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        table = _Table(cfg.vocab_size, cfg.hidden_size, pd,
                       name="embed_tokens")()
        x = table[input_ids].astype(dtype)
        for i in range(cfg.num_hidden_layers):
            x = Qwen3NextLayer(cfg, cfg.is_attention(i),
                               name=f"layers_{i}")(x)
        x = rms_norm(x, _leaf(pd, "norm", (cfg.hidden_size, 1), "weight"),
                     cfg.rms_norm_eps)
        return jnp.dot(x.astype(dtype),
                       _leaf(pd, "lm_head", (cfg.hidden_size,
                                             cfg.vocab_size)).astype(dtype),
                       preferred_element_type=jnp.float32)


def tp_rules(config: Qwen3NextConfig):
    """Sharding rules for TRAINING-style tensor parallelism: attention like
    Llama's, the experts over "ep" on the expert axis, a DeltaNet layer's
    value heads over "tp".  (The serving engine raises for tp > 1 with this
    model: a state row is not sharded, ``engine_v2.py``.)"""
    tp = "tp"
    return {
        "q_proj/kernel": P(None, tp, None),
        "k_proj/kernel": P(None, tp, None),
        "v_proj/kernel": P(None, tp, None),
        "o_proj/kernel": P(tp, None),
        "in_proj_qkvz/kernel": P(None, None),
        "in_proj_ba/kernel": P(None, None),
        "conv1d/weight": P(None, None),
        "out_proj/kernel": P(tp, None),
        "moe/gate/kernel": P(None, None),
        "moe/w1": P("ep", None, tp),
        "moe/w3": P("ep", None, tp),
        "moe/w2": P("ep", tp, None),
        "moe/shared_w1": P(None, None, tp),
        "moe/shared_w3": P(None, None, tp),
        "moe/shared_w2": P(None, tp, None),
        "embed_tokens/embedding": P(tp, None),
        "lm_head/kernel": P(None, tp),
    }
