"""Motif-3 (``model_type: Motif``): grouped differential attention over a
LATENT cache with a sliding window on three layers of four (GDLA), a
four-stream residual mixed by manifold-constrained hyper-connections (mHC),
and PolyNorm-gated feed-forwards: dense in the first layers, then
sigmoid-routed experts beside one shared expert.

The residual of a token is ``n = mhc_expansion_rate`` STREAMS ``X [n, D]``;
``X_0`` is ``n`` copies of the embedding row, the logits are ``head(N(sum of
the last layer's streams))``.  A layer is two sublayers, attention ``A`` then
the feed-forward ``F``, each wrapped the same way, with its own parameters
(``N`` an RMSNorm with ``rms_norm_eps``):

    x~     = N(vec(X))                                           [n D]
    H_pre  = sigmoid(a_pre  (x~ P_pre)  + b_pre)                 [n]
    H_post = 2 sigmoid(a_post (x~ P_post) + b_post)              [n]
    H_res  = Sinkhorn_t(exp(a_res mat(x~ P_res) + b_res))        [n, n]
             t = mhc_sinkhorn_iters sweeps, each: rows divided by their
             sums, then columns by theirs; float32
    h  = N_sub(H_pre X)                                          [D]
    X' = H_res X + H_post^T (x) Sub(h)                           Sub = A or F

Attention ``A`` (GDLA) of the token at position ``p`` in layer ``l``, ``H``
query heads in ``G = num_key_value_heads`` K/V groups of ``H / G``, the last
head of a group its NOISE head, ``S = H - num_noise_heads`` signal heads, ``r
= kv_lora_rank``, ``dn = head_dim - qk_rope_head_dim``, ``dr``, ``dv``:

    c_q = N(h W_qa);  q = c_q W_qb -> H x (q_n [dn] ; q_r [dr]);  q_r <- rope
    (c ; k_r) = h W_kva;  c = N(c);  k_r <- rope        the cache row, r + dr
    group g:  k_n^g = c W_uk^g [dn],  v^g = c W_uv^g [dv]
    a_h = softmax_j((q_n^h . k_n^g(j) + q_r^h . k_r(j)) / sqrt(dn + dr)) v^g(j)
          over j <= p, and j > p - sliding_window on a window layer
    lam = sigmoid(h W_lam) [S];   o_s = a_s - lam_s a_noise(g(s))
    y   = ((o [S dv]) * sigmoid(h W_gate)) W_o
    absorbed, the same numbers: qlat_h = q_n^h W_uk^g^T [r] against the rows
          themselves, olat_h = sum_j p_hj c_j; the subtraction on the LATENT
          outputs (the group shares W_uv), then o_s = olat_s W_uv^g

Layer ``l`` reads everything where ``l % sliding_window_period ==
sliding_window_period - 1`` and its window elsewhere (``layer_windows``); rope
is plain (``rope_theta`` = ``swa_rope_theta``, no yarn), half-split.

Feed-forward ``F``: ``W_down(PolyNorm(h W_gate) * (h W_up))`` of
``intermediate_size`` in the first ``n_dense_first_layers`` layers; after them
``shared(h) + sum over the top k of w_e E_e(h)``: scores ``sigmoid(h W_r)`` in
float32 over all ``num_experts``, the ``experts_top_k`` largest, weights
divided by their sum (``route_norm``) times ``route_scale``, on the experts'
outputs; every ``E_e`` and the shared expert the same gated form of
``moe_intermediate_size`` with a PolyNorm of its own:

    PolyNorm(z) = s (w1 z^3 / rms(z^3) + w2 z^2 / rms(z^2) + w3 z / rms(z)
                     + clip(b, -c, c))
                  rms over the feed-forward's width, eps = rms_norm_eps,
                  s = polynorm_output_scale, c = polynorm_bias_clamp

What the published ``config.json`` leaves open is read as
``perfbench/configs/motif3_beta_1chip.json`` lists under ``assumed``
(which head of a group is the noise head, which layer of a period is the full
one, where lambda and the gate read, mHC as the mHC paper states it, one
PolyNorm an expert); ``max_window_layers``, ``k_ratio``, ``hidden_clamp``,
``load_balance_coeff`` and ``num_nextn_predict_layers`` are carried and used
by nothing here.

What a cache keeps of a token is the latent row ``(c ; k_r)``
(``kv_latent_dim``).  ``MotifModel`` is the dense forward in the expanded
form (the tests, ``param_shapes``); serving is
``inference/v2/ragged_forward.motif_ragged_step`` over the paged latent
cache.  **One chip's share**: ``num_experts`` is the ROUTER'S width,
``experts_held`` / ``first_expert`` say which experts' stacks this model holds
(``moe/held_experts.py``).

Leaves of a layer: ``attn_mhc`` and ``mlp_mhc``, each ``{norm/weight [n D],
proj/kernel [n D, 2 n + n n] (P_pre | P_post | P_res), alpha [3] (a_pre,
a_post, a_res), bias [1, 2 n + n n]}``; ``input_layernorm`` /
``post_attention_layernorm`` (the two ``N_sub``); ``self_attn/{q_a_proj,
q_a_layernorm, q_b_proj [q_lora_rank, H, dn + dr], kv_a_proj [D, r + dr],
kv_a_layernorm, k_b_proj [r / 4, 4, G, dn], v_b_proj [r / 16, 16, G, dv] (the
rank axis in rows: ``[r, G, .]`` by a reshape), lambda_proj [D, S],
gate_proj [D, S dv], o_proj [S dv, D]}``; ``mlp/{gate,up,down}_proj`` and
``mlp/poly [1, 4]`` (w1, w2, w3, b) in a dense layer; ``moe/gate [D, E]``,
``moe/{w1,w3} [held, D, I]``, ``moe/w2 [held, I, D]``, ``moe/poly [held,
4]``, ``moe/shared_{gate,up,down}_proj`` and ``moe/shared_poly [1, 4]`` in a
routed one.
"""

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..moe.held_experts import held_experts_apply, route
from ..telemetry import names as _names
from .pangu_ultra_moe import _leaves, mla_down, rms_norm

#: the rank axis of ``k_b_proj`` / of ``v_b_proj`` is held in rows of this
#: many (``[r, G, .]`` by a reshape): a seeded generator that draws a leaf at
#: ``1 / sqrt(shape[0])`` then gives keys of std 2 and values of std 4 at
#: a rank of 512 (``perfbench/configs/motif3_beta_1chip.json``,
#: ``assumed.weights``, has why)
K_RANK_ROWS, V_RANK_ROWS = 4, 16


@dataclass(frozen=True)
class MotifConfig:
    """The keys of the published ``config.json`` by their own names, and what
    a chip holds of a routed layer (``experts_held``, ``first_expert``)."""
    vocab_size: int = 220160
    hidden_size: int = 4096
    intermediate_size: int = 12288         # a leading dense layer's width
    moe_intermediate_size: int = 1280      # one expert's, routed and shared
    num_hidden_layers: int = 53
    n_dense_first_layers: int = 2
    num_attention_heads: int = 80
    num_key_value_heads: int = 16          # K/V groups on the latent row
    num_noise_heads: int = 16              # one a group, its last head
    head_dim: int = 192                    # a query's: nope + rope
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    num_experts: int = 384                 # the router's width
    num_shared_experts: int = 1
    experts_top_k: int = 8
    experts_held: Optional[int] = None     # None: all of them
    first_expert: int = 0
    route_norm: bool = True
    route_scale: float = 2.0
    score_func: str = "sigmoid"
    sliding_window: int = 128
    sliding_window_period: int = 4
    sliding_window_pattern: str = "interleave"
    mhc_expansion_rate: int = 4
    mhc_sinkhorn_iters: int = 20
    polynorm_output_scale: float = 0.5
    polynorm_bias_clamp: float = 0.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    swa_rope_theta: float = 10000.0
    max_position_embeddings: int = 262144
    hidden_act: str = "poly_norm"
    attention_cls: str = "gdla"
    diff_v2: bool = True
    elementwise_attn_output_gate: bool = True
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self):
        if (self.hidden_act != "poly_norm" or self.attention_cls != "gdla"
                or not self.diff_v2 or not self.elementwise_attn_output_gate
                or self.score_func != "sigmoid" or self.tie_word_embeddings
                or self.sliding_window_pattern != "interleave"
                or self.num_shared_experts != 1
                or self.rope_theta != self.swa_rope_theta):
            raise ValueError(
                "MotifConfig: PolyNorm, GDLA in its second form with an "
                "element-wise output gate, a sigmoid router, one shared "
                "expert, an interleaved window, one rope base and an untied "
                "head are what this model implements")
        if self.num_noise_heads != self.num_key_value_heads or \
                self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("one noise head a K/V group of equal groups")
        if self.kv_lora_rank % max(K_RANK_ROWS, V_RANK_ROWS):
            raise ValueError("kv_lora_rank in rows of K_RANK_ROWS and of "
                             "V_RANK_ROWS")
        if not 0 <= self.n_dense_first_layers <= self.num_hidden_layers:
            raise ValueError("n_dense_first_layers lies outside the layers")
        if not 0 <= self.first_expert <= self.num_experts - self.held:
            raise ValueError("the held experts lie outside the router")

    @property
    def held(self):
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def qk_nope_head_dim(self):
        return self.head_dim - self.qk_rope_head_dim

    @property
    def signal_heads(self):
        return self.num_attention_heads - self.num_noise_heads

    @property
    def kv_latent_dim(self):
        """What a cache keeps of a token in a layer: ``(c ; k_r)``
        (``inference/v2/ragged.BlockedKVCache`` lays its buffers out by it)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self):
        return self.head_dim ** -0.5

    @property
    def layer_windows(self):
        """The sliding window of each layer, 0 where it reads everything: the
        LAST layer of every period is the full one."""
        period = self.sliding_window_period
        return tuple(0 if l % period == period - 1 else self.sliding_window
                     for l in range(self.num_hidden_layers))

    def routed(self, layer):
        return layer >= self.n_dense_first_layers


def motif_tiny(**overrides):
    """Test-scale config: two dense layers and six routed ones (two whole
    periods of a window of 16), 32 experts of which 8 are held, 2 a token, 10
    heads in 2 groups on a latent row of 32 + 8, four streams."""
    return MotifConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=8,
        n_dense_first_layers=2, num_attention_heads=10,
        num_key_value_heads=2, num_noise_heads=2, head_dim=24,
        qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=48, kv_lora_rank=32,
        num_experts=32, experts_top_k=2, experts_held=8, sliding_window=16,
        rope_theta=100.0, swa_rope_theta=100.0, max_position_embeddings=512,
        dtype="float32"), **overrides})


# ------------------------------------------------------------------- mHC
def sinkhorn(m, sweeps):
    """``m [n, n, ...]`` (positive; rows the first axis) made doubly
    stochastic by ``sweeps`` sweeps, each: rows divided by their sums, then
    columns by theirs.  The sums are written out as adds of the ``n`` slices,
    so that XLA fuses a sweep's eight steps into one elementwise kernel over
    the trailing axes (a reduction would end a fusion, twice a sweep); the
    sweeps are a loop, four to an iteration (all twenty in line are 13 000
    instructions a step program at 16 sublayers)."""
    n = m.shape[0]

    def sweep(_, m):
        m = m / sum(m[:, j] for j in range(n))[:, None]
        return m / sum(m[i] for i in range(n))[None]

    return jax.lax.fori_loop(0, sweeps, sweep, m, unroll=min(4, sweeps))


@jax.named_scope(_names.SCOPE_MHC)
def mhc_maps(X, p, cfg):
    """The three mappings of one sublayer for the streams ``X [T, n, D]``:
    ``(H_pre [n, T], H_post [n, T], H_res [n, n, T])``, float32, the token
    axis LAST (a row of ``n`` or ``n n`` numbers a token would fill 4 or 16
    of a vector's 128 lanes).  ``RMSNorm(x) P = (x (w * P)) / rms(x)``: the
    normed ``[T, n D]`` array is never made."""
    T, n, _ = X.shape
    x = X.reshape(T, -1).astype(jnp.float32)
    w = p["norm"]["weight"].astype(jnp.float32)[:, None] \
        * p["proj"]["kernel"].astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1) + cfg.rms_norm_eps)
    m = (jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST) * inv[:, None]).T
    a = p["alpha"].astype(jnp.float32)
    b = p["bias"].astype(jnp.float32).reshape(-1, 1)
    h_pre = jax.nn.sigmoid(a[0] * m[:n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * m[n:2 * n] + b[n:2 * n])
    res = jnp.exp(a[2] * m[2 * n:] + b[2 * n:]).reshape(n, n, T)
    return h_pre, h_post, sinkhorn(res, cfg.mhc_sinkhorn_iters)


@jax.named_scope(_names.SCOPE_MHC)
def mhc_read(X, h_pre):
    """``H_pre X [T, D]``: what a sublayer reads of the streams, float32
    (its norm rounds it)."""
    return sum(h_pre[i][:, None] * X[:, i].astype(jnp.float32)
               for i in range(X.shape[1]))


@jax.named_scope(_names.SCOPE_MHC)
def mhc_write(X, y, h_post, h_res):
    """``H_res X + H_post^T (x) y`` -> the streams ``[T, n, D]`` in ``X``'s
    type, the sums in float32."""
    n = X.shape[1]
    x32, y32 = X.astype(jnp.float32), y.astype(jnp.float32)
    return jnp.stack(
        [sum(h_res[i, j][:, None] * x32[:, j] for j in range(n))
         + h_post[i][:, None] * y32 for i in range(n)], axis=1).astype(X.dtype)


def mhc_sublayer(X, p, norm_weight, sub, cfg):
    """One wrapped sublayer over the streams ``X [T, n, D]``: ``sub`` takes
    the normed read ``h [T, D]`` (in ``X``'s type) and returns the branch."""
    h_pre, h_post, h_res = mhc_maps(X, p, cfg)
    h = rms_norm(mhc_read(X, h_pre), norm_weight, cfg.rms_norm_eps) \
        .astype(X.dtype)
    return mhc_write(X, sub(h), h_post, h_res)


# -------------------------------------------------------------- PolyNorm
@jax.named_scope(_names.SCOPE_POLYNORM)
def poly_norm(z, coef, cfg):
    """PolyNorm over the last axis of ``z [..., I]`` with the coefficients
    ``coef [..., 4]`` (w1, w2, w3, b; broadcast against ``z``'s rows: one set
    a feed-forward, so in an expert layer each row's own expert's), float32
    inside, back in ``z``'s type."""
    z32, c = z.astype(jnp.float32), coef.astype(jnp.float32)
    eps = cfg.rms_norm_eps

    def normed(p):
        return p * jax.lax.rsqrt(jnp.mean(jnp.square(p), -1, keepdims=True)
                                 + eps)

    clamp = cfg.polynorm_bias_clamp
    out = (c[..., 0:1] * normed(z32 * z32 * z32)
           + c[..., 1:2] * normed(z32 * z32) + c[..., 2:3] * normed(z32)
           + jnp.clip(c[..., 3:4], -clamp, clamp))
    return (cfg.polynorm_output_scale * out).astype(z.dtype)


@functools.lru_cache(maxsize=None)
def _expert_act(cfg):
    """``held_experts_apply``'s row-wise ``act`` for ``cfg``, one object a
    configuration (a static argument of its jitted layer)."""
    return lambda z, coef: poly_norm(z, coef, cfg)


def gated_poly(h, gate, up, down, coef, cfg):
    """``W_down(PolyNorm(h W_gate) * (h W_up))`` with one set of
    coefficients ``coef [1, 4]``."""
    return (poly_norm(h @ gate, coef, cfg) * (h @ up)) @ down


def moe_layer(h, router_logits, moe, cfg, live=None, kernel=False):
    """``(F_l(h) [T, D], counts [held])`` of a routed layer for rows ``h [T,
    D]``: the shared expert plus the held experts' part of the scaled routed
    sum (``live``, ``kernel``: ``held_experts_apply``'s), and the copies that
    landed on each held expert."""
    dtype = h.dtype
    with jax.named_scope(_names.SCOPE_MOE_ROUTER):
        topi, topw = route(router_logits, cfg.experts_top_k, "sigmoid",
                           cfg.route_norm, scale=cfg.route_scale)
    with jax.named_scope(_names.SCOPE_MOE_EXPERTS):
        routed, counts = held_experts_apply(
            h, topi, topw, moe["w1"].astype(dtype), moe["w2"].astype(dtype),
            moe["w3"].astype(dtype), first_expert=cfg.first_expert,
            experts=cfg.num_experts, live=live, kernel=kernel,
            act=_expert_act(cfg), act_coef=moe["poly"])
    with jax.named_scope(_names.SCOPE_MOE_SHARED):
        shared = gated_poly(h, *(moe[f"shared_{n}_proj"]["kernel"]
                                 .astype(dtype)
                                 for n in ("gate", "up", "down")),
                            moe["shared_poly"], cfg)
    return routed + shared, counts


# ------------------------------------------------------------------ GDLA
def group_kernels(attn, dtype):
    """``(W_uk [r, G, dn], W_uv [r, G, dv])`` from the leaves, whose rank
    axis is held in rows (a reshape, no copy)."""
    view = lambda w: w.reshape((-1, ) + w.shape[2:]).astype(dtype)
    return view(attn["k_b_proj"]["kernel"]), view(attn["v_b_proj"]["kernel"])


def mla_view(attn, dtype):
    """The leaves ``_mla_block`` reads, ``k_b_proj`` / ``v_b_proj`` as
    :func:`group_kernels`'s."""
    w_uk, w_uv = group_kernels(attn, dtype)
    return dict(attn, k_b_proj={"kernel": w_uk}, v_b_proj={"kernel": w_uv})


def diff_gates(h, attn):
    """``(lam [T, S] float32, gate [T, S dv] in h's type)`` of the rows ``h``
    (normed): one sigmoid a signal head, and the element-wise output gate."""
    with jax.named_scope(_names.SCOPE_DIFF_ATTN):
        lam = jax.nn.sigmoid((h @ attn["lambda_proj"]["kernel"]
                              .astype(h.dtype)).astype(jnp.float32))
        gate = jax.nn.sigmoid(h @ attn["gate_proj"]["kernel"].astype(h.dtype))
    return lam, gate


def gdla_expanded(h, attn, cfg, window):
    """GDLA of ``h [B, S, D]`` in the EXPANDED form: a K/V group's keys and
    values made from the latent rows, one causal (windowed) softmax a head in
    float32, the group's noise output subtracted, the gate, ``o_proj``."""
    dtype = h.dtype
    B, S, _ = h.shape
    r, G = cfg.kv_lora_rank, cfg.num_key_value_heads
    pos = jnp.arange(S)
    q_n, q_r, latent = mla_down(h, attn, pos[None], cfg)
    c, k_r = latent[..., :r], latent[..., r:]
    w_uk, w_uv = group_kernels(attn, dtype)
    f32 = lambda x: x.astype(jnp.float32)
    k_n = f32(jnp.einsum("btc,cgn->btgn", c, w_uk))
    v = f32(jnp.einsum("btc,cgv->btgv", c, w_uv))
    by_group = lambda a: f32(a).reshape(B, S, G, -1, a.shape[-1])
    scores = (jnp.einsum("bsgqn,btgn->bgqst", by_group(q_n), k_n)
              + jnp.einsum("bsgqr,btr->bgqst", by_group(q_r), f32(k_r))) \
        * cfg.softmax_scale
    mask = pos[:, None] >= pos[None, :]
    if window:
        mask &= pos[None, :] > pos[:, None] - window
    probs = jax.nn.softmax(
        jnp.where(mask, scores, jnp.finfo(jnp.float32).min), axis=-1)
    out = jnp.einsum("bgqst,btgv->bsgqv", probs, v)
    lam, gate = diff_gates(h, attn)
    out = out[:, :, :, :-1] - lam.reshape(B, S, G, -1, 1) * out[:, :, :, -1:]
    return (out.reshape(B, S, -1).astype(dtype) * gate) \
        @ attn["o_proj"]["kernel"].astype(dtype)


# ------------------------------------------------------- the dense forward
class MotifAttention(nn.Module):
    config: MotifConfig
    window: int

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        D = h.shape[-1]
        H, G, S = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.signal_heads)
        r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        attn = _leaves(
            jnp.dtype(cfg.param_dtype),
            kernels=(("q_a_proj", (D, cfg.q_lora_rank)),
                     ("q_b_proj", (cfg.q_lora_rank, H, dn + dr)),
                     ("kv_a_proj", (D, r + dr)),
                     ("k_b_proj", (r // K_RANK_ROWS, K_RANK_ROWS, G, dn)),
                     ("v_b_proj", (r // V_RANK_ROWS, V_RANK_ROWS, G, dv)),
                     ("lambda_proj", (D, S)), ("gate_proj", (D, S * dv)),
                     ("o_proj", (S * dv, D))),
            weights=(("q_a_layernorm", (cfg.q_lora_rank, )),
                     ("kv_a_layernorm", (r, ))))
        return gdla_expanded(h, attn, cfg, self.window)


def _poly_init(key, shape, dtype):
    """PolyNorm's (w1, w2, w3, b) as PolyCom starts them: thirds and 0."""
    return jnp.broadcast_to(jnp.asarray([1 / 3, 1 / 3, 1 / 3, 0.0], dtype),
                            shape)


class MotifMLP(nn.Module):
    """A leading dense layer's feed-forward (``mlp``)."""
    config: MotifConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        D, I = cfg.hidden_size, cfg.intermediate_size
        mlp = _leaves(pdtype, kernels=(
            ("gate_proj", (D, I)), ("up_proj", (D, I)),
            ("down_proj", (I, D))))
        poly = self.param("poly", _poly_init, (1, 4), pdtype)
        return gated_poly(h, *(mlp[f"{n}_proj"]["kernel"].astype(h.dtype)
                               for n in ("gate", "up", "down")), poly, cfg)


class MotifMoeBlock(nn.Module):
    """Router, the held experts' stacks and the shared expert (``moe``)."""
    config: MotifConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        D, I = cfg.hidden_size, cfg.moe_intermediate_size
        rows = h.reshape(-1, D)
        moe = _leaves(pdtype, kernels=(
            ("gate", (D, cfg.num_experts)),
            ("shared_gate_proj", (D, I)), ("shared_up_proj", (D, I)),
            ("shared_down_proj", (I, D))))
        init = nn.initializers.lecun_normal(in_axis=1, out_axis=2,
                                            batch_axis=0)
        moe.update(w1=self.param("w1", init, (cfg.held, D, I), pdtype),
                   w2=self.param("w2", init, (cfg.held, I, D), pdtype),
                   w3=self.param("w3", init, (cfg.held, D, I), pdtype),
                   poly=self.param("poly", _poly_init, (cfg.held, 4),
                                   pdtype),
                   shared_poly=self.param("shared_poly", _poly_init, (1, 4),
                                          pdtype))
        router_logits = rows.astype(jnp.float32) \
            @ moe["gate"]["kernel"].astype(jnp.float32)
        out, _ = moe_layer(rows, router_logits, moe, cfg)
        return out.reshape(h.shape)


class MotifMhc(nn.Module):
    """One sublayer's mHC parameters (``attn_mhc`` / ``mlp_mhc``)."""
    config: MotifConfig

    @nn.compact
    def __call__(self):
        cfg = self.config
        pdtype = jnp.dtype(cfg.param_dtype)
        n = cfg.mhc_expansion_rate
        wide, maps = n * cfg.hidden_size, 2 * n + n * n
        p = _leaves(pdtype, kernels=(("proj", (wide, maps)), ),
                    weights=(("norm", (wide, )), ))
        p["alpha"] = self.param("alpha", nn.initializers.ones, (3, ), pdtype)
        p["bias"] = self.param("bias", nn.initializers.zeros, (1, maps),
                               pdtype)
        return p


class MotifLayer(nn.Module):
    config: MotifConfig
    layer: int

    @nn.compact
    def __call__(self, X):
        cfg = self.config
        B, S, n, D = X.shape
        norms = _leaves(jnp.dtype(cfg.param_dtype), weights=tuple(
            (name, (D, )) for name in ("input_layernorm",
                                       "post_attention_layernorm")))
        attn = MotifAttention(cfg, cfg.layer_windows[self.layer],
                              name="self_attn")
        ffn = MotifMoeBlock(cfg, name="moe") if cfg.routed(self.layer) \
            else MotifMLP(cfg, name="mlp")
        rows = lambda y: y.reshape(B * S, *y.shape[2:])
        for mhc, norm, sub in (("attn_mhc", "input_layernorm", attn),
                               ("mlp_mhc", "post_attention_layernorm", ffn)):
            X = mhc_sublayer(
                rows(X), MotifMhc(cfg, name=mhc)(), norms[norm]["weight"],
                lambda h: rows(sub(h.reshape(B, S, D))), cfg) \
                .reshape(B, S, n, D)
        return X


class MotifModel(nn.Module):
    """Causal LM, dense forward: ``__call__(input_ids)`` -> float32 logits
    ``[B, S, vocab]``."""
    config: MotifConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        dtype, pdtype = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=dtype,
                     param_dtype=pdtype, name="embed_tokens")(input_ids)
        X = jnp.repeat(x[:, :, None], cfg.mhc_expansion_rate, axis=2)
        for i in range(cfg.num_hidden_layers):
            X = MotifLayer(cfg, i, name=f"layers_{i}")(X)
        top = _leaves(pdtype, kernels=(
            ("lm_head", (cfg.hidden_size, cfg.vocab_size)), ),
            weights=(("norm", (cfg.hidden_size, )), ))
        x = rms_norm(jnp.sum(X.astype(jnp.float32), axis=2).astype(dtype),
                     top["norm"]["weight"], cfg.rms_norm_eps)
        return x.astype(jnp.float32) \
            @ top["lm_head"]["kernel"].astype(jnp.float32)


def tp_rules(config: MotifConfig):
    """Sharding rules: the per-head projections over "tp" on the heads (a
    K/V group's on the groups), the low-rank ones replicated (the mHC
    mappings too: no rule names them); the experts over "ep" on the expert
    axis."""
    tp = "tp"
    return {
        "q_a_proj/kernel": P(None, None),
        "kv_a_proj/kernel": P(None, None),
        "q_b_proj/kernel": P(None, tp, None),
        "k_b_proj/kernel": P(None, None, tp, None),
        "v_b_proj/kernel": P(None, None, tp, None),
        "lambda_proj/kernel": P(None, tp),
        "self_attn/gate_proj/kernel": P(None, tp),
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
