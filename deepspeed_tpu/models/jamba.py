"""Jamba (``model_type: jamba``; AI21-Jamba2-3B / Jamba Reasoning 3B): Mamba-1
layers beside a few attention layers, every layer with a dense gated MLP.

Layer ``i`` is attention where ``i % attn_layer_period == attn_layer_offset``
and Mamba-1 elsewhere; with ``num_experts: 1`` every feed-forward is the dense
SwiGLU (the ``expert_layer_*`` keys select nothing).  ``N`` an RMSNorm
(``rms_norm_eps``), ``D`` hidden, ``C = mamba_expand x D`` inner channels,
``S = mamba_d_state``, ``R = mamba_dt_rank``, ``K = mamba_d_conv``:

    x'  = x  + Mixer( N_in(x) );      x'' = x' + SwiGLU( N_ff(x') )
    logits = N_f(x_L) E^T                      (the table E is tied)

    Mamba-1:  [u | z] = h W_in                           (D -> 2 C, no bias)
              u  = silu( conv_K(u) + b_conv )            causal, depthwise
              [dt | B | Cm] = u W_x                      (C -> R + S + S)
              dt = N_dt(dt); B = N_b(B); Cm = N_c(Cm)    Jamba's inner norms
              dt = softplus( dt W_dt + b_dt )            (R -> C)
              A  = -exp(A_log)                           [S, C]
              h_t[:, c] = exp(dt_t[c] A[:, c]) h_{t-1}[:, c] + dt_t[c] u_t[c] B_t
              y_t[c]    = h_t[:, c] . Cm_t + D[c] u_t[c]
              out = ( y * silu(z) ) W_out                (C -> D)
    attention: multi-query (``num_key_value_heads`` 1 of 20), head size D / H,
              NO positional encoding (the Mamba layers carry order), causal,
              scores / sqrt(head size), softmax in float32.

What a sequence carries from token to token in a Mamba layer is FIXED in
size: ``h`` (``[S, C]``) and the last ``K - 1`` rows of ``u`` before the
convolution: ``JambaConfig.recurrent_state`` is the statement a cache lays
those layers' entries out by (``inference/v2/ragged.BlockedKVCache``).  The
recurrence runs in float32; between steps ``h`` is held in the model's dtype
(as the published implementations' inference caches hold it).

``JambaModel`` is the dense forward (the tests, ``param_shapes``); serving is
``inference/v2/ragged_forward.jamba_ragged_step``.

Leaves: ``mamba/{in_proj/kernel [2C, D]`` and ``dt_proj/kernel [C, R]`` (the
published ``[out, in]``), ``conv1d/weight [K, C]`` (tap-major: row ``K - 1``
multiplies the current token), ``conv1d/bias [C, 1], x_proj/kernel [C, R +
2S], dt_layernorm/weight [R], b_layernorm/weight [S], c_layernorm/weight [S],
dt_proj/bias [1, C], A_log [1, S * C]`` (state-major: ``reshape(S, C)``), ``D
[C], out_proj/kernel [C, D]}``; ``self_attn/{q,k}_proj/kernel [D, heads,
Dh]``, ``self_attn/v_proj/kernel [Hkv * Dh, D]`` (published ``[out, in]``),
``self_attn/o_proj/kernel [H * Dh, D]``; ``mlp/{gate,up}_proj/kernel
[D, I]``, ``mlp/down_proj/kernel [D, I]`` (published ``[out, in]``);
``input_layernorm``, ``pre_ff_layernorm``, ``final_layernorm``;
``embed_tokens/weight [V, D]``.  Which way a matrix is held changes no
number of a trained model.

Where the numbers are rounded.  A matrix product reads ``cfg.dtype``
(bfloat16) inputs and sums in float32; the recurrence, the softplus, the
norms and the softmax are float32 inside.  What lies BETWEEN them (the
residual stream, ``in_proj``'s output ``u`` and ``z``, the convolution's
output, ``dt`` / ``B`` / ``C`` after their norms, ``y``, the gate, the MLP's
two products) is held in ``cfg.activation_dtype``: by default the model's
dtype, which is where the published implementations round (transformers'
``modeling_jamba.py`` keeps the residual and every module's output in the
model's dtype).  ``activation_dtype="float32"`` keeps all of it float32 up to
the one rounding the next product reads, as ``mamba_ssm``'s reference block
keeps its residual (``residual_in_fp32``), and ``x_proj``, whose 192 outputs
are the recurrence's ``dt``, ``B`` and ``C``, then reads ``u`` unrounded (a
float32 product): the activation traffic doubles, and a deep stack's logits
come about twice as near a float32 forward's.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class JambaConfig:
    """The keys of the published ``config.json`` by their own names."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2           # carried: num_experts is 1
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    sliding_window: int = 0                # published null: none
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    #: what lies between the matrix products is held in ("" = ``dtype``, the
    #: published rounding; "float32": the module docstring)
    activation_dtype: str = ""

    def __post_init__(self):
        if (self.num_experts != 1 or self.mamba_proj_bias
                or not self.mamba_conv_bias or self.hidden_act != "silu"
                or not self.tie_word_embeddings or self.sliding_window):
            raise ValueError(
                "JambaConfig: one expert (a dense MLP a layer), a biased "
                "convolution, unbiased projections, silu, a tied table and "
                "no sliding window are what this model implements")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads do not divide")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def act_dtype(self):
        return jnp.dtype(self.activation_dtype or self.dtype)

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    def is_attention(self, layer):
        return layer % self.attn_layer_period == self.attn_layer_offset

    @property
    def layer_kinds(self):
        """``"pages"`` (an attention layer: K/V a token) or ``"state"`` (a
        Mamba layer: a fixed row a sequence), a layer."""
        return tuple("pages" if self.is_attention(i) else "state"
                     for i in range(self.num_hidden_layers))

    @property
    def recurrent_state(self):
        """What a cache keeps of a SEQUENCE in a ``"state"`` layer, as the
        shapes of one sequence's row: the convolution's last ``K - 1`` inputs
        and the recurrence's ``h``.  The statement a cache lays those layers
        out by (``inference/v2/ragged.BlockedKVCache``)."""
        return {"kinds": self.layer_kinds,
                "conv": (self.mamba_d_conv - 1, self.d_inner),
                "ssm": (self.mamba_d_state, self.d_inner)}


def jamba_tiny(**overrides):
    """Test-scale config: one period of 6 layers, attention at layer 2 (between
    Mamba layers), 4 query heads on one KV head."""
    return JambaConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=1,
        attn_layer_period=6, attn_layer_offset=2, mamba_d_state=16,
        mamba_dt_rank=8, max_position_embeddings=512, dtype="float32"),
        **overrides})


def attention_leaves(a, cfg):
    """An attention layer's leaves as ``[in, heads, Dh]`` projections (what
    ``ragged_forward._ragged_attention_block`` reads): ``v_proj`` is held
    ``[Hkv * Dh, D]``."""
    v = a["v_proj"]["kernel"].T.reshape(
        cfg.hidden_size, cfg.num_key_value_heads, cfg.head_dim)
    return dict(a, v_proj={"kernel": v})


def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * weight.astype(jnp.float32)).astype(x.dtype)


def held(x, cfg):
    """``x`` rounded to the activations' type, float32 again for the
    arithmetic that follows (nothing, with float32 activations)."""
    return x.astype(cfg.act_dtype).astype(jnp.float32)


def mamba_A(mp, cfg):
    """``A = -exp(A_log)``, float32 ``[S, C]``."""
    return -jnp.exp(mp["A_log"].astype(jnp.float32).reshape(
        cfg.mamba_d_state, cfg.d_inner))


def ssm_inputs(u, mp, cfg):
    """What the recurrence reads of the convolved rows ``u [..., C]``, all
    float32: ``dt [..., C]`` (after the inner norm, ``dt_proj`` with its bias
    and the softplus), ``B`` and ``Cm`` ``[..., S]`` (after their norms); each
    product's and each norm's output passes the activations' type."""
    dtype, act = jnp.dtype(cfg.dtype), cfg.act_dtype
    R, S, eps = cfg.mamba_dt_rank, cfg.mamba_d_state, cfg.rms_norm_eps
    # x_proj (C -> R + 2 S, under a hundredth of the layer's products) reads
    # u as it is held: its outputs ARE the recurrence's inputs, so with
    # float32 activations it is a float32 product
    full = act == jnp.float32
    xin = u if full else u.astype(dtype)
    dbc = jnp.dot(xin, mp["x_proj"]["kernel"].astype(xin.dtype),
                  precision=jax.lax.Precision.HIGHEST if full else None,
                  preferred_element_type=jnp.float32).astype(act)
    norm = lambda v, name: rms_norm(v, mp[name]["weight"], eps)
    dt = norm(dbc[..., :R], "dt_layernorm")
    B = norm(dbc[..., R:R + S], "b_layernorm").astype(jnp.float32)
    Cm = norm(dbc[..., R + S:], "c_layernorm").astype(jnp.float32)
    dt = (jnp.einsum("...r,cr->...c", dt.astype(dtype),
                     mp["dt_proj"]["kernel"].astype(dtype),
                     preferred_element_type=jnp.float32)
          + mp["dt_proj"]["bias"].astype(jnp.float32)).astype(act)
    return jax.nn.softplus(dt.astype(jnp.float32)), B, Cm


def in_proj(h, mp, cfg):
    """``(x, z)`` ``[..., C]`` each of normed rows ``h``, in the
    activations' type."""
    dtype = jnp.dtype(cfg.dtype)
    xz = jnp.einsum("...d,kd->...k", h.astype(dtype),
                    mp["in_proj"]["kernel"].astype(dtype),
                    preferred_element_type=jnp.float32).astype(cfg.act_dtype)
    return xz[..., :cfg.d_inner], xz[..., cfg.d_inner:]


def ssm_gate_out(y, u, z, mp, cfg):
    """``((y + D u) silu(z)) W_out`` of scan outputs ``y`` (float32), in the
    activations' type."""
    dtype = jnp.dtype(cfg.dtype)
    y = held(y + mp["D"].astype(jnp.float32) * u.astype(jnp.float32), cfg)
    y = (y * held(jax.nn.silu(z.astype(jnp.float32)), cfg)).astype(dtype)
    return jnp.dot(y, mp["out_proj"]["kernel"].astype(dtype),
                   preferred_element_type=jnp.float32).astype(cfg.act_dtype)


def gated_mlp(h, mlp, cfg):
    """``W_down (silu(W_gate h) * W_up h)`` of normed rows ``h``, in the
    activations' type; ``down_proj`` is held ``[D, I]``."""
    dtype = jnp.dtype(cfg.dtype)
    h = h.astype(dtype)
    product = lambda name: held(jnp.dot(
        h, mlp[name]["kernel"].astype(dtype),
        preferred_element_type=jnp.float32), cfg)
    # with float32 activations the two products stay float32 up to the one
    # rounding that down_proj reads: of the MLP's three roundings two are
    # saved
    mid = (held(jax.nn.silu(product("gate_proj")), cfg)
           * product("up_proj")).astype(dtype)
    return jnp.einsum("...i,di->...d", mid,
                      mlp["down_proj"]["kernel"].astype(dtype),
                      preferred_element_type=jnp.float32).astype(cfg.act_dtype)


def conv_out(acc, cfg):
    """``silu`` of the convolution's float32 sums ``acc`` (bias added), in
    the activations' type."""
    return jax.nn.silu(held(acc, cfg)).astype(cfg.act_dtype)


def mamba_mixer(h, mp, cfg):
    """The Mamba-1 mixer over whole sequences ``h [B, T, D]`` from a zero
    state: the dense forward (a ``lax.scan`` over the tokens)."""
    C, K = cfg.d_inner, cfg.mamba_d_conv
    x, z = in_proj(h, mp, cfg)
    w = mp["conv1d"]["weight"].astype(jnp.float32)             # [K, C]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    T = x.shape[1]
    u = conv_out(sum(w[k] * xp[:, k:k + T].astype(jnp.float32)
                     for k in range(K))
                 + mp["conv1d"]["bias"].astype(jnp.float32)[:, 0], cfg)
    dt, B, Cm = ssm_inputs(u, mp, cfg)
    A = mamba_A(mp, cfg)

    def token(hs, row):
        dt_t, dtu_t, B_t, C_t = row                # [B, C] [B, C] [B, S] [B, S]
        hs = jnp.exp(dt_t[:, None, :] * A) * hs \
            + dtu_t[:, None, :] * B_t[:, :, None]
        return hs, jnp.sum(hs * C_t[:, :, None], axis=1)

    rows = tuple(jnp.moveaxis(a, 1, 0)
                 for a in (dt, dt * u.astype(jnp.float32), B, Cm))
    h0 = jnp.zeros((x.shape[0], cfg.mamba_d_state, C), jnp.float32)
    _, y = jax.lax.scan(token, h0, rows)
    return ssm_gate_out(jnp.moveaxis(y, 0, 1), u, z, mp, cfg)


class _Leaves(nn.Module):
    """The leaves ``<name>/<leaf>`` of one module: ones where 1-D (a norm's
    scale), else drawn with ``shape[0]`` as the fan-in (``[in, ...out]``)."""
    shapes: tuple            # ((leaf, shape), ...)
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self):
        drawn = lambda shape: nn.initializers.lecun_normal(
            in_axis=0, out_axis=tuple(range(1, len(shape))))
        return {leaf: self.param(
            leaf, nn.initializers.ones if len(shape) == 1 else drawn(shape),
            shape, self.param_dtype) for leaf, shape in self.shapes}


def _leaf(pdtype, name, shape, leaf="kernel"):
    return _Leaves(((leaf, shape), ), pdtype, name=name)()[leaf]


class JambaMamba(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        D, C, S, R, K = (cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state,
                         cfg.mamba_dt_rank, cfg.mamba_d_conv)
        pd = jnp.dtype(cfg.param_dtype)
        mod = lambda name, **shapes: _Leaves(tuple(shapes.items()), pd,
                                             name=name)()
        mp = {"in_proj": mod("in_proj", kernel=(2 * C, D)),
              "conv1d": mod("conv1d", weight=(K, C), bias=(C, 1)),
              "x_proj": mod("x_proj", kernel=(C, R + 2 * S)),
              "dt_layernorm": mod("dt_layernorm", weight=(R, )),
              "b_layernorm": mod("b_layernorm", weight=(S, )),
              "c_layernorm": mod("c_layernorm", weight=(S, )),
              "dt_proj": mod("dt_proj", kernel=(C, R), bias=(1, C)),
              "out_proj": mod("out_proj", kernel=(C, D))}
        # drawn wide (fan-in 1), as a trained A spans decades
        mp["A_log"] = self.param("A_log", nn.initializers.normal(1.0),
                                 (1, S * C), pd)
        mp["D"] = self.param("D", nn.initializers.ones, (C, ), pd)
        return mamba_mixer(h, mp, cfg)


class JambaAttention(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        D, H, Hkv, Dh = (cfg.hidden_size, cfg.num_attention_heads,
                         cfg.num_key_value_heads, cfg.head_dim)
        pd, dtype = jnp.dtype(cfg.param_dtype), jnp.dtype(cfg.dtype)
        leaf = lambda name, shape: _leaf(pd, name, shape).astype(dtype)
        q = jnp.einsum("btd,dhk->bthk", h, leaf("q_proj", (D, H, Dh)))
        k = jnp.einsum("btd,dhk->bthk", h, leaf("k_proj", (D, Hkv, Dh)))
        v = jnp.einsum("btd,fd->btf", h, leaf("v_proj", (Hkv * Dh, D))) \
            .reshape(h.shape[:2] + (Hkv, Dh))
        g, T = H // Hkv, h.shape[1]
        qg = q.reshape(q.shape[:2] + (Hkv, g, Dh)).astype(jnp.float32)
        s = jnp.einsum("btkgd,bskd->bkgts", qg, k.astype(jnp.float32)) \
            * Dh ** -0.5
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, jnp.finfo(jnp.float32).min),
                           axis=-1)
        o = jnp.einsum("bkgts,bskd->btkgd", p, v.astype(jnp.float32))
        return o.reshape(o.shape[:2] + (H * Dh, )).astype(dtype) \
            @ leaf("o_proj", (H * Dh, D))


class JambaMLP(nn.Module):
    config: JambaConfig

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        pd = jnp.dtype(cfg.param_dtype)
        D, I = cfg.hidden_size, cfg.intermediate_size
        return gated_mlp(h, {
            name: {"kernel": _leaf(pd, name, shape)} for name, shape in (
                ("gate_proj", (D, I)), ("up_proj", (D, I)),
                ("down_proj", (D, I)))}, cfg)


class JambaLayer(nn.Module):
    config: JambaConfig
    attention: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        pd = jnp.dtype(cfg.param_dtype)
        norm = lambda y, name: rms_norm(
            y, _leaf(pd, name, (cfg.hidden_size, ), "weight"),
            cfg.rms_norm_eps)
        h = norm(x, "input_layernorm").astype(jnp.dtype(cfg.dtype))
        x = x + (JambaAttention(cfg, name="self_attn")(h) if self.attention
                 else JambaMamba(cfg, name="mamba")(h)).astype(x.dtype)
        return x + JambaMLP(cfg, name="mlp")(norm(x, "pre_ff_layernorm"))


class JambaModel(nn.Module):
    """Causal LM, dense forward: ``__call__(input_ids)`` -> float32 logits
    ``[B, T, vocab]``."""
    config: JambaConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        dtype, pd = jnp.dtype(cfg.dtype), jnp.dtype(cfg.param_dtype)
        table = _leaf(pd, "embed_tokens", (cfg.vocab_size, cfg.hidden_size),
                      "weight")
        x = table[input_ids].astype(cfg.act_dtype)    # the residual stream
        for i in range(cfg.num_hidden_layers):
            x = JambaLayer(cfg, cfg.is_attention(i), name=f"layers_{i}")(x)
        x = rms_norm(x, _leaf(pd, "final_layernorm", (cfg.hidden_size, ),
                              "weight"), cfg.rms_norm_eps)
        return jnp.einsum("btd,vd->btv", x.astype(dtype), table.astype(dtype),
                          preferred_element_type=jnp.float32)


def tp_rules(config: JambaConfig):
    """Sharding rules for TRAINING-style tensor parallelism: the inner
    channels of a Mamba layer and the MLP's width over "tp"; the one KV head
    replicated.  (The serving engine raises for tp > 1 with this model: a
    state row has no head axis, ``engine_v2.py``.)"""
    tp = "tp"
    return {
        "in_proj/kernel": P(tp, None),
        "conv1d/weight": P(None, tp),
        "conv1d/bias": P(tp, None),
        "x_proj/kernel": P(tp, None),
        "dt_proj/kernel": P(tp, None),
        "dt_proj/bias": P(None, tp),
        "mamba/A_log": P(None, None),
        "mamba/D": P(tp),
        "out_proj/kernel": P(tp, None),
        "q_proj/kernel": P(None, tp, None),
        "k_proj/kernel": P(None, None, None),
        "v_proj/kernel": P(None, None),
        "o_proj/kernel": P(tp, None),
        "gate_proj/kernel": P(None, tp),
        "up_proj/kernel": P(None, tp),
        "down_proj/kernel": P(None, tp),
        "embed_tokens/weight": P(tp, None),
    }
