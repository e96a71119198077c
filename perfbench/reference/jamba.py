"""Plain float32 reference of the Jamba forward pass (AI21-Jamba2-3B).

Written from the published ``config.json`` (``model_type: jamba``) and the
description of ``JambaForCausalLM``: a tied token table; layer ``i`` is
multi-query causal attention WITHOUT any positional encoding where ``i %
attn_layer_period == attn_layer_offset`` and a Mamba-1 mixer elsewhere; every
layer ends in a dense SwiGLU (``num_experts: 1``); RMSNorm before each branch
and before the tied head.  The Mamba-1 mixer, with Jamba's own RMSNorms on
``dt``, ``B`` and ``C``:

    [u | z] = h W_in;  u = silu(conv_4(u) + b);  [dt | B | C] = u W_x
    dt = softplus(N(dt) W_dt + b_dt);  B = N(B);  C = N(C);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) B_t;   y_t = h_t . C_t + D u_t
    out = (y silu(z)) W_out

Plain ``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``:
the recurrence is a ``lax.scan`` over the tokens, one token a step, with no
chunk and no kernel; no cache, no batching, nothing imported from
``deepspeed_tpu``.

It reads the *layout* of the system's parameter tree (a data format):

    embed_tokens/weight [V, D]           final_layernorm/weight [D]
    layers_<i>/{input_layernorm,pre_ff_layernorm}/weight [D]
    layers_<i>/mlp/{gate,up}_proj/kernel [D, I]      down_proj/kernel [D, I]
    layers_<i>/self_attn/{q,k}_proj/kernel [D, heads, Dh]  v_proj/kernel [Dh, D]
        o_proj/kernel [H*Dh, D]
    layers_<i>/mamba/in_proj/kernel [2C, D]   conv1d/weight [K, C] (row K-1: the
        current token)   conv1d/bias [C, 1]   x_proj/kernel [C, R + 2S]
        {dt,b,c}_layernorm/weight   dt_proj/kernel [C, R]   dt_proj/bias [1, C]
        A_log [1, S * C] (state-major: reshape(S, C))   D [C]   out_proj/kernel [C, D]

and upcasts one layer at a time.  Departures from the published code: the
attention's softmax is computed ``QUERY_ROWS`` query rows at a time (the same
numbers; a ``[H, rows, S]`` block of scores instead of ``[H, S, S]``); the
convolution is four shifted products instead of a ``conv1d`` call; nothing is
rounded to bfloat16 anywhere (the published inference cache holds ``h``
between calls in the model's dtype: that is the engine's to lose against
this).  There is no training loss: the configuration trains nowhere.
"""

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = "highest"
#: query rows whose scores are held at once
QUERY_ROWS = 512


def f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def is_attention(i, cfg):
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def attention(q, k, v):
    """q: [S, H, Dh]; k, v: [S, Dh] (ONE key/value head) -> [S, H * Dh].
    Causal, no positions; ``QUERY_ROWS`` rows of queries at a time."""
    s, h, dh = q.shape
    rows = min(QUERY_ROWS, s)
    pad = -s % rows
    key = jnp.arange(s)[None, None, :]

    def block(args):
        qb, first = args                                   # [rows, H, Dh]
        scores = jnp.einsum("rhd,td->hrt", qb, k) / jnp.sqrt(jnp.float32(dh))
        mask = key <= (first + jnp.arange(rows))[None, :, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hrt,td->rhd", probs, v)

    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, rows, h, dh)
    out = jax.lax.map(block, (qb, jnp.arange(qb.shape[0]) * rows))
    return out.reshape(-1, h * dh)[:s]


def attention_mixer(h, a):
    q = jnp.einsum("sd,dhe->she", h, a["q_proj"]["kernel"])
    k = jnp.einsum("sd,dhe->she", h, a["k_proj"]["kernel"])[:, 0]
    v = h @ a["v_proj"]["kernel"].T                       # ONE KV head
    return attention(q, k, v) @ a["o_proj"]["kernel"]


def selective_scan(dt, u, B, C, A):
    """dt, u: [S, C]; B, C: [S, N]; A: [N, C] -> y [S, C]: one token a step
    from a zero state."""
    def token(hs, row):
        dt_t, u_t, B_t, C_t = row
        hs = jnp.exp(dt_t[None, :] * A) * hs \
            + (dt_t * u_t)[None, :] * B_t[:, None]
        return hs, jnp.sum(hs * C_t[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros_like(A), (dt, u, B, C))
    return y


def mamba_mixer(h, m, cfg):
    eps = cfg["rms_norm_eps"]
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    w = m["conv1d"]["weight"]                              # [K, C]
    taps, c = w.shape
    xz = h @ m["in_proj"]["kernel"].T
    x, z = xz[:, :c], xz[:, c:]
    s = x.shape[0]
    xp = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    u = sum(w[k] * xp[k:k + s] for k in range(taps)) \
        + m["conv1d"]["bias"][:, 0]
    u = jax.nn.silu(u)
    dbc = u @ m["x_proj"]["kernel"]
    dt = rms_norm(dbc[:, :r], m["dt_layernorm"]["weight"], eps)
    B = rms_norm(dbc[:, r:r + n], m["b_layernorm"]["weight"], eps)
    C = rms_norm(dbc[:, r + n:], m["c_layernorm"]["weight"], eps)
    dt = jax.nn.softplus(dt @ m["dt_proj"]["kernel"].T
                         + m["dt_proj"]["bias"])
    A = -jnp.exp(m["A_log"].reshape(n, c))
    y = selective_scan(dt, u, B, C, A) + m["D"] * u
    return (y * jax.nn.silu(z)) @ m["out_proj"]["kernel"]


def swiglu(h, gate, up, down):
    """``down`` is held ``[D, I]``."""
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down.T


def layer(x, lp, cfg, attention_layer):
    lp = f32(lp)
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, lp["input_layernorm"]["weight"], eps)
    x = x + (attention_mixer(h, lp["self_attn"]) if attention_layer
             else mamba_mixer(h, lp["mamba"], cfg))
    m = lp["mlp"]
    h = rms_norm(x, lp["pre_ff_layernorm"]["weight"], eps)
    return x + swiglu(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                      m["down_proj"]["kernel"])


def hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


@partial(jax.jit, static_argnames=("cfg_items", "attention_layer"))
def _layer_jit(x, lp, cfg_items, attention_layer):
    with jax.default_matmul_precision(HIGHEST):
        return layer(x, lp, dict(cfg_items), attention_layer)


@partial(jax.jit, static_argnames=("eps", ))
def _head_jit(norm, table, x, eps):
    with jax.default_matmul_precision(HIGHEST):
        x = rms_norm(x, jnp.asarray(norm, jnp.float32), eps)
        return x @ jnp.asarray(table, jnp.float32).T


def logits_at(params, ids, positions, cfg):
    """Float32 logits [len(positions), V] of ONE sequence ``ids`` [S] at the
    given positions: one full forward, a jitted call per layer so that only
    one layer is ever upcast."""
    items = hashable(cfg)
    table = params["embed_tokens"]["weight"]
    x = jnp.asarray(table[jnp.asarray(ids, jnp.int32)], jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer_jit(x, params[f"layers_{i}"], items, is_attention(i, cfg))
    sel = x[jnp.asarray(positions, jnp.int32)]
    return _head_jit(params["final_layernorm"]["weight"], table, sel,
                     cfg["rms_norm_eps"])
