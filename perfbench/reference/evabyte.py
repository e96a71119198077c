"""Plain float32 reference of the EvaByte forward pass (serving only: the
configuration has no ``train`` depth; the PR that brings a training cell
brings ``train_losses``).

Written from the published ``config.json`` (EvaByte/EvaByte), the EVA paper
(Zheng et al., "Efficient Attention via Control Variates", ICLR 2023) and
the EvaByte release notes, as ISSUE 27 spells the layer.  With ``x`` the
residual stream, ``t`` a position from 0, ``W = window_size``, ``C =
chunk_size``, ``s = 1 / sqrt(head_dim)``:

1. ``h = RMSNorm(x)`` with weight ``(1 + g)`` (``norm_add_unit_offset``);
   ``q_t, k_t = RoPE_t(h_t Wq), RoPE_t(h_t Wk)`` (half-split ``rotate_half``),
   ``v_t = h_t Wv``; as many key/value heads as heads, no bias.
2. Per head two learned vectors ``phi, mu``.  For every COMPLETE chunk ``j``
   (positions ``jC .. jC + C - 1``, keys after rotary): ``a_m = softmax`` over
   the chunk's positions of ``k_m . phi`` (no further scale);
   ``k~_j = sum_m a_m k_m + mu``, ``v~_j = sum_m a_m v_m``.
3. The query at ``t``, in window ``w = t // W``, reads under ONE softmax the
   exact keys ``wW <= m <= t`` and the summaries of every chunk ``j < wW / C``
   of EARLIER windows.  Windows do not slide.
4. ``x = x + o Wo``; ``x = x + (silu(h' Wg) * (h' Wu)) Wd`` with
   ``h' = RMSNorm(x)`` (unit offset).
5. After the last layer RMSNorm (unit offset) and ONE matrix ``[hidden,
   num_pred_heads * vocab]``: columns ``vocab * i .. vocab * i + vocab - 1``
   are head ``i``, which predicts byte ``t + 1 + i``.

Plain ``jax.numpy`` in float32 under ``jax.default_matmul_precision(
"highest")``: no kernel, no cache, no batching, nothing imported from
``deepspeed_tpu``.  It reads the *layout* of the system's parameter tree (a
data format):

    embed_tokens/embedding [V, D]   norm/weight [D, 1]   lm_head/kernel [D, heads * V]
    layers_<i>/input_layernorm/weight    layers_<i>/post_attention_layernorm/weight
    (a norm's weight is the offset g of point 1, a column)
    layers_<i>/self_attn/{q,k,v}_proj/kernel [D, H, Dh]    o_proj/kernel [H*Dh, D]
    layers_<i>/self_attn/eva_phi, eva_mu [H, Dh]
    layers_<i>/mlp/{gate,up}_proj/kernel [D, I]            down_proj/kernel [I, D]

and upcasts one layer at a time.  Departures from the published code, none
of them mathematics: the sequence is padded with token 0 to whole windows
(the padding lies after every real position, which no real query reads); the
keys and values of all rows are made first, in blocks of rows, then the
queries go block by block (at most ``Q_BLOCK`` rows, all of one window)
through attention and the MLP, so that 20 480 tokens fit beside a serving
engine; a block's score matrix spans its window's keys and ALL summaries,
with the two masks of point 3.
"""

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = "highest"
#: query rows of one attention block (a window is whole blocks)
Q_BLOCK = 512


def f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def rms_norm(x, g, cfg):
    g = g.reshape(-1)                 # stored as a column [D, 1]
    weight = 1.0 + g if cfg["norm_add_unit_offset"] else g
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + cfg["rms_norm_eps"]) * weight


def rotary(x, positions, theta):
    """x: [S, heads, Dh]; rotate_half convention."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summaries(k, v, phi, mu, chunk):
    """Point 2: k, v [S, H, Dh] -> k~, v~ [S // chunk, H, Dh]."""
    s, h, dh = k.shape
    kc = k.reshape(s // chunk, chunk, h, dh)
    vc = v.reshape(s // chunk, chunk, h, dh)
    a = jax.nn.softmax(jnp.einsum("jmhd,hd->jmh", kc, phi), axis=1)
    return (jnp.einsum("jmh,jmhd->jhd", a, kc) + mu[None],
            jnp.einsum("jmh,jmhd->jhd", a, vc))


def layer(x, lp, cfg):
    """x: [S, D] float32, S whole windows -> [S, D]."""
    lp = f32(lp)
    a, m = lp["self_attn"], lp["mlp"]
    s_len = x.shape[0]
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    theta = cfg["rope_theta"]
    rows = min(Q_BLOCK, window)
    n_blocks = s_len // rows
    scale = a["q_proj"]["kernel"].shape[-1] ** -0.5

    def keys_values(b):
        xb = jax.lax.dynamic_slice_in_dim(x, b * rows, rows)
        h = rms_norm(xb, lp["input_layernorm"]["weight"], cfg)
        pos = b * rows + jnp.arange(rows)
        return (rotary(jnp.einsum("sd,dhe->she", h, a["k_proj"]["kernel"]),
                       pos, theta),
                jnp.einsum("sd,dhe->she", h, a["v_proj"]["kernel"]))

    k, v = jax.lax.map(keys_values, jnp.arange(n_blocks))
    k = k.reshape(s_len, *k.shape[2:])
    v = v.reshape(s_len, *v.shape[2:])
    k_far, v_far = summaries(k, v, a["eva_phi"], a["eva_mu"], chunk)
    chunk_window = jnp.arange(s_len // chunk) * chunk // window

    def block(b):
        xb = jax.lax.dynamic_slice_in_dim(x, b * rows, rows)
        pos = b * rows + jnp.arange(rows)
        w = b * rows // window
        h = rms_norm(xb, lp["input_layernorm"]["weight"], cfg)
        q = rotary(jnp.einsum("sd,dhe->she", h, a["q_proj"]["kernel"]), pos,
                   theta)
        k_win = jax.lax.dynamic_slice_in_dim(k, w * window, window)
        v_win = jax.lax.dynamic_slice_in_dim(v, w * window, window)
        near = jnp.einsum("qhd,mhd->hqm", q, k_win) * scale
        see = (w * window + jnp.arange(window))[None, :] <= pos[:, None]
        near = jnp.where(see[None], near, -jnp.inf)
        far = jnp.einsum("qhd,jhd->hqj", q, k_far) * scale
        far = jnp.where((chunk_window < w)[None, None, :], far, -jnp.inf)
        probs = jax.nn.softmax(jnp.concatenate([near, far], -1), axis=-1)
        out = jnp.einsum("hqm,mhd->qhd", probs[..., :window], v_win) \
            + jnp.einsum("hqj,jhd->qhd", probs[..., window:], v_far)
        xb = xb + out.reshape(rows, -1) @ a["o_proj"]["kernel"]
        h2 = rms_norm(xb, lp["post_attention_layernorm"]["weight"], cfg)
        return xb + (jax.nn.silu(h2 @ m["gate_proj"]["kernel"])
                     * (h2 @ m["up_proj"]["kernel"])) @ m["down_proj"]["kernel"]

    return jax.lax.map(block, jnp.arange(n_blocks)).reshape(x.shape)


def hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


@partial(jax.jit, static_argnames=("cfg_items", ))
def _layer_jit(x, lp, cfg_items):
    with jax.default_matmul_precision(HIGHEST):
        return layer(x, lp, dict(cfg_items))


@partial(jax.jit, static_argnames=("cfg_items", ))
def _head_jit(params_head, x, cfg_items):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision(HIGHEST):
        x = rms_norm(x, jnp.asarray(params_head["norm"]["weight"],
                                    jnp.float32), cfg)
        logits = x @ jnp.asarray(params_head["lm_head"]["kernel"],
                                 jnp.float32)
    return logits.reshape(x.shape[0], cfg["num_pred_heads"],
                          cfg["vocab_size"])


def all_head_logits_at(params, ids, positions, cfg):
    """Float32 logits [len(positions), num_pred_heads, V] of ONE sequence
    ``ids`` [S] at the given positions: one full forward, a jitted call per
    layer so that only one layer is ever upcast."""
    items = hashable(cfg)
    ids = jnp.asarray(ids, jnp.int32)
    ids = jnp.pad(ids, (0, -ids.shape[0] % cfg["window_size"]))
    x = jnp.asarray(params["embed_tokens"]["embedding"], jnp.float32)[ids]
    for i in range(cfg["num_hidden_layers"]):
        x = _layer_jit(x, params[f"layers_{i}"], items)
    sel = x[jnp.asarray(positions, jnp.int32)]
    return _head_jit({"norm": params["norm"], "lm_head": params["lm_head"]},
                     sel, items)


def logits_at(params, ids, positions, cfg):
    """Head 0's logits [len(positions), V]: the next-byte distribution,
    which is what greedy decoding streams."""
    return all_head_logits_at(params, ids, positions, cfg)[:, 0]
