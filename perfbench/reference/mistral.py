"""Plain float32 reference of the Mistral-7B forward pass, loss and AdamW.

Written from the published architecture (Mistral-7B-v0.1 ``config.json`` and
the ``MistralForCausalLM`` description): token embedding, pre-norm blocks of
RMSNorm -> grouped-query causal attention with rotary embeddings (half-split
``rotate_half`` convention) and a sliding window -> RMSNorm -> SwiGLU, final
RMSNorm, untied lm-head.  Plain ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no batching,
nothing imported from ``deepspeed_tpu``.

It reads the *layout* of the system's parameter tree (a data format):

    embed_tokens/embedding [V, D]        norm/weight [D]    lm_head/kernel [D, V]
    layers_<i>/input_layernorm/weight    layers_<i>/post_attention_layernorm/weight
    layers_<i>/self_attn/{q,k,v}_proj/kernel [D, heads, Dh]   o_proj/kernel [H*Dh, D]
    layers_<i>/mlp/{gate,up}_proj/kernel [D, I]               down_proj/kernel [I, D]

and upcasts one layer at a time, so a 16-layer reference never holds the whole
model in float32.  Departures from the published code: attention is computed
one key/value group at a time (same numbers, a [rep, S, S] score block instead
of [H, S, S]) and recomputed in the backward pass (``jax.checkpoint``: memory,
not mathematics); the window mask is ``0 <= i - j < window``.
"""

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = "highest"


def f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rotary(x, positions, theta):
    """x: [S, heads, Dh]; rotate_half convention of the published code."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window):
    """q: [S, H, Dh]; k, v: [S, Hkv, Dh] -> [S, H*Dh].  Causal, windowed.
    One key/value group at a time (``lax.map``), each group's scores
    recomputed in the backward pass instead of kept."""
    s, h, dh = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = j <= i
    if window:
        mask &= (i - j) < window

    @jax.checkpoint
    def group(qkv):
        qg, kg, vg = qkv                       # [S, rep, Dh], [S, Dh], [S, Dh]
        scores = jnp.einsum("srd,td->rst", qg, kg) / jnp.sqrt(jnp.float32(dh))
        scores = jnp.where(mask[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("rst,td->srd", probs, vg)

    qg = q.reshape(s, hkv, rep, dh).transpose(1, 0, 2, 3)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(s, h * dh)   # [S, Hkv*rep*Dh]


def attention_block(x, lp, cfg):
    """x + Attn(RMSNorm(x)); lp: one layer's parameters."""
    lp = f32(lp)
    a = lp["self_attn"]
    h = rms_norm(x, lp["input_layernorm"]["weight"], cfg["rms_norm_eps"])
    pos = jnp.arange(x.shape[0])
    q = rotary(jnp.einsum("sd,dhe->she", h, a["q_proj"]["kernel"]), pos,
               cfg["rope_theta"])
    k = rotary(jnp.einsum("sd,dhe->she", h, a["k_proj"]["kernel"]), pos,
               cfg["rope_theta"])
    v = jnp.einsum("sd,dhe->she", h, a["v_proj"]["kernel"])
    out = attention(q, k, v, cfg.get("sliding_window") or 0)
    return x + out @ a["o_proj"]["kernel"]


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def mlp_block(x, lp, cfg):
    lp = f32(lp)
    m = lp["mlp"]
    h = rms_norm(x, lp["post_attention_layernorm"]["weight"],
                 cfg["rms_norm_eps"])
    return x + swiglu(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                      m["down_proj"]["kernel"])


def layer(x, lp, cfg):
    return mlp_block(attention_block(x, lp, cfg), lp, cfg)


def embed(params, ids):
    return jnp.asarray(params["embed_tokens"]["embedding"], jnp.float32)[ids]


def head(params, x, cfg):
    x = rms_norm(x, jnp.asarray(params["norm"]["weight"], jnp.float32),
                 cfg["rms_norm_eps"])
    return x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32)


def hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


@partial(jax.jit, static_argnames=("cfg_items", "layer_fn"))
def _layer_jit(x, lp, cfg_items, layer_fn):
    with jax.default_matmul_precision(HIGHEST):
        return layer_fn(x, lp, dict(cfg_items))


@partial(jax.jit, static_argnames=("cfg_items",))
def _head_jit(params_head, x, cfg_items):
    with jax.default_matmul_precision(HIGHEST):
        return head(params_head, x, dict(cfg_items))


def logits_at(params, ids, positions, cfg, layer_fn=layer):
    """Float32 logits [len(positions), V] of ONE sequence ``ids`` [S] at the
    given positions: one full forward, a jitted call per layer so that only
    one layer is ever upcast."""
    items = hashable(cfg)
    x = embed(params, jnp.asarray(ids, jnp.int32))
    for i in range(cfg["num_hidden_layers"]):
        x = _layer_jit(x, params[f"layers_{i}"], items, layer_fn)
    sel = x[jnp.asarray(positions, jnp.int32)]
    return _head_jit({"norm": params["norm"], "lm_head": params["lm_head"]},
                     sel, items)


# ------------------------------------------------------------------ training
def sequence_loss(params, ids, cfg, layer_fn=layer):
    """Sum of next-token cross-entropies of one sequence and their count."""
    x = embed(params, ids)
    step = jax.checkpoint(lambda x, lp: layer_fn(x, lp, cfg))
    for i in range(cfg["num_hidden_layers"]):
        x = step(x, params[f"layers_{i}"])
    logits = head(params, x, cfg)[:-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, ids[1:, None], axis=-1)[:, 0]
    return jnp.sum(nll), nll.shape[0]


def make_loss_and_grad(cfg, n_seqs, layer_fn=layer, shardings=None):
    """Jitted ``(params, ids[S]) -> (loss share, grads)`` of one sequence of a
    batch of ``n_seqs``: its part of the token-mean cross-entropy.
    ``shardings``: where the gradients are to live (a tree of shardings like
    the parameters'), for a reference spread over several chips."""
    def one(params, ids):
        with jax.default_matmul_precision(HIGHEST):
            def f(p):
                total, n = sequence_loss(p, ids, cfg, layer_fn)
                return total / (n * n_seqs)
            return jax.value_and_grad(f)(params)
    if shardings is None:
        return jax.jit(one)
    return jax.jit(one, out_shardings=(None, shardings))


def batch_loss_and_grad(loss_and_grad, params, batch):
    """Token-mean cross-entropy over a batch [B, S] and its gradient, taken a
    sequence at a time and accumulated."""
    loss, grads = None, None
    for b in range(batch.shape[0]):
        l, g = loss_and_grad(params, jnp.asarray(batch[b], jnp.int32))
        if loss is None:
            loss, grads = l, g
        else:
            loss = loss + l
            grads = jax.tree_util.tree_map(jnp.add, grads, g)
    return loss, grads


@partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "weight_decay",
                                   "bias_correction"), donate_argnums=(0, 2, 3))
def adamw_step(params, grads, m, v, t, *, lr, b1=0.9, b2=0.999, eps=1e-8,
               weight_decay=0.0, bias_correction=True):
    """Decoupled-decay Adam as the engine's FusedAdam defaults define it:
    ``p -= lr * (m_hat / (sqrt(v_hat) + eps) + wd * p)``; ``t`` counts from 1."""
    def upd(p, g, m_, v_):
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        if bias_correction:
            mh, vh = m_ / (1 - b1 ** t), v_ / (1 - b2 ** t)
        else:
            mh, vh = m_, v_
        p = p - lr * (mh / (jnp.sqrt(vh) + eps) + weight_decay * p)
        return p, m_, v_
    out = jax.tree_util.tree_map(upd, params, grads, m, v)
    is_leaf = lambda x: isinstance(x, tuple)
    pick = lambda i: jax.tree_util.tree_map(lambda o: o[i], out,
                                            is_leaf=is_leaf)
    return pick(0), pick(1), pick(2)


def train_losses(params, batch, cfg, *, steps, adam, layer_fn=layer,
                 shardings=None):
    """Losses of ``batch`` [B, S] before any update and after each of ``steps``
    AdamW updates on that same batch, from float32 ``params`` (consumed)."""
    fn = make_loss_and_grad(cfg, batch.shape[0], layer_fn, shardings)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for t in range(steps + 1):
        loss, grads = batch_loss_and_grad(fn, params, batch)
        losses.append(float(loss))
        if t < steps:
            params, m, v = adamw_step(params, grads, m, v,
                                      jnp.float32(t + 1), **adam)
        del grads
    return losses
