"""Plain float32 reference of SmallThinker-21BA3B-Instruct: forward pass, loss
and AdamW.

Written from the published ``config.json`` (the model-configs guide's catalog
row ``SmallThinker-21BA3B-Instruct``) and the family's description.  Layer
``l`` on its input ``x [S, D]``:

    r = x W_r                                the router reads x AS IT ENTERS
    h = x + W_o Attn_l(RMSNorm_1(x))         grouped-query, causal, no bias
        sliding_window_layout[l] = 1: key j is seen iff 0 <= i - j < window
        rope_layout[l] = 1: rotary on q and k (half-split);  0: no positions
    p = softmax(r) over all E;  top k;  w_i = p_i / sum_i p_i   (norm_topk_prob)
    y = h + sum_i w_i W2[e_i] (relu(W1[e_i] u) * W3[e_i] u),   u = RMSNorm_2(h)

then the final RMSNorm and an untied head; the loss is the mean next-token
cross-entropy.  Plain ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, nothing imported from
``deepspeed_tpu``.  What the config has no key for is stated in the sizes,
as the configuration's ``assumed`` states it: ``router_input`` (``"layer_input"``;
``"attention_norm"`` would feed the router ``RMSNorm_1(x)``) and
``expert_activation`` (``"relu"``; ``"silu"`` would make the experts SwiGLU).

The layout it reads (a data format, Mixtral's):

    embed_tokens/embedding [V, D]        norm/weight [D]    lm_head/kernel [D, V]
    layers_<i>/input_layernorm/weight    layers_<i>/post_attention_layernorm/weight
    layers_<i>/self_attn/{q,k,v}_proj/kernel [D, heads, Dh]   o_proj/kernel [H*Dh, D]
    layers_<i>/moe/gate/kernel [D, E]    layers_<i>/moe/{w1,w3} [held, D, I]   w2 [held, I, D]

**One chip's share** (README.md).  The sizes state ``experts_held`` and
``first_expert``: the router keeps its width (the gate's own shape) and its
experts per token, the stacks hold the experts ``first_expert .. first_expert
+ experts_held - 1`` alone, the block adds those experts' part and nothing in
place of the others, and the loss is over the vocabulary's slice (the
embedding's and the head's own shapes).

Departures from the published code, none of them mathematics: every HELD
expert is computed for every token and weighted by 0 where the token is not
routed to it (no sorting, no gather).  So that 656.5 M float32 parameters with
their gradients and two moments (10.5 GB) and 8192 tokens fit one 16 GB chip,
everything is computed in blocks and recomputed in the backward pass
(``jax.checkpoint``): a layer, inside it attention one key/value group and
:data:`QUERY_BLOCK` queries at a time and the experts :data:`ROW_BLOCK` tokens
at a time, and the head with its loss :data:`ROW_BLOCK` tokens at a time (in
blocks of TOKENS, not of the vocabulary: a token's log-softmax then needs no
running maximum).
"""

import os
from functools import partial

import jax
import jax.numpy as jnp

from perfbench.loader import load_file

base = load_file(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "mistral.py"))
HIGHEST = base.HIGHEST
f32, hashable = base.f32, base.hashable
rms_norm, rotary, embed = base.rms_norm, base.rotary, base.embed
batch_loss_and_grad = base.batch_loss_and_grad

#: queries a block of the attention; a sequence this does not divide is one
QUERY_BLOCK = 1024
#: tokens a block of the expert layer and of the head
ROW_BLOCK = 1024

ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def layer_kinds(cfg):
    """``[(window, rotary)]`` of the layers run: the first
    ``num_hidden_layers`` entries of the two published layouts."""
    n = cfg["num_hidden_layers"]
    return [(cfg["sliding_window_size"] * int(w), int(r)) for w, r in
            zip(cfg["sliding_window_layout"][:n], cfg["rope_layout"][:n])]


def _blocks(n, block):
    """``(block length, count)``: ``n`` rows in whole blocks, or in one."""
    return (block, n // block) if n % block == 0 else (n, 1)


def attention(q, k, v, window):
    """q: [S, H, Dh]; k, v: [S, Hkv, Dh] -> [S, H*Dh].  Causal; ``window``
    > 0: key j is seen iff ``0 <= i - j < window``."""
    s, h, dh = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    block, n = _blocks(s, QUERY_BLOCK)
    j = jnp.arange(s)[None, :]

    @jax.checkpoint
    def part(qb, first, kg, vg):            # [block, rep, Dh], [S, Dh] x 2
        i = first + jnp.arange(block)[:, None]
        mask = j <= i
        if window:
            mask &= (i - j) < window
        scores = jnp.einsum("brd,td->rbt", qb, kg) / jnp.sqrt(jnp.float32(dh))
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
        return jnp.einsum("rbt,td->brd", probs, vg)

    def group(qkv):
        qg, kg, vg = qkv                    # [n, block, rep, Dh], [S, Dh] x 2
        return jax.lax.map(
            lambda a: part(a[0], a[1], kg, vg),
            (qg, jnp.arange(n) * block))

    qg = q.reshape(n, block, hkv, rep, dh).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    # [Hkv, n, block, rep, Dh] -> [S, Hkv * rep * Dh]
    return out.transpose(1, 2, 0, 3, 4).reshape(s, h * dh)


def attention_block(x, lp, cfg, window, turn):
    """``x + Attn(RMSNorm_1(x))``; ``turn``: rotary on q and k."""
    a = lp["self_attn"]
    h = rms_norm(x, lp["input_layernorm"]["weight"], cfg["rms_norm_eps"])
    q = jnp.einsum("sd,dhe->she", h, a["q_proj"]["kernel"])
    k = jnp.einsum("sd,dhe->she", h, a["k_proj"]["kernel"])
    v = jnp.einsum("sd,dhe->she", h, a["v_proj"]["kernel"])
    if turn:
        pos = jnp.arange(x.shape[0])
        q = rotary(q, pos, cfg["rope_theta"])
        k = rotary(k, pos, cfg["rope_theta"])
    return x + attention(q, k, v, window) @ a["o_proj"]["kernel"]


def route(router_logits, k, renormalise=True):
    """``[S, E]``: each token's weight on every expert of the router, 0 where
    it is not among the token's ``k``: softmax over all ``E``, the ``k``
    largest, divided by their sum where ``renormalise``."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if renormalise:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32)
                   * top[..., None], axis=1)


def held_experts(cfg, stacked):
    """``(first, count)`` of the experts this share holds; with no share
    stated, all that are stacked."""
    if cfg.get("experts_held") is None:
        return 0, stacked
    return int(cfg.get("first_expert", 0)), int(cfg["experts_held"])


def moe_block(x, h, lp, cfg):
    """``h + sum_i w_i expert_{e_i}(RMSNorm_2(h))`` over the held experts,
    routed by the layer's input ``x``."""
    m = lp["moe"]
    u = rms_norm(h, lp["post_attention_layernorm"]["weight"],
                 cfg["rms_norm_eps"])
    if cfg.get("router_input", "layer_input") == "layer_input":
        seen = x
    else:                                   # a reading the config does not bear
        seen = rms_norm(x, lp["input_layernorm"]["weight"],
                        cfg["rms_norm_eps"])
    weights = route(seen @ m["gate"]["kernel"], cfg["num_experts_per_tok"],
                    cfg.get("norm_topk_prob", True))
    first, held = held_experts(cfg, m["w1"].shape[0])
    act = ACTIVATIONS[cfg.get("expert_activation", "relu")]

    @jax.checkpoint
    def part(ub, wb):                       # [block, D], [block, held]
        gate = jnp.einsum("bd,edi->ebi", ub, m["w1"])
        up = jnp.einsum("bd,edi->ebi", ub, m["w3"])
        out = jnp.einsum("ebi,eid->ebd", act(gate) * up, m["w2"])
        return jnp.einsum("ebd,be->bd", out, wb)

    s, d = u.shape
    block, n = _blocks(s, ROW_BLOCK)
    out = jax.lax.map(lambda a: part(*a), (
        u.reshape(n, block, d),
        weights[:, first:first + held].reshape(n, block, held)))
    return h + out.reshape(s, d)


def layer(x, lp, cfg, window, turn):
    lp = f32(lp)
    return moe_block(x, attention_block(x, lp, cfg, window, turn), lp, cfg)


def head(params, x, cfg):
    x = rms_norm(x, jnp.asarray(params["norm"]["weight"], jnp.float32),
                 cfg["rms_norm_eps"])
    return x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32)


@partial(jax.jit, static_argnames=("cfg_items", "window", "turn"))
def _layer_jit(x, lp, cfg_items, window, turn):
    with jax.default_matmul_precision(HIGHEST):
        return layer(x, lp, dict(cfg_items), window, turn)


@partial(jax.jit, static_argnames=("cfg_items",))
def _head_jit(params_head, x, cfg_items):
    with jax.default_matmul_precision(HIGHEST):
        return head(params_head, x, dict(cfg_items))


def logits_at(params, ids, positions, cfg):
    """Float32 logits [len(positions), V] of ONE sequence ``ids`` [S] at the
    given positions: one full forward, a jitted call a layer."""
    items = hashable(cfg)
    x = embed(params, jnp.asarray(ids, jnp.int32))
    for i, (window, turn) in enumerate(layer_kinds(cfg)):
        x = _layer_jit(x, params[f"layers_{i}"], items, window, turn)
    sel = x[jnp.asarray(positions, jnp.int32)]
    return _head_jit({"norm": params["norm"], "lm_head": params["lm_head"]},
                     sel, items)


# ------------------------------------------------------------------ training
def sequence_loss(params, ids, cfg):
    """Sum of next-token cross-entropies of one sequence and their count."""
    x = embed(params, ids)
    for i, (window, turn) in enumerate(layer_kinds(cfg)):
        step = jax.checkpoint(partial(layer, cfg=cfg, window=window,
                                      turn=turn))
        x = step(x, params[f"layers_{i}"])
    x = rms_norm(x[:-1], jnp.asarray(params["norm"]["weight"], jnp.float32),
                 cfg["rms_norm_eps"])
    w = jnp.asarray(params["lm_head"]["kernel"], jnp.float32)

    @jax.checkpoint
    def part(xb, tb, real):                 # [block, D], [block], [block]
        logp = jax.nn.log_softmax(xb @ w, axis=-1)
        nll = -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(real, nll, 0.0))

    # S - 1 tokens are rarely whole blocks: zero rows fill the last, unread
    n_tokens, d = x.shape
    block = min(ROW_BLOCK, n_tokens)
    n = -(-n_tokens // block)
    fill = n * block - n_tokens
    nll = jnp.sum(jax.lax.map(lambda a: part(*a), (
        jnp.pad(x, ((0, fill), (0, 0))).reshape(n, block, d),
        jnp.pad(ids[1:], (0, fill)).reshape(n, block),
        (jnp.arange(n * block) < n_tokens).reshape(n, block))))
    return nll, n_tokens


def make_loss_and_grad(cfg, n_seqs, shardings=None):
    """Jitted ``(params, ids[S]) -> (loss share, grads)`` of one sequence of a
    batch of ``n_seqs``: its part of the token-mean cross-entropy."""
    def one(params, ids):
        with jax.default_matmul_precision(HIGHEST):
            def f(p):
                total, n = sequence_loss(p, ids, cfg)
                return total / (n * n_seqs)
            return jax.value_and_grad(f)(params)
    if shardings is None:
        return jax.jit(one)
    return jax.jit(one, out_shardings=(None, shardings))


def train_losses(params, batch, cfg, *, steps, adam, shardings=None):
    """Losses of ``batch`` [B, S] before any update and after each of ``steps``
    AdamW updates on that same batch, from float32 ``params`` (consumed);
    AdamW as ``mistral.py`` beside this file does it."""
    fn = make_loss_and_grad(cfg, batch.shape[0], shardings)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for t in range(steps + 1):
        loss, grads = batch_loss_and_grad(fn, params, batch)
        losses.append(float(loss))
        if t < steps:
            params, m, v = base.adamw_step(params, grads, m, v,
                                           jnp.float32(t + 1), **adam)
        del grads
    return losses
