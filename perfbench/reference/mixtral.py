"""Plain float32 reference of the Mixtral-8x7B forward pass, loss and AdamW.

Written from the published architecture (Mixtral-8x7B-v0.1 ``config.json`` and
the ``MixtralForCausalLM`` description): Mistral's block (``mistral.py``
beside this file: RMSNorm, grouped-query causal attention with rotary
embeddings, an optional window) with the MLP replaced by a sparse block:
router (a [D, E] matrix) -> softmax over the E experts -> the k largest ->
renormalised to sum 1 -> the weighted sum of those k SwiGLU experts.  Plain
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
nothing imported from ``deepspeed_tpu``.

The layout it reads beside Mistral's (a data format):

    layers_<i>/moe/gate/kernel [D, E]
    layers_<i>/moe/{w1,w3} [E, D, I]     layers_<i>/moe/w2 [E, I, D]

Departures from the published code: every expert is computed for every token
and weighted by 0 where the token is not routed to it (same numbers; no
sorting, no gather), one expert at a time (``lax.scan`` over the expert axis),
so only one expert is ever upcast.

**Routing is stated.**  Top-k routing is not continuous: where a token's k-th
and (k+1)-th router logits lie closer than the rounding error of the system
under test, that system and this reference send the token to different
experts, and both are right.  ``logits_and_routing_at`` therefore returns,
beside the logits, each requested token's router margin at every layer (k-th
largest router logit minus the (k+1)-th), and can compute the logits with the
k-th and (k+1)-th expert exchanged at one layer for one token alone: the
second of the two answers ``jobs/serve.py`` accepts for a position inside the
configuration's margin.  ``router_logit_error`` is how that margin is measured
(README.md).

**One chip's share** (README.md, "One chip's share").  Where the sizes state
``experts_held`` (and ``first_expert``, default 0), the router keeps its
width E (the gate's own shape) and its experts per token, the stacks ``w1 /
w3 / w2`` hold only the experts ``first_expert .. first_expert + experts_held
- 1``, and the block adds those experts' part of the result and nothing in
place of the others: the partial result goes on to the next layer.  A token
whose k-th and (k+1)-th experts are BOTH held elsewhere gives this share the
same experts either way, so its margin is reported as infinite: no second
answer is asked for there.  With neither key every expert is held and nothing
below differs from before.
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
batch_loss_and_grad = base.batch_loss_and_grad


def rounded(x, cfg):
    """``x`` rounded to ``cfg["round_activations_to"]`` and back, where the
    sizes state one: the reference as a system serving in that type would
    compute it (weights as given, every activation it writes rounded)."""
    to = cfg.get("round_activations_to")
    return x.astype(to).astype(jnp.float32) if to else x


def attention_block(x, lp, cfg):
    """``mistral.attention_block`` with the rounding points marked."""
    r = partial(rounded, cfg=cfg)
    a = f32(lp["self_attn"])
    h = r(base.rms_norm(x, f32(lp["input_layernorm"]["weight"]),
                        cfg["rms_norm_eps"]))
    pos = jnp.arange(x.shape[0])
    q = r(base.rotary(r(jnp.einsum("sd,dhe->she", h, a["q_proj"]["kernel"])),
                      pos, cfg["rope_theta"]))
    k = r(base.rotary(r(jnp.einsum("sd,dhe->she", h, a["k_proj"]["kernel"])),
                      pos, cfg["rope_theta"]))
    v = r(jnp.einsum("sd,dhe->she", h, a["v_proj"]["kernel"]))
    out = r(base.attention(q, k, v, cfg.get("sliding_window") or 0))
    return r(x + r(out @ a["o_proj"]["kernel"]))


def held_experts(cfg):
    """``(first, count)`` of the experts this share holds, or None where the
    sizes state no share (every expert is held)."""
    if cfg.get("experts_held") is None:
        return None
    return int(cfg.get("first_expert", 0)), int(cfg["experts_held"])


def route(router_logits, k, flip_token=-1, renormalise=True, held=None):
    """``(weights [S, E], margin [S])``: each token's weight on every expert
    (0 where it is not routed there) and its router margin, the k-th largest
    router logit minus the (k+1)-th (inf where k == E, and, under a share
    ``held = (first, count)``, where both of those experts are held
    elsewhere).  The token at index ``flip_token`` takes its (k+1)-th expert
    in place of its k-th."""
    s, e = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)
    top, idx = jax.lax.top_k(router_logits, min(k + 1, e))
    if k < e:
        margin = top[:, k - 1] - top[:, k]
        if held is not None:
            here = (idx[:, k - 1:] >= held[0]) & \
                (idx[:, k - 1:] < held[0] + held[1])
            margin = jnp.where(jnp.any(here, axis=1), margin, jnp.inf)
        last = jnp.where(jnp.arange(s) == flip_token, idx[:, k],
                         idx[:, k - 1])
        idx = jnp.concatenate([idx[:, :k - 1], last[:, None]], axis=1)
    else:
        margin = jnp.full((s,), jnp.inf, jnp.float32)
    w = jnp.take_along_axis(probs, idx, axis=-1)
    if renormalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                      * w[..., None], axis=1)
    return weights, margin


def moe_block(x, lp, cfg, flip_token=-1, weights=None):
    """``(x + MoE(RMSNorm(x)), router logits [S, E], margin [S], weights
    [S, E])``.  ``weights`` given: routed so, whatever the router says."""
    r = partial(rounded, cfg=cfg)
    m = lp["moe"]
    h = r(base.rms_norm(x, f32(lp["post_attention_layernorm"]["weight"]),
                        cfg["rms_norm_eps"]))
    router_logits = h @ f32(m["gate"]["kernel"])
    held = held_experts(cfg)
    own, margin = route(router_logits, cfg["num_experts_per_tok"],
                        flip_token, cfg.get("norm_topk_prob", True), held)
    weights = own if weights is None else weights
    columns = weights.T                      # one row an expert of the router
    if held is not None:                     # the stacks hold these alone
        columns = columns[held[0]:held[0] + held[1]]

    def expert(acc, e):
        w1, w3, w2, col = e                  # one expert, upcast here
        act = r(jax.nn.silu(r(h @ f32(w1))) * r(h @ f32(w3)))
        return acc + r(r(act @ f32(w2)) * col[:, None]), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (m["w1"], m["w3"], m["w2"], columns))
    return r(x + r(out)), router_logits, margin, weights


def layer(x, lp, cfg):
    return moe_block(attention_block(x, lp, cfg), lp, cfg)[0]


logits_at = partial(base.logits_at, layer_fn=layer)
make_loss_and_grad = partial(base.make_loss_and_grad, layer_fn=layer)
train_losses = partial(base.train_losses, layer_fn=layer)


# ------------------------------------------------------------------- routing
@partial(jax.jit, static_argnames=("cfg_items",))
def _head_jit(params_head, x, cfg_items):
    with jax.default_matmul_precision(HIGHEST):
        return base.head(params_head, x, dict(cfg_items))


@partial(jax.jit, static_argnames=("cfg_items",))
def _routed_layer_jit(x, lp, flip_token, weights, cfg_items):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision(HIGHEST):
        return moe_block(attention_block(x, lp, cfg), lp, cfg, flip_token,
                         weights)


def logits_and_routing_at(params, ids, positions, cfg, flip=None):
    """``logits_at`` with the routing stated: ``(logits [P, V], margins
    [P, L])``, the float32 logits of ONE sequence at ``positions`` and the
    router margin of the token at each of them at every layer.  With ``flip =
    (layer, position)`` the token at that position (and no other) takes its
    (k+1)-th expert in place of its k-th at that layer."""
    items = hashable(cfg)
    x = base.embed(params, jnp.asarray(ids, jnp.int32))
    margins = []
    for i in range(cfg["num_hidden_layers"]):
        token = flip[1] if flip is not None and flip[0] == i else -1
        x, _, margin, _ = _routed_layer_jit(
            x, params[f"layers_{i}"], jnp.int32(token), None, items)
        margins.append(margin)
    at = jnp.asarray(positions, jnp.int32)
    logits = _head_jit({"norm": params["norm"], "lm_head": params["lm_head"]},
                       x[at], items)
    return logits, jnp.stack(margins)[:, at].T


def router_logit_error(params, ids, cfg, serving_type="bfloat16"):
    """The largest difference, over one sequence's tokens, layers and experts,
    between the float32 router logits and those of the same reference with
    every activation rounded to ``serving_type`` where a system serving in
    that type rounds (``rounded``): how far rounding alone moves a router
    logit.  The rounded pass is ROUTED AS the float32 one, layer by layer: a
    token it would send elsewhere is the discontinuity being sized, and what
    follows a flip is no rounding error.  The worst over the seeds run is the
    configuration's ``measured_worst["serve.router_margin"]``."""
    exact = hashable(cfg)
    lossy = hashable(dict(cfg, round_activations_to=serving_type))
    x = xr = base.embed(params, jnp.asarray(ids, jnp.int32))
    worst = 0.0
    for i in range(cfg["num_hidden_layers"]):
        lp, none = params[f"layers_{i}"], jnp.int32(-1)
        x, router, _, weights = _routed_layer_jit(x, lp, none, None, exact)
        xr, router_r, _, _ = _routed_layer_jit(xr, lp, none, weights, lossy)
        worst = max(worst, float(jnp.max(jnp.abs(router - router_r))))
    return worst
