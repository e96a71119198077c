"""Plain float32 reference of the Cohere2-MoE forward pass (Command A+,
``model_type: cohere2_moe``), share-aware.

Written from the published ``config.json`` (the catalog's row
``command-a-plus-05-2026``) and the configuration file's three ``assumed``
readings.  With ``D`` hidden, ``E`` the router's width, ``k`` experts a
token, for layer ``l`` of kind ``layer_kinds[l]`` (``S`` sliding, ``F`` full):

    h   = LN(x) = (x - mean(x)) / sqrt(var(x) + eps) * g          (no bias)
    q, k, v = h Wq, h Wk, h Wv
    S:  q, k turned by rotary on INTERLEAVED pairs (x[2i], x[2i+1]), theta
        ``rope_theta``, the whole head; causal mask with 0 <= i - j < window
    F:  no rotary, no positions at all; causal mask only
    a   = softmax(q k^T / sqrt(head_dim)) v Wo            (grouped-query)
    s   = sigmoid(h Wr) [E];  S = top-k of s;  w_e = s_e / sum_{e' in S} s_e'
    r   = sum_{e in S, held} w_e W2_e (silu(W1_e h) * W3_e h)
    c   = 1/n sum_{i<n} V2_i (silu(V1_i h) * V3_i h)
    x'  = x + a + r + c                      (parallel block: ONE norm)
    logits = LN_f(x_L) Emb^T * logit_scale   (tied embedding)

Plain ``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``; nothing imported from ``deepspeed_tpu``.  The layout it reads
(a data format):

    embed_tokens/weight [V, D]             norm/weight [D]
    layers_<i>/input_layernorm/weight [D]
    layers_<i>/self_attn/{q,k,v}_proj/kernel [D, heads, Dh]   o_proj/kernel [H*Dh, D]
    layers_<i>/moe/gate/kernel [D, E]
    layers_<i>/moe/{w1,w3} [held, D, I]           moe/w2 [held, I, D]
    layers_<i>/moe/{shared_w1,shared_w3} [n, D, I]   moe/shared_w2 [n, I, D]

**One chip's share** (``perfbench/README.md``).  The sizes state
``experts_held`` and ``first_expert``; the router keeps its width (the
gate's own shape), ``S`` and ``w_e`` are taken over all of it, the stacks hold
the experts ``first_expert .. first_expert + experts_held - 1`` and ``r``
sums over those alone: nothing stands in for the rest and the partial ``x'``
goes on.  A token whose k-th and (k+1)-th experts are BOTH held elsewhere
gives this share the same experts either way: its margin is reported as
infinite.

**Routing is stated** (as ``reference/mixtral.py``): ``logits_and_routing_at``
returns each requested token's router margin at every layer, on the router
LOGITS (the sigmoid is monotone: the k largest scores are the k largest
logits), and can exchange the k-th and (k+1)-th expert at one layer for one
token.  ``router_logit_error`` sizes the margin with the rounding points of
THIS block.

Departures from the published code, none of them mathematics:

* every held expert is computed for every token and weighted by 0 where the
  token is not routed to it, one expert upcast at a time (``lax.scan``), in
  blocks of ``MOE_ROWS`` tokens;
* attention is computed one key/value group at a time and, inside a group,
  in blocks of ``QUERY_ROWS`` queries against all keys (at 16 384 tokens one
  group's ``[16, S, S]`` scores would be 17 GB), the group's part of the
  output projection added as it is made;
* a SECOND answer (``flip``) whose token lies at or after the first position
  the first answer was asked for recomputes the tokens from that position on
  alone, against the first answer's keys and values of the tokens before it:
  a causal model's earlier tokens do not see a later token's routing, so the
  numbers are those of a whole forward pass (``test_perfbench_cohere2_moe.py``
  holds the two to each other) at a hundredth of its cost.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
QUERY_ROWS = 256
MOE_ROWS = 2048
#: the layer kinds (letters of ``layer_kinds``) whose q and k are turned by
#: the rotary, and those that read a window: the sliding layers, both
ROTARY_KINDS = WINDOW_KINDS = "S"


def score(router_logits):
    """A router logit's score (``expert_selection_fn: sigmoid``)."""
    return jax.nn.sigmoid(router_logits)


def f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


def hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


def rounded(x, cfg):
    """``x`` rounded to ``cfg["round_activations_to"]`` and back, where the
    sizes state one: the reference as a system serving in that type would
    compute it (weights as given, every activation it writes rounded)."""
    to = cfg.get("round_activations_to")
    return x.astype(to).astype(jnp.float32) if to else x


def layer_norm(x, weight, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * weight


def rotary_pairs(x, positions, theta):
    """x: [S, heads, Dh]; interleaved pairs (x[2i], x[2i+1]) (``rope_gptj``)."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(x.shape[:-1] + (dh // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1) \
        .reshape(x.shape)


def blocks_of(x, rows):
    """``x [S, ...]`` as ``[n, rows, ...]``, padded with zeros."""
    pad = -x.shape[0] % rows
    x = jnp.pad(x, ((0, pad), ) + ((0, 0), ) * (x.ndim - 1))
    return x.reshape((-1, rows) + x.shape[1:])


def group_attention(q, k, v, q_pos, window):
    """One key/value group: q ``[Sq, rep, Dh]`` at positions ``q_pos [Sq]``
    against keys and values ``[S, Dh]`` at positions ``0 .. S - 1`` ->
    ``[Sq, rep * Dh]``.  Causal, inside ``window`` if any; in blocks of
    ``QUERY_ROWS`` queries."""
    sq, rep, dh = q.shape
    rows = min(QUERY_ROWS, sq)
    key_pos = jnp.arange(k.shape[0])[None, :]

    def block(args):
        qb, pb = args                              # [rows, rep, Dh], [rows]
        dist = pb[:, None] - key_pos
        mask = dist >= 0
        if window:
            mask &= dist < window
        scores = jnp.einsum("srd,td->rst", qb, k) / jnp.sqrt(jnp.float32(dh))
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
        return jnp.einsum("rst,td->srd", probs, v)

    out = jax.lax.map(block, (blocks_of(q, rows), blocks_of(q_pos, rows)))
    return out.reshape(-1, rep * dh)[:sq]


def attention_part(h, a, cfg, kind, pos0, k_before, v_before):
    """``(Attn(h) [S, D], k, v [pos0 + S, Hkv, Dh])`` for the tokens at
    positions ``pos0 .. pos0 + S - 1``; ``k_before, v_before`` are the keys
    and values of the tokens before them (None: there are none)."""
    r = partial(rounded, cfg=cfg)
    pos = pos0 + jnp.arange(h.shape[0])
    q = r(jnp.einsum("sd,dhe->she", h, a["q_proj"]["kernel"]))
    k = r(jnp.einsum("sd,dhe->she", h, a["k_proj"]["kernel"]))
    v = r(jnp.einsum("sd,dhe->she", h, a["v_proj"]["kernel"]))
    if kind in ROTARY_KINDS:
        q = r(rotary_pairs(q, pos, cfg["rope_theta"]))
        k = r(rotary_pairs(k, pos, cfg["rope_theta"]))
    window = cfg["sliding_window"] if kind in WINDOW_KINDS else 0
    if k_before is not None:
        k = jnp.concatenate([k_before, k])
        v = jnp.concatenate([v_before, v])
    s, heads, dh = q.shape
    hkv = k.shape[1]
    rep = heads // hkv
    wo = a["o_proj"]["kernel"].reshape(hkv, rep * dh, -1)

    def group(acc, g):
        qg, kg, vg, wg = g
        out = r(group_attention(qg, kg, vg, pos, window))
        return acc + out @ wg, None

    out, _ = jax.lax.scan(
        group, jnp.zeros((s, wo.shape[-1]), jnp.float32),
        (q.reshape(s, hkv, rep, dh).transpose(1, 0, 2, 3),
         k.transpose(1, 0, 2), v.transpose(1, 0, 2), wo))
    return r(out), k, v


def held_experts(cfg):
    """``(first, count)`` of the experts this share holds, or None where the
    sizes state no share (every expert is held)."""
    if cfg.get("experts_held") is None:
        return None
    return int(cfg.get("first_expert", 0)), int(cfg["experts_held"])


def route(router_logits, k, flip_token=-1, renormalise=True, held=None):
    """``(weights [S, E], margin [S])``: each token's weight on every expert
    (0 where it is not routed there; sigmoid scores, normalised over the k
    chosen) and its router margin, the k-th largest router LOGIT minus the
    (k+1)-th (inf where k == E, and, under a share ``held = (first, count)``,
    where both of those experts are held elsewhere).  The token at index
    ``flip_token`` takes its (k+1)-th expert in place of its k-th."""
    s, e = router_logits.shape
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
    w = score(jnp.take_along_axis(router_logits, idx, axis=-1))
    if renormalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                      * w[..., None], axis=1)
    return weights, margin


def moe_part(h, m, cfg, flip_token=-1, weights=None):
    """``(r + c [S, D], router logits [S, E], margin [S], weights [S, E])``.
    ``weights`` given: routed so, whatever the router says."""
    r = partial(rounded, cfg=cfg)
    router_logits = h @ f32(m["gate"]["kernel"])
    held = held_experts(cfg)
    own, margin = route(router_logits, cfg["num_experts_per_tok"],
                        flip_token, cfg.get("norm_topk_prob", True), held)
    weights = own if weights is None else weights
    columns = weights
    if held is not None:                     # the stacks hold these alone
        columns = columns[:, held[0]:held[0] + held[1]]
    n = m["shared_w1"].shape[0]

    def block(args):
        hb, cols = args                      # [rows, D], [rows, held]

        def expert(acc, e):
            w1, w3, w2, col = e              # one expert, upcast here
            act = r(jax.nn.silu(r(hb @ f32(w1))) * r(hb @ f32(w3)))
            return acc + r(r(act @ f32(w2)) * col[:, None]), None

        def shared(acc, e):
            w1, w3, w2 = e
            act = r(jax.nn.silu(r(hb @ f32(w1))) * r(hb @ f32(w3)))
            return acc + act @ f32(w2), None

        zero = jnp.zeros_like(hb)
        routed, _ = jax.lax.scan(expert, zero,
                                 (m["w1"], m["w3"], m["w2"], cols.T))
        mean, _ = jax.lax.scan(
            shared, zero, (m["shared_w1"], m["shared_w3"], m["shared_w2"]))
        return r(r(routed) + r(mean / n))

    rows = min(MOE_ROWS, h.shape[0])
    out = jax.lax.map(block, (blocks_of(h, rows), blocks_of(columns, rows)))
    return out.reshape(-1, h.shape[1])[:h.shape[0]], router_logits, margin, \
        weights


def layer(x, lp, cfg, kind, pos0=0, k_before=None, v_before=None,
          flip_token=-1, weights=None):
    """``(x', router logits, margin, weights, k, v)`` of one layer of kind
    ``kind`` for the tokens at positions ``pos0 ..``."""
    r = partial(rounded, cfg=cfg)
    h = r(layer_norm(x, f32(lp["input_layernorm"]["weight"]),
                     cfg["layer_norm_eps"]))
    a, k, v = attention_part(h, f32(lp["self_attn"]), cfg, kind, pos0,
                             k_before, v_before)
    moe, router_logits, margin, weights = moe_part(h, lp["moe"], cfg,
                                                   flip_token, weights)
    return r(r(x + a) + moe), router_logits, margin, weights, k, v


def embed(params, ids):
    return jnp.asarray(params["embed_tokens"]["weight"], jnp.float32)[ids]


def head(params, x, cfg):
    x = layer_norm(x, jnp.asarray(params["norm"]["weight"], jnp.float32),
                   cfg["layer_norm_eps"])
    return x @ jnp.asarray(params["embed_tokens"]["weight"],
                           jnp.float32).T * cfg.get("logit_scale", 1)


@partial(jax.jit, static_argnames=("cfg_items", "kind", "pos0"))
def _layer_jit(x, lp, k_before, v_before, flip_token, weights, cfg_items,
               kind, pos0=0):
    with jax.default_matmul_precision(HIGHEST):
        return layer(x, lp, dict(cfg_items), kind, pos0, k_before, v_before,
                     flip_token, weights)


@partial(jax.jit, static_argnames=("cfg_items",))
def _head_jit(params_head, x, cfg_items):
    with jax.default_matmul_precision(HIGHEST):
        return head(params_head, x, dict(cfg_items))


def _head(params, x, cfg):
    return _head_jit({"norm": params["norm"],
                      "embed_tokens": params["embed_tokens"]}, x,
                     hashable(cfg))


def logits_at(params, ids, positions, cfg):
    """Float32 logits [len(positions), V] of ONE sequence ``ids`` [S] at the
    given positions."""
    return logits_and_routing_at(params, ids, positions, cfg, _keep=False)[0]


#: the newest first answer's sequence, the first position it was asked for,
#: and per layer the tokens' hidden states from that position on and every
#: token's keys and values: what a second answer is recomputed from
_FIRST = {}


def logits_and_routing_at(params, ids, positions, cfg, flip=None, _keep=True):
    """``(logits [P, V], margins [P, L])``: the float32 logits of ONE sequence
    at ``positions`` and the router margin of the token at each of them at
    every layer.  With ``flip = (layer, position)`` the token at that position
    (and no other) takes its (k+1)-th expert in place of its k-th at that
    layer."""
    items, kinds = hashable(cfg), cfg["layer_kinds"]
    ids = np.asarray(ids, np.int32)
    at = np.asarray(positions, np.int32)
    none = jnp.int32(-1)
    first = _FIRST if flip is not None and _FIRST.get("ids") is not None \
        and np.array_equal(_FIRST["ids"], ids) \
        and flip[1] >= _FIRST["start"] <= at.min() else None
    if first is None:
        start, begin = 0, 0
        x = embed(params, jnp.asarray(ids))
    else:                       # the tokens from ``start`` on, from ``begin``
        start, begin = first["start"], flip[0]
        x = first["x"][begin]
    if flip is None:
        _FIRST.clear()
        if _keep:
            _FIRST.update(ids=ids, start=int(at.min()), x=[], kv=[])
    margins = [jnp.full((len(ids) - start, ), jnp.inf)] * begin
    for i in range(begin, cfg["num_hidden_layers"]):
        token = jnp.int32(flip[1] - start) \
            if flip is not None and flip[0] == i else none
        before = (None, None) if first is None else \
            tuple(a[:start] for a in first["kv"][i])
        if flip is None and _keep:
            _FIRST["x"].append(x[_FIRST["start"]:])
        x, _, margin, _, k, v = _layer_jit(
            x, params[f"layers_{i}"], *before, token, None, items, kinds[i],
            start)
        if flip is None and _keep:
            _FIRST["kv"].append((k, v))
        margins.append(margin)
    logits = _head(params, x[jnp.asarray(at - start)], cfg)
    return logits, jnp.stack(margins)[:, at - start].T


def router_logit_error(params, ids, cfg, serving_type="bfloat16"):
    """The largest difference, over one sequence's tokens, layers and experts,
    between the float32 router logits and those of the same reference with
    every activation rounded to ``serving_type`` where a system serving in
    that type rounds (``rounded``: the norm, the three projections, the
    rotary, each group's attention output, the output projection, each
    expert's three products and its weighted part, the routed sum, the shared
    experts' mean, and the two adds of the parallel block's residual).  The
    rounded pass is ROUTED AS the float32 one, layer by layer.  The worst over
    the seeds run is the configuration's
    ``measured_worst["serve.router_margin"]``."""
    exact = hashable(cfg)
    lossy = hashable(dict(cfg, round_activations_to=serving_type))
    x = xr = embed(params, jnp.asarray(ids, jnp.int32))
    worst, none = 0.0, jnp.int32(-1)
    for i, kind in enumerate(cfg["layer_kinds"]):
        lp = params[f"layers_{i}"]
        x, router, _, weights, _, _ = _layer_jit(x, lp, None, None, none,
                                                 None, exact, kind)
        xr, router_r, *_ = _layer_jit(xr, lp, None, None, none, weights,
                                      lossy, kind)
        worst = max(worst, float(jnp.max(jnp.abs(router - router_r))))
    return worst
