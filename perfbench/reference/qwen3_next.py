"""Plain float32 reference of the Qwen3-Next forward pass
(Qwen3-Next-80B-A3B-Instruct, ``model_type: qwen3_next``), share-aware.

Written from the published ``config.json`` (the catalog's row
``Qwen3-Next-80B-A3B-Instruct``) and the configuration file's ``assumed``
readings (transformers' ``modeling_qwen3_next.py`` as the builder knows it).
Layer ``i`` (0-based) is full attention iff ``(i + 1) %
full_attention_interval == 0`` and a Gated DeltaNet mixer elsewhere; every
layer ``x = x + mixer(N(x))``, ``x = x + moe(N(x))``; ``N`` the zero-centred
RMSNorm ``x rsqrt(mean(x^2) + eps) (1 + w)``:

    Gated DeltaNet (``Hk`` key heads, ``Hv`` value heads, both of 128):
        [q | k | v | z] = h W_qkvz;  [b | a] = h W_ba
        (q | k | v) = silu(conv_4(q | k | v))          causal, depthwise, NO bias
        q = l2(q) / sqrt(128);  k = l2(k)              value heads 2j, 2j + 1
                                                       read key head j
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)     a head
        a head's state S [128 (key), 128 (value)], a token:
            S  = exp(g_t) S
            d  = beta_t (v_t - S^T k_t)
            S  = S + k_t d^T
            o_t = S^T q_t
        out = ( w o rsqrt(mean(o^2) + eps) silu(z) ) W_out   (a plain w, a head)
    gated attention (``H`` query heads on ``Hkv`` key/value heads of 256):
        [query | gate] a head = h W_q;  k = h W_k;  v = h W_v
        query, k = N_head(query), N_head(k), then a rotary on the FIRST
        ``partial_rotary_factor x 256`` values of a head (half-rotation,
        theta ``rope_theta``); causal softmax / sqrt(256)
        out = ( attn * sigmoid(gate) ) W_o
    expert layer (``E`` the router's width, ``k`` a token):
        p = softmax(h W_r) [E];  S = top-k of p;  w_e = p_e / sum_{S} p
        r = sum_{e in S, held} w_e W2_e (silu(W1_e h) * W3_e h)
        c = sigmoid(h . w_g) V2 (silu(V1 h) * V3 h)
    logits = N_f(x_L) W_head                            (the head is untied)

Plain ``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``: the delta rule is the RECURRENCE above, a ``lax.scan`` over the
tokens, one token a step, with no chunk form and no kernel; no cache, no
batching, nothing imported from ``deepspeed_tpu``.  The layout it reads (a
data format):

    embed_tokens/embedding [V, D]     lm_head/kernel [D, V]     norm/weight [D, 1]
    layers_<i>/{input_layernorm,post_attention_layernorm}/weight [D, 1]
    layers_<i>/linear_attn/in_proj_qkvz/kernel [D, 2 Hk 128 + 2 Hv 128]
        (q | k | v | z, each head-major; the published interleaving by key-head
        group is bookkeeping)   in_proj_ba/kernel [D, 2 Hv] (b | a)
        conv1d/weight [K, 2 Hk 128 + Hv 128] (row K - 1: the current token)
        A_log [1, Hv]   dt_bias [1, Hv]   norm/weight [128]
        out_proj/kernel [Hv 128, D]
    layers_<i>/self_attn/q_proj/kernel [D, H, 2 x 256] (a head's query | gate)
        {k,v}_proj/kernel [D, Hkv, 256]   {q,k}_norm/weight [256]
        o_proj/kernel [H 256, D]
    layers_<i>/moe/gate/kernel [D, E]   moe/{w1,w3} [held, D, I]   moe/w2 [held, I, D]
        moe/{shared_w1,shared_w3} [1, D, Is]   moe/shared_w2 [1, Is, D]
        moe/shared_gate/kernel [D, 1]

**One chip's share** (``perfbench/README.md``).  The sizes state
``experts_held`` and ``first_expert``; the router keeps its width (the gate's
own shape), ``S`` and ``w_e`` are taken over all of it, the stacks hold the
experts ``first_expert .. first_expert + experts_held - 1`` and ``r`` sums
over those alone; the shared expert and its gate are whole.  A token whose
k-th and (k+1)-th experts are BOTH held elsewhere gives this share the same
experts either way: its margin is reported as infinite.

**Routing is stated** (as ``reference/mixtral.py``): ``logits_and_routing_at``
returns each requested token's router margin at every layer, on the router
LOGITS (the softmax is monotone), and can exchange the k-th and (k+1)-th
expert at one layer for one token.  ``router_logit_error`` sizes the margin
with the rounding points of THIS block.

**Named switches** of the sizes, each a planted fault or the comparison's
lower-precision control (``tools/serve_fault_check.py``, the tests); absent,
the model is the one above: ``rule_decay`` (True; False: ``g = 0``),
``rule_beta`` (True; False: ``beta = 1``), ``rule_l2_norm`` (True; False: q
and k not L2-normalised), ``state_reset_every`` (0; n: the delta rule's state
starts from zeros at every position that n divides, a state not carried from
one chunk or step to the next), ``conv_reset_every`` (0; n: the
convolution's earlier inputs read as zeros there), ``state_held_in`` (None;
``"bfloat16"``: the state rounded to that type after every token, a state
HELD in it), ``attention_gate`` (True; False: the sigmoid gate on the
attention's output dropped), ``norm_one_plus_w`` (True; False: ``w`` for ``1 +
w`` in every zero-centred norm), ``rotary_whole_head`` (False; True: the
rotary on all 256 values of a head), ``shared_expert_gate`` (True; False: the
shared expert's gate dropped) and ``weight_mantissa_bits`` (None; 3: every
matrix rounded to an 8-bit float's three mantissa bits, the CONTROL).

Departures from the published code, none of them mathematics: every held
expert is computed for every token and weighted by 0 where the token is not
routed to it (one expert upcast at a time, blocks of ``MOE_ROWS`` tokens);
attention one key/value group at a time in blocks of ``QUERY_ROWS`` queries;
the convolution is four shifted products; a SECOND answer (``flip``) whose
token lies at or after the first position the first answer was asked for
recomputes the tokens from that position on alone, from what the first answer
kept of the tokens before it (an attention layer's keys and values, a
DeltaNet layer's state and last three convolution inputs AT that position):
a causal model's earlier tokens do not see a later token's routing.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
QUERY_ROWS = 256
MOE_ROWS = 2048
L2_EPS = 1e-6


def f32(tree, bits=None):
    """``tree`` in float32; with ``bits``, every MATRIX (a leaf of two or
    more axes neither of which is 1) rounded first to that many mantissa bits
    behind the leading one (the lower-precision control)."""
    def leaf(x):
        x = jnp.asarray(x, jnp.float32)
        if bits is None or x.ndim < 2 or 1 in x.shape:
            return x
        mantissa, exponent = jnp.frexp(x)        # mantissa in [0.5, 1)
        steps = 2.0 ** (bits + 1)
        return jnp.ldexp(jnp.round(mantissa * steps) / steps, exponent)
    return jax.tree_util.tree_map(leaf, tree)


def matrices(tree, cfg):
    return f32(tree, cfg.get("weight_mantissa_bits"))


def hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


def rounded(x, cfg):
    """``x`` rounded to ``cfg["round_activations_to"]`` and back, where the
    sizes state one: the reference as a system serving in that type would
    compute it (weights as given, every activation it writes rounded)."""
    to = cfg.get("round_activations_to")
    return x.astype(to).astype(jnp.float32) if to else x


def is_attention(i, cfg):
    return (i + 1) % cfg["full_attention_interval"] == 0


def rms_norm(x, weight, eps, cfg=None):
    """The zero-centred RMSNorm: ``(1 + w)``; ``weight`` [n, 1] or [n]."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    one = 1.0 if (cfg or {}).get("norm_one_plus_w", True) else 0.0
    return x * jax.lax.rsqrt(var + eps) * (one + weight.reshape(-1))


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def blocks_of(x, rows):
    """``x [S, ...]`` as ``[n, rows, ...]``, padded with zeros."""
    pad = -x.shape[0] % rows
    x = jnp.pad(x, ((0, pad), ) + ((0, 0), ) * (x.ndim - 1))
    return x.reshape((-1, rows) + x.shape[1:])


# ------------------------------------------------------- Gated DeltaNet
def delta_rule(q, k, v, g, beta, state, fresh, held_in=None):
    """The recurrence, one token a step.  q, k, v: [S, Hv, 128]; g, beta:
    [S, Hv]; state: [Hv, 128 (key), 128 (value)]; ``fresh [S]``: the tokens
    that start from a zero state (none, but under ``state_reset_every``) ->
    (o [S, Hv, 128], state)."""
    def token(s, row):
        q_t, k_t, v_t, g_t, b_t, z_t = row
        s = jnp.where(z_t, 0, s) * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d[:, None, :]
        o = jnp.einsum("hkv,hk->hv", s, q_t)
        if held_in:
            s = s.astype(held_in).astype(jnp.float32)
        return s, o

    state, o = jax.lax.scan(token, state, (q, k, v, g, beta, fresh))
    return o, state


def gdn_mixer(h, m, cfg, pos0, before):
    """``(out [S, D], (state, conv rows))`` of the tokens ``h`` at positions
    ``pos0 ..``; ``before``: what the tokens before them left (None: nothing,
    a zero state)."""
    r = partial(rounded, cfg=cfg)
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    eps, s = cfg["rms_norm_eps"], h.shape[0]
    w = m["conv1d"]["weight"]                              # [K, C]
    taps, c = w.shape
    qkvz = r(h @ m["in_proj_qkvz"]["kernel"])
    ba = h @ m["in_proj_ba"]["kernel"]
    mixed, z = qkvz[:, :c], qkvz[:, c:]
    state, tail = before if before is not None else (
        jnp.zeros((hv, dk, dv), jnp.float32),
        jnp.zeros((taps - 1, c), jnp.float32))
    xp = jnp.concatenate([tail, mixed])
    pos = pos0 + jnp.arange(s)
    every = cfg.get("conv_reset_every", 0)
    # tap t of the token at ``pos`` is the input ``taps - 1 - t`` tokens back
    seen = lambda t: 1.0 if not every else \
        (pos % every >= taps - 1 - t)[:, None].astype(jnp.float32)
    u = r(jax.nn.silu(sum(w[t] * xp[t:t + s] * seen(t)
                          for t in range(taps))))
    q = u[:, :hk * dk].reshape(s, hk, dk)
    k = u[:, hk * dk:2 * hk * dk].reshape(s, hk, dk)
    v = u[:, 2 * hk * dk:].reshape(s, hv, dv)
    l2 = l2_norm if cfg.get("rule_l2_norm", True) else (lambda x: x)
    q = jnp.repeat(l2(q) * dk ** -0.5, hv // hk, axis=1)
    k = jnp.repeat(l2(k), hv // hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(m["A_log"][0]) * jax.nn.softplus(ba[:, hv:]
                                                  + m["dt_bias"][0])
    if not cfg.get("rule_decay", True):
        g = jnp.zeros_like(g)
    if not cfg.get("rule_beta", True):
        beta = jnp.ones_like(beta)
    every = cfg.get("state_reset_every", 0)
    fresh = pos % every == 0 if every else jnp.zeros((s, ), bool)
    o, state = delta_rule(q, k, v, g, beta, state, fresh,
                          cfg.get("state_held_in"))
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = m["norm"]["weight"] * (o * jax.lax.rsqrt(var + eps))
    o = r(o * jax.nn.silu(z.reshape(s, hv, dv)))
    return r(o.reshape(s, hv * dv) @ m["out_proj"]["kernel"]), \
        (state, xp[-(taps - 1):])


# ------------------------------------------------------ gated attention
def rotary_half(x, positions, theta, rotary_dim):
    """x: [S, heads, Dh]; the first ``rotary_dim`` values of a head turned,
    half-rotation (value ``i`` with value ``i + rotary_dim / 2``)."""
    half = rotary_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                           / rotary_dim))
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def group_attention(q, k, v, q_pos):
    """One key/value group: q ``[Sq, rep, Dh]`` at positions ``q_pos [Sq]``
    against keys and values ``[S, Dh]`` at positions ``0 .. S - 1`` ->
    ``[Sq, rep * Dh]``; causal, in blocks of ``QUERY_ROWS`` queries."""
    sq, rep, dh = q.shape
    rows = min(QUERY_ROWS, sq)
    key_pos = jnp.arange(k.shape[0])[None, :]

    def block(args):
        qb, pb = args
        mask = pb[:, None] >= key_pos
        scores = jnp.einsum("srd,td->rst", qb, k) / jnp.sqrt(jnp.float32(dh))
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
        return jnp.einsum("rst,td->srd", probs, v)

    out = jax.lax.map(block, (blocks_of(q, rows), blocks_of(q_pos, rows)))
    return out.reshape(-1, rep * dh)[:sq]


def attention_mixer(h, a, cfg, pos0, before):
    """``(out [S, D], (k, v) [pos0 + S, Hkv, Dh])`` for the tokens at
    positions ``pos0 ..``; ``before``: the keys and values of the tokens
    before them (None: there are none)."""
    r = partial(rounded, cfg=cfg)
    eps, dh = cfg["rms_norm_eps"], cfg["head_dim"]
    pos = pos0 + jnp.arange(h.shape[0])
    qg = r(jnp.einsum("sd,dhe->she", h, a["q_proj"]["kernel"]))
    q, gate = qg[..., :dh], qg[..., dh:]
    k = r(jnp.einsum("sd,dhe->she", h, a["k_proj"]["kernel"]))
    v = r(jnp.einsum("sd,dhe->she", h, a["v_proj"]["kernel"]))
    turn = lambda x: r(rotary_half(
        x, pos, cfg["rope_theta"], dh if cfg.get("rotary_whole_head")
        else int(dh * cfg["partial_rotary_factor"])))
    q = turn(r(rms_norm(q, a["q_norm"]["weight"], eps, cfg)))
    k = turn(r(rms_norm(k, a["k_norm"]["weight"], eps, cfg)))
    if before is not None:
        k = jnp.concatenate([before[0], k])
        v = jnp.concatenate([before[1], v])
    s, heads, _ = q.shape
    hkv = k.shape[1]
    rep = heads // hkv
    wo = a["o_proj"]["kernel"].reshape(hkv, rep * dh, -1)
    gate = jax.nn.sigmoid(gate) if cfg.get("attention_gate", True) \
        else jnp.ones_like(gate)
    gate = gate.reshape(s, hkv, rep * dh).transpose(1, 0, 2)

    def group(acc, g):
        qg_, kg, vg, gg, wg = g
        out = r(r(group_attention(qg_, kg, vg, pos)) * gg)
        return acc + out @ wg, None

    out, _ = jax.lax.scan(
        group, jnp.zeros((s, wo.shape[-1]), jnp.float32),
        (q.reshape(s, hkv, rep, dh).transpose(1, 0, 2, 3),
         k.transpose(1, 0, 2), v.transpose(1, 0, 2), gate, wo))
    return r(out), (k, v)


# ---------------------------------------------------------- expert layer
def held_experts(cfg):
    """``(first, count)`` of the experts this share holds, or None where the
    sizes state no share (every expert is held)."""
    if cfg.get("experts_held") is None:
        return None
    return int(cfg.get("first_expert", 0)), int(cfg["experts_held"])


def route(router_logits, k, flip_token=-1, renormalise=True, held=None):
    """``(weights [S, E], margin [S])``: each token's weight on every expert
    (0 where it is not routed there; softmax over ALL the experts, then
    normalised over the k chosen) and its router margin, the k-th largest
    router LOGIT minus the (k+1)-th (inf where k == E, and, under a share
    ``held = (first, count)``, where both of those experts are held
    elsewhere).  The token at index ``flip_token`` takes its (k+1)-th expert
    in place of its k-th."""
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
    w = jnp.take_along_axis(jax.nn.softmax(router_logits, axis=-1), idx,
                            axis=-1)
    if renormalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                      * w[..., None], axis=1)
    return weights, margin


def moe_part(h, m, cfg, flip_token=-1, weights=None):
    """``(r + c [S, D], router logits [S, E], margin [S], weights [S, E])``.
    ``weights`` given: routed so, whatever the router says."""
    r = partial(rounded, cfg=cfg)
    mat = partial(matrices, cfg=cfg)
    router_logits = h @ mat(m["gate"]["kernel"])
    held = held_experts(cfg)
    own, margin = route(router_logits, cfg["num_experts_per_tok"],
                        flip_token, cfg.get("norm_topk_prob", True), held)
    weights = own if weights is None else weights
    columns = weights
    if held is not None:                     # the stacks hold these alone
        columns = columns[:, held[0]:held[0] + held[1]]
    shared_gate = jax.nn.sigmoid(h @ f32(m["shared_gate"]["kernel"])) \
        if cfg.get("shared_expert_gate", True) else jnp.ones_like(h[:, :1])

    def block(args):
        hb, cols, sg = args                  # [rows, D], [rows, held], [rows, 1]

        def expert(acc, e):
            w1, w3, w2, col = e              # one expert, upcast here
            act = r(jax.nn.silu(r(hb @ mat(w1))) * r(hb @ mat(w3)))
            return acc + r(r(act @ mat(w2)) * col[:, None]), None

        routed, _ = jax.lax.scan(expert, jnp.zeros_like(hb),
                                 (m["w1"], m["w3"], m["w2"], cols.T))
        act = r(jax.nn.silu(r(hb @ mat(m["shared_w1"][0])))
                * r(hb @ mat(m["shared_w3"][0])))
        shared = r(r(act @ mat(m["shared_w2"][0])) * sg)
        return r(r(routed) + shared)

    rows = min(MOE_ROWS, h.shape[0])
    out = jax.lax.map(block, (blocks_of(h, rows), blocks_of(columns, rows),
                              blocks_of(shared_gate, rows)))
    return out.reshape(-1, h.shape[1])[:h.shape[0]], router_logits, margin, \
        weights


def layer(x, lp, cfg, attention_layer, pos0=0, before=None, flip_token=-1,
          weights=None):
    """``(x', router logits, margin, weights, kept)`` of one layer for the
    tokens at positions ``pos0 ..``; ``kept``: what the layer's mixer leaves
    for the tokens after them (``before`` of a later call)."""
    r = partial(rounded, cfg=cfg)
    eps = cfg["rms_norm_eps"]
    h = r(rms_norm(x, f32(lp["input_layernorm"]["weight"]), eps, cfg))
    if attention_layer:
        mixed, kept = attention_mixer(h, matrices(lp["self_attn"], cfg), cfg,
                                      pos0, before)
    else:
        mixed, kept = gdn_mixer(h, matrices(lp["linear_attn"], cfg), cfg,
                                pos0, before)
    x = r(x + mixed)
    h = r(rms_norm(x, f32(lp["post_attention_layernorm"]["weight"]), eps,
                   cfg))
    moe, router_logits, margin, weights = moe_part(h, lp["moe"], cfg,
                                                   flip_token, weights)
    return r(x + moe), router_logits, margin, weights, kept


def embed(params, ids):
    return jnp.asarray(params["embed_tokens"]["embedding"], jnp.float32)[ids]


@partial(jax.jit, static_argnames=("cfg_items", "attention_layer", "pos0"))
def _layer_jit(x, lp, before, flip_token, weights, cfg_items,
               attention_layer, pos0=0):
    with jax.default_matmul_precision(HIGHEST):
        return layer(x, lp, dict(cfg_items), attention_layer, pos0, before,
                     flip_token, weights)


@partial(jax.jit, static_argnames=("cfg_items", ))
def _head_jit(norm, kernel, x, cfg_items):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision(HIGHEST):
        x = rms_norm(x, jnp.asarray(norm, jnp.float32), cfg["rms_norm_eps"],
                     cfg)
        return x @ matrices(kernel, cfg)


def _head(params, x, cfg):
    return _head_jit(params["norm"]["weight"], params["lm_head"]["kernel"],
                     x, hashable(cfg))


def logits_at(params, ids, positions, cfg):
    """Float32 logits [len(positions), V] of ONE sequence ``ids`` [S] at the
    given positions."""
    return logits_and_routing_at(params, ids, positions, cfg, _keep=False)[0]


#: the newest first answer's sequence, the first position it was asked for,
#: and per layer the tokens' hidden states from that position on and what the
#: tokens before it left the layer's mixer: what a second answer is
#: recomputed from
_FIRST = {}


def logits_and_routing_at(params, ids, positions, cfg, flip=None, _keep=True):
    """``(logits [P, V], margins [P, L])``: the float32 logits of ONE sequence
    at ``positions`` and the router margin of the token at each of them at
    every layer.  With ``flip = (layer, position)`` the token at that position
    (and no other) takes its (k+1)-th expert in place of its k-th at that
    layer."""
    items = hashable(cfg)
    ids = np.asarray(ids, np.int32)
    at = np.asarray(positions, np.int32)
    none = jnp.int32(-1)
    depth = cfg["num_hidden_layers"]
    first = _FIRST if flip is not None and _FIRST.get("ids") is not None \
        and np.array_equal(_FIRST["ids"], ids) \
        and flip[1] >= _FIRST["start"] <= at.min() else None
    if first is not None:       # the tokens from ``start`` on, from ``begin``
        start, begin = first["start"], flip[0]
        x = first["x"][begin]
        margins = [jnp.full((len(ids) - start, ), jnp.inf)] * begin
        for i in range(begin, depth):
            token = jnp.int32(flip[1] - start) if flip[0] == i else none
            x, _, margin, _, _ = _layer_jit(
                x, params[f"layers_{i}"], first["before"][i], token, None,
                items, is_attention(i, cfg), start)
            margins.append(margin)
        return _head(params, x[jnp.asarray(at - start)], cfg), \
            jnp.stack(margins)[:, at - start].T
    keep = flip is None and _keep
    if flip is None:
        _FIRST.clear()
    # the first answer that keeps is made in two stretches, the tokens before
    # the first asked position and those from it on, so that what the first
    # stretch leaves each mixer IS what a second answer starts from
    split = int(at.min()) if keep else 0
    if keep:
        _FIRST.update(ids=ids, start=split, x=[], before=[])
    x = embed(params, jnp.asarray(ids))
    margins = []
    for i in range(depth):
        lp, attn = params[f"layers_{i}"], is_attention(i, cfg)
        flips = lambda lo, n: jnp.int32(flip[1] - lo) \
            if flip is not None and flip[0] == i and lo <= flip[1] < lo + n \
            else none
        before, parts, margin = None, [], []
        for lo, hi in ((0, split), (split, len(ids))):
            if hi == lo:
                continue
            if keep and lo == split:
                _FIRST["x"].append(x[split:])
                _FIRST["before"].append(before)
            y, _, m, _, before = _layer_jit(
                x[lo:hi], lp, before, flips(lo, hi - lo), None, items, attn,
                lo)
            parts.append(y)
            margin.append(m)
        x = jnp.concatenate(parts)
        margins.append(jnp.concatenate(margin))
    return _head(params, x[jnp.asarray(at)], cfg), jnp.stack(margins)[:, at].T


def router_logit_error(params, ids, cfg, serving_type="bfloat16"):
    """The largest difference, over one sequence's tokens, layers and experts,
    between the float32 router logits and those of the same reference with
    every activation rounded to ``serving_type`` where a system serving in
    that type rounds (``rounded``: the norms, the projections, the
    convolution's output, the gated output, the rotary, each group's
    attention output and its gated form, the output projections, each
    expert's three products and its weighted part, the routed sum, the gated
    shared expert, and both residual adds; the delta rule and its state stay
    float32, as the served model holds them).  The rounded pass is ROUTED AS
    the float32 one, layer by layer.  The worst over the seeds run is the
    configuration's ``measured_worst["serve.router_margin"]``."""
    exact = hashable(cfg)
    lossy = hashable(dict(cfg, round_activations_to=serving_type))
    x = xr = embed(params, jnp.asarray(ids, jnp.int32))
    worst, none = 0.0, jnp.int32(-1)
    for i in range(cfg["num_hidden_layers"]):
        lp, attn = params[f"layers_{i}"], is_attention(i, cfg)
        x, router, _, weights, _ = _layer_jit(x, lp, None, none, None, exact,
                                              attn)
        xr, router_r, *_ = _layer_jit(xr, lp, None, none, weights, lossy,
                                      attn)
        worst = max(worst, float(jnp.max(jnp.abs(router - router_r))))
    return worst
