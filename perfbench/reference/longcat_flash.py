"""Plain float32 reference of the LongCat-Flash-Chat forward pass
(``attention_method: MLA``, ``zero_expert_type: identity``), share-aware, in
the EXPANDED form of its multi-head latent attention: per-head keys and values
are made from the latent row, there is no cache and no absorbed product
anywhere in this file.

Written from the published ``config.json`` (the catalog's row
``LongCat-Flash-Chat``), the technical report (arXiv:2509.01322, section 2)
and the configuration file's ``assumed`` readings.  ``N`` is an RMSNorm
(``rms_norm_eps``), ``D`` hidden, ``H`` heads, ``r`` = ``kv_lora_rank``,
``dn`` / ``dr`` / ``dv`` the nope, rope and value head sizes, ``E`` =
``n_routed_experts`` real experts, ``Z`` = ``zero_expert_num`` identity ones,
``k`` = ``moe_topk``; layer ``l``:

    a1 = x  + MLA_0(N_in0(x))
    h1 = N_post0(a1)
    s  = MoE(h1)                         # the shortcut: kept, added at the end
    d1 = a1 + FFN_0(h1)                  # SwiGLU of ``ffn_hidden_size``
    a2 = d1 + MLA_1(N_in1(d1))
    h2 = N_post1(a2)
    x' = a2 + FFN_1(h2) + s

    MLA_i(h): c_q = N(h W_qa);  (q_n [dn] ; q_r [dr]) = (c_q W_qb) * sqrt(D / q_lora_rank)
            (c [r] ; k_r [dr]) = h W_kva;  c' = N(c) * sqrt(D / r);  k_r is NOT scaled
            q_r, k_r <- rope, half-split (x[i] with x[i + dr/2]), theta ``rope_theta``;
            k_r is ONE head shared by all
            k_i = (c' W_uk,i ; k_r),  v_i = c' W_uv,i
            p_i = causal softmax( q_i . k_i / sqrt(dn + dr) );  out = concat_i(p_i v_i) W_o
    MoE(h): p = softmax(h W_r) over E + Z;  the k largest of p + b
            (``e_score_correction_bias``);  w_e = routed_scaling_factor * p_e, not renormalised
            sum over chosen e < E, held, of w_e SwiGLU_e(h)  +  (sum over chosen e >= E of w_e) h
    logits = N_f(x_L) W_head                                   (untied head)

Plain ``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``; nothing imported from ``deepspeed_tpu``.  The layout it reads
(a data format):

    embed_tokens/embedding [V, D]      norm/weight [D]      lm_head/kernel [D, V]
    layers_<l>/{input,post_attention}_layernorm_<i>/weight [D]            i = 0, 1
    layers_<l>/self_attn_<i>/q_a_proj/kernel [D, q_lora_rank]   q_a_layernorm/weight
    layers_<l>/self_attn_<i>/q_b_proj/kernel [H * (dn + dr), q_lora_rank]   ([out, in]: a head's
                                      rows are its dn nope then its dr rope query dims)
    layers_<l>/self_attn_<i>/k_b_proj/kernel [r, H, dn]   v_b_proj/kernel [r, H, dv]
                                      (the published kv_b_proj as its two halves, W_uk and W_uv)
    layers_<l>/self_attn_<i>/kv_a_proj/kernel [D, r + dr]       kv_a_layernorm/weight [r]
    layers_<l>/self_attn_<i>/o_proj/kernel [H * dv, D]
    layers_<l>/mlp_<i>/{gate,up,down}_proj/kernel
    layers_<l>/moe/gate/kernel [D, E + Z]     moe/e_score_correction_bias [E + Z]
    layers_<l>/moe/{w1,w3} [held, D, I]       moe/w2 [held, I, D]

**One chip's share.**  ``sizes["experts_held"]`` and ``["first_expert"]`` say
which real experts' stacks the weights hold (None: all ``E``); a chosen real
expert held elsewhere adds nothing, here as in the program.  The identity
experts are computed always: they are neither here nor elsewhere.

**Routing is stated** (``logits_and_routing_at``).  The choice is by ``p +
b``; two scores whose relative error is at most ``eps`` can change order only
where ``((p + b)_k - (p + b)_{k+1}) / (p_k + p_{k+1}) < eps``: that quotient
is the MARGIN of a token at a layer, ``router_logit_error`` measures ``eps``
(the largest ``|log p - log p~|`` over tokens, layers and experts between the
float32 pass and one rounded where a bfloat16 system rounds), and with a
constant bias the margin is half the k-th and (k+1)-th router logits'
difference.  A pair wholly among real experts held elsewhere cannot change
this share's answer and reads infinite; a pair with an identity expert in it
can.  With ``flip = (layer, position)`` that token takes its (k+1)-th in place
of its k-th there.

Readings a ``sizes`` may state beside the published keys, each the published
behaviour by default (``tools/serve_fault_check.py`` plants them as faults):
``identity_experts`` (True), ``shortcut_from`` (0: the sublayer whose normed
output the experts read), ``second_attention_reads`` (1: the latent rows the
second attention attends), ``norm_topk_prob`` (False), ``held_experts_part``
(True); and ``weight_mantissa_bits`` (None: the weights as they are stored;
3: every matrix rounded to an 8-bit float's three mantissa bits before it is
used, the comparison's lower-precision CONTROL).

Departures from the published code, none of them mathematics:

* the rotary is the half-split form on ``W_qb`` / ``W_kva`` columns permuted
  once (the published code turns interleaved pairs; with seeded weights the
  two are one distribution);
* every layer is computed in blocks of ``ROW_BLOCK`` tokens against the latent
  rows ``(c' ; k_r)`` of ALL the tokens, which are made first for each of the
  two attentions (they are row-wise): at 16 416 tokens one ``[S, D]`` float32
  array is 403 MB, and the reference runs beside the engine.  Inside a block
  the heads are taken ``HEAD_BLOCK`` at a time and the queries ``QUERY_ROWS``
  at a time against all keys; a dense SwiGLU ``MLP_COLS`` columns of its
  width at a time; every held expert for every token, weighted by 0 where the
  token is not routed to it, one expert upcast at a time;
* a SECOND answer (``flip``) whose token lies at or after the first position
  the first answer was asked for recomputes the tokens from that position on
  alone, against the first answer's latent rows of the tokens before it.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
ROW_BLOCK = 2048
QUERY_ROWS = 256
HEAD_BLOCK = 8
MLP_COLS = 2048


def f32(x, bits=None):
    """``x`` in float32; with ``bits``, rounded first to that many mantissa
    bits behind the leading one (``weight_mantissa_bits``)."""
    x = jnp.asarray(x, jnp.float32)
    if bits is None:
        return x
    mantissa, exponent = jnp.frexp(x)            # mantissa in [0.5, 1)
    steps = 2.0 ** (bits + 1)
    return jnp.ldexp(jnp.round(mantissa * steps) / steps, exponent)


def matrix(cfg):
    """What reads a weight MATRIX for these sizes (norm weights and the
    choice bias are read by ``f32`` itself, never rounded)."""
    return partial(f32, bits=cfg.get("weight_mantissa_bits"))


def hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


def rounded(x, cfg):
    """``x`` rounded to ``cfg["round_activations_to"]`` and back, where the
    sizes state one (``router_logit_error``)."""
    to = cfg.get("round_activations_to")
    return x.astype(to).astype(jnp.float32) if to else x


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def rope_half(x, positions, theta):
    """x: [S, d] or [S, heads, d] turned by ``positions [S]``, half-split."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv
    if x.ndim == 3:
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def blocks_of(x, rows):
    """``x [S, ...]`` as ``[n, rows, ...]``, padded with zeros."""
    pad = -x.shape[0] % rows
    x = jnp.pad(x, ((0, pad), ) + ((0, 0), ) * (x.ndim - 1))
    return x.reshape((-1, rows) + x.shape[1:])


def lora_scale(cfg, which):
    """``mla_scale_q_lora`` / ``mla_scale_kv_lora``: sqrt(D / rank) where the
    key is set, 1 where it is not."""
    if not cfg.get(f"mla_scale_{which}_lora", True):
        return 1.0
    return math.sqrt(cfg["hidden_size"] / cfg[f"{which}_lora_rank"])


def latent_rows(h, a, cfg, pos):
    """The latent row ``(c' [r] ; k_r [dr])`` of each token: ``h [S, D]``
    (normed) at positions ``pos``."""
    r, w = partial(rounded, cfg=cfg), matrix(cfg)
    rank = cfg["kv_lora_rank"]
    ckv = r(h @ w(a["kv_a_proj"]["kernel"]))
    c = r(rms_norm(ckv[:, :rank], f32(a["kv_a_layernorm"]["weight"]),
                   cfg["rms_norm_eps"]) * lora_scale(cfg, "kv"))
    return jnp.concatenate(
        [c, r(rope_half(ckv[:, rank:], pos, cfg["rope_theta"]))], -1)


def attention_rows(h, pos, a, cfg, latent):
    """``MLA(h) [R, D]`` for the rows ``h [R, D]`` (normed) at positions
    ``pos [R]`` against the latent rows ``latent [S, r + dr]`` of the tokens
    at positions ``0 .. S - 1``: expanded, ``HEAD_BLOCK`` heads at a time."""
    r, w = partial(rounded, cfg=cfg), matrix(cfg)
    rank, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    heads = cfg["num_attention_heads"]
    hb = min(HEAD_BLOCK, heads)
    c_all, kr_all = latent[:, :rank], latent[:, rank:]
    key_pos = jnp.arange(latent.shape[0])[None, :]
    # the factor is on the query (both parts); it is linear in c_q, and a
    # system may round it there
    c_q = r(rms_norm(r(h @ w(a["q_a_proj"]["kernel"])),
                     f32(a["q_a_layernorm"]["weight"]), cfg["rms_norm_eps"])
            * lora_scale(cfg, "q"))
    scale = 1.0 / math.sqrt(dn + cfg["qk_rope_head_dim"])
    rows = min(QUERY_ROWS, h.shape[0])

    def by_heads(m):            # [in, H, e] -> [H / hb, in, hb, e]
        return jnp.moveaxis(m.reshape(m.shape[0], heads // hb, hb, -1), 1, 0)

    w_uq = a["q_b_proj"]["kernel"]          # [H * e, in]: its heads' rows
    w_uq = w_uq.reshape(heads // hb, -1, w_uq.shape[1])
    w_o = a["o_proj"]["kernel"]
    w_o = w_o.reshape(heads // hb, w_o.shape[0] // (heads // hb), -1)

    def head_block(acc, ws):
        w_uq, w_uk, w_uv, wo = ws
        q = r(jnp.einsum("sq,heq->she", c_q,
                         w(w_uq).reshape(hb, -1, w_uq.shape[-1])))
        q_n, q_r = q[..., :dn], r(rope_half(q[..., dn:], pos,
                                            cfg["rope_theta"]))
        k_n = r(jnp.einsum("tc,chn->thn", c_all, w(w_uk)))
        v = r(jnp.einsum("tc,chv->thv", c_all, w(w_uv)))

        def queries(args):
            qn, qr, pb = args
            scores = (jnp.einsum("shn,thn->hst", qn, k_n)
                      + jnp.einsum("shr,tr->hst", qr, kr_all)) * scale
            mask = key_pos <= pb[:, None]
            probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf),
                                   -1)
            return jnp.einsum("hst,thv->shv", probs, v)

        out = jax.lax.map(queries, (blocks_of(q_n, rows),
                                    blocks_of(q_r, rows),
                                    blocks_of(pos, rows)))
        out = r(out.reshape(-1, hb * v.shape[-1])[:h.shape[0]])
        return acc + out @ w(wo), None

    out, _ = jax.lax.scan(
        head_block, jnp.zeros_like(h),
        (w_uq, by_heads(a["k_b_proj"]["kernel"]),
         by_heads(a["v_b_proj"]["kernel"]), w_o))
    return r(out)


def dense_rows(h, mlp, cfg):
    """A dense SwiGLU for rows ``h``, ``MLP_COLS`` columns of its width at a
    time (the sum over the width's columns is the product)."""
    r, w = partial(rounded, cfg=cfg), matrix(cfg)
    width = mlp["gate_proj"]["kernel"].shape[1]
    cols = math.gcd(width, MLP_COLS)

    def part(j, acc):
        cut = lambda m, axis: w(jax.lax.dynamic_slice_in_dim(
            m["kernel"], j * cols, cols, axis))
        act = r(jax.nn.silu(r(h @ cut(mlp["gate_proj"], 1)))
                * r(h @ cut(mlp["up_proj"], 1)))
        return acc + act @ cut(mlp["down_proj"], 0)

    return r(jax.lax.fori_loop(0, width // cols, part, jnp.zeros_like(h)))


def held_experts(cfg):
    """``(first, count)`` of the real experts this share holds."""
    if cfg.get("experts_held") is None:
        return 0, int(cfg["n_routed_experts"])
    return int(cfg.get("first_expert", 0)), int(cfg["experts_held"])


def route(scores, bias, k, real, held, flip=None, renormalise=False,
          scale=1.0):
    """``(weights [S, E + Z], margin [S])``: each token's weight on every
    output of the router (0 where it is not chosen; ``scale`` x its score,
    divided by the chosen scores' sum where ``renormalise``) and its margin
    (the module docstring): over the k-th and (k+1)-th of ``scores + bias``,
    infinite where k is the router's width or both of them are real experts
    (``< real``) outside ``held = (first, count)``.  A token where ``flip
    [S]`` is set takes its (k+1)-th in place of its k-th."""
    s, e = scores.shape
    choice = scores + bias
    top, idx = jax.lax.top_k(choice, min(k + 1, e))
    if k < e:
        pair = idx[:, k - 1:]
        p_pair = jnp.take_along_axis(scores, pair, axis=-1)
        margin = (top[:, k - 1] - top[:, k]) / jnp.sum(p_pair, axis=-1)
        here = (pair >= real) | ((pair >= held[0])
                                 & (pair < held[0] + held[1]))
        margin = jnp.where(jnp.any(here, axis=1), margin, jnp.inf)
        last = idx[:, k - 1] if flip is None else \
            jnp.where(flip, idx[:, k], idx[:, k - 1])
        idx = jnp.concatenate([idx[:, :k - 1], last[:, None]], axis=1)
    else:
        margin = jnp.full((s,), jnp.inf, jnp.float32)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                      * (w * scale)[..., None], axis=1)
    return weights, margin


def moe_rows(h, m, cfg, flip=None, weights=None):
    """``(MoE(h) [R, D], log scores [R, E + Z], margin [R], weights [R, E +
    Z])`` of the expert branch.  ``weights`` given: routed so, whatever the
    router says."""
    r, w = partial(rounded, cfg=cfg), matrix(cfg)
    real = int(cfg["n_routed_experts"])
    held = held_experts(cfg)
    log_p = jax.nn.log_softmax(h @ w(m["gate"]["kernel"]), axis=-1)
    own, margin = route(
        jnp.exp(log_p), f32(m["e_score_correction_bias"]), cfg["moe_topk"],
        real, held, flip, cfg.get("norm_topk_prob", False),
        cfg.get("routed_scaling_factor", 1.0))
    weights = own if weights is None else weights
    columns = weights[:, held[0]:held[0] + held[1]]

    def expert(acc, e):
        w1, w3, w2, col = e                  # one expert, upcast here
        act = r(jax.nn.silu(r(h @ w(w1))) * r(h @ w(w3)))
        return acc + r(r(act @ w(w2)) * col[:, None]), None

    out = jnp.zeros_like(h)
    if cfg.get("held_experts_part", True):
        out, _ = jax.lax.scan(expert, out,
                              (m["w1"], m["w3"], m["w2"], columns.T))
    if cfg.get("identity_experts", True):
        out = r(out) + r(h * jnp.sum(weights[:, real:], axis=-1)[:, None])
    return r(out), log_p, margin, weights


def layer(x, lp, cfg, pos0=0, latent_before=None, flip_token=-1,
          weights=None):
    """``(x', log scores, margin, weights, (latent_0, latent_1))`` of one
    layer for the tokens ``x [S, D]`` at positions ``pos0 ..``;
    ``latent_before``: the two attentions' latent rows of the tokens before
    them (None: there are none); ``latent_i``: those of all the tokens up to
    the last of these."""
    r = partial(rounded, cfg=cfg)
    eps = cfg["rms_norm_eps"]
    norm = lambda y, name: r(rms_norm(y, f32(lp[name]["weight"]), eps))
    s = x.shape[0]
    pos = pos0 + jnp.arange(s)
    rows = min(ROW_BLOCK, s)
    blocked = lambda y: blocks_of(y, rows)
    unblocked = lambda y: y.reshape((-1, ) + y.shape[2:])[:s]
    source = cfg.get("shortcut_from", 0)

    def latents(y, i):
        own = unblocked(jax.lax.map(
            lambda args: latent_rows(norm(args[0], f"input_layernorm_{i}"),
                                     lp[f"self_attn_{i}"], cfg, args[1]),
            (blocked(y), blocked(pos))))
        return own if latent_before is None else \
            jnp.concatenate([latent_before[i], own])

    def sublayer(i, latent):
        """Block-wise: attention ``i`` and its dense SwiGLU; the expert
        branch where ``i`` is its source."""
        def block(args):
            xb, pb, flip_b, weights_b = args
            a = r(xb + attention_rows(norm(xb, f"input_layernorm_{i}"), pb,
                                      lp[f"self_attn_{i}"], cfg, latent))
            h = norm(a, f"post_attention_layernorm_{i}")
            d = r(a + dense_rows(h, lp[f"mlp_{i}"], cfg))
            if i != source:
                return d
            return (d, ) + moe_rows(h, lp["moe"], cfg, flip_b, weights_b)
        return block

    flips = blocked(jnp.arange(s) == flip_token)
    routed_as = None if weights is None else blocked(weights)
    latent_0 = latents(x, 0)
    out = jax.lax.map(sublayer(0, latent_0),
                      (blocked(x), blocked(pos), flips, routed_as))
    d1, branch = (out[0], out[1:]) if source == 0 else (out, None)
    d1 = unblocked(d1)
    latent_1 = latents(d1, 1)
    attended = (latent_0, latent_1)[cfg.get("second_attention_reads", 1)]
    out = jax.lax.map(sublayer(1, attended),
                      (blocked(d1), blocked(pos), flips, routed_as))
    d2, branch = (out[0], out[1:]) if source == 1 else (out, branch)
    shortcut, log_p, margin, w = (unblocked(y) for y in branch)
    return (r(unblocked(d2) + shortcut), log_p, margin, w,
            (latent_0, latent_1))


def embed(params, ids, cfg):
    return matrix(cfg)(params["embed_tokens"]["embedding"][ids])


def head(params, x, cfg):
    x = rms_norm(x, f32(params["norm"]["weight"]), cfg["rms_norm_eps"])
    return x @ matrix(cfg)(params["lm_head"]["kernel"])


@partial(jax.jit, static_argnames=("cfg_items", "pos0"), donate_argnums=0)
def _layer_jit(x, lp, latent_before, flip_token, weights, cfg_items, pos0=0):
    """One layer; ``x`` is DONATED (the layer's output takes its place: one
    ``[S, D]`` array of a 16 k sequence is 403 MB beside a live engine)."""
    with jax.default_matmul_precision(HIGHEST):
        return layer(x, lp, dict(cfg_items), pos0, latent_before, flip_token,
                     weights)


@partial(jax.jit, static_argnames=("cfg_items",))
def _head_jit(params_head, x, cfg_items):
    with jax.default_matmul_precision(HIGHEST):
        return head(params_head, x, dict(cfg_items))


def _head(params, x, cfg):
    return _head_jit({"norm": params["norm"], "lm_head": params["lm_head"]},
                     x, hashable(cfg))


def logits_at(params, ids, positions, cfg):
    """Float32 logits [len(positions), V] of ONE sequence ``ids`` [S] at the
    given positions."""
    return logits_and_routing_at(params, ids, positions, cfg, _keep=False)[0]


#: the newest first answer's sequence, the first position it was asked for,
#: and per layer the tokens' hidden states from that position on and every
#: token's latent rows (both attentions'): what a second answer is recomputed
#: from
_FIRST = {}


def logits_and_routing_at(params, ids, positions, cfg, flip=None, _keep=True):
    """``(logits [P, V], margins [P, L])``: the float32 logits of ONE sequence
    at ``positions`` and the router margin of the token at each of them at
    every layer.  With ``flip = (layer, position)`` the token at that position
    (and no other) takes its (k+1)-th choice in place of its k-th at that
    layer."""
    items = hashable(cfg)
    ids = np.asarray(ids, np.int32)
    at = np.asarray(positions, np.int32)
    none = jnp.int32(-1)
    first = _FIRST if flip is not None and _FIRST.get("ids") is not None \
        and np.array_equal(_FIRST["ids"], ids) \
        and flip[1] >= _FIRST["start"] <= at.min() else None
    if first is None:
        start, begin = 0, 0
        x = embed(params, jnp.asarray(ids), cfg)
    else:                       # the tokens from ``start`` on, from ``begin``
        start, begin = first["start"], flip[0]
        x = jnp.array(first["x"][begin], copy=True)     # the call donates it
    if flip is None:
        _FIRST.clear()
        if _keep:
            _FIRST.update(ids=ids, start=int(at.min()), x=[], latent=[])
    margins = [jnp.full((len(ids) - start, ), jnp.inf)] * begin
    for i in range(begin, cfg["num_hidden_layers"]):
        token = jnp.int32(flip[1] - start) \
            if flip is not None and flip[0] == i else none
        before = None if first is None else tuple(
            lat[:start] for lat in first["latent"][i])
        if flip is None and _keep:
            _FIRST["x"].append(jnp.array(x[_FIRST["start"]:], copy=True))
        x, _, margin, _, latent = _layer_jit(
            x, params[f"layers_{i}"], before, token, None, items, start)
        if flip is None and _keep:
            _FIRST["latent"].append(latent)
        margins.append(margin)
    logits = _head(params, x[jnp.asarray(at - start)], cfg)
    return logits, jnp.stack(margins)[:, at - start].T


def router_logit_error(params, ids, cfg, serving_type="bfloat16"):
    """The largest difference, over one sequence's tokens, layers and router
    outputs, between the float32 LOG scores (``log softmax`` of the router
    logits: the relative error of a score) and those of the same reference
    with every activation rounded to ``serving_type`` where a system serving
    in that type rounds (``rounded``: each norm, each projection, the rotary,
    the expanded keys and values, each head block's attention output, the
    output projection, each dense product, each expert's three products and
    its weighted part, the identity part, every residual add).  The rounded
    pass is ROUTED AS the float32 one, layer by layer.  The worst over the
    seeds run is the configuration's
    ``measured_worst["serve.router_margin"]``."""
    exact = hashable(cfg)
    lossy = hashable(dict(cfg, round_activations_to=serving_type))
    x = embed(params, jnp.asarray(ids, jnp.int32), cfg)
    xr = embed(params, jnp.asarray(ids, jnp.int32), cfg)    # each donates
    worst, none = 0.0, jnp.int32(-1)
    for i in range(cfg["num_hidden_layers"]):
        lp = params[f"layers_{i}"]
        x, log_p, _, weights, _ = _layer_jit(x, lp, None, none, None, exact)
        xr, log_pr, *_ = _layer_jit(xr, lp, None, none, weights, lossy)
        worst = max(worst, float(jnp.max(jnp.abs(log_p - log_pr))))
    return worst
