"""Plain float32 reference of the openPangu-Ultra-MoE forward pass
(``model_type: pangu_ultra_moe``), share-aware, in the EXPANDED form of its
multi-head latent attention: per-head keys and values are made from the
latent row, there is no cache and no absorbed product anywhere in this file.

Written from the published ``config.json`` (the catalog's row
``openPangu-Ultra-MoE-718B``) and the configuration file's ``assumed``
readings.  ``N`` is an RMSNorm (``rms_norm_eps``), ``D`` hidden, ``H`` heads,
``r`` = ``kv_lora_rank``, ``dn`` / ``dr`` / ``dv`` the nope, rope and value
head sizes, ``E`` the router's width, ``k`` experts a token; layer ``l``:

    a   = N_post_attn( MLA( N_in(x) ) );          x'  = x  + a    (sandwich_norm)
    m   = N_post_mlp ( F_l( N_pre_mlp(x') ) );    x'' = x' + m
    F_l = SwiGLU of ``intermediate_size``            for l < first_k_dense_replace
    F_l(h) = SwiGLU_shared(h) + s * sum_{e in top-k, held} w_e SwiGLU_e(h)   otherwise
             sc = sigmoid(h W_r) over all E;  top k of sc;  w_e = sc_e / sum_top-k sc
             s = routed_scaling_factor
    MLA(h): c_q = N(h W_dq);  q = c_q W_uq -> H x (q_n [dn] ; q_r [dr])
            (c_kv [r] ; k_r [dr]) = h W_dkv;  c = N(c_kv)
            q_r, k_r <- rope, half-split (x[i] with x[i + dr/2]), theta ``rope_theta``;
            k_r is ONE head shared by all
            k_i = (c W_uk,i ; k_r),  v_i = c W_uv,i
            p_i = causal softmax( q_i . k_i / sqrt(dn + dr) );  out = concat_i(p_i v_i) W_o
    logits = N_f(x_L) W_head                                   (untied head)

Plain ``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``; nothing imported from ``deepspeed_tpu``.  The layout it reads
(a data format):

    embed_tokens/embedding [V, D]      norm/weight [D]      lm_head/kernel [D, V]
    layers_<i>/{input,post_attention,pre_mlp,post_mlp}_layernorm/weight [D]
    layers_<i>/self_attn/q_a_proj/kernel [D, q_lora_rank]   q_a_layernorm/weight
    layers_<i>/self_attn/q_b_proj/kernel [q_lora_rank, H, dn + dr]
    layers_<i>/self_attn/kv_a_proj/kernel [D, r + dr]       kv_a_layernorm/weight [r]
    layers_<i>/self_attn/k_b_proj/kernel [r, H, dn]         v_b_proj/kernel [r, H, dv]
    layers_<i>/self_attn/o_proj/kernel [H * dv, D]
    layers_<i>/mlp/{gate,up,down}_proj/kernel                  (l < first_k_dense_replace)
    layers_<i>/moe/gate/kernel [D, E]     moe/{w1,w3} [held, D, I]    moe/w2 [held, I, D]
    layers_<i>/moe/shared_{gate,up}_proj/kernel [D, Is]   shared_down_proj/kernel [Is, D]

**One chip's share**, **routing is stated**: as ``reference/cohere2_moe.py``
(``sizes["experts_held"]``, ``["first_expert"]``; ``logits_and_routing_at``
with ``flip``; ``router_logit_error``).  A leading dense layer has no router:
its margin is infinite.

Departures from the published code, none of them mathematics:

* every layer is computed in blocks of ``ROW_BLOCK`` tokens against the
  latent rows ``(c ; k_r)`` of ALL the tokens, which are made first (they are
  row-wise): at 16 416 tokens one ``[S, D]`` float32 array is 504 MB, and the
  reference runs beside the engine.  Inside a block the heads are taken
  ``HEAD_BLOCK`` at a time (their keys and values expanded from the latent
  rows there and then) and the queries ``QUERY_ROWS`` at a time against all
  keys; a leading layer's SwiGLU ``MLP_COLS`` columns of its width at a time;
  every held expert for every token, weighted by 0 where the token is not
  routed to it, one expert upcast at a time;
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
#: the four norms of a layer (``sandwich_norm``), and which of them norm a
#: branch on its way OUT (a planted fault leaves those out)
NORMS = ("input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
         "post_mlp_layernorm")
POST_NORMS = (NORMS[1], NORMS[3])


def f32(tree):
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), tree)


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


def score_scale(cfg):
    """The softmax scale of the attention scores."""
    return 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])


def latent_rows(h, a, cfg, pos):
    """The latent row ``(c [r] ; k_r [dr])`` of each token: ``h [S, D]``
    (normed) at positions ``pos``."""
    r = partial(rounded, cfg=cfg)
    rank = cfg["kv_lora_rank"]
    ckv = r(h @ f32(a["kv_a_proj"]["kernel"]))
    c = r(rms_norm(ckv[:, :rank], f32(a["kv_a_layernorm"]["weight"]),
                   cfg["rms_norm_eps"]))
    return jnp.concatenate(
        [c, r(rope_half(ckv[:, rank:], pos, cfg["rope_theta"]))], -1)


def rope_score(q_r, k_r):
    """The rotary part of the scores: q_r ``[Sq, heads, dr]`` against the one
    shared k_r ``[S, dr]`` -> ``[heads, Sq, S]``."""
    return jnp.einsum("shr,tr->hst", q_r, k_r)


def attention_rows(h, pos, a, cfg, latent):
    """``MLA(h) [R, D]`` for the rows ``h [R, D]`` (normed) at positions
    ``pos [R]`` against the latent rows ``latent [S, r + dr]`` of the tokens
    at positions ``0 .. S - 1``: expanded, ``HEAD_BLOCK`` heads at a time."""
    r = partial(rounded, cfg=cfg)
    rank, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    heads = a["q_b_proj"]["kernel"].shape[1]
    hb = min(HEAD_BLOCK, heads)
    c_all, kr_all = latent[:, :rank], latent[:, rank:]
    key_pos = jnp.arange(latent.shape[0])[None, :]
    c_q = r(rms_norm(r(h @ f32(a["q_a_proj"]["kernel"])),
                     f32(a["q_a_layernorm"]["weight"]), cfg["rms_norm_eps"]))
    scale = score_scale(cfg)
    rows = min(QUERY_ROWS, h.shape[0])

    def by_heads(w):            # [in, H, e] -> [H / hb, in, hb, e]
        return w.reshape(w.shape[0], heads // hb, hb, w.shape[2]) \
            .transpose(1, 0, 2, 3)

    w_o = a["o_proj"]["kernel"]
    w_o = w_o.reshape(heads // hb, w_o.shape[0] // (heads // hb), -1)

    def head_block(acc, w):
        w_uq, w_uk, w_uv, wo = w
        q = r(jnp.einsum("sq,qhe->she", c_q, f32(w_uq)))
        q_n, q_r = q[..., :dn], r(rope_half(q[..., dn:], pos,
                                            cfg["rope_theta"]))
        k_n = r(jnp.einsum("tc,chn->thn", c_all, f32(w_uk)))
        v = r(jnp.einsum("tc,chv->thv", c_all, f32(w_uv)))

        def queries(args):
            qn, qr, pb = args
            scores = (jnp.einsum("shn,thn->hst", qn, k_n)
                      + rope_score(qr, kr_all)) * scale
            mask = key_pos <= pb[:, None]
            probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf),
                                   -1)
            return jnp.einsum("hst,thv->shv", probs, v)

        out = jax.lax.map(queries, (blocks_of(q_n, rows),
                                    blocks_of(q_r, rows),
                                    blocks_of(pos, rows)))
        out = r(out.reshape(-1, hb * v.shape[-1])[:h.shape[0]])
        return acc + out @ f32(wo), None

    out, _ = jax.lax.scan(
        head_block, jnp.zeros_like(h),
        (by_heads(a["q_b_proj"]["kernel"]), by_heads(a["k_b_proj"]["kernel"]),
         by_heads(a["v_b_proj"]["kernel"]), w_o))
    return r(out)


def dense_rows(h, mlp, cfg):
    """A leading layer's SwiGLU for rows ``h``, ``MLP_COLS`` columns of its
    width at a time (the sum over the width's columns is the product)."""
    r = partial(rounded, cfg=cfg)
    width = mlp["gate_proj"]["kernel"].shape[1]
    cols = math.gcd(width, MLP_COLS)

    def part(j, acc):
        cut = lambda w, axis: f32(jax.lax.dynamic_slice_in_dim(
            w["kernel"], j * cols, cols, axis))
        act = r(jax.nn.silu(r(h @ cut(mlp["gate_proj"], 1)))
                * r(h @ cut(mlp["up_proj"], 1)))
        return acc + act @ cut(mlp["down_proj"], 0)

    return r(jax.lax.fori_loop(0, width // cols, part, jnp.zeros_like(h)))


def held_experts(cfg):
    """``(first, count)`` of the experts this share holds, or None where the
    sizes state no share (every expert is held)."""
    if cfg.get("experts_held") is None:
        return None
    return int(cfg.get("first_expert", 0)), int(cfg["experts_held"])


def route(router_logits, k, flip=None, renormalise=True, held=None,
          scale=1.0):
    """``(weights [S, E], margin [S])``: each token's weight on every expert
    (0 where it is not routed there; sigmoid scores, normalised over the k
    chosen, times ``scale``) and its router margin, the k-th largest router
    LOGIT minus the (k+1)-th (inf where k == E, and, under a share ``held =
    (first, count)``, where both of those experts are held elsewhere).  A
    token where ``flip [S]`` is set takes its (k+1)-th expert in place of its
    k-th."""
    s, e = router_logits.shape
    top, idx = jax.lax.top_k(router_logits, min(k + 1, e))
    if k < e:
        margin = top[:, k - 1] - top[:, k]
        if held is not None:
            here = (idx[:, k - 1:] >= held[0]) & \
                (idx[:, k - 1:] < held[0] + held[1])
            margin = jnp.where(jnp.any(here, axis=1), margin, jnp.inf)
        last = idx[:, k - 1] if flip is None else \
            jnp.where(flip, idx[:, k], idx[:, k - 1])
        idx = jnp.concatenate([idx[:, :k - 1], last[:, None]], axis=1)
    else:
        margin = jnp.full((s,), jnp.inf, jnp.float32)
    w = jax.nn.sigmoid(jnp.take_along_axis(router_logits, idx, axis=-1))
    if renormalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                      * (w * scale)[..., None], axis=1)
    return weights, margin


def moe_rows(h, m, cfg, flip=None, weights=None):
    """``(F_l(h) [R, D], router logits [R, E], margin [R], weights [R, E])``
    of a routed layer.  ``weights`` given: routed so, whatever the router
    says."""
    r = partial(rounded, cfg=cfg)
    router_logits = h @ f32(m["gate"]["kernel"])
    held = held_experts(cfg)
    own, margin = route(router_logits, cfg["num_experts_per_tok"], flip,
                        cfg.get("norm_topk_prob", True), held,
                        cfg.get("routed_scaling_factor", 1.0))
    weights = own if weights is None else weights
    columns = weights
    if held is not None:                     # the stacks hold these alone
        columns = columns[:, held[0]:held[0] + held[1]]

    def expert(acc, e):
        w1, w3, w2, col = e                  # one expert, upcast here
        act = r(jax.nn.silu(r(h @ f32(w1))) * r(h @ f32(w3)))
        return acc + r(r(act @ f32(w2)) * col[:, None]), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                             (m["w1"], m["w3"], m["w2"], columns.T))
    shared = r(jax.nn.silu(r(h @ f32(m["shared_gate_proj"]["kernel"])))
               * r(h @ f32(m["shared_up_proj"]["kernel"]))) \
        @ f32(m["shared_down_proj"]["kernel"])
    return r(r(routed) + r(shared)), router_logits, margin, weights


def layer(x, lp, cfg, routed, pos0=0, latent_before=None, flip_token=-1,
          weights=None):
    """``(x'', router logits, margin, weights, latent)`` of one layer for the
    tokens ``x [S, D]`` at positions ``pos0 ..``; ``latent_before``: the
    latent rows of the tokens before them (None: there are none); ``latent``:
    those of all the tokens up to the last of these.  A leading dense layer
    (``routed`` False) returns no router logits, an infinite margin and no
    weights."""
    r = partial(rounded, cfg=cfg)
    eps = cfg["rms_norm_eps"]
    a = lp["self_attn"]

    def norm(y, name):
        if name in NORMS[1::2] and name not in POST_NORMS:
            return y                    # a branch left un-normed on its way out
        return r(rms_norm(y, f32(lp[name]["weight"]), eps))

    s = x.shape[0]
    pos = pos0 + jnp.arange(s)
    rows = min(ROW_BLOCK, s)
    blocked = lambda y: blocks_of(y, rows)
    unblocked = lambda y: y.reshape((-1, ) + y.shape[2:])[:s]

    latent = unblocked(jax.lax.map(
        lambda args: latent_rows(norm(args[0], NORMS[0]), a, cfg, args[1]),
        (blocked(x), blocked(pos))))
    if latent_before is not None:
        latent = jnp.concatenate([latent_before, latent])

    def block(args):
        xb, pb, flip_b, weights_b = args
        att = attention_rows(norm(xb, NORMS[0]), pb, a, cfg, latent)
        x1 = r(xb + norm(att, NORMS[1]))
        h = norm(x1, NORMS[2])
        if routed:
            m, router, margin, w = moe_rows(h, lp["moe"], cfg, flip_b,
                                            weights_b)
        else:
            m, router, margin, w = dense_rows(h, lp["mlp"], cfg), None, \
                jnp.full(h.shape[:1], jnp.inf), None
        return r(x1 + norm(m, NORMS[3])), router, margin, w

    out, router, margin, w = jax.lax.map(
        block, (blocked(x), blocked(pos), blocked(jnp.arange(s) == flip_token),
                None if weights is None else blocked(weights)))
    return (unblocked(out), None if router is None else unblocked(router),
            unblocked(margin), None if w is None else unblocked(w), latent)


def embed(params, ids):
    return jnp.asarray(params["embed_tokens"]["embedding"], jnp.float32)[ids]


def head(params, x, cfg):
    x = rms_norm(x, jnp.asarray(params["norm"]["weight"], jnp.float32),
                 cfg["rms_norm_eps"])
    return x @ jnp.asarray(params["lm_head"]["kernel"], jnp.float32)


@partial(jax.jit, static_argnames=("cfg_items", "routed", "pos0"))
def _layer_jit(x, lp, latent_before, flip_token, weights, cfg_items, routed,
               pos0=0):
    with jax.default_matmul_precision(HIGHEST):
        return layer(x, lp, dict(cfg_items), routed, pos0, latent_before,
                     flip_token, weights)


@partial(jax.jit, static_argnames=("cfg_items",))
def _head_jit(params_head, x, cfg_items):
    with jax.default_matmul_precision(HIGHEST):
        return head(params_head, x, dict(cfg_items))


def _head(params, x, cfg):
    return _head_jit({"norm": params["norm"], "lm_head": params["lm_head"]},
                     x, hashable(cfg))


def is_routed(cfg, i):
    return i >= cfg["first_k_dense_replace"]


def logits_at(params, ids, positions, cfg):
    """Float32 logits [len(positions), V] of ONE sequence ``ids`` [S] at the
    given positions."""
    return logits_and_routing_at(params, ids, positions, cfg, _keep=False)[0]


#: the newest first answer's sequence, the first position it was asked for,
#: and per layer the tokens' hidden states from that position on and every
#: token's latent rows: what a second answer is recomputed from
_FIRST = {}


def logits_and_routing_at(params, ids, positions, cfg, flip=None, _keep=True):
    """``(logits [P, V], margins [P, L])``: the float32 logits of ONE sequence
    at ``positions`` and the router margin of the token at each of them at
    every layer (infinite at a leading dense layer).  With ``flip = (layer,
    position)`` the token at that position (and no other) takes its (k+1)-th
    expert in place of its k-th at that layer."""
    items = hashable(cfg)
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
            _FIRST.update(ids=ids, start=int(at.min()), x=[], latent=[])
    margins = [jnp.full((len(ids) - start, ), jnp.inf)] * begin
    for i in range(begin, cfg["num_hidden_layers"]):
        token = jnp.int32(flip[1] - start) \
            if flip is not None and flip[0] == i else none
        before = None if first is None else first["latent"][i][:start]
        if flip is None and _keep:
            _FIRST["x"].append(x[_FIRST["start"]:])
        x, _, margin, _, latent = _layer_jit(
            x, params[f"layers_{i}"], before, token, None, items,
            is_routed(cfg, i), start)
        if flip is None and _keep:
            _FIRST["latent"].append(latent)
        margins.append(margin)
    logits = _head(params, x[jnp.asarray(at - start)], cfg)
    return logits, jnp.stack(margins)[:, at - start].T


def router_logit_error(params, ids, cfg, serving_type="bfloat16"):
    """The largest difference, over one sequence's tokens, routed layers and
    experts, between the float32 router logits and those of the same
    reference with every activation rounded to ``serving_type`` where a
    system serving in that type rounds (``rounded``: each norm, each
    projection, the rotary, the expanded keys and values, each head block's
    attention output, the output projection, each expert's three products and
    its weighted part, the routed sum, the shared expert, both residual
    adds).  The rounded pass is ROUTED AS the float32 one, layer by layer.
    The worst over the seeds run is the configuration's
    ``measured_worst["serve.router_margin"]``."""
    exact = hashable(cfg)
    lossy = hashable(dict(cfg, round_activations_to=serving_type))
    x = xr = embed(params, jnp.asarray(ids, jnp.int32))
    worst, none = 0.0, jnp.int32(-1)
    for i in range(cfg["num_hidden_layers"]):
        lp, routed = params[f"layers_{i}"], is_routed(cfg, i)
        x, router, _, weights, _ = _layer_jit(x, lp, None, none, None, exact,
                                              routed)
        xr, router_r, *_ = _layer_jit(xr, lp, None, none, weights, lossy,
                                      routed)
        if routed:
            worst = max(worst, float(jnp.max(jnp.abs(router - router_r))))
    return worst
