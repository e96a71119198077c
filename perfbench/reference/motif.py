"""Plain float32 reference of the Motif-3 forward pass (``model_type:
Motif``), share-aware: grouped differential attention in the EXPANDED form of
its latent attention (a K/V group's keys and values are made from the latent
row; no cache, no absorbed product, no kernel anywhere in this file), a
four-stream mHC residual, PolyNorm-gated feed-forwards.

Written from the published ``config.json`` (the catalog's row
``Motif-3-Beta``) and the configuration file's ``assumed`` readings.  ``N`` is
an RMSNorm (``rms_norm_eps``), ``D`` hidden, ``n`` = ``mhc_expansion_rate``
streams, ``H`` query heads in ``G`` = ``num_key_value_heads`` groups of ``H /
G`` (the LAST head of a group its noise head), ``S`` = ``H -
num_noise_heads`` signal heads, ``r`` = ``kv_lora_rank``, ``dn`` = ``head_dim
- qk_rope_head_dim``, ``dr``, ``dv``, ``E`` the router's width, ``k`` experts
a token.  The residual of a token is ``X [n, D]``, ``X_0`` = ``n`` copies of
the embedding row; every sublayer ``Sub`` (attention ``A``, then the
feed-forward ``F_l``) of layer ``l`` is wrapped alike, with parameters of its
own:

    x~ = N_w(vec(X)) [n D]
    H_pre  = sigmoid(a_pre (x~ P_pre) + b_pre) [n]
    H_post = 2 sigmoid(a_post (x~ P_post) + b_post) [n]
    H_res  = Sinkhorn(exp(a_res mat(x~ P_res) + b_res)) [n, n]:
             ``mhc_sinkhorn_iters`` sweeps of (rows / their sums, columns / theirs)
    h = N_sub(H_pre X);    X' = H_res X + H_post^T (x) Sub(h)
    A(h):  c_q = N(h W_qa);  q = c_q W_qb -> H x (q_n [dn] ; q_r [dr]);  q_r <- rope
           (c ; k_r) = h W_kva;  c = N(c);  k_r <- rope     (half-split, ``rope_theta``)
           k_n^g = c W_uk^g,  v^g = c W_uv^g                (head h reads group h // (H / G))
           a_h = softmax_j((q_n^h . k_n^g(j) + q_r^h . k_r(j)) / sqrt(dn + dr)) v^g(j)
                 j <= p, and j > p - sliding_window where l % period != period - 1
           lam = sigmoid(h W_lam) [S];   o_s = a_s - lam_s a_noise(g(s))
           A = ((o ; S dv) * sigmoid(h W_gate)) W_o
    F_l(h) = W_down(PolyNorm(h W_gate) * (h W_up))                 l < n_dense_first_layers
    F_l(h) = G_shared(h) + sum_{e in top-k, held} w_e G_e(h)       otherwise; G the same gated form
             sc = sigmoid(h W_r) over all E;  top k;  w_e = route_scale sc_e / sum_top-k sc
    PolyNorm(z) = s (w1 z^3 / rms(z^3) + w2 z^2 / rms(z^2) + w3 z / rms(z) + clip(b, -c, c))
    logits = N_f(sum_i X_L[i]) W_head                             (untied head)

Plain ``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``; nothing imported from ``deepspeed_tpu``.  The layout it reads
(a data format):

    embed_tokens/embedding [V, D]      norm/weight [D]      lm_head/kernel [D, V]
    layers_<i>/{attn,mlp}_mhc/{norm/weight [n D], proj/kernel [n D, 2 n + n n],
                               alpha [3], bias [1, 2 n + n n]}   (pre | post | res)
    layers_<i>/{input,post_attention}_layernorm/weight [D]        (the two N_sub)
    layers_<i>/self_attn/q_a_proj/kernel [D, q_lora_rank]   q_a_layernorm/weight
    layers_<i>/self_attn/q_b_proj/kernel [q_lora_rank, H, dn + dr]
    layers_<i>/self_attn/kv_a_proj/kernel [D, r + dr]       kv_a_layernorm/weight [r]
    layers_<i>/self_attn/k_b_proj/kernel [r / 4, 4, G, dn]  v_b_proj/kernel [r / 16, 16, G, dv]
                                  (the rank axis in rows: [r, G, .] by a reshape)
    layers_<i>/self_attn/{lambda_proj [D, S], gate_proj [D, S dv], o_proj [S dv, D]}/kernel
    layers_<i>/mlp/{gate,up,down}_proj/kernel, mlp/poly [1, 4] (w1 w2 w3 b)   (dense layer)
    layers_<i>/moe/gate/kernel [D, E]   moe/{w1,w3} [held, D, I]   moe/w2 [held, I, D]
    layers_<i>/moe/poly [held, 4]       moe/shared_{gate,up,down}_proj/kernel   moe/shared_poly [1, 4]

**One chip's share**, **routing is stated**: as ``reference/cohere2_moe.py``
(``sizes["experts_held"]``, ``["first_expert"]``; ``logits_and_routing_at``
with ``flip``; ``router_logit_error``).  A dense layer has no router: its
margin is infinite.

**Named switches of the sizes** (each a reading of ``assumed``, or a fault a
tool plants; the default is the configuration's reading): ``differential``
(True; False: no noise output is subtracted), ``window_dropped_on_layer`` /
``window_put_on_layer`` (-1: none; a layer index: that window layer reads
everything / that full layer reads its window), ``mhc_sinkhorn_iters``,
``mhc_identity_res`` (False; True: ``H_res`` is the identity),
``hidden_act`` (``poly_norm``; ``silu``: SiLU on every gate),
``cache_row_mantissa_bits`` (None; 3: the latent row ``(c ; k_r)`` rounded to
an 8-bit float's three mantissa bits before anything reads it) and
``weight_mantissa_bits`` (None; 3: every matrix so rounded, the comparison's
lower-precision CONTROL).

Departures from the published code, none of them mathematics: every layer is
computed in blocks of ``ROW_BLOCK`` tokens against the latent rows of ALL the
tokens, which are made first (they are row-wise, as the attention's input ``h``
and the write's mappings are: at 16 416 tokens the streams are one ``[S, n,
D]`` float32 array of 1.08 GB, and the reference runs beside the engine);
inside a block the K/V groups are taken one at a time and the queries
``QUERY_ROWS`` at a time; a dense layer's feed-forward ``MLP_COLS`` columns of
its width at a time in TWO passes (PolyNorm's three mean squares over the
whole width first); every held expert for every token, weighted by 0 where
the token is not routed to it.  A SECOND answer (``flip``) whose token lies at
or after the first position the first answer was asked for recomputes the
tokens from that position on alone, against the first answer's latent rows.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
ROW_BLOCK = 1024
QUERY_ROWS = 256
MLP_COLS = 2048


def f32(x, bits=None):
    """``x`` in float32; with ``bits``, rounded first to that many mantissa
    bits behind the leading one."""
    x = jnp.asarray(x, jnp.float32)
    if bits is None:
        return x
    mantissa, exponent = jnp.frexp(x)            # mantissa in [0.5, 1)
    steps = 2.0 ** (bits + 1)
    return jnp.ldexp(jnp.round(mantissa * steps) / steps, exponent)


def matrix(cfg):
    """What reads a weight MATRIX for these sizes (norm weights, mHC's
    scalars and biases and PolyNorm's coefficients are read by ``f32``
    itself, never rounded)."""
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


def layer_window(cfg, i):
    """The sliding window of layer ``i``; 0: it reads everything."""
    period = cfg["sliding_window_period"]
    full = i % period == period - 1
    if i == cfg.get("window_dropped_on_layer", -1):
        full = True
    if i == cfg.get("window_put_on_layer", -1):
        full = False
    return 0 if full else int(cfg["sliding_window"])


# ------------------------------------------------------------------- mHC
def sinkhorn(m, sweeps):
    """``m [..., n, n]`` positive -> doubly stochastic by ``sweeps`` sweeps."""
    for _ in range(sweeps):
        m = m / jnp.sum(m, axis=-1, keepdims=True)      # rows
        m = m / jnp.sum(m, axis=-2, keepdims=True)      # columns
    return m


def mhc_maps(X, p, cfg):
    """``(H_pre [R, n], H_post [R, n], H_res [R, n, n])`` of the streams ``X
    [R, n, D]``."""
    rows, n, _ = X.shape
    x = rms_norm(X.reshape(rows, -1), f32(p["norm"]["weight"]),
                 cfg["rms_norm_eps"])
    m = x @ matrix(cfg)(p["proj"]["kernel"])
    a, b = f32(p["alpha"]), f32(p["bias"])[0]
    h_pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    if cfg.get("mhc_identity_res", False):
        return h_pre, h_post, jnp.broadcast_to(jnp.eye(n), (rows, n, n))
    res = jnp.exp(a[2] * m[:, 2 * n:].reshape(rows, n, n)
                  + b[2 * n:].reshape(n, n))
    return h_pre, h_post, sinkhorn(res, int(cfg["mhc_sinkhorn_iters"]))


def mhc_read(X, h_pre, norm_weight, cfg):
    """``N_sub(H_pre X) [R, D]``."""
    return rounded(rms_norm(jnp.einsum("rn,rnd->rd", h_pre, X),
                            f32(norm_weight), cfg["rms_norm_eps"]), cfg)


def mhc_write(X, y, h_post, h_res, cfg):
    return rounded(jnp.einsum("rij,rjd->rid", h_res, X)
                   + h_post[:, :, None] * y[:, None, :], cfg)


# -------------------------------------------------------------- PolyNorm
def gate_act(z, coef, cfg):
    """PolyNorm of ``z [R, I]`` with ``coef`` (w1, w2, w3, b), or the SiLU a
    planted fault puts in its place."""
    if cfg.get("hidden_act", "poly_norm") == "silu":
        return jax.nn.silu(z)
    eps = cfg["rms_norm_eps"]
    normed = lambda p: p * jax.lax.rsqrt(
        jnp.mean(jnp.square(p), -1, keepdims=True) + eps)
    clamp = cfg["polynorm_bias_clamp"]
    return cfg["polynorm_output_scale"] * (
        coef[0] * normed(z ** 3) + coef[1] * normed(z ** 2)
        + coef[2] * normed(z) + jnp.clip(coef[3], -clamp, clamp))


def gated(h, w_gate, w_up, w_down, coef, cfg):
    """``W_down(act(h W_gate) * (h W_up))`` of one narrow feed-forward."""
    r, mat = partial(rounded, cfg=cfg), matrix(cfg)
    act = r(gate_act(r(h @ mat(w_gate)), f32(coef), cfg))
    return r(act * r(h @ mat(w_up))) @ mat(w_down)


def dense_rows(h, mlp, cfg):
    """A dense layer's feed-forward for rows ``h``, ``MLP_COLS`` columns of
    its width at a time: PolyNorm's three mean squares over the WHOLE width
    in a first pass, the gated product in a second."""
    r, mat = partial(rounded, cfg=cfg), matrix(cfg)
    width = mlp["gate_proj"]["kernel"].shape[1]
    cols = math.gcd(width, MLP_COLS)
    cut = lambda w, j, axis: mat(jax.lax.dynamic_slice_in_dim(
        w["kernel"], j * cols, cols, axis))
    coef = f32(mlp["poly"])[0]
    silu = cfg.get("hidden_act", "poly_norm") == "silu"

    def squares(j, acc):
        z = r(h @ cut(mlp["gate_proj"], j, 1))
        return acc + jnp.stack([jnp.sum(z ** (2 * p), -1) for p in (3, 2, 1)])

    ms = jax.lax.fori_loop(0, width // cols, squares,
                           jnp.zeros((3, h.shape[0]))) / width
    inv = jax.lax.rsqrt(ms + cfg["rms_norm_eps"])[:, :, None]
    clamp = cfg["polynorm_bias_clamp"]

    def part(j, acc):
        z = r(h @ cut(mlp["gate_proj"], j, 1))
        act = jax.nn.silu(z) if silu else cfg["polynorm_output_scale"] * (
            coef[0] * z ** 3 * inv[0] + coef[1] * z ** 2 * inv[1]
            + coef[2] * z * inv[2] + jnp.clip(coef[3], -clamp, clamp))
        act = r(r(act) * r(h @ cut(mlp["up_proj"], j, 1)))
        return acc + act @ cut(mlp["down_proj"], j, 0)

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
    own, margin = route(router_logits, cfg["experts_top_k"], flip,
                        cfg.get("route_norm", True), held,
                        cfg.get("route_scale", 1.0))
    weights = own if weights is None else weights
    columns = weights
    if held is not None:                     # the stacks hold these alone
        columns = columns[:, held[0]:held[0] + held[1]]

    def expert(acc, e):
        w1, w3, w2, coef, col = e            # one expert, upcast here
        return acc + r(r(gated(h, w1, w3, w2, coef, cfg))
                       * col[:, None]), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                             (m["w1"], m["w3"], m["w2"], m["poly"],
                              columns.T))
    shared = gated(h, m["shared_gate_proj"]["kernel"],
                   m["shared_up_proj"]["kernel"],
                   m["shared_down_proj"]["kernel"], m["shared_poly"][0], cfg)
    return r(r(routed) + r(shared)), router_logits, margin, weights


# ------------------------------------------------------------------ GDLA
def group_kernel(w, cfg):
    """``[r / rows, rows, G, e]`` -> ``[G, r, e]`` float32."""
    return jnp.moveaxis(matrix(cfg)(w).reshape((-1, ) + w.shape[2:]), 1, 0)


def latent_rows(h, a, cfg, pos):
    """The latent row ``(c [r] ; k_r [dr])`` of each token: ``h [S, D]``
    (normed) at positions ``pos``."""
    r = partial(rounded, cfg=cfg)
    rank = cfg["kv_lora_rank"]
    ckv = r(h @ matrix(cfg)(a["kv_a_proj"]["kernel"]))
    c = r(rms_norm(ckv[:, :rank], f32(a["kv_a_layernorm"]["weight"]),
                   cfg["rms_norm_eps"]))
    row = jnp.concatenate(
        [c, r(rope_half(ckv[:, rank:], pos, cfg["rope_theta"]))], -1)
    return f32(row, cfg.get("cache_row_mantissa_bits"))


def attention_rows(h, pos, a, cfg, latent, window):
    """``A(h) [R, D]`` for the rows ``h [R, D]`` (normed) at positions ``pos
    [R]`` against the latent rows ``latent [S, r + dr]`` of the tokens at
    positions ``0 .. S - 1``: expanded, one K/V group at a time."""
    r, mat = partial(rounded, cfg=cfg), matrix(cfg)
    rank = cfg["kv_lora_rank"]
    dn = cfg["head_dim"] - cfg["qk_rope_head_dim"]
    heads, groups = a["q_b_proj"]["kernel"].shape[1], a["k_b_proj"][
        "kernel"].shape[2]
    per = heads // groups                    # a group's heads, the noise one last
    c_all, kr_all = latent[:, :rank], latent[:, rank:]
    key_pos = jnp.arange(latent.shape[0])[None, :]
    c_q = r(rms_norm(r(h @ mat(a["q_a_proj"]["kernel"])),
                     f32(a["q_a_layernorm"]["weight"]), cfg["rms_norm_eps"]))
    scale = 1.0 / math.sqrt(cfg["head_dim"])
    rows = min(QUERY_ROWS, h.shape[0])
    lam = jax.nn.sigmoid(r(h @ mat(a["lambda_proj"]["kernel"])))
    if not cfg.get("differential", True):
        lam = jnp.zeros_like(lam)
    lam = lam.reshape(h.shape[0], groups, per - 1)
    w_uq = a["q_b_proj"]["kernel"]
    w_uq = jnp.moveaxis(w_uq.reshape(w_uq.shape[0], groups, per, -1), 1, 0)

    def group(_, w):
        w_uq, w_uk, w_uv, lam_g = w
        q = r(jnp.einsum("sq,qhe->she", c_q, mat(w_uq)))
        q_n, q_r = q[..., :dn], r(rope_half(q[..., dn:], pos,
                                            cfg["rope_theta"]))
        k_n, v = r(c_all @ w_uk), r(c_all @ w_uv)         # the group's own

        def queries(args):
            qn, qr, pb = args
            scores = (jnp.einsum("shn,tn->hst", qn, k_n)
                      + jnp.einsum("shr,tr->hst", qr, kr_all)) * scale
            mask = key_pos <= pb[:, None]
            if window:
                mask &= key_pos > pb[:, None] - window
            probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf),
                                   -1)
            return jnp.einsum("hst,tv->shv", probs, v)

        out = jax.lax.map(queries, (blocks_of(q_n, rows),
                                    blocks_of(q_r, rows),
                                    blocks_of(pos, rows)))
        out = r(out.reshape((-1, ) + out.shape[2:])[:h.shape[0]])
        return None, r(out[:, :-1] - lam_g[:, :, None] * out[:, -1:])

    _, o = jax.lax.scan(group, None, (
        w_uq, group_kernel(a["k_b_proj"]["kernel"], cfg),
        group_kernel(a["v_b_proj"]["kernel"], cfg),
        jnp.moveaxis(lam, 1, 0)))                    # [G, R, per - 1, dv]
    o = jnp.moveaxis(o, 0, 1).reshape(h.shape[0], -1)
    gate = jax.nn.sigmoid(r(h @ mat(a["gate_proj"]["kernel"])))
    return r(r(o * gate) @ mat(a["o_proj"]["kernel"]))


def layer(X, lp, cfg, routed, window, pos0=0, latent_before=None,
          flip_token=-1, weights=None):
    """``(X'', router logits, margin, weights, latent)`` of one layer (its
    feed-forward ``routed`` or dense, its attention's ``window``, 0: none)
    for the tokens' streams ``X [S, n, D]`` at positions ``pos0 ..``;
    ``latent_before``: the latent rows of the tokens before them (None:
    there are none); ``latent``: those of all the tokens up to the last of
    these.  A dense layer returns no router logits, an infinite margin and
    no weights."""
    a = lp["self_attn"]
    s = X.shape[0]
    pos = pos0 + jnp.arange(s)
    rows = min(ROW_BLOCK, s)
    blocked = lambda y: blocks_of(y, rows)
    unblocked = lambda y: y.reshape((-1, ) + y.shape[2:])[:s]

    def attention_input(xb):
        h_pre, _, _ = mhc_maps(xb, lp["attn_mhc"], cfg)
        return mhc_read(xb, h_pre, lp["input_layernorm"]["weight"], cfg)

    latent = unblocked(jax.lax.map(
        lambda args: latent_rows(attention_input(args[0]), a, cfg, args[1]),
        (blocked(X), blocked(pos))))
    if latent_before is not None:
        latent = jnp.concatenate([latent_before, latent])

    def block(args):
        xb, pb, flip_b, weights_b = args
        _, h_post, h_res = mhc_maps(xb, lp["attn_mhc"], cfg)
        att = attention_rows(attention_input(xb), pb, a, cfg, latent, window)
        x1 = mhc_write(xb, att, h_post, h_res, cfg)
        h_pre, h_post, h_res = mhc_maps(x1, lp["mlp_mhc"], cfg)
        h = mhc_read(x1, h_pre, lp["post_attention_layernorm"]["weight"], cfg)
        if routed:
            m, router, margin, w = moe_rows(h, lp["moe"], cfg, flip_b,
                                            weights_b)
        else:
            m, router, margin, w = dense_rows(h, lp["mlp"], cfg), None, \
                jnp.full(h.shape[:1], jnp.inf), None
        return mhc_write(x1, m, h_post, h_res, cfg), router, margin, w

    out, router, margin, w = jax.lax.map(
        block, (blocked(X), blocked(pos), blocked(jnp.arange(s) == flip_token),
                None if weights is None else blocked(weights)))
    return (unblocked(out), None if router is None else unblocked(router),
            unblocked(margin), None if w is None else unblocked(w), latent)


def embed(params, ids, cfg):
    x = jnp.asarray(params["embed_tokens"]["embedding"], jnp.float32)[ids]
    return jnp.repeat(x[:, None], int(cfg["mhc_expansion_rate"]), axis=1)


def head(params, X, cfg):
    x = rms_norm(jnp.sum(X, axis=1),
                 jnp.asarray(params["norm"]["weight"], jnp.float32),
                 cfg["rms_norm_eps"])
    return x @ matrix(cfg)(params["lm_head"]["kernel"])


@partial(jax.jit, static_argnames=("cfg_items", "routed", "window", "pos0"))
def _layer_jit(X, lp, latent_before, flip_token, weights, cfg_items, routed,
               window, pos0=0):
    with jax.default_matmul_precision(HIGHEST):
        return layer(X, lp, dict(cfg_items), routed, window, pos0,
                     latent_before, flip_token, weights)


def _layer(X, lp, latent_before, flip_token, weights, cfg, index, pos0=0):
    """Layer ``index``: layers of one kind (dense or routed, window or none)
    share one compiled program."""
    return _layer_jit(X, lp, latent_before, flip_token, weights,
                      hashable(cfg), index >= cfg["n_dense_first_layers"],
                      layer_window(cfg, index), pos0)


@partial(jax.jit, static_argnames=("cfg_items",))
def _head_jit(params_head, X, cfg_items):
    with jax.default_matmul_precision(HIGHEST):
        return head(params_head, X, dict(cfg_items))


def _head(params, X, cfg):
    return _head_jit({"norm": params["norm"], "lm_head": params["lm_head"]},
                     X, hashable(cfg))


def logits_at(params, ids, positions, cfg):
    """Float32 logits [len(positions), V] of ONE sequence ``ids`` [S] at the
    given positions."""
    return logits_and_routing_at(params, ids, positions, cfg, _keep=False)[0]


#: the newest first answer's sequence, the first position it was asked for,
#: and per layer the tokens' streams from that position on and every token's
#: latent rows: what a second answer is recomputed from
_FIRST = {}


def logits_and_routing_at(params, ids, positions, cfg, flip=None, _keep=True):
    """``(logits [P, V], margins [P, L])``: the float32 logits of ONE sequence
    at ``positions`` and the router margin of the token at each of them at
    every layer (infinite at a dense layer).  With ``flip = (layer,
    position)`` the token at that position (and no other) takes its (k+1)-th
    expert in place of its k-th at that layer."""
    ids = np.asarray(ids, np.int32)
    at = np.asarray(positions, np.int32)
    none = jnp.int32(-1)
    first = _FIRST if flip is not None and _FIRST.get("ids") is not None \
        and np.array_equal(_FIRST["ids"], ids) \
        and flip[1] >= _FIRST["start"] <= at.min() else None
    if first is None:
        start, begin = 0, 0
        X = embed(params, jnp.asarray(ids), cfg)
    else:                       # the tokens from ``start`` on, from ``begin``
        start, begin = first["start"], flip[0]
        X = first["x"][begin]
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
            _FIRST["x"].append(X[_FIRST["start"]:])
        X, _, margin, _, latent = _layer(
            X, params[f"layers_{i}"], before, token, None, cfg, i, start)
        if flip is None and _keep:
            _FIRST["latent"].append(latent)
        margins.append(margin)
    logits = _head(params, X[jnp.asarray(at - start)], cfg)
    return logits, jnp.stack(margins)[:, at - start].T


def router_logit_error(params, ids, cfg, serving_type="bfloat16"):
    """The largest difference, over one sequence's tokens, routed layers and
    experts, between the float32 router logits and those of the same
    reference with every activation rounded to ``serving_type`` where a
    system serving in that type rounds (``rounded``: each sublayer's normed
    read of the streams and its write back to them, each projection, the
    rotary, a group's keys and values, its heads' outputs and their
    difference, the gated output, the output projection, each
    feed-forward's products).  The rounded pass is ROUTED AS the float32
    one, layer by layer.  The worst over the seeds run is the
    configuration's ``measured_worst["serve.router_margin"]``."""
    lossy = dict(cfg, round_activations_to=serving_type)
    X = Xr = embed(params, jnp.asarray(ids, jnp.int32), cfg)
    worst, none = 0.0, jnp.int32(-1)
    for i in range(cfg["num_hidden_layers"]):
        lp = params[f"layers_{i}"]
        X, router, _, weights, _ = _layer(X, lp, None, none, None, cfg, i)
        Xr, router_r, *_ = _layer(Xr, lp, None, none, weights, lossy, i)
        if router is not None:
            worst = max(worst, float(jnp.max(jnp.abs(router - router_r))))
    return worst
