"""Plain float32 reference of the Ouro forward pass (``model_type: ouro``; a
LOOPED language model: "Scaling Latent Reasoning via Looped Language Models",
ByteDance Seed, arXiv:2510.25741).

Written from the published ``config.json`` (the catalog's row ``Ouro-2.6B``),
the report and the configuration file's ``assumed`` readings.  ``N_*`` is an
RMSNorm (``rms_norm_eps``), ``T = total_ut_steps``, ``L =
num_hidden_layers``; layer ``l``'s weights are THE SAME in every pass ``t``:

    h(0)   = E[ids]
    for t in 1..T:
        x = h(t-1)
        for l in 1..L:                       sandwich norms
            x = x + N_in2,l ( Attn_l  ( N_in,l  (x) ) )
            x = x + N_post2,l( SwiGLU_l( N_post,l(x) ) )
        h(t)   = N_final(x)                  one final norm, after EVERY pass
        lam(t) = sigmoid( h(t) . w_gate + b_gate )
    p(t) = lam(t) * prod_{j<t} (1 - lam(j))  for t < T;  p(T) = prod_{j<T} (1 - lam(j))
    logits = h(T) W_head

``Attn_l``: ``num_attention_heads`` query and ``num_key_value_heads`` K/V
heads of ``head_dim``, no bias; rotary on ``q`` and ``k`` over the whole head
(half-split ``rotate_half`` pairs, theta ``rope_theta``, the same positions
in every pass); causal softmax at scale ``head_dim^-1/2``.  A forward over a
whole sequence has no cache: pass ``t`` of layer ``l`` attends over the keys
and values that pass ``t`` of layer ``l`` computed, which is what a cache
with one entry a (pass, layer) pair holds (``forward(..., keep_kv=True)``
returns them by that pair: what entry ``(t-1) * L + (l-1)`` has to hold).

Plain ``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``; no cache, no kernel, nothing imported from ``deepspeed_tpu``.
One layer is upcast at a time (a jitted call a layer and pass), so ``T x L``
bodies never hold more than one layer in float32.  The layout it reads (a
data format):

    embed_tokens/embedding [V, D]    norm/weight [D]    lm_head/kernel [D, V]
    early_exit_gate/kernel [D, 1]    early_exit_gate/bias [1]
    layers_<l>/{input_layernorm, post_attention_layernorm}/weight [D]
    layers_<l>/{input_layernorm_2, post_attention_layernorm_2}/weight [D / 32, 32]
                (a gain vector held in rows of 32, the hidden axis row-major)
    layers_<l>/self_attn/{q,k,v}_proj/kernel [D, heads, Dh]    o_proj/kernel [H * Dh, D]
    layers_<l>/mlp/{gate,up}_proj/kernel [D, I]                down_proj/kernel [I, D]

Readings a ``sizes`` may state beside the published keys, each the published
behaviour by default (``tools/serve_fault_check.py`` plants them as faults):
``passes_run`` (``total_ut_steps``); ``norm_between_passes`` (True: the final
norm's output feeds the next pass; False: the un-normed ``x`` does, the gate
and the head still read the normed one); ``pass_reads`` (``"own"``: a pass
attends over its own keys and values; ``"previous"``: pass ``t > 1`` attends,
with its own queries, over what pass ``t - 1`` of the layer computed;
``"last"``: every pass attends over what the LAST pass of a sound forward
computed at that layer, i.e. one cache entry a layer, shared, as it stands
once a token is through); ``post_sublayer_norms`` (True); and
``weight_mantissa_bits`` (None: the matrices as they are stored; 3: each
rounded to an 8-bit float's three mantissa bits before it is used, the
comparison's lower-precision CONTROL).
"""

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = "highest"


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
    gate's bias are read by ``f32`` itself, never rounded)."""
    return partial(f32, bits=cfg.get("weight_mantissa_bits"))


def hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def rotary(x, positions, theta):
    """x: [S, heads, Dh]; the published code's ``rotate_half`` pairs."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """q: [S, H, Dh]; k, v: [S, Hkv, Dh] -> [S, H * Dh]; causal."""
    s, h, dh = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(jnp.float32(dh))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    return jnp.einsum("hst,thd->shd", probs, v).reshape(s, h * dh)


def layer(x, lp, cfg, kv_from=None):
    """One layer of one pass over a whole sequence ``x [S, D]``: ``(x', k,
    v)``, ``k`` (turned) and ``v`` being what THIS pass computed; the
    attention reads ``kv_from`` in their place where it is given."""
    w, eps = matrix(cfg), cfg["rms_norm_eps"]
    norm = lambda y, name: rms_norm(y, f32(lp[name]["weight"]).reshape(-1),
                                    eps)
    after = norm if cfg.get("post_sublayer_norms", True) else lambda y, _: y
    a, m = lp["self_attn"], lp["mlp"]
    h = norm(x, "input_layernorm")
    pos = jnp.arange(x.shape[0])
    proj = lambda name: jnp.einsum("sd,dhe->she", h, w(a[name]["kernel"]))
    q = rotary(proj("q_proj"), pos, cfg["rope_theta"])
    k = rotary(proj("k_proj"), pos, cfg["rope_theta"])
    v = proj("v_proj")
    out = attention(q, *((k, v) if kv_from is None else kv_from)) \
        @ w(a["o_proj"]["kernel"])
    x = x + after(out, "input_layernorm_2")
    h = norm(x, "post_attention_layernorm")
    out = (jax.nn.silu(h @ w(m["gate_proj"]["kernel"]))
           * (h @ w(m["up_proj"]["kernel"]))) @ w(m["down_proj"]["kernel"])
    return x + after(out, "post_attention_layernorm_2"), k, v


@partial(jax.jit, static_argnames=("cfg_items", ))
def _layer_jit(x, lp, kv_from, cfg_items):
    with jax.default_matmul_precision(HIGHEST):
        return layer(x, lp, dict(cfg_items), kv_from)


@partial(jax.jit, static_argnames=("cfg_items", ))
def _between_jit(x, norm, gate, cfg_items):
    """``(h(t), lam(t))`` from a pass's output."""
    with jax.default_matmul_precision(HIGHEST):
        cfg = dict(cfg_items)
        h = rms_norm(x, f32(norm["weight"]), cfg["rms_norm_eps"])
        z = h @ matrix(cfg)(gate["kernel"])
        return h, jax.nn.sigmoid(z[:, 0] + f32(gate["bias"])[0])


@partial(jax.jit, static_argnames=("cfg_items", ))
def _head_jit(h, head, cfg_items):
    with jax.default_matmul_precision(HIGHEST):
        return h @ matrix(dict(cfg_items))(head["kernel"])


def exit_distribution(lam):
    """``p [T, S]`` from the gates ``lam [T, S]``; the last pass takes what
    is left, so ``lam(T)`` is not read and ``p`` sums to 1."""
    p, left = [], jnp.ones_like(lam[0])
    for gate in lam[:-1]:
        p.append(gate * left)
        left = left * (1.0 - gate)
    return jnp.stack(p + [left])


def forward(params, ids, positions, cfg, keep_kv=(), kv_shared=None):
    """One full forward of ONE sequence ``ids [S]``: ``{"logits": float32
    [len(positions), V], "lam": [passes run, S]}`` and, with ``keep_kv``
    (True: every pass; or the passes to keep, from 0), ``"kv": {(t, l): (k,
    v)}`` (layers from 0 too).  ``kv_shared``: a ``{l: (k, v)}`` every pass
    attends over (``pass_reads: "last"``)."""
    items = hashable(cfg)
    layers, reads = cfg["num_hidden_layers"], cfg.get("pass_reads", "own")
    if reads == "last" and kv_shared is None:
        last = cfg.get("passes_run", cfg["total_ut_steps"]) - 1
        sound = forward(params, ids, positions, dict(cfg, pass_reads="own"),
                        keep_kv=(last, ))
        kv_shared = {l: sound["kv"][last, l] for l in range(layers)}
    ids = jnp.asarray(ids, jnp.int32)
    x = f32(params["embed_tokens"]["embedding"][ids])
    lam, kv, before = [], {}, {}
    for t in range(cfg.get("passes_run", cfg["total_ut_steps"])):
        for l in range(layers):
            kv_from = kv_shared[l] if kv_shared else before.get(l)
            x, k, v = _layer_jit(x, params[f"layers_{l}"], kv_from, items)
            if keep_kv is True or t in keep_kv:
                kv[t, l] = (k, v)
            if reads == "previous":
                before[l] = (k, v)
        h, gate = _between_jit(x, params["norm"], params["early_exit_gate"],
                               items)
        lam.append(gate)
        if cfg.get("norm_between_passes", True):
            x = h
    out = {"logits": _head_jit(h[jnp.asarray(positions, jnp.int32)],
                               params["lm_head"], items),
           "lam": jnp.stack(lam)}
    if kv:
        out["kv"] = kv
    return out


def logits_at(params, ids, positions, cfg):
    """Float32 logits ``[len(positions), V]`` of ONE sequence ``ids [S]`` at
    the given positions, as ``jobs/serve.py`` calls it."""
    return forward(params, ids, positions, cfg)["logits"]
