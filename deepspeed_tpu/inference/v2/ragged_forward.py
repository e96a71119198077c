"""Ragged (paged-KV) model forward (reference
``inference/v2/model_implementations/llama_v2`` + the ragged kernel suite
``kernels/ragged_ops``: linear_blocked_kv_rotary, blocked flash, logits_gather).

One jitted function processes a *flat token buffer* ``[T]`` — the union of
prefill chunks and single decode tokens from many sequences — against the
paged KV cache.  The reference does this with hand-written CUDA (atom builder
+ blocked flash); here the batch metadata (positions, sequence slots, block
tables) turns the same computation into gathers/scatters XLA schedules, and
the attention core is ``_paged_attention``: the Pallas paged kernels on a
TPU, an XLA gather elsewhere.  The buffer has ONE layout: the rows a
sequence gets in a step are contiguous with consecutive positions
(``engine_v2._build_batch``), dead rows carry slot 0.

Token semantics: every token's K/V is written to the cache *before* attention
runs, and each token attends to cache positions ≤ its own — so a multi-token
prefill chunk is causal within itself and sees all earlier chunks, and a
decode token sees the whole prefix.  Exactly FastGen's ragged semantics.
"""

import functools

import jax
import jax.numpy as jnp

from ...models.evabyte import chunk_summaries
from ...models.llama import _rope_freqs
from ...telemetry import names as _names
from .ragged import window_row_positions


def _program(name, **jit_kwargs):
    """``jax.jit`` the decorated function as the program ``name``: jit names
    the compiled module after ``__name__``, which is what a profiler's
    module line shows (telemetry/names.py)."""
    def wrap(fn):
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn, **jit_kwargs)
    return wrap


def _ragged_program(arch, step_counts=(), slot_rows=False):
    """The ragged step of ``arch``.  ``step_counts``: the names of what the
    step counts ON THE DEVICE, the int32 vector it returns third (each a sum:
    two steps' values add); the engine fetches it with the tokens a request
    waits for and puts it among the ``ds:serve.step`` counts.  ``slot_rows``:
    the step takes the static ``slot_rows=True`` from a caller whose buffer
    has ONE row a slot, row ``i`` slot ``i`` (``decode_burst``)."""
    def wrap(fn):
        program = _program(_names.PROGRAM_RAGGED_STEP + arch,
                           static_argnames=("cfg", "block_size", "use_kernel",
                                            "kv_dtype") + (
                               ("slot_rows", ) if slot_rows else ()),
                           donate_argnums=(1, ))(fn)
        program.step_counts = tuple(step_counts)
        program.slot_rows = bool(slot_rows)
        return program
    return wrap


def _rotary(x, cos, sin, positions):
    """x: [T, H, Dh]; positions: [T]."""
    c = cos[positions][:, None, :]
    s = sin[positions][:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


@jax.named_scope(_names.SCOPE_NORM)
def _rmsnorm(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * w).astype(x.dtype)


def _paged_attention(q, k_cache, v_cache, block_tables, seq_slots, positions,
                     block_size, window=0, use_kernel=True, kv_scales=None):
    """q: [T, H, Dh]; caches: [num_blocks, bs, Hkv, Dh]; block_tables:
    [max_seqs, maxb]; seq_slots, positions: [T]; window: sliding-window size
    (0 → full causal).  Returns [T, H, Dh].

    On TPU: ``ops/pallas/paged_attention.paged_attention``, which picks the
    kernel by the shape (run-tiled — the rows of one sequence share each
    page load, and only live pages are visited — or one grid row a token).
    Off a TPU (and at ``use_kernel=False``): XLA gather of each token's
    block run with position masking — chosen by
    ``ops/_use_kernels.use_pallas_kernels``, the same gate as every other
    kernel dispatch site.  (A dead row, slot 0, comes back zero from the
    kernels and as attention over the garbage block from the gather;
    nothing reads it.)

    ``kv_scales=(k_scales, v_scales)`` ([num_blocks, bs, Hkv] f32 each) is
    the quantized-KV read path: the caches hold int8/fp8 rows and only the
    gathered context is dequantized (per-(token, head) scale applied inside
    the same f32 widening the math does anyway).  The Pallas kernel doesn't
    consume scales, so this path always takes the XLA gather."""
    from ...ops._use_kernels import use_pallas_kernels
    if use_kernel and kv_scales is None and use_pallas_kernels():
        from ...ops.pallas.paged_attention import paged_attention
        return paged_attention(q, k_cache, v_cache, block_tables, seq_slots,
                               positions, window=window,
                               block_size=block_size)
    tables_t = block_tables[seq_slots]
    T, H, Dh = q.shape
    from ...ops.pallas.paged_attention import page_kv_heads
    # whatever tokens a row of a page holds: the same bytes, row-major
    Hkv = page_kv_heads(k_cache.shape, block_size)
    maxb = tables_t.shape[1]
    ctx = maxb * block_size
    k_ctx = k_cache[tables_t].reshape(T, ctx, Hkv, Dh)
    v_ctx = v_cache[tables_t].reshape(T, ctx, Hkv, Dh)
    if kv_scales is not None:
        # dequant-on-read: per-(token, head) scales broadcast over Dh
        ks, vs = kv_scales
        k_ctx = (k_ctx.astype(jnp.float32)
                 * ks[tables_t].reshape(T, ctx, Hkv)[:, :, :, None])
        v_ctx = (v_ctx.astype(jnp.float32)
                 * vs[tables_t].reshape(T, ctx, Hkv)[:, :, :, None])
    g = H // Hkv
    qg = q.reshape(T, Hkv, g, Dh).astype(jnp.float32)
    scores = jnp.einsum("tkgd,tckd->tkgc", qg,
                        k_ctx.astype(jnp.float32)) * (Dh**-0.5)
    pos_ctx = jnp.arange(ctx)[None, None, None, :]
    pos_q = positions[:, None, None, None]
    mask = pos_ctx <= pos_q
    if window:
        mask &= pos_ctx > pos_q - window
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("tkgc,tckd->tkgd", probs, v_ctx.astype(jnp.float32))
    return out.reshape(T, H, Dh).astype(q.dtype)


def _qkv(h, proj, dtype):
    """DenseGeneral [T, D] → [T, H, Dh] with optional bias (Qwen2)."""
    y = jnp.einsum("td,dhk->thk", h, proj["kernel"].astype(dtype))
    if "bias" in proj:
        y = y + proj["bias"].astype(dtype)
    return y


def _lin(h, p, dtype):
    """Plain linear with optional bias."""
    y = h @ p["kernel"].astype(dtype)
    return y + p["bias"].astype(dtype) if "bias" in p else y


def _attn_cfg_view(cfg, sliding_window=0):
    """The subset of model config the shared attention block reads —
    one adapter for every non-llama architecture."""
    import types
    return types.SimpleNamespace(
        num_attention_heads=cfg.num_attention_heads, head_dim=cfg.head_dim,
        sliding_window=sliding_window, dtype=cfg.dtype)


@jax.named_scope(_names.SCOPE_LM_HEAD)
def _head_logits(params, x, last_token_idx, embed_key="embed_tokens"):
    """logits_gather epilogue shared by the zoo steps: gather each slot's
    last token, tied-embedding or lm_head projection (with optional bias)."""
    xl = x[last_token_idx].astype(jnp.float32)
    if "lm_head" in params:
        logits = xl @ params["lm_head"]["kernel"].astype(jnp.float32)
        if "bias" in params["lm_head"]:
            logits = logits + params["lm_head"]["bias"].astype(jnp.float32)
        return logits
    logits = xl @ params[embed_key]["embedding"].T.astype(jnp.float32)
    if "lm_head_bias" in params:  # tied phi: weight shared, bias live
        logits = logits + params["lm_head_bias"].astype(jnp.float32)
    return logits


@jax.named_scope(_names.SCOPE_KV_CACHE)
def _kv_scatter(kv_layer, k, v, blk, off, kv_dtype=None):
    """Everything a step spends to put its K/V rows ``[T, Hkv, Dh]`` into a
    layer's pages at ``(blk, off)``: the scatter into the donated buffers
    and, on the quantized path, the encoding and the scale scatter."""
    if kv_dtype is None:
        from ...ops.pallas.paged_attention import page_row_tokens
        k_pages, v_pages = kv_layer
        if page_row_tokens(k.shape[1], k.shape[2], k_pages.dtype) == 2:
            at = (blk, off // 2, off % 2)       # two tokens a row
            return (k_pages.at[at].set(k[:, 0].astype(k_pages.dtype)),
                    v_pages.at[at].set(v[:, 0].astype(v_pages.dtype)))
        return (k_pages.at[blk, off].set(k.astype(k_pages.dtype)),
                v_pages.at[blk, off].set(v.astype(v_pages.dtype)))
    from .kv_codec import codec
    encode, _ = codec(kv_dtype)
    k_pages, v_pages, k_scales, v_scales = kv_layer
    qk, sk = encode(k)          # [T, Hkv, Dh] narrow, [T, Hkv] f32
    qv, sv = encode(v)
    return (k_pages.at[blk, off].set(qk), v_pages.at[blk, off].set(qv),
            k_scales.at[blk, off].set(sk), v_scales.at[blk, off].set(sv))


@jax.named_scope(_names.SCOPE_ATTENTION)
def _ragged_attention_block(lp_attn, h, kv_layer, blk, off, block_tables,
                            seq_slots, positions, cos, sin, *, cfg, block_size,
                            rotary=True, rotary_dim=None,
                            use_kernel=True, kv_dtype=None,
                            row_positions=None, after_scatter=None,
                            window=None, qk_norm=None, out_gate=None):
    """Shared attention sub-block: qkv → rotary → cache scatter → paged
    attention → output projection.  Returns (attn_out [T, D], new kv_layer).
    ``row_positions`` (default: ``positions``) are the positions inside the
    block-table row that the paged attention masks by, where they are not
    the positions the rotary turns by; ``after_scatter(kv_layer)`` may add
    to the layer's cache between the scatter and the attention (a
    window-plus-summary cache: ``evabyte_ragged_step``).
    kv_layer: the layer's entry of the cache (``ragged.BlockedKVCache``),
    ``(k_pages, v_pages)``, each [num_blocks, bs, Hkv, Dh] and a donated
    buffer of its own: the scatter updates it in place and the paged
    attention reads that buffer, so no step ever copies a layer's pages.
    With ``kv_dtype`` set the entry is ``(k_pages, v_pages, k_scales,
    v_scales)`` (narrow pages, scales [num_blocks, bs, Hkv] f32): K/V rows
    are encoded once on the scatter write and dequantized on read inside
    the paged attention (``kv_codec.py``).  ``rotary_dim`` < head_dim →
    partial rotary (phi family); ``rotary`` may be the layer's own turn ``x
    [T, heads, Dh] -> x`` (interleaved pairs: ``cohere2_moe_ragged_step``).
    ``window`` is the LAYER's sliding window (0: none; default: the model's
    one ``cfg.sliding_window``).  A GATED attention
    (``qwen3_next_ragged_step``): ``qk_norm`` ``(q, k) -> (q, k)`` is a
    per-head norm of q and k BEFORE the rotary; with ``out_gate`` the rows of
    ``q_proj`` are a head's ``[query | gate]``, ``2 Dh`` long, and the
    attention's output is multiplied by ``sigmoid(gate)`` before ``o_proj``
    (both under ``ds.attn_gate``).  Their defaults leave every other model's
    block as it was."""
    dtype = jnp.dtype(cfg.dtype)
    H, Dh = cfg.num_attention_heads, cfg.head_dim
    q = _qkv(h, lp_attn["q_proj"], dtype)
    k = _qkv(h, lp_attn["k_proj"], dtype)
    v = _qkv(h, lp_attn["v_proj"], dtype)
    if out_gate:
        q, gate = q[..., :Dh], q[..., Dh:]
    if qk_norm is not None:
        with jax.named_scope(_names.SCOPE_ATTN_GATE):
            q, k = qk_norm(q, k)
    if callable(rotary):
        q, k = rotary(q), rotary(k)
    elif rotary:
        if rotary_dim and rotary_dim < Dh:
            rot = lambda x: jnp.concatenate(
                [_rotary(x[..., :rotary_dim], cos, sin, positions),
                 x[..., rotary_dim:]], axis=-1)
            q, k = rot(q), rot(k)
        else:
            q = _rotary(q, cos, sin, positions)
            k = _rotary(k, cos, sin, positions)
    kv_layer = _kv_scatter(kv_layer, k, v, blk, off, kv_dtype)
    if after_scatter is not None:
        kv_layer = after_scatter(kv_layer)
    k_cache, v_cache = kv_layer[:2]
    kv_scales = kv_layer[2:] or None
    out = _paged_attention(q, k_cache, v_cache, block_tables, seq_slots,
                           positions if row_positions is None
                           else row_positions, block_size,
                           window=getattr(cfg, "sliding_window", 0)
                           if window is None else window,
                           use_kernel=use_kernel, kv_scales=kv_scales)
    if out_gate:
        with jax.named_scope(_names.SCOPE_ATTN_GATE):
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32)) \
                .astype(out.dtype)
    o = out.reshape(out.shape[0], H * Dh)
    o = jnp.einsum("tf,fd->td", o, lp_attn["o_proj"]["kernel"].astype(dtype))
    if "bias" in lp_attn["o_proj"]:
        o = o + lp_attn["o_proj"]["bias"].astype(dtype)
    return o, kv_layer


def _swiglu(x, h2, mlp, dtype, scope=_names.SCOPE_MLP):
    """``x + SwiGLU(h2)``, under ``scope``."""
    with jax.named_scope(scope):
        gate = h2 @ mlp["gate_proj"]["kernel"].astype(dtype)
        up = h2 @ mlp["up_proj"]["kernel"].astype(dtype)
        return x + (jax.nn.silu(gate) * up) @ mlp["down_proj"][
            "kernel"].astype(dtype)


@_ragged_program("llama")
def llama_ragged_step(params, kv_data, token_ids, positions, seq_slots,
                      block_tables, last_token_idx, *, cfg, block_size,
                      use_kernel=True, kv_dtype=None):
    """One ragged engine iteration for the Llama family.

    Args:
      params: LlamaModel param tree (``models/llama.py`` naming).
      kv_data: the paged cache (donated): one ``(k_pages, v_pages)`` entry
        a layer, each [num_blocks, bs, Hkv, Dh] and a buffer of its own
        (``ragged.BlockedKVCache.layers``; with ``kv_dtype``, the two scale
        arrays beside them).  Every leaf comes back aliased to its input.
      token_ids/positions/seq_slots: [T] flat batch (padding: slot 0 = the
        reserved garbage block row, position 0).
      block_tables: [max_seqs, maxb] int32.
      last_token_idx: [max_seqs] int32 — buffer index of each slot's last
        scheduled token (logits gather; 0 for idle slots).

    Returns (logits [max_seqs, V] fp32, new kv_data).
    """
    dtype = jnp.dtype(cfg.dtype)
    H, Hkv, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    eps = cfg.rms_norm_eps
    cos, sin = _rope_freqs(Dh, cfg.max_position_embeddings, cfg.rope_theta,
                           cfg.rope_scaling)
    cos = jnp.asarray(cos, jnp.float32)
    sin = jnp.asarray(sin, jnp.float32)

    with jax.named_scope(_names.SCOPE_EMBED):
        x = params["embed_tokens"]["embedding"][token_ids].astype(dtype)
    blk = block_tables[seq_slots, positions // block_size]   # [T]
    off = positions % block_size

    kv_data = list(kv_data)
    for l in range(cfg.num_hidden_layers):
        lp = params[f"layers_{l}"]
        h = _rmsnorm(x, lp["input_layernorm"]["weight"], eps)
        # scatter this batch's K/V into the paged cache (linear_blocked_kv_
        # rotary analog), then attend against the updated pages
        attn_out, kv_data[l] = _ragged_attention_block(
            lp["self_attn"], h, kv_data[l], blk, off, block_tables,
            seq_slots, positions, cos, sin, cfg=cfg, block_size=block_size,
            use_kernel=use_kernel, kv_dtype=kv_dtype)
        x = x + attn_out
        h2 = _rmsnorm(x, lp["post_attention_layernorm"]["weight"], eps)
        x = _swiglu(x, h2, lp["mlp"], dtype)

    return _lm_head(params, x, last_token_idx, cfg), tuple(kv_data)


@jax.named_scope(_names.SCOPE_LM_HEAD)
def _lm_head(params, x, last_token_idx, cfg):
    """logits_gather analog: only each slot's last token reaches the head."""
    eps = cfg.rms_norm_eps
    x = _rmsnorm(x, params["norm"]["weight"], eps)
    xl = x[last_token_idx].astype(jnp.float32)               # [max_seqs, D]
    if cfg.tie_word_embeddings:
        return xl @ params["embed_tokens"]["embedding"].T.astype(jnp.float32)
    return xl @ params["lm_head"]["kernel"].astype(jnp.float32)


@_ragged_program("mixtral")
def mixtral_ragged_step(params, kv_data, token_ids, positions, seq_slots,
                        block_tables, last_token_idx, *, cfg, block_size,
                        use_kernel=True, kv_dtype=None):
    """One ragged engine iteration for Mixtral (reference
    ``inference/v2/model_implementations/mixtral/``): Llama attention skeleton
    with the MLP replaced by the exact top-k sparse MoE (``moe_apply`` —
    grouped ``ragged_dot`` over tokens sorted by expert, no token dropping)."""
    from ...models.mixtral import moe_apply

    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.rms_norm_eps
    live = seq_slots != 0          # a dead row of the buffer is not routed
    cos, sin = _rope_freqs(cfg.head_dim, cfg.max_position_embeddings,
                           cfg.rope_theta, cfg.rope_scaling)
    cos = jnp.asarray(cos, jnp.float32)
    sin = jnp.asarray(sin, jnp.float32)

    with jax.named_scope(_names.SCOPE_EMBED):
        x = params["embed_tokens"]["embedding"][token_ids].astype(dtype)
    blk = block_tables[seq_slots, positions // block_size]
    off = positions % block_size

    kv_data = list(kv_data)
    for l in range(cfg.num_hidden_layers):
        lp = params[f"layers_{l}"]
        h = _rmsnorm(x, lp["input_layernorm"]["weight"], eps)
        attn_out, kv_data[l] = _ragged_attention_block(
            lp["self_attn"], h, kv_data[l], blk, off, block_tables,
            seq_slots, positions, cos, sin, cfg=cfg, block_size=block_size,
            use_kernel=use_kernel, kv_dtype=kv_dtype)
        x = x + attn_out
        h2 = _rmsnorm(x, lp["post_attention_layernorm"]["weight"], eps)
        with jax.named_scope(_names.SCOPE_MLP):
            moe = lp["moe"]
            router_logits = (h2.astype(jnp.float32)
                             @ moe["gate"]["kernel"].astype(jnp.float32))
            moe_out = moe_apply(
                h2, router_logits, moe["w1"].astype(dtype),
                moe["w2"].astype(dtype), moe["w3"].astype(dtype),
                cfg.num_experts_per_tok,
                norm_topk=getattr(cfg, "norm_topk_prob", True), live=live)
            if "shared_gate_proj" in moe:  # qwen2-moe dense shared expert
                g = h2 @ moe["shared_gate_proj"]["kernel"].astype(dtype)
                u = h2 @ moe["shared_up_proj"]["kernel"].astype(dtype)
                sh = (jax.nn.silu(g) * u) @ moe["shared_down_proj"][
                    "kernel"].astype(dtype)
                mix = jax.nn.sigmoid(
                    h2.astype(jnp.float32)
                    @ moe["shared_expert_gate"]["kernel"].astype(jnp.float32))
                moe_out = moe_out + (mix * sh.astype(jnp.float32)).astype(
                    moe_out.dtype)
        x = x + moe_out

    return _lm_head(params, x, last_token_idx, cfg), tuple(kv_data)


@jax.named_scope(_names.SCOPE_NORM)
def _layernorm(x, p, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * p["scale"]
            + p["bias"]).astype(x.dtype)


@_ragged_program("falcon")
def falcon_ragged_step(params, kv_data, token_ids, positions, seq_slots,
                       block_tables, last_token_idx, *, cfg, block_size,
                       use_kernel=True, kv_dtype=None):
    """One ragged engine iteration for Falcon (reference
    ``inference/v2/model_implementations/falcon/``): parallel-block layout —
    attention and the GELU MLP read the same layernormed input and add into
    the residual together."""
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.layer_norm_epsilon
    cos, sin = _rope_freqs(cfg.head_dim, cfg.max_position_embeddings,
                           cfg.rope_theta)
    cos = jnp.asarray(cos, jnp.float32)
    sin = jnp.asarray(sin, jnp.float32)

    with jax.named_scope(_names.SCOPE_EMBED):
        x = params["word_embeddings"]["embedding"][token_ids].astype(dtype)
    blk = block_tables[seq_slots, positions // block_size]
    off = positions % block_size
    acfg = _attn_cfg_view(cfg)

    kv_data = list(kv_data)
    for l in range(cfg.num_hidden_layers):
        lp = params[f"h_{l}"]
        if cfg.new_decoder_architecture:
            h_attn = _layernorm(x, lp["ln_attn"], eps)
            h_mlp = _layernorm(x, lp["ln_mlp"], eps)
        else:
            h_attn = h_mlp = _layernorm(x, lp["input_layernorm"], eps)
        attn_params = {"q_proj": lp["q_proj"], "k_proj": lp["k_proj"],
                       "v_proj": lp["v_proj"], "o_proj": lp["dense"]}
        attn_out, kv_data[l] = _ragged_attention_block(
            attn_params, h_attn, kv_data[l], blk, off, block_tables,
            seq_slots, positions, cos, sin, cfg=acfg, block_size=block_size,
            use_kernel=use_kernel, kv_dtype=kv_dtype)
        if not cfg.parallel_attn:
            x = x + attn_out
            h_mlp = _layernorm(x, lp["post_attention_layernorm"], eps)
        with jax.named_scope(_names.SCOPE_MLP):
            mlp = _lin(jax.nn.gelu(_lin(h_mlp, lp["dense_h_to_4h"], dtype)),
                       lp["dense_4h_to_h"], dtype)
        x = (x + attn_out + mlp) if cfg.parallel_attn else (x + mlp)

    x = _layernorm(x, params["ln_f"], eps)
    return _head_logits(params, x, last_token_idx,
                        embed_key="word_embeddings"), tuple(kv_data)


@_ragged_program("opt")
def opt_ragged_step(params, kv_data, token_ids, positions, seq_slots,
                    block_tables, last_token_idx, *, cfg, block_size,
                    use_kernel=True, kv_dtype=None):
    """One ragged engine iteration for OPT (reference
    ``inference/v2/model_implementations/opt/``): learned positions (+2
    offset), pre-LN blocks, ReLU MLP, no rotary."""
    from ...models.opt import OPT_POSITION_OFFSET

    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.layer_norm_eps

    with jax.named_scope(_names.SCOPE_EMBED):
        x = (params["embed_tokens"]["embedding"][token_ids]
             + params["embed_positions"]["embedding"][
                 positions + OPT_POSITION_OFFSET]).astype(dtype)
    blk = block_tables[seq_slots, positions // block_size]
    off = positions % block_size
    acfg = _attn_cfg_view(cfg)

    kv_data = list(kv_data)
    for l in range(cfg.num_hidden_layers):
        lp = params[f"layers_{l}"]
        h = _layernorm(x, lp["self_attn_layer_norm"], eps) \
            if cfg.do_layer_norm_before else x
        attn_params = {"q_proj": lp["q_proj"], "k_proj": lp["k_proj"],
                       "v_proj": lp["v_proj"], "o_proj": lp["out_proj"]}
        attn_out, kv_data[l] = _ragged_attention_block(
            attn_params, h, kv_data[l], blk, off, block_tables,
            seq_slots, positions, None, None, cfg=acfg, block_size=block_size,
            rotary=False, use_kernel=use_kernel, kv_dtype=kv_dtype)
        x = x + attn_out
        if not cfg.do_layer_norm_before:
            x = _layernorm(x, lp["self_attn_layer_norm"], eps)
        h = _layernorm(x, lp["final_layer_norm"], eps) \
            if cfg.do_layer_norm_before else x
        with jax.named_scope(_names.SCOPE_MLP):
            x = x + _lin(jax.nn.relu(_lin(h, lp["fc1"], dtype)), lp["fc2"],
                         dtype)
        if not cfg.do_layer_norm_before:
            x = _layernorm(x, lp["final_layer_norm"], eps)

    if cfg.do_layer_norm_before:
        x = _layernorm(x, params["final_layer_norm"], eps)
    return _head_logits(params, x, last_token_idx), tuple(kv_data)


@_ragged_program("phi")
def phi_ragged_step(params, kv_data, token_ids, positions, seq_slots,
                    block_tables, last_token_idx, *, cfg, block_size,
                    use_kernel=True, kv_dtype=None):
    """One ragged engine iteration for Phi-2 (reference
    ``inference/v2/model_implementations/phi/``): parallel block, partial
    rotary, LayerNorm, biased linears (incl. lm_head)."""
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.layer_norm_eps
    rd = cfg.rotary_dim
    cos, sin = _rope_freqs(rd, cfg.max_position_embeddings, cfg.rope_theta)
    cos = jnp.asarray(cos, jnp.float32)
    sin = jnp.asarray(sin, jnp.float32)

    with jax.named_scope(_names.SCOPE_EMBED):
        x = params["embed_tokens"]["embedding"][token_ids].astype(dtype)
    blk = block_tables[seq_slots, positions // block_size]
    off = positions % block_size
    acfg = _attn_cfg_view(cfg)

    kv_data = list(kv_data)
    for l in range(cfg.num_hidden_layers):
        lp = params[f"layers_{l}"]
        h = _layernorm(x, lp["input_layernorm"], eps)
        attn_params = {"q_proj": lp["q_proj"], "k_proj": lp["k_proj"],
                       "v_proj": lp["v_proj"], "o_proj": lp["dense"]}
        attn_out, kv_data[l] = _ragged_attention_block(
            attn_params, h, kv_data[l], blk, off, block_tables,
            seq_slots, positions, cos, sin, cfg=acfg, block_size=block_size,
            rotary_dim=rd,
            use_kernel=use_kernel, kv_dtype=kv_dtype)
        with jax.named_scope(_names.SCOPE_MLP):
            mlp = _lin(jax.nn.gelu(_lin(h, lp["fc1"], dtype)), lp["fc2"],
                       dtype)
        x = x + attn_out + mlp

    x = _layernorm(x, params["final_layernorm"], eps)
    return _head_logits(params, x, last_token_idx), tuple(kv_data)


def _eva_summaries(kv_layer, phi, mu, block_tables, seq_slots, positions,
                    row_pos, *, chunk, per_window, block_size):
    """Pool every chunk this step completes and write its summary: the rows
    whose position ends a chunk, at most ``T // chunk`` + one a sequence.
    The chunk's K/V are read back from the cache (its first tokens may have
    arrived in earlier steps); ``k~ = sum_m a_m k_m + mu``, ``v~ = sum_m a_m
    v_m`` with ``a = softmax_m(k_m . phi)`` in float32.  The summary goes to
    the sequence's summary block in the making (the last columns of its
    block-table row), where no query sees it until the window closes."""
    with jax.named_scope(_names.SCOPE_EVA_SUMMARY):
        T = positions.shape[0]
        maxb = block_tables.shape[1]
        ends = (seq_slots != 0) & (positions % chunk == chunk - 1)
        n = T // chunk + block_tables.shape[0]
        rows = jnp.nonzero(ends, size=n, fill_value=0)[0]
        slot = jnp.where(jnp.arange(n) < jnp.sum(ends), seq_slots[rows], 0)
        first = row_pos[rows] - (chunk - 1)        # the chunk's first row
        blk = block_tables[slot, first // block_size][:, None]
        off = (first % block_size)[:, None] + jnp.arange(chunk)[None, :]
        # [n, C, H, Dh]: each completed chunk as a sequence of one chunk
        k_pages, v_pages = kv_layer
        ks, vs = chunk_summaries(k_pages[blk, off], v_pages[blk, off], phi,
                                 mu, chunk)
        ks, vs = ks[:, 0], vs[:, 0]
        j = (positions[rows] // chunk) % per_window   # its place in the window
        sblk = block_tables[slot, maxb - per_window // block_size
                            + j // block_size]
        return (k_pages.at[sblk, j % block_size].set(ks.astype(k_pages.dtype)),
                v_pages.at[sblk, j % block_size].set(vs.astype(v_pages.dtype)))


@_ragged_program("evabyte")
def evabyte_ragged_step(params, kv_data, token_ids, positions, seq_slots,
                        block_tables, last_token_idx, *, cfg, block_size,
                        use_kernel=True, kv_dtype=None):
    """One ragged engine iteration for EvaByte (``models/evabyte.py`` has the
    layer's equations).  A sequence's block-table row is ``[summary blocks
    of its closed windows | blocks of its current window | ... | the summary
    block in the making]`` (``ragged.py``): the rotary turns by the real
    position, the cache is addressed and the causal mask taken by the
    position inside that row, so plain paged attention over the row IS the
    one softmax over the window's exact keys and the earlier windows'
    summaries.  The summaries of the chunks this step completes are made and
    written before the attention runs.  No row of a step lies beyond its
    sequence's window end (the batch builder's and the burst's bound).  The
    residual stream is float32 (``fp32_skip_add``); all ``num_pred_heads``
    heads are computed, head 0's logits (the next byte) are returned."""
    if kv_dtype is not None:
        raise NotImplementedError("kv_cache_dtype with EvaByte")
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.rms_norm_eps
    window, chunk = cfg.window_size, cfg.chunk_size
    per_window = window // chunk
    cos, sin = _rope_freqs(cfg.head_dim, cfg.max_position_embeddings,
                           cfg.rope_theta)
    cos = jnp.asarray(cos, jnp.float32)
    sin = jnp.asarray(sin, jnp.float32)
    unit = 1.0 if cfg.norm_add_unit_offset else 0.0
    norm = lambda x, p: _rmsnorm(               # the offset g is a column
        x, unit + p["weight"][:, 0].astype(jnp.float32), eps).astype(dtype)

    with jax.named_scope(_names.SCOPE_EMBED):
        x = params["embed_tokens"]["embedding"][token_ids].astype(dtype) \
            .astype(jnp.float32)
    row_pos = window_row_positions(positions, window, chunk)
    blk = block_tables[seq_slots, row_pos // block_size]
    off = row_pos % block_size

    kv_data = list(kv_data)
    for l in range(cfg.num_hidden_layers):
        lp = params[f"layers_{l}"]
        attn = lp["self_attn"]
        summarise = functools.partial(
            _eva_summaries, phi=attn["eva_phi"], mu=attn["eva_mu"],
            block_tables=block_tables, seq_slots=seq_slots,
            positions=positions, row_pos=row_pos, chunk=chunk,
            per_window=per_window, block_size=block_size)
        attn_out, kv_data[l] = _ragged_attention_block(
            attn, norm(x, lp["input_layernorm"]), kv_data[l], blk,
            off, block_tables, seq_slots, positions, cos, sin, cfg=cfg,
            block_size=block_size, use_kernel=use_kernel,
            row_positions=row_pos, after_scatter=summarise)
        x = x + attn_out.astype(jnp.float32)
        x = _swiglu(x, norm(x, lp["post_attention_layernorm"]), lp["mlp"],
                    dtype)

    with jax.named_scope(_names.SCOPE_LM_HEAD):
        xl = norm(x, params["norm"])[last_token_idx].astype(jnp.float32)
        heads = xl @ params["lm_head"]["kernel"].astype(jnp.float32)
    return heads[:, :cfg.vocab_size], tuple(kv_data)


@_ragged_program("cohere2_moe", step_counts=(_names.COUNT_EXPERT_COPIES,
                                              _names.COUNT_EXPERT_ACTIVE))
def cohere2_moe_ragged_step(params, kv_data, token_ids, positions, seq_slots,
                            block_tables, last_token_idx, *, cfg, block_size,
                            use_kernel=True, kv_dtype=None):
    """One ragged engine iteration for Cohere2-MoE (``models/cohere2_moe.py``
    has the layer's equations): ONE LayerNorm feeds the attention and the
    expert block side by side; a sliding layer turns q and k by rotary on
    interleaved pairs and reads its window, a full layer has no positions and
    reads everything (``cfg.layer_windows``: the window is the layer's).  The
    expert block routes the LIVE rows over the router's full width and
    computes the held experts' part (``moe/held_experts.py``) beside the
    averaged shared experts.  Every layer keeps every token in ONE block
    table: a window layer's pages past its window are held and not read.

    The held experts' grouped matmuls are the Pallas ``ds_grouped_matmul``
    where the step's kernels are on (``use_kernel`` and the gate of every
    kernel dispatch site): docs/kernels.md has the v5e readings at this
    step's shapes that put it ahead of ``lax.ragged_dot`` there.

    Returns ``(logits, new kv_data, counts)``; ``counts`` (int32, the
    program's ``step_counts``): the (row, expert) copies that landed on a
    held expert and the held experts with at least one, summed over the
    layers."""
    from ...models.cohere2_moe import layer_norm, moe_layer, rotary_pairs
    from ...ops._use_kernels import use_pallas_kernels

    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.layer_norm_eps
    live = seq_slots != 0
    gmm_kernel = use_kernel and use_pallas_kernels()
    turn = lambda x: rotary_pairs(x, positions, cfg.rope_theta)

    with jax.named_scope(_names.SCOPE_EMBED):
        x = params["embed_tokens"]["weight"][token_ids].astype(dtype)
    blk = block_tables[seq_slots, positions // block_size]
    off = positions % block_size

    kv_data = list(kv_data)
    counts = []
    for l, window in enumerate(cfg.layer_windows):
        lp = params[f"layers_{l}"]
        with jax.named_scope(_names.SCOPE_NORM):
            h = layer_norm(x, lp["input_layernorm"]["weight"], eps)
        attn_out, kv_data[l] = _ragged_attention_block(
            lp["self_attn"], h, kv_data[l], blk, off, block_tables,
            seq_slots, positions, None, None, cfg=cfg, block_size=block_size,
            rotary=turn if window else False, window=window,
            use_kernel=use_kernel, kv_dtype=kv_dtype)
        with jax.named_scope(_names.SCOPE_MLP):
            moe = lp["moe"]
            stack = lambda name: moe[name].astype(dtype)
            with jax.named_scope(_names.SCOPE_MOE_ROUTER):
                router_logits = (h.astype(jnp.float32)
                                 @ moe["gate"]["kernel"].astype(jnp.float32))
            moe_out, landed = moe_layer(
                h, router_logits, stack("w1"), stack("w2"), stack("w3"),
                stack("shared_w1"), stack("shared_w2"), stack("shared_w3"),
                cfg, live=live, kernel=gmm_kernel)
        counts.append(landed)
        x = x + attn_out + moe_out

    with jax.named_scope(_names.SCOPE_LM_HEAD):
        # only each slot's last token reaches the head; the tied embedding is
        # read in the type it is held in, the products summed in float32
        xl = layer_norm(x[last_token_idx], params["norm"]["weight"], eps)
        logits = jnp.einsum("td,vd->tv", xl,
                            params["embed_tokens"]["weight"].astype(dtype),
                            preferred_element_type=jnp.float32)
        if cfg.logit_scale != 1:
            logits = logits * cfg.logit_scale
    counts = jnp.stack(counts)                        # [layers, held]
    return logits, tuple(kv_data), jnp.stack(
        [jnp.sum(counts), jnp.sum(counts > 0)])


#: rows of a stretch of the buffer that ``_mla_block`` takes through the
#: absorbed form together, or skips where none of them takes it
_ABSORBED_STRETCH_ROWS = 256


def _latent_attention(q, pages, block_tables, seq_slots, positions,
                      block_size, *, rank, scale, use_kernel=True, window=0):
    """Absorbed multi-head latent attention over the paged latent cache: q
    ``[T, H, row]`` (``(q_lat [rank] ; q_r ; zeros)`` a head, as long as the
    cache's row), pages ``[num_blocks, bs, row]`` -> ``[T, H, rank]``, the
    softmax-weighted sum of the first ``rank`` values of the rows at
    positions ``<=`` the query's (the last ``window`` of them where the layer
    has a window).  On a TPU the Pallas ``ds_paged_latent``
    (``ops/pallas/paged_attention.paged_latent_attention``), elsewhere and
    for a shape it does not take an XLA gather of each row's block run."""
    from ...ops._use_kernels import use_pallas_kernels
    from ...ops.pallas.paged_attention import (latent_tiled,
                                               paged_latent_attention)
    if use_kernel and use_pallas_kernels() and latent_tiled(
            q.shape[1], pages.dtype):
        return paged_latent_attention(q, pages, block_tables, seq_slots,
                                      positions, rank=rank, scale=scale,
                                      window=window)
    tables_t = block_tables[seq_slots]
    T, ctx = q.shape[0], tables_t.shape[1] * block_size
    rows = pages[tables_t].reshape(T, ctx, -1).astype(jnp.float32)
    scores = jnp.einsum("thl,tcl->thc", q.astype(jnp.float32), rows) * scale
    mask = jnp.arange(ctx)[None, None, :] <= positions[:, None, None]
    if window:
        mask &= jnp.arange(ctx)[None, None, :] > \
            positions[:, None, None] - window
    probs = jax.nn.softmax(
        jnp.where(mask, scores, jnp.finfo(jnp.float32).min), axis=-1)
    return jnp.einsum("thc,tcr->thr", probs, rows[..., :rank]).astype(q.dtype)


@jax.named_scope(_names.SCOPE_DIFF_ATTN)
def _differential(o, lam):
    """Grouped differential attention's subtraction on the heads' OUTPUTS: ``o
    [n, H, w]`` holds, K/V group by K/V group, the group's signal heads and
    then its ONE noise head; ``lam [n, S]`` (float32) one factor a signal
    head.  Returns ``[n, S, w]``: ``o_s - lam_s o_noise(group of s)``.  It is
    linear along ``w``, so it may be taken before an up-projection that the
    group's heads share."""
    n, H, w = o.shape
    per_group = H // (H - lam.shape[1])        # heads a group, the noise head too
    o = o.reshape(n, -1, per_group, w).astype(jnp.float32)
    out = o[:, :, :-1] - lam.reshape(n, -1, per_group - 1, 1) * o[:, :, -1:]
    return out.reshape(n, lam.shape[1], w)


@jax.named_scope(_names.SCOPE_ATTENTION)
def _mla_block(attn, h, kv_layer, blk, off, block_tables, seq_slots,
               positions, *, cfg, block_size, use_kernel, q_scale=1.0,
               kv_scale=1.0, slot_rows=False, window=0, lam=None,
               out_gate=None):
    """Multi-head latent attention of one cache entry over the ragged buffer
    (``models/pangu_ultra_moe.py`` has the equations): the latent row ``(c ;
    k_r)`` of each token goes into the entry's one cache buffer, and a row
    reads the cache in one of TWO forms, picked by the length of its run
    (``paged_attention.latent_row_forms``, the batch builder's choice too).
    ABSORBED (a decode row, a short run, every row of a burst): the head's
    query is taken into the latent space (``q_n W_uk^T``), attends the rows
    themselves (``ds_paged_latent``), and the latent output comes back
    through ``W_uv``.  EXPANDED (a prefill chunk's rows): the per-head keys
    and values are made from the latent pages inside ``ds_paged_mla_chunk``,
    once a run, and the query and the output stay as they are.  Rows of the
    other form reach each kernel dead (slot 0) and come back zero.  No
    per-head key or value is kept, in the cache or in HBM.  ``q_scale`` /
    ``kv_scale``: ``mla_down``'s.  ``slot_rows``: the buffer has ONE row a
    slot (a burst's): every run is one row, and the program holds the
    absorbed kernel alone.  Returns (attn_out [T, D], new kv_layer).

    What grouped differential attention over a latent cache adds
    (``models/motif.py``), each by an argument whose default leaves the
    block as it was.  ``window``: the layer's sliding window, in both forms.
    ``k_b_proj`` / ``v_b_proj`` of FEWER heads than the query's: K/V groups,
    each read by ``H / G`` adjacent query heads.  ``lam [T, S]``: a group's
    last head is its noise head, whose output is subtracted from the group's
    signal heads' (:func:`_differential`): in the absorbed form on the LATENT
    outputs, before the up-projection through the group's one ``W_uv``, so a
    noise head's up-projection is never computed.  ``out_gate [T, S * dv]``
    multiplies the heads' outputs before ``o_proj``."""
    from ...models.pangu_ultra_moe import mla_down
    from ...ops._use_kernels import use_pallas_kernels
    from ...ops.pallas.paged_attention import (
        latent_min_rows, latent_row_forms, paged_mla_chunk_attention)
    dtype = jnp.dtype(cfg.dtype)
    rank = cfg.kv_lora_rank
    pages, = kv_layer
    with jax.named_scope(_names.SCOPE_MLA_DOWN):
        q_n, q_r, latent = mla_down(h, attn, positions, cfg, q_scale,
                                    kv_scale)
    spare = pages.shape[-1] - latent.shape[-1]
    with jax.named_scope(_names.SCOPE_KV_CACHE):
        pages = pages.at[blk, off].set(
            jnp.pad(latent, ((0, 0), (0, spare))).astype(pages.dtype))
    w_uk = attn["k_b_proj"]["kernel"].astype(dtype)
    w_uv = attn["v_b_proj"]["kernel"].astype(dtype)
    T = h.shape[0]
    groups = w_uk.shape[1]
    # one product a head where every head has its own W_uk / W_uv: the
    # grouped product at one head a group is the same mathematics, and
    # compiles to one more relayout ([T, H, 1, dv] -> [T, H, dv]) in a burst
    grouped = groups != cfg.num_attention_heads
    by_group = lambda a: a.reshape(a.shape[0], groups, -1, a.shape[-1])
    heads = lambda a: a.reshape(a.shape[0], -1, a.shape[-1])
    # None: no row of a buffer this short, or of this shape, is expanded
    min_rows = latent_min_rows(cfg, pages.shape[-1], pages.dtype, T) \
        if use_kernel and use_pallas_kernels() and not slot_rows else None

    def absorbed(q_n, q_r, slots, positions, *lam):
        """The absorbed form of the rows given (the buffer's, or a stretch
        of them): ``[n, heads out, v_head_dim]``, zero where ``slots`` is
        0."""
        with jax.named_scope(_names.SCOPE_MLA_ABSORB):
            q_lat = heads(jnp.einsum("tgqn,cgn->tgqc", by_group(q_n), w_uk)) \
                if grouped else jnp.einsum("thn,chn->thc", q_n, w_uk)
            q = jnp.pad(jnp.concatenate([q_lat, q_r], axis=-1),
                        ((0, 0), (0, 0), (0, spare)))
        o_lat = _latent_attention(q, pages, block_tables, slots, positions,
                                  block_size, rank=rank,
                                  scale=cfg.softmax_scale,
                                  use_kernel=use_kernel, window=window)
        if lam:
            o_lat = _differential(o_lat, *lam).astype(dtype)
        with jax.named_scope(_names.SCOPE_MLA_ABSORB):
            if grouped:
                return heads(jnp.einsum("tgqc,cgv->tgqv", by_group(o_lat),
                                        w_uv))
            return jnp.einsum("thc,chv->thv", o_lat, w_uv)

    lam = () if lam is None else (lam, )

    if min_rows is None:
        o = absorbed(q_n, q_r, seq_slots, positions, *lam)
    else:
        # the absorbed form's three products are owed by ITS rows alone: a
        # stretch of the buffer that holds none of them (a chunk's rows, dead
        # rows) skips them
        slots = jnp.where(latent_row_forms(jnp, seq_slots, positions,
                                           min_rows), 0, seq_slots)
        n = T // _ABSORBED_STRETCH_ROWS if T % _ABSORBED_STRETCH_ROWS == 0 \
            else 1
        stretches = lambda a: a.reshape((n, T // n) + a.shape[1:])
        shape = (T // n, lam[0].shape[1] if lam else q_n.shape[1],
                 w_uv.shape[-1])
        o = jax.lax.map(
            lambda rows: jax.lax.cond(
                jnp.any(rows[2] != 0), lambda: absorbed(*rows),
                lambda: jnp.zeros(shape, dtype)),
            tuple(map(stretches, (q_n, q_r, slots, positions) + lam))) \
            .reshape((T, ) + shape[1:])
        q = jnp.pad(jnp.concatenate([q_n, q_r], axis=-1),
                    ((0, 0), (0, 0), (0, spare)))
        expanded = paged_mla_chunk_attention(
            q, pages, w_uk, w_uv, block_tables, seq_slots, positions,
            rank=rank, scale=cfg.softmax_scale, min_rows=min_rows,
            window=window)
        if lam:
            expanded = _differential(expanded, *lam).astype(dtype)
        o = o + expanded
    o = o.reshape(o.shape[0], -1)
    if out_gate is not None:
        with jax.named_scope(_names.SCOPE_DIFF_ATTN):
            o = o * out_gate
    o = o @ attn["o_proj"]["kernel"].astype(dtype)
    return o, (pages, )


@_ragged_program("pangu_ultra_moe", step_counts=(
    _names.COUNT_EXPERT_COPIES, _names.COUNT_EXPERT_ACTIVE), slot_rows=True)
def pangu_ultra_moe_ragged_step(params, kv_data, token_ids, positions,
                                seq_slots, block_tables, last_token_idx, *,
                                cfg, block_size, use_kernel=True,
                                kv_dtype=None, slot_rows=False):
    """One ragged engine iteration for openPangu-Ultra-MoE
    (``models/pangu_ultra_moe.py`` has the layer's equations): sandwich
    norms (each branch normed going in AND coming out), multi-head latent
    attention over a LATENT cache (``kv_data``: one ``(pages, )`` entry a
    layer, ``[num_blocks, bs, row]``, donated and scattered in place), a
    dense SwiGLU in the first ``first_k_dense_replace`` layers and in the
    rest the held experts' part of the scaled routed sum
    (``moe/held_experts.py``) beside the shared expert.  The grouped matmuls
    are the Pallas ``ds_grouped_matmul`` where the step's kernels are on, as
    ``cohere2_moe_ragged_step``'s.  ``slot_rows``: ``_mla_block``'s.

    Returns ``(logits, new kv_data, counts)``; ``counts`` as
    ``cohere2_moe_ragged_step``'s, over the routed layers."""
    if kv_dtype is not None:
        raise NotImplementedError("kv_cache_dtype with a latent cache")
    from ...models.pangu_ultra_moe import moe_layer
    from ...ops._use_kernels import use_pallas_kernels

    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.rms_norm_eps
    live = seq_slots != 0
    gmm_kernel = use_kernel and use_pallas_kernels()

    with jax.named_scope(_names.SCOPE_EMBED):
        x = params["embed_tokens"]["embedding"][token_ids].astype(dtype)
    blk = block_tables[seq_slots, positions // block_size]
    off = positions % block_size

    kv_data = list(kv_data)
    counts = []
    for l in range(cfg.num_hidden_layers):
        lp = params[f"layers_{l}"]
        norm = lambda y, name: _rmsnorm(y, lp[name]["weight"], eps)
        attn_out, kv_data[l] = _mla_block(
            lp["self_attn"], norm(x, "input_layernorm"), kv_data[l], blk,
            off, block_tables, seq_slots, positions, cfg=cfg,
            block_size=block_size, use_kernel=use_kernel, slot_rows=slot_rows)
        x = x + norm(attn_out, "post_attention_layernorm")
        h = norm(x, "pre_mlp_layernorm")
        if cfg.routed(l):
            with jax.named_scope(_names.SCOPE_MLP):
                with jax.named_scope(_names.SCOPE_MOE_ROUTER):
                    router_logits = h.astype(jnp.float32) @ lp["moe"][
                        "gate"]["kernel"].astype(jnp.float32)
                m, landed = moe_layer(h, router_logits, lp["moe"], cfg,
                                      live=live, kernel=gmm_kernel)
            counts.append(landed)
        else:               # the branch alone: its norm comes before the add
            m = _swiglu(0, h, lp["mlp"], dtype)
        x = x + norm(m, "post_mlp_layernorm")

    with jax.named_scope(_names.SCOPE_LM_HEAD):
        xl = _rmsnorm(x[last_token_idx], params["norm"]["weight"], eps)
        logits = jnp.einsum("td,dv->tv", xl,
                            params["lm_head"]["kernel"].astype(dtype),
                            preferred_element_type=jnp.float32)
    counts = jnp.stack(counts)                        # [routed layers, held]
    return logits, tuple(kv_data), jnp.stack(
        [jnp.sum(counts), jnp.sum(counts > 0)])

@_ragged_program("longcat_flash", step_counts=(
    _names.COUNT_EXPERT_COPIES, _names.COUNT_EXPERT_ACTIVE,
    _names.COUNT_ZERO_EXPERT_COPIES), slot_rows=True)
def longcat_flash_ragged_step(params, kv_data, token_ids, positions,
                              seq_slots, block_tables, last_token_idx, *,
                              cfg, block_size, use_kernel=True,
                              kv_dtype=None, slot_rows=False):
    """One ragged engine iteration for LongCat-Flash
    (``models/longcat_flash.py`` has the layer's equations): TWO latent
    attentions a layer, each with its own weights and its own cache entry
    (``kv_data[2 l]`` and ``kv_data[2 l + 1]``, ``_mla_block`` with the
    model's two scale factors), a dense SwiGLU after each, and the expert
    branch on a SHORTCUT: it reads the first attention's normed output and is
    added after the second feed-forward, so nothing between the two waits for
    it.  The branch is the held experts' part of the routed sum
    (``moe/held_experts.py``, the router's width counting the identity
    experts) plus the identity experts' weighted copy of its input.
    ``slot_rows``: ``_mla_block``'s.

    Returns ``(logits, new kv_data, counts)``; ``counts`` as
    ``cohere2_moe_ragged_step``'s and, third, the (live row, layer, chosen
    identity expert) triples."""
    if kv_dtype is not None:
        raise NotImplementedError("kv_cache_dtype with a latent cache")
    from ...models.longcat_flash import mla_weights, moe_branch
    from ...ops._use_kernels import use_pallas_kernels

    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.rms_norm_eps
    live = seq_slots != 0
    gmm_kernel = use_kernel and use_pallas_kernels()

    with jax.named_scope(_names.SCOPE_EMBED):
        x = params["embed_tokens"]["embedding"][token_ids].astype(dtype)
    blk = block_tables[seq_slots, positions // block_size]
    off = positions % block_size

    kv_data = list(kv_data)
    counts, zero_copies = [], []
    for l in range(cfg.num_layers):
        lp = params[f"layers_{l}"]
        norm = lambda y, name: _rmsnorm(y, lp[name]["weight"], eps)
        shortcut = None
        for i in (0, 1):
            entry = 2 * l + i
            attn_out, kv_data[entry] = _mla_block(
                mla_weights(lp[f"self_attn_{i}"], cfg),
                norm(x, f"input_layernorm_{i}"), kv_data[entry], blk, off,
                block_tables, seq_slots, positions, cfg=cfg,
                block_size=block_size, use_kernel=use_kernel,
                q_scale=cfg.q_scale, kv_scale=cfg.kv_scale,
                slot_rows=slot_rows)
            x = x + attn_out
            h = norm(x, f"post_attention_layernorm_{i}")
            if i == 0:
                with jax.named_scope(_names.SCOPE_MLP):
                    shortcut, landed, zero = moe_branch(
                        h, lp["moe"], cfg, live=live, kernel=gmm_kernel)
                counts.append(landed)
                zero_copies.append(zero)
            x = _swiglu(x, h, lp[f"mlp_{i}"], dtype, _names.SCOPE_DENSE_FFN)
        x = x + shortcut

    with jax.named_scope(_names.SCOPE_LM_HEAD):
        xl = _rmsnorm(x[last_token_idx], params["norm"]["weight"], eps)
        logits = jnp.einsum("td,dv->tv", xl,
                            params["lm_head"]["kernel"].astype(dtype),
                            preferred_element_type=jnp.float32)
    counts = jnp.stack(counts)                        # [layers, held]
    return logits, tuple(kv_data), jnp.stack(
        [jnp.sum(counts), jnp.sum(counts > 0), sum(zero_copies)])


# ----------------------------------------------------------------- Ouro
@_ragged_program("ouro", step_counts=(_names.COUNT_LOOP_ROW_PASSES,
                                      _names.COUNT_GATE_EXIT_PASSES_Q8))
def ouro_ragged_step(params, kv_data, token_ids, positions, seq_slots,
                     block_tables, last_token_idx, *, cfg, block_size,
                     use_kernel=True, kv_dtype=None):
    """One ragged engine iteration for a LOOPED model (``models/ouro.py`` has
    the equations): the stack of ``L`` sandwich-norm layers run ``T =
    total_ut_steps`` times over the rows, the SAME ``params[f"layers_{l}"]``
    in every pass, the final norm between two passes (the last pass's is
    ``_lm_head``'s), the exit gate on every pass's output but the last.

    The loop over the passes is ROLLED (``lax.fori_loop``; the program holds
    ``L`` layer bodies, not ``T x L``: a quarter of the compile time at ``T``
    4): ``kv_data`` is one ``(k_pages, v_pages)`` a LAYER, each ``[T x
    num_blocks, bs, Hkv, Dh]`` and the loop's carry, and pass ``t`` of layer
    ``l`` reads and writes cache entry ``t * L + l``: the pages ``[t *
    num_blocks, (t + 1) * num_blocks)`` of buffer ``l``, reached by adding
    ``t * num_blocks`` to the block table (``ragged.BlockedKVCache``,
    ``entries_a_buffer``).  A pass never reads another pass's keys.

    Returns ``(logits, new kv_data, counts)``: ``counts`` (int32 ``[2]``, the
    program's ``step_counts``) are the live rows x the passes they ran, and
    the sum over the live rows of the pass the exit distribution expects a
    row to leave after (``models/ouro.exit_distribution``), in 1/256ths: the
    distribution is computed and counted, not acted on."""
    if kv_dtype is not None:
        raise NotImplementedError("kv_cache_dtype with a looped model's cache")
    from ...models.ouro import exit_gate

    dtype = jnp.dtype(cfg.dtype)
    eps, Dh = cfg.rms_norm_eps, cfg.head_dim
    T, L = cfg.total_ut_steps, cfg.num_hidden_layers
    live = seq_slots != 0
    # the rotary's angles from the rows' positions (the same in every pass):
    # no table of max_position_embeddings rows in the program
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32)
                                    / Dh))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    turn = lambda y: _rotary(y, cos, sin, jnp.arange(y.shape[0]))

    with jax.named_scope(_names.SCOPE_EMBED):
        x = params["embed_tokens"]["embedding"][token_ids].astype(dtype)
    blk = block_tables[seq_slots, positions // block_size]
    off = positions % block_size
    num_blocks = kv_data[0][0].shape[0] // T

    def between(x, left, expect):
        """After a pass but the last: its output normed, the gate on it, the
        share of a row still in the loop (``left``), and the pass a row is
        expected to leave after so far: the sum over ``t`` of ``t p(t)`` is
        one plus the sum of ``left`` after each pass but the last."""
        with jax.named_scope(_names.SCOPE_UT_PASS), \
                jax.named_scope(_names.SCOPE_UT_NORM):
            h = _rmsnorm(x, params["norm"]["weight"], eps)
        with jax.named_scope(_names.SCOPE_EXIT_GATE):
            left = left * (1.0 - exit_gate(h, params["early_exit_gate"]))
        return h, left, expect + left

    def one_pass(t, carry):
        x, kv, left, expect = carry
        x, left, expect = jax.lax.cond(
            t > 0, between, lambda *kept: kept, x, left, expect)
        shift = t * num_blocks                  # pass t's pages of a buffer
        kv = list(kv)
        with jax.named_scope(_names.SCOPE_UT_PASS):
            for l in range(L):
                lp = params[f"layers_{l}"]
                # a post-sublayer gain is held [D / 32, 32] (models/ouro.py)
                norm = lambda y, name: _rmsnorm(
                    y, lp[name]["weight"].reshape(-1), eps)
                attn_out, kv[l] = _ragged_attention_block(
                    lp["self_attn"], norm(x, "input_layernorm"), kv[l],
                    blk + shift, off, block_tables + shift, seq_slots,
                    positions, None, None, cfg=cfg, block_size=block_size,
                    rotary=turn, use_kernel=use_kernel)
                x = x + norm(attn_out, "input_layernorm_2")
                m = _swiglu(0, norm(x, "post_attention_layernorm"),
                            lp["mlp"], dtype)
                x = x + norm(m, "post_attention_layernorm_2")
        return x, tuple(kv), left, expect

    ones = jnp.ones(x.shape[:1], jnp.float32)
    x, kv_data, _, expect = jax.lax.fori_loop(
        0, T, one_pass, (x, tuple(kv_data), ones, ones))
    with jax.named_scope(_names.SCOPE_EXIT_GATE):
        counts = jnp.stack([
            T * jnp.sum(live),
            jnp.round(256.0 * jnp.sum(jnp.where(live, expect, 0.0)))
            .astype(jnp.int32)])
    return _lm_head(params, x, last_token_idx, cfg), kv_data, counts


# ---------------------------------------------------------------- Motif
@_ragged_program("motif", step_counts=(
    _names.COUNT_EXPERT_COPIES, _names.COUNT_EXPERT_ACTIVE), slot_rows=True)
def motif_ragged_step(params, kv_data, token_ids, positions, seq_slots,
                      block_tables, last_token_idx, *, cfg, block_size,
                      use_kernel=True, kv_dtype=None, slot_rows=False):
    """One ragged engine iteration for Motif-3 (``models/motif.py`` has the
    equations): the residual of a row is ``mhc_expansion_rate`` STREAMS ``[T,
    n, D]`` (bfloat16 between sublayers); each sublayer, attention and then
    the feed-forward, reads a learned mix of them and writes back through
    two more (``mhc_sublayer``: the three mappings and the Sinkhorn sweeps in
    float32).  Attention is grouped differential attention over the LATENT
    cache (``_mla_block`` with the layer's window, K/V groups, the
    subtraction and the output gate; ``kv_data``: one ``(pages, )`` a layer),
    the feed-forward PolyNorm-gated: dense in the leading layers, then the
    held experts' part of the scaled routed sum beside the shared expert, as
    ``pangu_ultra_moe_ragged_step``'s.  Every layer keeps every token in ONE
    block table: a window layer's pages past its window are held and not
    read.  ``slot_rows``: ``_mla_block``'s.

    Returns ``(logits, new kv_data, counts)``; ``counts`` as
    ``cohere2_moe_ragged_step``'s, over the routed layers."""
    if kv_dtype is not None:
        raise NotImplementedError("kv_cache_dtype with a latent cache")
    from ...models.motif import (diff_gates, gated_poly, mhc_sublayer,
                                 mla_view, moe_layer)
    from ...ops._use_kernels import use_pallas_kernels

    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.rms_norm_eps
    live = seq_slots != 0
    gmm_kernel = use_kernel and use_pallas_kernels()

    with jax.named_scope(_names.SCOPE_EMBED):
        x = params["embed_tokens"]["embedding"][token_ids].astype(dtype)
        X = jnp.repeat(x[:, None], cfg.mhc_expansion_rate, axis=1)
    blk = block_tables[seq_slots, positions // block_size]
    off = positions % block_size

    kv_data = list(kv_data)
    counts = []
    for l, window in enumerate(cfg.layer_windows):
        lp = params[f"layers_{l}"]

        def attention(h):
            attn = mla_view(lp["self_attn"], dtype)
            with jax.named_scope(_names.SCOPE_ATTENTION):
                lam, gate = diff_gates(h, attn)
            out, kv_data[l] = _mla_block(
                attn, h, kv_data[l], blk, off, block_tables, seq_slots,
                positions, cfg=cfg, block_size=block_size,
                use_kernel=use_kernel, slot_rows=slot_rows, window=window,
                lam=lam, out_gate=gate)
            return out

        def feed_forward(h):
            with jax.named_scope(_names.SCOPE_MLP):
                if not cfg.routed(l):
                    return gated_poly(
                        h, *(lp["mlp"][f"{n}_proj"]["kernel"].astype(dtype)
                             for n in ("gate", "up", "down")),
                        lp["mlp"]["poly"], cfg)
                with jax.named_scope(_names.SCOPE_MOE_ROUTER):
                    router_logits = h.astype(jnp.float32) @ lp["moe"][
                        "gate"]["kernel"].astype(jnp.float32)
                m, landed = moe_layer(h, router_logits, lp["moe"], cfg,
                                      live=live, kernel=gmm_kernel)
            counts.append(landed)
            return m

        X = mhc_sublayer(X, lp["attn_mhc"], lp["input_layernorm"]["weight"],
                         attention, cfg)
        X = mhc_sublayer(X, lp["mlp_mhc"],
                         lp["post_attention_layernorm"]["weight"],
                         feed_forward, cfg)

    with jax.named_scope(_names.SCOPE_LM_HEAD):
        xl = jnp.sum(X[last_token_idx].astype(jnp.float32), axis=1)
        xl = _rmsnorm(xl.astype(dtype), params["norm"]["weight"], eps)
        logits = jnp.einsum("td,dv->tv", xl,
                            params["lm_head"]["kernel"].astype(dtype),
                            preferred_element_type=jnp.float32)
    counts = jnp.stack(counts)                        # [routed layers, held]
    return logits, tuple(kv_data), jnp.stack(
        [jnp.sum(counts), jnp.sum(counts > 0)])


# ---------------------------------------------------------------- Jamba
def _run_plan(seq_slots, positions, n_slots):
    """What the recurrent layers of a step read of its buffer, made once a
    step.  A RUN is the contiguous rows of one sequence.  By row: ``idx``,
    the row's place in its run; ``flags``, ``ops/pallas/selective_scan``'s
    (a run's first row takes its slot's state, or zeros where the run starts
    at position 0; its last row leaves the state); ``n_live``.  By slot:
    whether the step holds a run of it (``has_run``), the run's length and
    last row, and whether it starts at position 0 (``fresh``)."""
    from ...ops.pallas.selective_scan import LOAD, STORE, ZERO
    T = seq_slots.shape[0]
    t = jnp.arange(T, dtype=jnp.int32)
    live = seq_slots != 0
    edge = jnp.full((1, ), -1, seq_slots.dtype)
    start = live & (seq_slots != jnp.concatenate([edge, seq_slots[:-1]]))
    end = live & (seq_slots != jnp.concatenate([seq_slots[1:], edge]))
    idx = t - jax.lax.cummax(jnp.where(start, t, 0))
    fresh = positions == idx
    flags = jnp.where(start, jnp.where(fresh, ZERO, LOAD), 0) \
        | jnp.where(end, STORE, 0)
    zeros = jnp.zeros(n_slots, jnp.int32)
    last_row = zeros.at[seq_slots].max(jnp.where(live, t, 0))
    return dict(
        slots=seq_slots, live=live, idx=idx, flags=flags.astype(jnp.int32),
        n_live=jnp.max(jnp.where(live, t + 1, 0))[None],
        has_run=zeros.at[seq_slots].max(live.astype(jnp.int32)) > 0,
        run_len=zeros.at[seq_slots].add(live.astype(jnp.int32)),
        last_row=last_row, fresh=fresh[last_row])


def _slot_plan(seq_slots, positions):
    """:func:`_run_plan` of a buffer with ONE row a slot (row ``i`` slot
    ``i``, a burst's): every live row is a run of one token."""
    return dict(live=seq_slots != 0, fresh=positions == 0)


def _conv_runs(x, conv_state, conv, plan):
    """The causal depthwise convolution over the runs of a ragged buffer ``x
    [T, C]``: a row's taps come from its run's earlier rows and, before the
    run's first row, from its slot's ``conv_state [K - 1, slots, C]`` (plane
    ``K - 2`` the newest; zeros for a run that starts at position 0).
    Returns ``(conv + bias [T, C] float32, new conv_state)`` (a convolution
    without the leaf ``bias`` has none): every run leaves its last ``K - 1``
    inputs, in the state's type."""
    w = conv["weight"].astype(jnp.float32)                 # [K, C]
    K, S = w.shape[0], conv_state.shape[1]
    x32 = x.astype(jnp.float32)
    acc = w[K - 1] * x32
    if "bias" in conv:
        acc = acc + conv["bias"].astype(jnp.float32)[:, 0]
    idx, slots = plan["idx"], plan["slots"]
    for j in range(1, K):                    # the run's own earlier rows
        back = jnp.pad(x32, ((j, 0), (0, 0)))[:-j]
        acc = acc + jnp.where((idx >= j)[:, None], back, 0) * w[K - 1 - j]
    # what a run's first K - 1 rows take from the slot's state: made a SLOT
    # (a few rows each), gathered a row
    old = jnp.where(plan["fresh"][None, :, None], 0, conv_state)
    st = old.astype(jnp.float32)
    corr = jnp.stack([sum(w[K - 1 - j] * st[K - 1 - (j - i)]
                          for j in range(i + 1, K)) for i in range(K - 1)])
    corr = jnp.concatenate([corr.reshape((K - 1) * S, -1),
                            jnp.zeros((1, x.shape[1]), jnp.float32)])
    acc = acc + corr[jnp.where(plan["live"] & (idx < K - 1),
                               idx * S + slots, (K - 1) * S)]
    # the state a run leaves: its last K - 1 inputs, the old planes shifted
    # where the run is shorter
    planes = []
    for k in range(K - 1):
        back = K - 2 - k                     # rows before the run's last
        new = x[jnp.maximum(plan["last_row"] - back, 0)] \
            .astype(conv_state.dtype)
        for length in range(1, back + 1):
            new = jnp.where((plan["run_len"] == length)[:, None],
                            old[k + length], new)
        planes.append(jnp.where(plan["has_run"][:, None], new,
                                conv_state[k]))
    return acc, jnp.stack(planes)


def _conv_slots(x, conv_state, conv, plan):
    """:func:`_conv_runs` for a buffer of ONE row a slot (``x [slots, C]``,
    row ``i`` slot ``i``): elementwise over the state's planes."""
    w = conv["weight"].astype(jnp.float32)
    K = w.shape[0]
    old = jnp.where(plan["fresh"][None, :, None], 0, conv_state)
    acc = w[K - 1] * x.astype(jnp.float32)
    if "bias" in conv:
        acc = acc + conv["bias"].astype(jnp.float32)[:, 0]
    acc = acc + sum(w[k] * old[k].astype(jnp.float32) for k in range(K - 1))
    new = jnp.concatenate([old[1:], x[None].astype(conv_state.dtype)])
    return acc, jnp.where(plan["live"][None, :, None], new, conv_state)


def _scan_runs(dt, u, B, Cm, A, ssm_state, plan, use_kernel):
    """The recurrence over the runs of a ragged buffer (``ssm_state [slots,
    S, C]``, a row a slot, donated): the Pallas ``ds_selective_scan`` on a
    TPU, a ``lax.scan`` over the rows elsewhere; ``h`` is float32 inside a
    run and takes the state's type where the run leaves it.  Returns ``(y [T,
    C] float32, new ssm_state)``."""
    from ...ops._use_kernels import use_pallas_kernels
    from ...ops.pallas.selective_scan import (LOAD, STORE, ZERO,
                                              selective_scan, state_tile)
    if use_kernel and use_pallas_kernels() and state_tile(ssm_state):
        return selective_scan(dt, dt * u.astype(jnp.float32), B, Cm, A,
                              ssm_state, plan["slots"], plan["flags"],
                              plan["n_live"])

    def token(carry, row):
        state, h = carry
        dt_t, u_t, B_t, C_t, slot, flag = row
        h = jnp.where((flag & LOAD) != 0, state[slot].astype(jnp.float32), h)
        h = jnp.where((flag & ZERO) != 0, 0, h)
        h = jnp.exp(dt_t[None, :] * A) * h \
            + (dt_t * u_t)[None, :] * B_t[:, None]
        state = state.at[slot].set(jnp.where(
            (flag & STORE) != 0, h.astype(state.dtype), state[slot]))
        return (state, h), jnp.sum(h * C_t[:, None], axis=0)

    (ssm_state, _), y = jax.lax.scan(
        token, (ssm_state, jnp.zeros(A.shape, jnp.float32)),
        (dt, u.astype(jnp.float32), B, Cm, plan["slots"], plan["flags"]))
    return y, ssm_state


def _scan_slots(dt, u, B, Cm, A, ssm_state, plan):
    """:func:`_scan_runs` for a buffer of ONE row a slot: one elementwise
    update of every slot's ``h``, the state buffer read once and written once
    in place."""
    h = jnp.where(plan["fresh"][:, None, None], 0,
                  ssm_state.astype(jnp.float32))
    h = jnp.exp(dt[:, None, :] * A) * h \
        + (dt * u.astype(jnp.float32))[:, None, :] * B[:, :, None]
    y = jnp.sum(h * Cm[:, :, None], axis=1)
    return y, jnp.where(plan["live"][:, None, None],
                        h.astype(ssm_state.dtype), ssm_state)


@jax.named_scope(_names.SCOPE_SSM)
def _ssm_block(mp, h, state, plan, *, cfg, use_kernel, slot_rows):
    """The Mamba-1 mixer of one layer over the step's buffer
    (``models/jamba.py`` has the equations).  ``state``: the layer's entry of
    the cache, ``(conv_state [K - 1, slots, C], ssm_state [slots, S, C])``,
    donated buffers of their own; a run starts from ITS slot's rows (zeros at
    position 0), never crosses into its neighbour's, and leaves its final
    state in the slot.  Returns (out [T, D], new state)."""
    from ...models.jamba import (conv_out, in_proj, mamba_A, ssm_gate_out,
                                 ssm_inputs)
    conv_state, ssm_state = state
    with jax.named_scope(_names.SCOPE_SSM_PROJ):
        x, z = in_proj(h, mp, cfg)
    with jax.named_scope(_names.SCOPE_SSM_CONV):
        acc, conv_state = (_conv_slots if slot_rows else _conv_runs)(
            x, conv_state, mp["conv1d"], plan)
        u = conv_out(acc, cfg)
    with jax.named_scope(_names.SCOPE_SSM_PROJ):
        dt, B, Cm = ssm_inputs(u, mp, cfg)
        A = mamba_A(mp, cfg)
    with jax.named_scope(_names.SCOPE_SSM_SCAN):
        if slot_rows:
            y, ssm_state = _scan_slots(dt, u, B, Cm, A, ssm_state, plan)
        else:
            y, ssm_state = _scan_runs(dt, u, B, Cm, A, ssm_state, plan,
                                      use_kernel)
    with jax.named_scope(_names.SCOPE_SSM_PROJ):
        out = ssm_gate_out(y, u, z, mp, cfg)
    return out, (conv_state, ssm_state)


@_ragged_program("jamba", slot_rows=True)
def jamba_ragged_step(params, kv_data, token_ids, positions, seq_slots,
                      block_tables, last_token_idx, *, cfg, block_size,
                      use_kernel=True, kv_dtype=None, slot_rows=False):
    """One ragged engine iteration for Jamba (``models/jamba.py`` has the
    layer's equations): Mamba-1 layers beside a few multi-query attention
    layers WITHOUT positions, a dense SwiGLU in every layer, a tied table.

    ``kv_data`` (donated) holds entries of TWO kinds (``ragged.py``): an
    attention layer's ``(k_pages, v_pages)``, scattered in place and read by
    the paged kernel as every other model's, and a Mamba layer's
    ``(conv_state, ssm_state)``, a row a sequence SLOT: the buffer holds
    several sequences' runs side by side (a prefill chunk of one, single
    decode rows of others), and every run starts from its own slot's state
    (zeros at position 0, so nothing is cleared on the host) and leaves its
    final state there.  ``slot_rows``: the buffer has ONE row a slot, row
    ``i`` slot ``i`` (a burst's): convolution and recurrence are then one
    elementwise update over the state buffers, in place."""
    if kv_dtype is not None:
        raise NotImplementedError("kv_cache_dtype with recurrent state")
    from ...models.jamba import attention_leaves, gated_mlp, rms_norm
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.rms_norm_eps

    with jax.named_scope(_names.SCOPE_EMBED):
        # the residual stream is held in the activations' type
        # (models/jamba.py: where the numbers are rounded); a matrix product
        # reads its input in the serving type
        x = params["embed_tokens"]["weight"][token_ids].astype(cfg.act_dtype)
    blk = block_tables[seq_slots, positions // block_size]
    off = positions % block_size
    plan = _slot_plan(seq_slots, positions) if slot_rows else \
        _run_plan(seq_slots, positions, block_tables.shape[0])

    kv_data = list(kv_data)
    for l in range(cfg.num_hidden_layers):
        lp = params[f"layers_{l}"]
        with jax.named_scope(_names.SCOPE_NORM):
            h = rms_norm(x, lp["input_layernorm"]["weight"], eps) \
                .astype(dtype)
        if cfg.is_attention(l):
            mixed, kv_data[l] = _ragged_attention_block(
                attention_leaves(lp["self_attn"], cfg), h, kv_data[l], blk,
                off, block_tables, seq_slots, positions, None, None, cfg=cfg,
                block_size=block_size, rotary=False, use_kernel=use_kernel)
        else:
            mixed, kv_data[l] = _ssm_block(
                lp["mamba"], h, kv_data[l], plan, cfg=cfg,
                use_kernel=use_kernel, slot_rows=slot_rows)
        x = x + mixed.astype(x.dtype)
        with jax.named_scope(_names.SCOPE_NORM):
            h2 = rms_norm(x, lp["pre_ff_layernorm"]["weight"], eps)
        with jax.named_scope(_names.SCOPE_MLP):
            x = x + gated_mlp(h2, lp["mlp"], cfg)

    with jax.named_scope(_names.SCOPE_LM_HEAD):
        # only each slot's last token reaches the head; the tied table is
        # read in the type it is held in, the products summed in float32
        xl = rms_norm(x[last_token_idx], params["final_layernorm"]["weight"],
                      eps).astype(dtype)
        logits = jnp.einsum("td,vd->tv", xl,
                            params["embed_tokens"]["weight"].astype(dtype),
                            preferred_element_type=jnp.float32)
    return logits, tuple(kv_data)


# ------------------------------------------------------------ Qwen3-Next
def _rule_slots(q, k, v, g, beta, state, live, fresh, use_kernel=True):
    """The delta rule's ONE-TOKEN form over a buffer of one row a slot (``q,
    k, v [slots, Hv, 128]``, ``g, beta [slots, Hv]``; ``state [slots, Hv, 128,
    128]`` float32, donated): one update of every live slot's row, read and
    written in place: the Pallas ``ds_gated_delta_slot`` on a TPU (a live row
    read ONCE for the two sums and the update, the others not at all),
    element-wise XLA elsewhere (every row read twice).  Returns ``(o [slots,
    Hv, 128], new state)``; ``o`` of a slot that is not live is zeros.  (The
    XLA form gave such a slot the rule of its stale row until PR 61; the
    kernel never reads that row, so the select below was ADDED to the XLA
    form to give both one contract.  Nobody reads those rows: ``_rule_runs``
    masks them, and a burst's dead slot yields no token.)"""
    from ...models.qwen3_next import delta_rule_token
    from ...ops._use_kernels import use_pallas_kernels
    from ...ops.pallas.gated_delta_rule import gated_delta_slot, head_block
    with jax.named_scope(_names.SCOPE_GDN_SLOT):
        if use_kernel and use_pallas_kernels() and head_block(state):
            return gated_delta_slot(q, k, v, g, beta, state, live, fresh)
        old = jnp.where(fresh[:, None, None, None], 0,
                        state.astype(jnp.float32))
        o, new = delta_rule_token(q, k, v, g, beta, old)
        return jnp.where(live[:, None, None], o, 0), jnp.where(
            live[:, None, None, None], new.astype(state.dtype), state)


def _rule_runs(q, k, v, g, beta, state, plan, use_kernel=True):
    """The delta rule over the runs of a ragged buffer (``q, k, v [T, Hv,
    128]``, ``g, beta [T, Hv]`` float32; ``state [slots, Hv, 128, 128]``
    float32, a row a slot, donated).  A run of ONE token (a decode row beside
    a chunk) takes the one-token form on its slot's row, all of them in one
    update (:func:`_rule_slots`; skipped where the step holds none).  A longer
    run is cut into chunks of ``GDN_CHUNK`` rows that never cross into its
    neighbour, and a loop over the step's chunks takes each through the
    matrix products of ``models/qwen3_next.delta_rule_chunk``: a run's first
    chunk starts from its slot's row (zeros at position 0), the state is
    carried from chunk to chunk in float32, and its last chunk leaves it in
    the slot.  Returns ``(o [T, Hv, 128] float32, new state)``."""
    from ...models.qwen3_next import GDN_CHUNK as C, delta_rule_chunk
    T, n_slots = q.shape[0], state.shape[0]
    slots, live = plan["slots"], plan["live"]
    single = plan["has_run"] & (plan["run_len"] == 1)            # [slots]
    rows = plan["last_row"]

    def one_token(state):
        o, state = _rule_slots(q[rows], k[rows], v[rows], g[rows], beta[rows],
                               state, single, plan["fresh"], use_kernel)
        return jnp.where((live & single[slots])[:, None, None], o[slots],
                         0), state

    out, state = jax.lax.cond(
        jnp.any(single), one_token,
        lambda state: (jnp.zeros(v.shape, jnp.float32), state), state)

    with jax.named_scope(_names.SCOPE_GDN_CHUNK):
        # the chunks of the step's longer runs, in the order of their slots
        long = plan["has_run"] & (plan["run_len"] > 1)
        n_chunks = jnp.where(long, -(-plan["run_len"] // C), 0)
        ends = jnp.cumsum(n_chunks)                          # [slots]
        first_row = rows - plan["run_len"] + 1
        pad = lambda x: jnp.pad(x, ((0, C), ) + ((0, 0), ) * (x.ndim - 1))
        qp, kp, vp, gp, bp = map(pad, (q, k, v, g, beta))

        def chunk(c, carry):
            out, state, s = carry
            # the run this chunk belongs to: a compare and a sum over the
            # slots (``searchsorted`` is a ``while`` of its own a trip)
            slot = jnp.sum(ends <= c, dtype=jnp.int32)
            j = c - (ends[slot] - n_chunks[slot])            # chunk of its run
            r0 = first_row[slot] + j * C
            valid = jnp.arange(C) < plan["run_len"][slot] - j * C
            cut = lambda x: jax.lax.dynamic_slice_in_dim(x, r0, C)
            keep = lambda x: jnp.where(
                valid.reshape((C, ) + (1, ) * (x.ndim - 1)), x, 0)
            row = jax.lax.dynamic_index_in_dim(state, slot, keepdims=False)
            s = jnp.where(j > 0, s, jnp.where(plan["fresh"][slot], 0,
                                              row.astype(jnp.float32)))
            o, s = delta_rule_chunk(cut(qp), keep(cut(kp)), keep(cut(vp)),
                                    keep(cut(gp)), keep(cut(bp)), s)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, jnp.where(valid[:, None, None], o, cut(out)), r0, 0)
            last = j == n_chunks[slot] - 1
            state = jax.lax.dynamic_update_index_in_dim(
                state, jnp.where(last, s.astype(state.dtype), row), slot, 0)
            return out, state, s

        out, state, _ = jax.lax.fori_loop(
            0, ends[n_slots - 1], chunk,
            (pad(out), state, jnp.zeros(state.shape[1:], jnp.float32)))
    return out[:T], state


@jax.named_scope(_names.SCOPE_GDN)
def _gdn_block(mp, h, state, plan, *, cfg, use_kernel, slot_rows):
    """The Gated DeltaNet mixer of one layer over the step's buffer
    (``models/qwen3_next.py`` has the equations and the rule's two forms).
    ``state``: the layer's entry of the cache, ``(conv_state [K - 1, slots,
    C] in the model's dtype, rule_state [slots, Hv, 128, 128] FLOAT32)``,
    donated buffers of their own; a run starts from ITS slot's rows (zeros at
    position 0), never reads its neighbour's, and leaves its last state and
    its last ``K - 1`` convolution inputs in the slot.  ``slot_rows`` (a
    burst's buffer: row ``i`` is slot ``i``): every live row is a run of one
    token, ONE update of the state buffer in place.  Returns (out [T, D], new
    state)."""
    from ...models.qwen3_next import (gdn_conv_out, gdn_gate_out, gdn_in_proj,
                                      gdn_rule_inputs)
    conv_state, rule_state = state
    with jax.named_scope(_names.SCOPE_GDN_PROJ):
        mixed, z, b, a = gdn_in_proj(h, mp, cfg)
    with jax.named_scope(_names.SCOPE_GDN_CONV):
        acc, conv_state = (_conv_slots if slot_rows else _conv_runs)(
            mixed, conv_state, mp["conv1d"], plan)
        u = gdn_conv_out(acc, cfg)
    with jax.named_scope(_names.SCOPE_GDN_PROJ):
        q, k, v, g, beta = gdn_rule_inputs(u, b, a, mp, cfg)
    with jax.named_scope(_names.SCOPE_GDN_RULE):
        if slot_rows:
            o, rule_state = _rule_slots(q, k, v, g, beta, rule_state,
                                        plan["live"], plan["fresh"],
                                        use_kernel)
        else:
            o, rule_state = _rule_runs(q, k, v, g, beta, rule_state, plan,
                                       use_kernel)
    with jax.named_scope(_names.SCOPE_GDN_PROJ):
        out = gdn_gate_out(o, z, mp, cfg)
    return out, (conv_state, rule_state)


@_ragged_program("qwen3_next", slot_rows=True, step_counts=(
    _names.COUNT_EXPERT_COPIES, _names.COUNT_EXPERT_ACTIVE))
def qwen3_next_ragged_step(params, kv_data, token_ids, positions, seq_slots,
                           block_tables, last_token_idx, *, cfg, block_size,
                           use_kernel=True, kv_dtype=None, slot_rows=False):
    """One ragged engine iteration for Qwen3-Next (``models/qwen3_next.py``
    has the layer's equations): three Gated DeltaNet layers in four beside a
    gated softmax attention, an expert layer with a gated shared expert in
    every layer, an untied head.

    ``kv_data`` (donated) holds entries of TWO kinds (``ragged.py``): an
    attention layer's ``(k_pages, v_pages)`` (2 KV heads of 256), scattered
    in place and read by the paged kernel as every other model's, and a
    DeltaNet layer's ``(conv_state, rule_state)``, a row a sequence SLOT, the
    rule's in float32 (:func:`_gdn_block`).  The expert layer routes the LIVE
    rows over the router's full width and computes the held experts' part
    (``moe/held_experts.py``) beside the shared expert, as
    :func:`cohere2_moe_ragged_step` does.  ``slot_rows``: the buffer has ONE
    row a slot, row ``i`` slot ``i`` (a burst's).

    Returns ``(logits, new kv_data, counts)``; ``counts`` (int32, the
    program's ``step_counts``): the (row, expert) copies that landed on a
    held expert and the held experts with at least one, summed over the
    layers."""
    if kv_dtype is not None:
        raise NotImplementedError("kv_cache_dtype with recurrent state")
    from ...models.qwen3_next import (head_norms, moe_layer, rms_norm,
                                      rotary_half)
    from ...ops._use_kernels import use_pallas_kernels
    dtype = jnp.dtype(cfg.dtype)
    eps = cfg.rms_norm_eps
    live = seq_slots != 0
    gmm_kernel = use_kernel and use_pallas_kernels()
    turn = lambda x: rotary_half(x, positions, cfg.rope_theta, cfg.rotary_dim)

    with jax.named_scope(_names.SCOPE_EMBED):
        x = params["embed_tokens"]["embedding"][token_ids].astype(dtype)
    blk = block_tables[seq_slots, positions // block_size]
    off = positions % block_size
    plan = _slot_plan(seq_slots, positions) if slot_rows else \
        _run_plan(seq_slots, positions, block_tables.shape[0])

    kv_data = list(kv_data)
    counts = []
    for l in range(cfg.num_hidden_layers):
        lp = params[f"layers_{l}"]
        with jax.named_scope(_names.SCOPE_NORM):
            h = rms_norm(x, lp["input_layernorm"]["weight"], eps)
        if cfg.is_attention(l):
            ap = lp["self_attn"]
            mixed, kv_data[l] = _ragged_attention_block(
                ap, h, kv_data[l], blk, off, block_tables, seq_slots,
                positions, None, None, cfg=cfg, block_size=block_size,
                rotary=turn, use_kernel=use_kernel, window=0,
                qk_norm=head_norms(ap, cfg), out_gate=True)
        else:
            mixed, kv_data[l] = _gdn_block(
                lp["linear_attn"], h, kv_data[l], plan, cfg=cfg,
                use_kernel=use_kernel, slot_rows=slot_rows)
        x = x + mixed
        with jax.named_scope(_names.SCOPE_NORM):
            h2 = rms_norm(x, lp["post_attention_layernorm"]["weight"], eps)
        with jax.named_scope(_names.SCOPE_MLP):
            moe_out, landed = moe_layer(h2, lp["moe"], cfg, live=live,
                                        kernel=gmm_kernel)
        counts.append(landed)
        x = x + moe_out

    with jax.named_scope(_names.SCOPE_LM_HEAD):
        # only each slot's last token reaches the head
        xl = rms_norm(x[last_token_idx], params["norm"]["weight"], eps)
        logits = jnp.dot(xl, params["lm_head"]["kernel"].astype(dtype),
                         preferred_element_type=jnp.float32)
    counts = jnp.stack(counts)                        # [layers, held]
    return logits, tuple(kv_data), jnp.stack(
        [jnp.sum(counts), jnp.sum(counts > 0)])


RAGGED_FORWARDS = {"LlamaModel": llama_ragged_step,
                   "MixtralModel": mixtral_ragged_step,
                   "FalconModel": falcon_ragged_step,
                   "OPTModel": opt_ragged_step,
                   "PhiModel": phi_ragged_step,
                   "EvaByteModel": evabyte_ragged_step,
                   "Cohere2MoeModel": cohere2_moe_ragged_step,
                   "PanguUltraMoeModel": pangu_ultra_moe_ragged_step,
                   "LongcatFlashModel": longcat_flash_ragged_step,
                   "JambaModel": jamba_ragged_step,
                   "OuroModel": ouro_ragged_step,
                   "MotifModel": motif_ragged_step,
                   "Qwen3NextModel": qwen3_next_ragged_step}


def _device_sample(logits, key, temperature, top_k, top_p):
    """Per-row categorical with the engine's generate options (temperature /
    top-k / nucleus top-p), all on device.  ``top_k`` is static (shapes);
    temperature/top_p are traced scalars.  Same filtering semantics as the
    host ``_sample_row``: smallest prefix reaching ``top_p``, always ≥ 1
    candidate."""
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    p = jax.nn.softmax(logits, axis=-1)
    sp = jnp.sort(p, axis=-1)[:, ::-1]                      # descending
    csum = jnp.cumsum(sp, axis=-1)
    # per row: the smallest kept probability of the nucleus prefix
    kept = jnp.where(csum - sp < top_p, sp, jnp.inf)
    thresh = jnp.min(kept, axis=-1, keepdims=True)
    logits = jnp.where(p < thresh, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@_program(
    _names.PROGRAM_DECODE_BURST,
    static_argnames=("step_fn", "cfg", "block_size", "k", "use_kernel",
                     "sample", "top_k", "kv_dtype"),
    donate_argnums=(1, ))
def decode_burst(params, kv_data, tok0, pos0, active, block_tables, *,
                 step_fn, cfg, block_size, k, use_kernel=True,
                 sample=False, key=None, temperature=1.0, top_k=0,
                 top_p=1.0, kv_dtype=None, counts0=None):
    """``k`` greedy decode iterations in ONE compiled program.

    The per-step serving loop pays a host round-trip per generated token
    (fetch argmax → rebuild the ragged batch → re-upload).  When every
    running sequence is in pure decode, that loop is a fixed-point the
    device can run alone: a ``lax.scan`` feeds each step's argmax back as
    the next step's input token, and the host fetches ``k`` tokens per
    sequence in one transfer.  TPU answer to the role CUDA graphs play in
    the reference's decode path (``inference/engine.py:519``
    ``_create_cuda_graph``) — here the whole multi-token loop is one XLA
    program, not a replayed capture.

    Layout: row ``i`` of the [max_seqs]-token batch belongs to slot ``i``
    (``last_token_idx = arange``); idle rows carry ``active=False`` and are
    steered to slot 0, whose block-table row is the reserved garbage block.
    Greedy only — sampling keeps the host loop (host RNG semantics).

    Args:
      tok0/pos0/active: [max_seqs] — each active slot's pending token and
        its position; block capacity for ``pos0 + k`` must be pre-ensured.
      step_fn: a RAGGED_FORWARDS value (the jitted wrapper's underlying
        function is inlined into the scan body).

    With ``sample=True`` each iteration draws from the temperature/top-k/
    top-p-filtered distribution with the jax PRNG ``key`` (split per
    iteration) instead of argmax — seed-deterministic, but a DIFFERENT
    stream than the host loop's numpy Generator, which is why the engine
    gates it behind ``decode_burst_sampling``.

    ``kv_data`` (the per-layer K and V buffers, donated) is the scan's
    carry: every buffer stays in place through the ``while``.

    Returns ([k, max_seqs] int32 tokens (one per iteration), new kv).  For a
    step that counts on the device (its ``step_counts``) the tokens come back
    flat with ``counts0`` (what earlier steps counted and no fetch has
    carried yet) plus the iterations' counts behind them: one array, one
    transfer.
    """
    n = tok0.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    slots = jnp.where(active, rows, 0)
    inner = getattr(step_fn, "__wrapped__", step_fn)
    if key is None:
        key = jax.random.PRNGKey(0)

    # a step with per-slot state takes the layout's statement (row i is
    # slot i): its update is then elementwise over the state buffers
    layout = {"slot_rows": True} if getattr(step_fn, "slot_rows", False) \
        else {}

    def body(carry, _):
        kv, toks, pos, key, *counts = carry
        logits, kv, *counted = inner(
            params, kv, jnp.where(active, toks, 0),
            jnp.where(active, pos, 0), slots, block_tables, rows, cfg=cfg,
            block_size=block_size, use_kernel=use_kernel, kv_dtype=kv_dtype,
            **layout)
        if counted:
            counts = [counts[0] + counted[0]]
        if sample:
            key, sub = jax.random.split(key)
            nxt = _device_sample(logits, sub, temperature, top_k, top_p)
        else:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (kv, nxt, pos + 1, key, *counts), nxt

    if counts0 is None:
        counts0 = jnp.zeros(len(getattr(step_fn, "step_counts", ())))
    counts0 = (counts0.astype(jnp.int32), ) if counts0.size else ()
    (kv_data, _, _, _, *counts), toks_out = jax.lax.scan(
        body, (kv_data, tok0.astype(jnp.int32), pos0.astype(jnp.int32),
               key, *counts0), None, length=k)
    if counts:
        toks_out = jnp.concatenate([toks_out.reshape(-1), counts[0]])
    return toks_out, kv_data
