"""Llama-family model (TPU-first flax implementation).

Fills the role of the reference's model coverage for Llama/Llama-2 (inference
containers ``module_inject/containers/llama.py``, FastGen impl
``inference/v2/model_implementations/llama_v2``) — but as a *training-capable*
flax module designed for the MXU:

* all matmuls batched [B*S, D]×[D, ·], bf16 compute, fp32 RMSNorm accums;
* rotary embeddings precomputed once (static S) and fused by XLA;
* GQA (n_kv_heads ≤ n_heads) with head-dim layouts [B, S, H, Dh];
* optional Ulysses attention (sp axis) via ``deepspeed_tpu.sequence``;
* ``remat`` flag → ``jax.checkpoint`` per block (activation checkpointing,
  reference ``runtime/activation_checkpointing``);
* TP logical sharding rules exposed via ``tp_rules()`` — column-parallel
  qkv/gate/up, row-parallel o/down (AutoTP analog, reference
  ``module_inject/auto_tp.py:273``).

Returns loss when ``labels`` is given (DeepSpeed 'model returns loss'
convention used across the reference's tests).
"""

from dataclasses import dataclass
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..runtime.activation_checkpointing import resolve_policy
from ..telemetry import names as _names


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    sliding_window: int = 0       # 0 → full causal (Mistral sets 4096)
    attention_bias: bool = False  # Qwen2-style q/k/v biases
    # RoPE scaling (HF rope_scaling): "none" | "linear" | "llama3".
    # Scalar fields (not a dict) so the frozen config stays hashable as a
    # flax static attribute.
    rope_scaling_type: str = "none"
    rope_scaling_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_position: int = 8192
    dtype: str = "bfloat16"
    # lm-head / final-logits matmul dtype.  fp32 (the HF default) runs the
    # [B*S, D]×[D, V] matmul at the MXU's fp32 rate — ~4× below bf16 peak;
    # with V=32k that single matmul can dominate a small model's step.
    # "bfloat16" computes logits on the fast path (CE upcasts to fp32 for
    # the logsumexp either way).
    head_dtype: str = "float32"
    # > 0 → fused chunked head+loss: the lm-head matmul and cross entropy
    # run a chunk of ROWS at a time (sequence/cross_entropy
    # .fused_linear_cross_entropy: the chunk's softmax is whole, so its
    # gradient products run in the same pass and nothing is computed twice)
    # and the [B, S, V] logits are never materialized in either pass.  Frees
    # ~V·S·B·(2+4) bytes of live HBM (bf16 logits + fp32 softmax), which is
    # what forces remat at larger batch.  Value = the bound on the logits
    # alive at once, as the width of a [B·S, value] array: a chunk holds
    # ceil(B·S · value / V) rows, e.g. 6400 for V=32000 is a fifth of the rows.
    loss_chunk_vocab: int = 0
    remat: bool = True
    # what a recomputed block keeps besides its input: a key of
    # runtime/activation_checkpointing/checkpointing._POLICIES.  The default
    # keeps the flash kernel's residuals (its output, log-sum-exp, and q / k /
    # v as it received them: 8 x hidden + 4 x heads bytes a token a layer in
    # bf16, against 2 x hidden for the input), so the backward pass runs
    # neither the forward kernel nor what feeds it again; on the XLA attention
    # path it keeps nothing.  "nothing_saveable" for a job at its memory
    # limit; "dots_saveable" keeps every matmul output; "none" is
    # jax.checkpoint's own default.
    remat_policy: str = "flash_residuals_saveable"
    use_ulysses: bool = False
    sp_backend: str = "ulysses"  # "ulysses" (a2a reshard) | "ring" (ppermute)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def rope_scaling(self):
        """Scaling tuple for :func:`_rope_freqs`, or None when unscaled."""
        if self.rope_scaling_type == "none":
            return None
        return (self.rope_scaling_type, self.rope_scaling_factor,
                self.rope_low_freq_factor, self.rope_high_freq_factor,
                self.rope_original_max_position)


def llama_7b(**overrides):
    return LlamaConfig(**{**dict(vocab_size=32000, hidden_size=4096,
                                 intermediate_size=11008, num_hidden_layers=32,
                                 num_attention_heads=32, num_key_value_heads=32),
                          **overrides})


def llama_13b(**overrides):
    return LlamaConfig(**{**dict(vocab_size=32000, hidden_size=5120,
                                 intermediate_size=13824, num_hidden_layers=40,
                                 num_attention_heads=40, num_key_value_heads=40),
                          **overrides})


def llama_tiny(**overrides):
    """Test-scale config."""
    return LlamaConfig(**{**dict(vocab_size=256, hidden_size=64,
                                 intermediate_size=128, num_hidden_layers=2,
                                 num_attention_heads=4, num_key_value_heads=2,
                                 max_position_embeddings=128),
                          **overrides})


def mistral_7b(**overrides):
    """Mistral-7B-v0.1: llama architecture + GQA + 4096 sliding window."""
    return LlamaConfig(**{**dict(vocab_size=32000, hidden_size=4096,
                                 intermediate_size=14336,
                                 num_hidden_layers=32,
                                 num_attention_heads=32,
                                 num_key_value_heads=8,
                                 sliding_window=4096, rope_theta=10000.0,
                                 max_position_embeddings=32768),
                          **overrides})


def qwen2_7b(**overrides):
    """Qwen2-7B: llama architecture + GQA + q/k/v biases."""
    return LlamaConfig(**{**dict(vocab_size=152064, hidden_size=3584,
                                 intermediate_size=18944,
                                 num_hidden_layers=28,
                                 num_attention_heads=28,
                                 num_key_value_heads=4,
                                 attention_bias=True, rope_theta=1e6,
                                 max_position_embeddings=131072),
                          **overrides})


def _rope_freqs(head_dim, max_len, theta, scaling=None):
    """cos/sin tables; ``scaling`` is ``LlamaConfig.rope_scaling`` —
    ``(type, factor, low_freq_factor, high_freq_factor, original_max)``.

    "linear" divides all frequencies by ``factor``; "llama3" is the HF
    piecewise rule (frequencies below the low-freq wavelength are scaled by
    ``factor``, above high-freq kept, smooth interpolation between)."""
    inv = 1.0 / (theta**(np.arange(0, head_dim, 2) / head_dim))
    if scaling is not None:
        stype, factor, low_f, high_f, orig_max = scaling
        if stype == "linear":
            inv = inv / factor
        elif stype == "llama3":
            wavelen = 2 * np.pi / inv
            low_wavelen = orig_max / low_f
            high_wavelen = orig_max / high_f
            smooth = (orig_max / wavelen - low_f) / (high_f - low_f)
            smoothed = ((1 - smooth) / factor + smooth) * inv
            inv = np.where(wavelen > low_wavelen, inv / factor,
                           np.where(wavelen < high_wavelen, inv, smoothed))
        else:
            raise ValueError(f"unsupported rope scaling type {stype!r}")
    t = np.arange(max_len)
    freqs = np.outer(t, inv)  # [S, Dh/2]
    return np.cos(freqs), np.sin(freqs)


def apply_rotary(x, cos, sin, positions=None):
    """x: [B, S, H, Dh]; cos/sin: [Smax, Dh/2]."""
    S = x.shape[1]
    if positions is None:
        c = cos[:S][None, :, None, :]
        s = sin[:S][None, :, None, :]
    else:
        c = cos[positions][:, :, None, :]
        s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def _lm_loss(logits, labels, attention_mask=None):
    """Shifted next-token cross-entropy (shared by the monolithic forward and
    the Infinity streaming head)."""
    from ..sequence.cross_entropy import softmax_cross_entropy_with_logits
    loss = softmax_cross_entropy_with_logits(logits[:, :-1], labels[:, 1:])
    if attention_mask is not None:
        m = attention_mask[:, 1:].astype(jnp.float32)
        return jnp.sum(loss * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(loss)


def _lm_loss_chunked(x, w, labels, attention_mask, chunk, head_dtype):
    """Shifted CE via the fused chunked head+loss (no [B, S, V] logits).
    ``x``: [B, S, D] final hidden states, ``w``: [D, V] head kernel.  The
    mask and its denominator go in as the rows' weights."""
    from ..sequence.cross_entropy import fused_linear_cross_entropy
    b, s, d = x.shape
    n = b * (s - 1)
    weights = None
    if attention_mask is not None:
        m = attention_mask[:, 1:].astype(jnp.float32).reshape(n)
        weights = m / jnp.maximum(jnp.sum(m), 1.0)
    return fused_linear_cross_entropy(
        x[:, :-1].reshape(n, d), w, labels[:, 1:].reshape(n),
        chunk, logit_dtype=head_dtype, row_weights=weights)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        w = self.param("weight", nn.initializers.ones, (x.shape[-1], ))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        return (y * w).astype(self.dtype)


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, attention_mask=None, decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        H, Hkv, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=dtype,
                        param_dtype=jnp.float32)
        qkv = partial(nn.DenseGeneral, use_bias=cfg.attention_bias,
                      dtype=dtype, param_dtype=jnp.float32)
        # the block's parts under names of their own (metadata: the ops
        # keep ``self_attn`` in their path); perfbench/train_step_trace.py
        # reads them
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            q = qkv(features=(H, Dh), name="q_proj")(x)
            k = qkv(features=(Hkv, Dh), name="k_proj")(x)
            v = qkv(features=(Hkv, Dh), name="v_proj")(x)

        cos, sin = _rope_freqs(Dh, cfg.max_position_embeddings, cfg.rope_theta,
                               cfg.rope_scaling)
        cos, sin = jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)

        if decode:
            # KV-cached path (inference): rotary offset by the cache cursor,
            # keys stored rotated (models/cache.py).
            from .cache import decode_attention, kv_cache_update

            def rotate_k(kk, start):
                pos = start + jnp.arange(kk.shape[1])[None, :]
                return apply_rotary(kk, cos, sin, positions=pos)

            k, v, start = kv_cache_update(self, k, v, rotate_fn=rotate_k)
            q = apply_rotary(
                q, cos, sin,
                positions=start + jnp.arange(S)[None, :])
            # GQA handled inside decode_attention (no cache-wide repeat)
            out = decode_attention(q, k, v, start,
                                   window=cfg.sliding_window)
        else:
            with jax.named_scope(_names.SCOPE_ATTN_ROTARY):
                q = apply_rotary(q, cos, sin)
                k = apply_rotary(k, cos, sin)

            if cfg.use_ulysses and cfg.sp_backend == "ring":
                if cfg.sliding_window:
                    raise NotImplementedError(
                        "sliding_window is not supported by the ring SP "
                        "backend; use sp_backend='ulysses'")
                # ring handles Hkv < H internally — K/V circulate the ICI
                # ring at native KV width (repeating first would multiply
                # every ppermute hop's bytes by H/Hkv)
                from ..sequence.ring_attention import RingAttention
                with jax.named_scope(_names.SCOPE_ATTN_CORE):
                    out = RingAttention()(q, k, v, causal=True)
            elif cfg.use_ulysses:
                # kv at NATIVE width: DistributedAttention aligns GQA
                # inside its reshard (a2a + local group-repeat, or routed
                # a2a) — repeating to H first would multiply the kv a2a's
                # wire bytes by H/Hkv
                from ..sequence.layer import DistributedAttention
                with jax.named_scope(_names.SCOPE_ATTN_CORE):
                    out = DistributedAttention()(q, k, v, causal=True,
                                                 window=cfg.sliding_window)
            else:
                # GQA: repeat kv heads up to H for the local core
                if Hkv != H:
                    rep = H // Hkv
                    with jax.named_scope(_names.SCOPE_ATTN_KV_REPEAT):
                        k = jnp.repeat(k, rep, axis=2)
                        v = jnp.repeat(v, rep, axis=2)
                from ..ops.attention import attention_core
                with jax.named_scope(_names.SCOPE_ATTN_CORE):
                    out = attention_core(q, k, v, causal=True,
                                         window=cfg.sliding_window)

        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            return dense(features=D, axis=-1, name="o_proj")(
                out.reshape(B, S, H * Dh))


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        dense = partial(nn.Dense, use_bias=False, dtype=dtype,
                        param_dtype=jnp.float32)
        gate = dense(cfg.intermediate_size, name="gate_proj")(x)
        up = dense(cfg.intermediate_size, name="up_proj")(x)
        return dense(cfg.hidden_size, name="down_proj")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, attention_mask=None, decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        h = x + LlamaAttention(cfg, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, dtype, name="input_layernorm")(x),
            attention_mask, decode=decode)
        return h + LlamaMLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, dtype, name="post_attention_layernorm")(h))


class LlamaModel(nn.Module):
    """Causal-LM.  ``__call__(input_ids, labels=None)`` → loss (scalar) if
    labels given else logits."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None,
                 decode=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, dtype=dtype,
                         name="embed_tokens")
        with jax.named_scope(_names.SCOPE_EMBED):
            x = embed(input_ids)

        block = LlamaBlock
        if cfg.remat and not decode:
            policy = resolve_policy(cfg.remat_policy)
            block = nn.remat(LlamaBlock, policy=policy, static_argnums=(3, ))
        for i in range(cfg.num_hidden_layers):
            x = block(cfg, name=f"layers_{i}")(x, attention_mask, decode)

        x = RMSNorm(cfg.rms_norm_eps, dtype, name="norm")(x)
        # the head and the loss under one scope, on both loss paths: a
        # device trace then says which ops (forward, and through JAX's
        # transpose( marker backward) are the lm-head's
        with jax.named_scope(_names.SCOPE_LM_HEAD_LOSS):
            hd = jnp.dtype(cfg.head_dtype)
            if cfg.loss_chunk_vocab and labels is not None and not decode:
                # fused chunked head+loss: pull the head kernel and skip the
                # monolithic [B, S, V] logits entirely
                if cfg.tie_word_embeddings:
                    w = embed.variables["params"]["embedding"].T
                else:
                    head = nn.Dense(cfg.vocab_size, use_bias=False,
                                    dtype=hd, param_dtype=jnp.float32,
                                    name="lm_head")
                    # one-row call creates/binds lm_head with the standard
                    # {kernel} layout (checkpoint/HF-ingest compatible); the
                    # unused output is dead code to XLA
                    head(x[:, :1].astype(hd))
                    w = head.variables["params"]["kernel"]
                return _lm_loss_chunked(x, w, labels, attention_mask,
                                        cfg.loss_chunk_vocab, hd)
            if cfg.tie_word_embeddings:
                logits = embed.attend(x.astype(hd))
            else:
                logits = nn.Dense(cfg.vocab_size, use_bias=False,
                                  dtype=hd, param_dtype=jnp.float32,
                                  name="lm_head")(x.astype(hd))
            if labels is None:
                return logits
            return _lm_loss(logits, labels, attention_mask)

    @nn.nowrap
    def streaming_parts(self):
        """ZeRO-Infinity param-streaming protocol (``runtime/zero/infinity``):
        expose the model as embed → L homogeneous blocks → head so the
        executor can stream one block's params HBM-resident at a time.
        Reference role: ``deepspeed/runtime/zero/partitioned_param_coordinator
        .py:276`` fetch/release over submodules — here the split is explicit
        because the executor drives per-block jitted calls.
        ``nn.nowrap``: the helper modules must be constructed OUTSIDE this
        module's scope machinery."""
        return llama_streaming_parts(self.config)


def llama_streaming_parts(cfg):
    from ..runtime.zero.infinity import StreamingSpec
    dtype = jnp.dtype(cfg.dtype)
    embed_mod = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, dtype=dtype)
    block_mod = LlamaBlock(cfg)
    norm_mod = RMSNorm(cfg.rms_norm_eps, dtype)
    hd = jnp.dtype(cfg.head_dtype)
    head_mod = (None if cfg.tie_word_embeddings else
                nn.Dense(cfg.vocab_size, use_bias=False, dtype=hd,
                         param_dtype=jnp.float32))
    block_keys = tuple(f"layers_{i}" for i in range(cfg.num_hidden_layers))
    resident_keys = ("embed_tokens", "norm") + \
        (() if cfg.tie_word_embeddings else ("lm_head", ))

    def embed_apply(res, input_ids, labels=None, attention_mask=None):
        return embed_mod.apply({"params": res["embed_tokens"]}, input_ids)

    def block_apply(w, x):
        # attention_mask intentionally not threaded: the monolithic
        # LlamaAttention also ignores it inside attention (causal-only
        # kernels); padding is handled at the loss (same _lm_loss in
        # head_apply), so streamed and monolithic trajectories agree
        return block_mod.apply({"params": w}, x, None, False)

    def head_apply(res, x, input_ids, labels=None, attention_mask=None):
        x = norm_mod.apply({"params": res["norm"]}, x)
        if cfg.tie_word_embeddings:
            logits = embed_mod.apply({"params": res["embed_tokens"]},
                                     x.astype(hd),
                                     method=embed_mod.attend)
        else:
            logits = head_mod.apply({"params": res["lm_head"]},
                                    x.astype(hd))
        if labels is None:
            return logits
        return _lm_loss(logits, labels, attention_mask)

    def init_block(rng, key, x):
        return block_mod.init(rng, x)["params"]

    def init_resident(rng, input_ids, labels=None, attention_mask=None):
        r_embed, r_norm, r_head = jax.random.split(rng, 3)
        x = jnp.zeros(
            (*np.asarray(input_ids).shape, cfg.hidden_size), dtype)
        res = {"embed_tokens": embed_mod.init(r_embed, input_ids)["params"],
               "norm": norm_mod.init(r_norm, x)["params"]}
        if not cfg.tie_word_embeddings:
            res["lm_head"] = head_mod.init(
                r_head, x.astype(hd))["params"]
        return res

    return StreamingSpec(block_keys=block_keys,
                         resident_keys=resident_keys,
                         embed_apply=embed_apply, block_apply=block_apply,
                         head_apply=head_apply, init_block=init_block,
                         init_resident=init_resident)


def tp_rules(config: LlamaConfig):
    """AutoTP-style sharding rules: param-path suffix → PartitionSpec.
    Column-parallel q/k/v/gate/up (+ embed vocab dim), row-parallel o/down.

    The ``"zero"`` pseudo-axis pins where the ZeRO-3 shard lands (expanded by
    ``ZeroPartitionPlan`` per stage).  Placement is chosen so ZeRO never
    shards a contracting/hidden dim: GSPMD would otherwise propagate
    hidden-dim sharding into the activations and full-rematerialize them back
    to (dp, sp) batch/seq sharding at every norm boundary (the round-1
    "involuntary full rematerialization" warnings).  q/k/v take it on the
    HEADS (``(tp, "zero")`` on dim 1: every zero axis that divides the heads
    lands there, 8 query and 2 K/V heads a chip for Mistral at dp=4) and only
    what is left over on the head dim (the trailing ``"zero"``; an axis is
    placed once); o/gate/up/down on their output dim, embed/lm_head on
    vocab.  Whole heads are whole lane tiles: on a four-chip host the TPU
    compiler windows each product over the kernel's shards and writes every
    partial product where the shard sits, so a shard of 32 of a head's 128
    lanes, on the dim rotary splits next, cost q and k a
    ``dynamic-update-slice`` and two layout copies a piece (60 ms of a
    556 ms step, PERF.md section 6, PR 57); on the heads the pieces are
    written in place, as the MLP's are (but for K's in the first forward,
    2 of the 8 heads its output keeps on one sublane tile: 4 ms).
    """
    tp = "tp"
    return {
        "q_proj/kernel": P(None, (tp, "zero"), "zero"),
        "k_proj/kernel": P(None, (tp, "zero"), "zero"),
        "v_proj/kernel": P(None, (tp, "zero"), "zero"),
        "o_proj/kernel": P(tp, "zero"),
        "gate_proj/kernel": P(None, (tp, "zero")),
        "up_proj/kernel": P(None, (tp, "zero")),
        "down_proj/kernel": P(tp, "zero"),
        "embed_tokens/embedding": P((tp, "zero"), None),
        "lm_head/kernel": P(None, (tp, "zero")),
    }


def param_count(config: LlamaConfig):
    D, I, V, L = (config.hidden_size, config.intermediate_size,
                  config.vocab_size, config.num_hidden_layers)
    H, Hkv, Dh = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim)
    per_layer = (D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D) + 3 * D * I + 2 * D
    total = V * D + L * per_layer + D
    if not config.tie_word_embeddings:
        total += D * V
    return total
