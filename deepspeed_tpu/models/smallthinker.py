"""SmallThinker (``PowerInfer/SmallThinker-21BA3B-Instruct``): a pre-norm
block whose router reads the layer's INPUT, ReLU-gated (ReGLU) experts, and
window layers with rotary beside full layers with no positions at all.  A
training-capable flax module on the engine's normal path.

Layer ``l`` on its input ``x [T, D]``, with ``E`` the router's width and ``k``
experts a token:

    r = float32(x) W_r                       the router reads x AS IT ENTERS
    h = x + W_o Attn_l(RMSNorm_1(x))         grouped-query, causal, no bias
        sliding_window_layout[l] = 1: key j is seen iff 0 <= i - j < window
        rope_layout[l] = 1: rotary on q and k (half-split);  0: no positions
    p = softmax(r) over all E;  top k;  w_i = p_i / sum_i p_i   (norm_topk_prob)
    y = h + sum_i w_i W2[e_i] (relu(W1[e_i] u) * W3[e_i] u),   u = RMSNorm_2(h)

then the final RMSNorm and an untied head.  Attention runs through
``ops.attention.attention_core`` with the LAYER's window (the flash kernel on
a TPU, its dead key blocks skipped): no ``S x S`` mask is made.

**One chip's share.**  ``moe_num_primary_experts`` is the ROUTER'S width;
``experts_held`` (default: all) and ``first_expert`` say which experts' stacks
this model holds (``moe/w1, w3 [held, D, I]``, ``moe/w2 [held, I, D]``).  The
top k and their weights are taken over the full width, the sum runs over the
chosen experts that are held, nothing stands in for the rest, and that
partial ``y`` goes on to the next layer (``moe/held_experts.py``).

**Counted on the device.**  With labels the model returns ``(loss, counts)``:
``counts [4] int32`` in the order of :data:`DEVICE_COUNTS`, summed over the
layers.  The engine reads ``SmallThinkerModel.device_counts`` and books them
on a later step's ``ds:train.micro`` span (docs/observability.md).
"""

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..moe.held_experts import held_experts_apply, in_blocks, route
from ..runtime.activation_checkpointing import resolve_policy
from ..telemetry import names as _names
from .llama import (RMSNorm, _lm_loss, _lm_loss_chunked, _rope_freqs,
                    apply_rotary)

#: what the model counts on the device in a training micro-step, in the order
#: of the vector it returns beside the loss
DEVICE_COUNTS = (_names.COUNT_EXPERT_COPIES, _names.COUNT_EXPERT_ACTIVE,
                 _names.COUNT_EXPERT_ROWS_MAX,
                 _names.COUNT_EXPERT_PADDED_CALLS)


@dataclass(frozen=True)
class SmallThinkerConfig:
    """The keys of the published ``config.json`` by their own names, what a
    chip holds of a layer (``experts_held``, ``first_expert``) and the
    program's own switches (as ``LlamaConfig`` names them)."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 16384
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    sliding_window_size: int = 4096
    # one entry a layer; None: a full layer with no positions first, then
    # three window layers with rotary, and so on (the published layouts)
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    moe_num_primary_experts: int = 64      # the router's width
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    experts_held: Optional[int] = None     # None: all of them
    first_expert: int = 0
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"                # the head and the loss: float32
    loss_chunk_vocab: int = 0              # LlamaConfig.loss_chunk_vocab
    remat: bool = True
    remat_policy: str = "flash_residuals_saveable"

    def __post_init__(self):
        if self.tie_word_embeddings or \
                not self.moe_primary_router_apply_softmax:
            raise ValueError("SmallThinkerConfig: an untied head and a "
                             "softmax router are what this model implements")
        for name, layout in (("sliding_window_layout", self.windows),
                             ("rope_layout", self.rotaries)):
            if len(layout) < self.num_hidden_layers:
                raise ValueError(f"{name} has {len(layout)} entries for "
                                 f"{self.num_hidden_layers} layers")
        if not 0 <= self.first_expert <= \
                self.moe_num_primary_experts - self.held:
            raise ValueError("the held experts lie outside the router")

    @property
    def held(self):
        return self.moe_num_primary_experts if self.experts_held is None \
            else self.experts_held

    def _layout(self, given):
        if given is not None:
            return tuple(int(v) for v in given[:self.num_hidden_layers])
        return tuple(int(i % 4 != 0) for i in range(self.num_hidden_layers))

    @property
    def windows(self):
        """Each layer's window in tokens; 0: every earlier key is seen."""
        return tuple(self.sliding_window_size * on
                     for on in self._layout(self.sliding_window_layout))

    @property
    def rotaries(self):
        """Each layer's rotary switch; 0: the layer has no positions."""
        return self._layout(self.rope_layout)


def smallthinker_tiny(**overrides):
    """Test-scale config: 8 experts, top 2, a window of 16."""
    return SmallThinkerConfig(**{**dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        max_position_embeddings=256, sliding_window_size=16,
        moe_num_primary_experts=8, moe_num_active_primary_experts=2,
        moe_ffn_hidden_size=32), **overrides})


class SmallThinkerAttention(nn.Module):
    """Grouped-query causal attention with the LAYER's window (0: none) and
    rotary switch."""
    config: SmallThinkerConfig
    window: int
    rotary: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = x.shape
        H, Hkv, Dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=dtype,
                        param_dtype=jnp.float32)
        # the block's parts under names of their own, as LlamaAttention's
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            q = dense(features=(H, Dh), name="q_proj")(x)
            k = dense(features=(Hkv, Dh), name="k_proj")(x)
            v = dense(features=(Hkv, Dh), name="v_proj")(x)
        if self.rotary:
            cos, sin = _rope_freqs(Dh, cfg.max_position_embeddings,
                                   cfg.rope_theta)
            cos = jnp.asarray(cos, jnp.float32)
            sin = jnp.asarray(sin, jnp.float32)
            with jax.named_scope(_names.SCOPE_ATTN_ROTARY):
                q = apply_rotary(q, cos, sin)
                k = apply_rotary(k, cos, sin)
        if Hkv != H:        # repeat kv heads up to H for the local core
            with jax.named_scope(_names.SCOPE_ATTN_KV_REPEAT):
                k = jnp.repeat(k, H // Hkv, axis=2)
                v = jnp.repeat(v, H // Hkv, axis=2)
        from ..ops.attention import attention_core
        with jax.named_scope(_names.SCOPE_ATTN_CORE):
            out = attention_core(q, k, v, causal=True, window=self.window)
        with jax.named_scope(_names.SCOPE_ATTN_PROJ):
            return dense(features=D, axis=-1, name="o_proj")(
                out.reshape(B, S, H * Dh))


class SmallThinkerMoe(nn.Module):
    """The router over the layer's input and the held ReGLU experts over the
    normed stream: ``(sum [B, S, D], copies on each held expert [held])``."""
    config: SmallThinkerConfig

    @nn.compact
    def __call__(self, router_input, u):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        B, S, D = u.shape
        E, H, I = (cfg.moe_num_primary_experts, cfg.held,
                   cfg.moe_ffn_hidden_size)
        with jax.named_scope(_names.SCOPE_MOE_ROUTER):
            router_logits = nn.Dense(
                E, use_bias=False, dtype=jnp.float32,
                param_dtype=jnp.float32, name="gate")(
                    router_input.reshape(-1, D).astype(jnp.float32))
            topi, topw = route(router_logits,
                               cfg.moe_num_active_primary_experts, "softmax",
                               cfg.norm_topk_prob)
        init = nn.initializers.lecun_normal(in_axis=1, out_axis=2,
                                            batch_axis=0)
        stack = lambda name, *shape: self.param(
            name, init, shape, jnp.float32).astype(dtype)
        w1, w3, w2 = (stack("w1", H, D, I), stack("w3", H, D, I),
                      stack("w2", H, I, D))
        with jax.named_scope(_names.SCOPE_MOE_EXPERTS):
            out, counts = held_experts_apply(
                u.reshape(-1, D), topi, topw, w1, w2, w3,
                first_expert=cfg.first_expert, experts=E, act=jax.nn.relu)
        return out.reshape(B, S, D), counts


class SmallThinkerBlock(nn.Module):
    config: SmallThinkerConfig
    window: int
    rotary: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        h = x + SmallThinkerAttention(
            cfg, self.window, self.rotary, name="self_attn")(
                RMSNorm(cfg.rms_norm_eps, dtype, name="input_layernorm")(x))
        with jax.named_scope(_names.SCOPE_MLP):
            out, counts = SmallThinkerMoe(cfg, name="moe")(
                x, RMSNorm(cfg.rms_norm_eps, dtype,
                           name="post_attention_layernorm")(h))
        return h + out, counts


class SmallThinkerModel(nn.Module):
    """Causal LM.  ``__call__(input_ids)`` -> logits; with ``labels`` ->
    ``(loss, counts [4] int32)``, the mean next-token cross-entropy and
    :data:`DEVICE_COUNTS` summed over the layers."""
    config: SmallThinkerConfig
    #: the engine's protocol for counts made on the device in a micro-step
    device_counts = DEVICE_COUNTS

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        # rows of unit elements: layer 0's router reads them un-normalised,
        # and its logits then spread as every later layer's do
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, dtype=dtype,
                         embedding_init=nn.initializers.normal(1.0),
                         name="embed_tokens")
        with jax.named_scope(_names.SCOPE_EMBED):
            x = embed(input_ids)

        block = SmallThinkerBlock
        if cfg.remat:
            block = nn.remat(SmallThinkerBlock,
                             policy=resolve_policy(cfg.remat_policy))
        landed = []
        for i, (window, rotary) in enumerate(zip(cfg.windows, cfg.rotaries)):
            x, counts = block(cfg, window, rotary, name=f"layers_{i}")(x)
            landed.append(counts)
        landed = jnp.stack(landed)                      # [layers, held]

        x = RMSNorm(cfg.rms_norm_eps, dtype, name="norm")(x)
        with jax.named_scope(_names.SCOPE_LM_HEAD_LOSS):
            hd = jnp.float32
            head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=hd,
                            param_dtype=jnp.float32, name="lm_head")
            if labels is None:
                return head(x.astype(hd))
            if cfg.loss_chunk_vocab:
                head(x[:, :1].astype(hd))   # bind; dead code to XLA
                loss = _lm_loss_chunked(
                    x, head.variables["params"]["kernel"], labels,
                    attention_mask, cfg.loss_chunk_vocab, hd)
            else:
                loss = _lm_loss(head(x.astype(hd)), labels, attention_mask)
        tokens = input_ids.size
        return loss, jnp.stack([
            jnp.sum(landed), jnp.sum(landed > 0),
            jnp.sum(jnp.max(landed, axis=1)),
            jnp.sum(in_blocks(landed, tokens,
                              cfg.moe_num_active_primary_experts,
                              cfg.moe_num_primary_experts))])


def tp_rules(config: SmallThinkerConfig):
    """Sharding rules: attention, embedding and head as Llama's (the ZeRO
    shard on a dim that is not contracted: q/k/v on the heads, what does
    not divide them on the head dim), the experts over "ep" on the expert
    axis as Mixtral's."""
    tp = "tp"
    return {
        "q_proj/kernel": P(None, (tp, "zero"), "zero"),
        "k_proj/kernel": P(None, (tp, "zero"), "zero"),
        "v_proj/kernel": P(None, (tp, "zero"), "zero"),
        "o_proj/kernel": P(tp, "zero"),
        "embed_tokens/embedding": P((tp, "zero"), None),
        "lm_head/kernel": P(None, (tp, "zero")),
        "moe/gate/kernel": P(None, None),
        "moe/w1": P("ep", None, (tp, "zero")),
        "moe/w3": P("ep", None, (tp, "zero")),
        "moe/w2": P("ep", (tp, "zero"), None),
    }
