"""Ouro (``model_type: ouro``; "Scaling Latent Reasoning via Looped Language
Models", ByteDance Seed, arXiv:2510.25741): a LOOPED language model.  ONE
stack of ``L = num_hidden_layers`` layers is run ``T = total_ut_steps`` times
a token, the same weights in every pass; the final norm is applied after
EVERY pass and its output is the next pass's input; an exit gate reads every
pass's output.

With ``N_*`` an RMSNorm (``rms_norm_eps``), layer ``l``'s weights THE SAME in
every pass ``t``:

    h(0)   = E[ids]                                   (embedding, untied)
    for t in 1..T:
        x = h(t-1)
        for l in 1..L:              (sandwich norms: before AND after a sublayer)
            x = x + N_in2,l ( Attn_l  ( N_in,l  (x) ) )
            x = x + N_post2,l( SwiGLU_l( N_post,l(x) ) )
        h(t)   = N_final(x)         (ONE final norm, after every pass)
        lam(t) = sigmoid( h(t) . w_gate + b_gate )      (``early_exit_gate``)
    p(t) = lam(t) * prod_{j<t} (1 - lam(j))  for t < T;  p(T) = prod_{j<T} (1 - lam(j))
    logits = h(T) W_head

``Attn_l`` is Llama's (``models/llama.py``: rotary over the whole head,
half-split pairs, the SAME positions in every pass, causal softmax in
float32), over the keys that pass ``t`` of layer ``l`` wrote: a pass never
reads another pass's keys or values.  So a cache keeps ``T x L`` entries a
token (``kv_cache_entries``), entry ``(t-1) * L + (l-1)`` for pass ``t`` of
layer ``l`` (the published ``UniversalTransformerCache``'s index), and the
``T`` entries of one layer's weights live in ONE buffer, pass-major
(``kv_entries_a_buffer``; ``inference/v2/ragged.py``), so that a serving step
can ROLL the loop over the passes (``ragged_forward.ouro_ragged_step``).

``early_exit_threshold`` 1.0 (the published value): the exit distribution's
CDF reaches 1 at ``T`` only, every token runs all ``T`` passes and the logits
are the last pass's; ``p`` is computed and counted, not acted on.  A threshold
under 1 would let rows of one batch leave the loop after different numbers of
passes; nothing here does that, and the config refuses it.

Leaves: ``embed_tokens/embedding``, ``layers_<l>/{input_layernorm,
input_layernorm_2, post_attention_layernorm, post_attention_layernorm_2}/
weight``, ``layers_<l>/self_attn/{q,k,v}_proj/kernel [D, heads, Dh]``,
``o_proj/kernel``, ``layers_<l>/mlp/{gate,up,down}_proj/kernel``,
``norm/weight``, ``early_exit_gate/{kernel [D, 1], bias [1]}``,
``lm_head/kernel``: ``L`` layers whatever ``T`` is.  The two POST-sublayer
gains (``input_layernorm_2``, ``post_attention_layernorm_2``) are held ``[D /
32, 32]``, the hidden axis row-major (``POST_NORM_LANES``): a data format (a
checkpoint's ``[D]`` vector reshaped), chosen for what a generator of seeded
weights draws from it.  A post-sublayer gain of ONE makes every branch as
large as the whole stream was going in: 192 such branches in a row are a
chaotic map (rounding grows until a bfloat16 run and a float32 run part ways)
that also forgets its input (every position drifts to one vector); a trained
model's are small.  A generator that sets 1-D leaves to one and draws 2-D
leaves at ``1 / sqrt(rows)`` draws this leaf at 0.125
(``perfbench/configs/ouro_2_6b_1chip.json``, ``assumed.weights``, has the
readings).
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import PartitionSpec as P

from ..telemetry import names as _names
from . import llama
from .llama import LlamaAttention, LlamaMLP, RMSNorm, _lm_loss, \
    _lm_loss_chunked


#: columns of a post-sublayer gain's leaf ``[hidden / 32, 32]`` (the module's
#: docstring says why it is not ``[hidden]``)
POST_NORM_LANES = 32


@dataclass(frozen=True)
class OuroConfig:
    """Ouro-2.6B as published: the defaults."""
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    tie_word_embeddings: bool = False
    total_ut_steps: int = 4           # passes of the stack a token
    early_exit_threshold: float = 1.0
    dtype: str = "bfloat16"
    head_dtype: str = "float32"       # LlamaConfig's
    loss_chunk_vocab: int = 0         # LlamaConfig's
    # what LlamaAttention reads and this model never sets
    sliding_window: int = 0
    attention_bias: bool = False
    rope_scaling = None
    use_ulysses: bool = False
    sp_backend: str = "ulysses"

    def __post_init__(self):
        if self.early_exit_threshold < 1.0:
            raise NotImplementedError(
                f"early_exit_threshold {self.early_exit_threshold} < 1: rows "
                "of one batch would leave the loop after different numbers "
                "of passes, which neither the model nor the serving step does")
        if self.total_ut_steps < 1 or self.hidden_size % POST_NORM_LANES:
            raise ValueError("total_ut_steps counts the passes: at least 1; "
                             "hidden_size is whole rows of POST_NORM_LANES")

    @property
    def kv_cache_entries(self):
        """Entries of the paged cache: one a (pass, layer) pair."""
        return self.total_ut_steps * self.num_hidden_layers

    @property
    def kv_entries_a_buffer(self):
        """Of those, how many live in ONE buffer, pass-major: a layer's."""
        return self.total_ut_steps


def ouro_tiny(**overrides):
    """Test-scale config: 2 layers x 3 passes, so that a pass index and a
    layer index cannot be exchanged unnoticed."""
    return OuroConfig(**{**dict(vocab_size=256, hidden_size=64,
                                intermediate_size=128, num_hidden_layers=2,
                                num_attention_heads=4, num_key_value_heads=4,
                                head_dim=16, max_position_embeddings=256,
                                total_ut_steps=3),
                         **overrides})


def exit_distribution(lam):
    """``p [T, ...]`` from the gates ``lam [T, ...]`` (float32): ``p(t) =
    lam(t) prod_{j<t} (1 - lam(j))`` for ``t < T``, the last pass takes what
    is left (``lam(T)`` is not read); sums to 1 over ``t``."""
    lam = jnp.asarray(lam, jnp.float32)
    # before[t]: prod_{j<t} (1 - lam(j)), what has not left before pass t
    before = jnp.concatenate([jnp.ones_like(lam[:1]),
                              jnp.cumprod(1.0 - lam[:-1], axis=0)])
    return jnp.concatenate([lam[:-1] * before[:-1], before[-1:]])


def exit_gate(h, gate):
    """``lam = sigmoid(h . w + b)`` in float32; ``h [..., D]`` -> ``[...]``."""
    z = h.astype(jnp.float32) @ gate["kernel"].astype(jnp.float32)
    return jax.nn.sigmoid(z[..., 0] + gate["bias"].astype(jnp.float32)[0])


class PostNorm(nn.Module):
    """``RMSNorm`` whose gain is held ``[D / POST_NORM_LANES,
    POST_NORM_LANES]`` (the module's docstring)."""
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        w = self.param("weight", nn.initializers.ones,
                       (d // POST_NORM_LANES, POST_NORM_LANES))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.eps)
                * w.reshape(d)).astype(self.dtype)


class OuroLayer(nn.Module):
    """A sandwich-norm layer: each sublayer normed going in AND coming out."""
    config: OuroConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype, name=name)
        after = lambda name: PostNorm(cfg.rms_norm_eps, dtype, name=name)
        a = LlamaAttention(cfg, name=_names.MODULE_ATTENTION)(
            norm("input_layernorm")(x))
        x = x + after("input_layernorm_2")(a)
        m = LlamaMLP(cfg, name=_names.MODULE_MLP)(
            norm("post_attention_layernorm")(x))
        return x + after("post_attention_layernorm_2")(m)


class OuroModel(nn.Module):
    """Causal LM.  ``__call__(input_ids)`` -> logits ``[B, S, vocab]`` of the
    LAST pass; with ``labels`` the last pass's cross-entropy (Llama's two loss
    paths); with ``return_gates`` ``(logits, lam [T, B, S])``."""
    config: OuroConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None,
                 return_gates=False):
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        with jax.named_scope(_names.SCOPE_EMBED):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, dtype=dtype,
                         name="embed_tokens")(input_ids)
        # ONE stack of modules, called in every pass: the weights are shared
        layers = [OuroLayer(cfg, name=f"layers_{i}")
                  for i in range(cfg.num_hidden_layers)]
        final = RMSNorm(cfg.rms_norm_eps, dtype, name="norm")
        gate = nn.Dense(1, param_dtype=jnp.float32, dtype=jnp.float32,
                        name="early_exit_gate")
        lam = []
        for _ in range(cfg.total_ut_steps):
            for layer in layers:
                x = layer(x)
            x = final(x)
            lam.append(jax.nn.sigmoid(gate(x.astype(jnp.float32))[..., 0]))
        with jax.named_scope(_names.SCOPE_LM_HEAD_LOSS):
            hd = jnp.dtype(cfg.head_dtype)
            head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=hd,
                            param_dtype=jnp.float32, name="lm_head")
            if cfg.loss_chunk_vocab and labels is not None:
                head(x[:, :1].astype(hd))       # binds lm_head/kernel
                return _lm_loss_chunked(
                    x, head.variables["params"]["kernel"], labels,
                    attention_mask, cfg.loss_chunk_vocab, hd)
            logits = head(x.astype(hd))
        if labels is not None:
            return _lm_loss(logits, labels, attention_mask)
        return (logits, jnp.stack(lam)) if return_gates else logits


def tp_rules(config: OuroConfig):
    """Llama's rules (the layer's matrices are Llama's); the gate's 2049
    numbers are replicated."""
    return {**llama.tp_rules(config),
            "early_exit_gate/kernel": P(None, None),
            "early_exit_gate/bias": P(None)}
