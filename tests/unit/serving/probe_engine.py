"""The decisive-logits probe model and a tiny paged engine over it: what
the quantized-KV parity tests compare codecs on."""

import numpy as np

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.models import llama


VOCAB = 64


def probe_model():
    """A tiny llama whose greedy decode is a deterministic walk with LARGE
    argmax margins (≫ int8-KV quantization noise), so a token-identity
    comparison measures the cache codec, not coin-flips on a random-init
    model's near-uniform logits.

    Identity embeddings scaled by 12 make the residual stream dominated
    by the last token's coordinate; a permutation lm_head (×8) maps that
    coordinate to a shifted next token — the model walks a 64-cycle
    modulated by the (random-init, fully exercised) attention/MLP blocks.
    Top-1/top-2 margin ≈ 20-30 against ≤ 0.1 of int8-KV logit error.
    Returns (model, params)."""
    cfg = llama.llama_tiny(dtype="float32", remat=False, vocab_size=VOCAB,
                           hidden_size=VOCAB, num_key_value_heads=2)
    model = llama.LlamaModel(cfg)
    params = dict(model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"])
    params["embed_tokens"] = {
        "embedding": 12.0 * jnp.eye(VOCAB, dtype=jnp.float32)}
    perm = (np.arange(VOCAB) + 17) % VOCAB    # coprime shift → full cycle
    head = np.zeros((VOCAB, VOCAB), np.float32)
    head[np.arange(VOCAB), perm] = 1.0
    params["lm_head"] = {"kernel": 8.0 * jnp.asarray(head)}
    return model, params


def probe_engine(kv_dtype=None):
    """An ``InferenceEngineV2`` over :func:`probe_model` with a roomy pool
    (96 blocks of 16 tokens) and decode bursts of 8."""
    model, params = probe_model()
    sm = dict(max_tracked_sequences=16, max_ragged_batch_size=64,
              max_ragged_sequence_count=12, max_context=256,
              block_size=16, num_blocks=96)
    return InferenceEngineV2(
        model, params=params,
        config=dict(dtype="float32", decode_burst=8,
                    kv_cache_dtype=kv_dtype, state_manager=sm))
