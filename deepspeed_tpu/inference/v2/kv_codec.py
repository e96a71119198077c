"""Quantized paged-KV codecs — ``kv_cache_dtype: int8 | fp8`` serving mode.

Built on the :mod:`deepspeed_tpu.comm.collectives.quantized` codec family
(the ZeRO++ lineage, arxiv 2306.10209): the paged KV cache stores values in
a narrow wire format plus one f32 scale per written token row, so one chip
holds ~2-4× more concurrent sequences than a bf16/f32 cache.  Quantization
happens once, on the cache-scatter write; the ragged forward dequantizes
**on read** — only the gathered attention context is ever widened, never
the whole cache.

Scale granularity is per (layer, k/v, token, head): one scale over a
token's ``[Dh]`` head row — 4·Hkv bytes/token/layer of overhead (well
under 2% for Dh ≥ 64), fine enough that int8 greedy decode stays
token-identical to the fp cache (``tests/unit/serving/test_kv_quant.py``
pins this over ≥64 decode steps).

TPU note: the quantized path reads through the XLA gather fallback of
``ragged_forward._paged_attention`` — the Pallas paged kernel streams fp
pages and does not (yet) consume scales, so ``use_kernel`` is forced off
when a codec is active.
"""

import jax.numpy as jnp

from ...comm.collectives.quantized import (ROWWISE_FORMATS, rowwise_codec,
                                           rowwise_storage_dtype)

#: accepted ``kv_cache_dtype`` spellings → canonical wire format
KV_CACHE_DTYPES = {"int8": "int8", "q8": "int8",
                   "fp8": "fp8", "fp8_e4m3": "fp8", "e4m3": "fp8"}


def resolve_kv_dtype(name):
    """``kv_cache_dtype`` config value → canonical format name or None.

    Unknown formats raise loudly at engine build (a typo must not silently
    serve an fp cache while the operator budgets for a quantized one)."""
    if name is None:
        return None
    fmt = KV_CACHE_DTYPES.get(str(name).lower())
    if fmt is None:
        raise ValueError(
            f"kv_cache_dtype={name!r} is not a quantized-KV format "
            f"(have {sorted(set(KV_CACHE_DTYPES))}; unset = full-precision "
            "cache)")
    return fmt


def storage_dtype(fmt):
    """Canonical format → element dtype the cache array is allocated as."""
    return rowwise_storage_dtype(fmt)


def codec(fmt):
    """Canonical format → (encode, decode) over ``[..., Hkv, Dh]`` values
    with one scale per ``[Dh]`` head row (decode returns f32)."""
    assert fmt in ROWWISE_FORMATS, fmt
    return rowwise_codec(fmt, reduce_axes=1)


def kv_bytes_per_token(num_layers, num_kv_heads, head_dim, fmt=None,
                       fp_dtype=jnp.bfloat16):
    """Cache bytes one token occupies (both K and V, all layers).
    ``fmt=None`` is the full-precision cache in ``fp_dtype``."""
    elems = 2 * num_layers * num_kv_heads * head_dim
    if fmt is None:
        return elems * jnp.dtype(fp_dtype).itemsize
    # int8 and fp8 both store 1 byte/element + one f32 scale per (layer,
    # k/v, token, head) row
    return (elems * jnp.dtype(storage_dtype(fmt)).itemsize
            + 2 * num_layers * num_kv_heads * 4)
