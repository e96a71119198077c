"""Floating-point quantization — FP8 / FP6 / FP12 (reference
``csrc/fp_quantizer/fp_quantize.cu`` + ``deepspeed/ops/fp_quantizer/
quantize.py`` API; backs FP6-LLM-style weight-only inference quant and the
qwZ ``fp8``/``fp6`` wire formats).

Format is parametrized exactly like the reference: ``q_bits`` total with
``mantissa_bits`` mantissa → ``exp_bits = q_bits - mantissa_bits - 1``:

    (8, 3) = e4m3   (native jnp.float8_e4m3fn cast on TPU — zero bit math)
    (6, 2) = e3m2   (FP6-LLM format, max 28)
    (12, 7) = e4m7

Per-group symmetric scaling (scale = absmax / fmt_max) like the int8
quantizer; codes are bit-packed for transport (4×6b → 3B, 2×12b → 3B).

TPU design note: the heavy op is the grouped absmax + round-to-grid, done by
one Pallas kernel (or a single XLA fusion on the fallback path); the packing
is pure lane-local integer shifts that XLA fuses into the same program — the
reference needs 850 LoC of CUDA for what the TPU compiler mostly does for
free here.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas._common import interpret_mode as _interpret
from .pallas.quantizer import _group_view, _pick_block

_LANES = 128


def _fmt(q_bits, mantissa_bits):
    exp_bits = q_bits - mantissa_bits - 1
    if exp_bits < 2:
        raise ValueError(f"q_bits={q_bits}, mantissa_bits={mantissa_bits} "
                         "leaves <2 exponent bits")
    bias = 2 ** (exp_bits - 1) - 1
    max_unb = (2 ** exp_bits - 1) - bias
    maxv = (2.0 - 2.0 ** (-mantissa_bits)) * 2.0 ** max_unb
    return exp_bits, bias, max_unb, maxv


def _floor_log2(a):
    """Exact floor(log2(a)) for normal positive fp32, via the exponent bits
    (``frexp`` has no Mosaic lowering; this is shifts on the VPU)."""
    bits = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    return (jnp.right_shift(bits, 23) & 0xFF).astype(jnp.int32) - 127


def round_to_fp_grid(y, q_bits, mantissa_bits):
    """Round ``y`` (already scaled into range) to the nearest representable
    value of the (q_bits, mantissa_bits) float grid.  Subnormals included;
    values beyond the grid max saturate.  Pure elementwise — differentiable
    under a straight-through estimator."""
    exp_bits, bias, max_unb, maxv = _fmt(q_bits, mantissa_bits)
    a = jnp.abs(y.astype(jnp.float32))
    # exponent of each value; clamp to the normal range (min side gives the
    # subnormal step automatically)
    e = _floor_log2(jnp.maximum(a, jnp.finfo(jnp.float32).tiny))
    e = jnp.clip(e, 1 - bias, max_unb)
    step = jnp.exp2((e - mantissa_bits).astype(jnp.float32))
    q = jnp.round(a / step) * step
    q = jnp.minimum(q, maxv)
    return jnp.sign(y) * q


def encode_fp(v, q_bits, mantissa_bits):
    """Exactly-representable value → integer code (sign|exp|mantissa)."""
    exp_bits, bias, max_unb, _ = _fmt(q_bits, mantissa_bits)
    a = jnp.abs(v.astype(jnp.float32))
    sign = (v < 0).astype(jnp.uint32)
    e = _floor_log2(jnp.maximum(a, jnp.finfo(jnp.float32).tiny))
    normal = a >= 2.0 ** (1 - bias)
    efield = jnp.where(normal, e + bias, 0).astype(jnp.uint32)
    # a / 2^e in [1, 2) for normals — exact power-of-two scaling
    man_norm = jnp.round((a * jnp.exp2(-e.astype(jnp.float32)) - 1.0)
                         * 2.0 ** mantissa_bits)
    man_sub = jnp.round(a * 2.0 ** (mantissa_bits - (1 - bias)))
    mfield = jnp.where(normal, man_norm, man_sub).astype(jnp.uint32)
    mfield = jnp.where(a == 0.0, 0, mfield)
    efield = jnp.where(a == 0.0, 0, efield)
    return (sign << (q_bits - 1)) | (efield << mantissa_bits) | mfield


def decode_fp(code, q_bits, mantissa_bits, dtype=jnp.float32):
    """Integer code → value."""
    exp_bits, bias, max_unb, _ = _fmt(q_bits, mantissa_bits)
    code = code.astype(jnp.uint32)
    sign = (code >> (q_bits - 1)) & 0x1
    efield = (code >> mantissa_bits) & ((1 << exp_bits) - 1)
    mfield = code & ((1 << mantissa_bits) - 1)
    normal = efield > 0
    mag = jnp.where(
        normal,
        (1.0 + mfield.astype(jnp.float32) * 2.0 ** (-mantissa_bits))
        * jnp.exp2(efield.astype(jnp.float32) - bias),
        mfield.astype(jnp.float32)
        * 2.0 ** ((1 - bias) - mantissa_bits))
    return (jnp.where(sign == 1, -mag, mag)).astype(dtype)


# ----------------------------------------------------------------- packing
def pack_codes(codes, q_bits):
    """[N] uint32 codes → packed uint8.  6-bit: 4 → 3 bytes; 12-bit: 2 → 3
    bytes; 8-bit: identity bytes."""
    if q_bits == 8:
        return codes.astype(jnp.uint8)
    if q_bits == 6:
        c = codes.reshape(-1, 4)
        b0 = (c[:, 0] << 2) | (c[:, 1] >> 4)
        b1 = ((c[:, 1] & 0xF) << 4) | (c[:, 2] >> 2)
        b2 = ((c[:, 2] & 0x3) << 6) | c[:, 3]
        return jnp.stack([b0, b1, b2], axis=1).astype(jnp.uint8).reshape(-1)
    if q_bits == 12:
        c = codes.reshape(-1, 2)
        b0 = c[:, 0] >> 4
        b1 = ((c[:, 0] & 0xF) << 4) | (c[:, 1] >> 8)
        b2 = c[:, 1] & 0xFF
        return jnp.stack([b0, b1, b2], axis=1).astype(jnp.uint8).reshape(-1)
    raise ValueError(f"no packing for q_bits={q_bits}")


def unpack_codes(packed, q_bits, n):
    if q_bits == 8:
        return packed.astype(jnp.uint32)[:n]
    p = packed.astype(jnp.uint32).reshape(-1, 3)
    if q_bits == 6:
        c0 = p[:, 0] >> 2
        c1 = ((p[:, 0] & 0x3) << 4) | (p[:, 1] >> 4)
        c2 = ((p[:, 1] & 0xF) << 2) | (p[:, 2] >> 6)
        c3 = p[:, 2] & 0x3F
        return jnp.stack([c0, c1, c2, c3], axis=1).reshape(-1)[:n]
    if q_bits == 12:
        c0 = (p[:, 0] << 4) | (p[:, 1] >> 4)
        c1 = ((p[:, 1] & 0xF) << 8) | p[:, 2]
        return jnp.stack([c0, c1], axis=1).reshape(-1)[:n]
    raise ValueError(f"no packing for q_bits={q_bits}")


# ------------------------------------------------------------- pallas core
def _fpq_kernel(x_ref, code_ref, s_ref, *, q_bits, mantissa_bits, maxv):
    x = x_ref[:].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax == 0.0, 1.0, absmax / maxv)
    v = round_to_fp_grid(x / scale, q_bits, mantissa_bits)
    code_ref[:] = encode_fp(v, q_bits, mantissa_bits).astype(jnp.uint8) \
        if q_bits <= 8 else encode_fp(v, q_bits, mantissa_bits).astype(
            jnp.uint16)
    s_ref[:] = jnp.broadcast_to(scale, s_ref.shape)


def quantize_fp(x, q_bits=8, mantissa_bits=3, group_size=512,
                use_pallas=None):
    """Per-group scaled FP quantization.

    Returns ``(packed_uint8, scales_f32 [groups], meta)``; fp8 e4m3 uses the
    native dtype bytes (bit-identical to a scaled ``astype(float8_e4m3fn)``).
    """
    _, _, _, maxv = _fmt(q_bits, mantissa_bits)
    group_size = max(_LANES, group_size - group_size % _LANES)
    tiles, n, groups = _group_view(x, group_size, _pick_block(group_size))
    meta = (x.shape, x.dtype, groups, q_bits, mantissa_bits, group_size)

    if q_bits == 8 and mantissa_bits == 3:
        # native e4m3fn: max is 448, NOT the generic (2-2^-m)·2^bias = 480 —
        # the "fn" encoding spends the top mantissa code on NaN
        e4m3_max = float(jnp.finfo(jnp.float8_e4m3fn).max)  # 448
        xf = tiles.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
        scale = jnp.where(absmax == 0.0, 1.0, absmax / e4m3_max)
        # clamp: x/scale can round a hair past the format max, and e4m3fn
        # overflows to NaN (no inf encoding)
        q8 = jnp.clip(xf / scale, -e4m3_max,
                      e4m3_max).astype(jnp.float8_e4m3fn)
        return jax.lax.bitcast_convert_type(q8, jnp.uint8), scale[:, 0], meta

    if use_pallas is None:
        use_pallas = not _interpret()
    if use_pallas:
        rows = tiles.shape[0]
        block = min(_pick_block(group_size), rows)
        spec = pl.BlockSpec((block, group_size), lambda i: (i, 0))
        s_spec = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
        code_dtype = jnp.uint8 if q_bits <= 8 else jnp.uint16
        codes, s = pl.pallas_call(
            functools.partial(_fpq_kernel, q_bits=q_bits,
                              mantissa_bits=mantissa_bits, maxv=maxv),
            grid=(rows // block, ),
            in_specs=[spec],
            out_specs=[spec, s_spec],
            out_shape=[jax.ShapeDtypeStruct(tiles.shape, code_dtype),
                       jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)],
            interpret=_interpret(),
        )(tiles)
        scales = s[:, 0]
    else:
        xf = tiles.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
        scales = jnp.where(absmax == 0.0, 1.0, absmax / maxv)[:, 0]
        v = round_to_fp_grid(xf / scales[:, None], q_bits, mantissa_bits)
        codes = encode_fp(v, q_bits, mantissa_bits)
    return pack_codes(codes.reshape(-1).astype(jnp.uint32), q_bits), \
        scales, meta


def dequantize_fp(packed, scales, meta, use_pallas=None):
    shape, dtype, groups, q_bits, mantissa_bits, group_size = meta
    n = 1
    for d in shape:
        n *= d
    if q_bits == 8 and mantissa_bits == 3:
        q8 = jax.lax.bitcast_convert_type(packed, jnp.float8_e4m3fn)
        vals = q8.astype(jnp.float32) * scales[:, None]
        return vals.reshape(-1)[:n].reshape(shape).astype(dtype)
    total = scales.shape[0] * group_size
    codes = unpack_codes(packed, q_bits, total)
    vals = decode_fp(codes, q_bits, mantissa_bits).reshape(
        scales.shape[0], group_size) * scales[:, None]
    return vals.reshape(-1)[:n].reshape(shape).astype(dtype)


class FP_Quantize:
    """Reference ``deepspeed/ops/fp_quantizer/quantize.py`` API surface.

    Stateless w.r.t. payloads: pass ``meta`` (third return of ``quantize``
    with ``return_meta_tensor=True``) back into ``dequantize`` — one
    instance may serve many tensors/formats concurrently."""

    def __init__(self, group_size=512):
        self.group_size = group_size

    def quantize(self, input, q_bits=8, q_mantisa_bits=3,
                 return_meta_tensor=False):
        packed, scales, meta = quantize_fp(
            input, q_bits=q_bits, mantissa_bits=q_mantisa_bits,
            group_size=self.group_size)
        if return_meta_tensor:
            return packed, scales, meta
        self._last_meta = meta
        return packed, scales

    def dequantize(self, input_q, scale=None, meta=None, q_bits=8,
                   q_mantisa_bits=3):
        if meta is None:
            meta = getattr(self, "_last_meta", None)
            if meta is None:
                raise ValueError(
                    "dequantize needs the meta from quantize(..., "
                    "return_meta_tensor=True) (or an immediately preceding "
                    "quantize call on this instance)")
            if meta[3] != q_bits or meta[4] != q_mantisa_bits:
                raise ValueError(
                    f"payload format ({q_bits},{q_mantisa_bits}) does not "
                    f"match the last quantize call ({meta[3]},{meta[4]}) — "
                    "pass meta explicitly")
        return dequantize_fp(input_q, scale, meta)
