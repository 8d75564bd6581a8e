"""Format-codec registry: one object per quantization format (paper §2.2).

DECA's premise is a *grid* of compression schemes — quant format x density —
flowing through one decompress pipeline. Every format-specific piece of that
pipeline lives here, on a single `Codec` object:

  * ``encode`` / ``decode``         numpy, offline compression of packed
                                    nonzero values (codes + stored scales),
  * ``decode_values``               jittable jnp dequantization — THE decode:
                                    the XLA reference (`kernels/ref.py`) and
                                    the Pallas kernel bodies (`kernels/
                                    deca_*.py`) both call this, so each
                                    format has exactly one jnp decoder,
  * ``decode_scales``               stored scale -> f32 multiplier
                                    (E8M0 vs bf16-bits),
  * ``kv_encode`` / ``kv_decode``   runtime KV-cache quantization over the
                                    head dim with one bf16 scale per
                                    (cache slot, KV head),
  * metadata                        ``bits``, ``scale_bits``, ``is_identity``
                                    (no dequant stage), ``kv_capable`` —
                                    consumed by `core/formats.py` geometry
                                    and the `core/roofsurface.py` 3D
                                    roofline, so a new format is priced
                                    automatically.

Adding a scheme is a one-file change: subclass, instantiate, `register()`.
`nf4` (NormalFloat4, LUT-decoded) is registered below as the proof — no
kernel, model, serving, or roofline code names it anywhere.

Sparsity is deliberately *not* here: the bitmask expansion stage is
format-agnostic (`kernels/deca_decompress.decompress_block`), exactly as in
the DECA PE where the crossbar sits after the format-specific LUT array.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# E2M1 magnitude grid (sign handled separately): code 0..7.
FP4_GRID = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0], dtype=np.float32)
_FP4_MIDS = (FP4_GRID[1:] + FP4_GRID[:-1]) / 2.0  # nearest-level cut points

# NormalFloat4 (QLoRA): 16 quantiles of N(0,1) normalized to [-1, 1].
NF4_LUT = np.array(
    [
        -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
        -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
        0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
        0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
        0.7229568362236023, 1.0,
    ],
    dtype=np.float32,
)

_SCALE_BITS = {"none": 0, "e8m0": 8, "bf16": 16}


# ---------------------------------------------------------------------------
# shared bit-twiddling helpers (numpy + jnp)
# ---------------------------------------------------------------------------

def _f32_to_bf16_bits(x: np.ndarray) -> np.ndarray:
    b = x.astype(np.float32).view(np.uint32)
    b = b + 0x7FFF + ((b >> 16) & 1)  # RNE
    return (b >> 16).astype(np.uint16)


def _bf16_bits_to_f32(b: np.ndarray) -> np.ndarray:
    return (b.astype(np.uint32) << 16).view(np.float32)


def quantize_bf8(x: np.ndarray) -> np.ndarray:
    """f32 -> E5M2 code (uint8), round-to-nearest-even via fp16 bits."""
    h = x.astype(np.float16).view(np.uint16).astype(np.uint32)
    lower, upper = h & 0xFF, h >> 8
    round_up = (lower > 0x80) | ((lower == 0x80) & (upper & 1 == 1))
    code = upper + round_up
    # avoid rounding a finite value into inf (exp=31, man=0)
    overflow = (code & 0x7F) == 0x7C
    code = np.where(overflow & ((upper & 0x7F) < 0x7C), upper, code)
    return code.astype(np.uint8)


def dequantize_bf8(code: np.ndarray) -> np.ndarray:
    return (code.astype(np.uint16) << 8).view(np.float16).astype(np.float32)


def quantize_bf8_jnp(x: jax.Array) -> jax.Array:
    """bf16/f32 -> E5M2 code (uint8), RNE — bit-identical to `quantize_bf8`."""
    h = jax.lax.bitcast_convert_type(
        x.astype(jnp.float16), jnp.uint16
    ).astype(jnp.uint32)
    lower, upper = h & 0xFF, h >> 8
    round_up = ((lower > 0x80) | ((lower == 0x80) & (upper & 1 == 1))).astype(
        jnp.uint32
    )
    code = upper + round_up
    overflow = (code & 0x7F) == 0x7C  # finite -> inf: keep truncated value
    code = jnp.where(overflow & ((upper & 0x7F) < 0x7C), upper, code)
    return code.astype(jnp.uint8)


def dequantize_bf8_jnp(code: jax.Array) -> jax.Array:
    # every E5M2 value is exact in bf16
    return _bf8_decode_jnp(code).astype(jnp.bfloat16)


def quantize_fp4(x: np.ndarray) -> np.ndarray:
    """f32 (already divided by group scale) -> E2M1 code (uint8 in [0,16))."""
    sign = (x < 0).astype(np.uint8)
    mag = np.abs(x, dtype=np.float32)
    # nearest grid point, ties to the lower one; no (..., 8) distance table,
    # which at a 9216 x 36864 weight would take tens of GB of host memory
    idx = np.searchsorted(_FP4_MIDS, mag, side="left").astype(np.uint8)
    return (sign << 3) | idx


def dequantize_fp4(code: np.ndarray) -> np.ndarray:
    mag = FP4_GRID[code & 0x7]
    return np.where(code >> 3 == 1, -mag, mag)


def _widen(codes: jax.Array) -> jax.Array:
    """Stored uint8 codes -> int32 in [0, 256). Every jnp decoder widens
    first: Mosaic has no 8-bit shifts and no uint8 -> float cast, and the
    int32 forms give the same values everywhere."""
    return codes.astype(jnp.int32)


def _f32_from_bits(bits: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _interleave_lanes(lo: jax.Array, hi: jax.Array) -> jax.Array:
    """(..., n) int32 nibbles x2 -> (..., 2n), `lo` at even and `hi` at odd
    positions of the last axis. Mosaic has no lane shuffle, so this is a
    one-hot contraction on the MXU: exact, since nibbles are exact in bf16
    and each output sums one of them with zeros."""
    n = lo.shape[-1]
    src = jax.lax.broadcasted_iota(jnp.int32, (n, 2 * n), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (n, 2 * n), 1)

    def place(x, offset):
        onehot = (dst == 2 * src + offset).astype(jnp.bfloat16)
        return jnp.dot(
            x.reshape(-1, n).astype(jnp.float32).astype(jnp.bfloat16), onehot,
            preferred_element_type=jnp.float32,
        )

    out = place(lo, 0) + place(hi, 1)
    return out.astype(jnp.int32).reshape(*lo.shape[:-1], 2 * n)


def _unpack_nibbles_jnp(codes: jax.Array, axis: int) -> jax.Array:
    """Packed uint8 -> int32 nibbles along `axis` (even index = low nibble)."""
    axis = axis % codes.ndim
    c = _widen(codes)
    lo, hi = c & 0xF, c >> 4
    if axis == codes.ndim - 1:
        return _interleave_lanes(lo, hi)
    stacked = jnp.stack([lo, hi], axis=axis + 1)
    shape = list(codes.shape)
    shape[axis] *= 2
    return stacked.reshape(shape)


def _pack_nibbles_np(nib: np.ndarray, axis: int) -> np.ndarray:
    """Nibble codes -> packed uint8 along `axis` (even index = low nibble)."""
    lo = np.take(nib, np.arange(0, nib.shape[axis], 2), axis=axis)
    hi = np.take(nib, np.arange(1, nib.shape[axis], 2), axis=axis)
    return (lo | (hi << 4)).astype(np.uint8)


def _unpack_nibbles_np(codes: np.ndarray, axis: int) -> np.ndarray:
    """Numpy mirror of `_unpack_nibbles_jnp`."""
    axis = axis % codes.ndim
    stacked = np.stack([codes & 0xF, codes >> 4], axis=axis + 1)
    shape = list(codes.shape)
    shape[axis] *= 2
    return stacked.reshape(shape)


def _pack_nibbles_jnp(nib: jax.Array, axis: int) -> jax.Array:
    axis = axis % nib.ndim
    idx_lo = [slice(None)] * nib.ndim
    idx_hi = [slice(None)] * nib.ndim
    idx_lo[axis] = slice(0, None, 2)
    idx_hi[axis] = slice(1, None, 2)
    return (nib[tuple(idx_lo)] | (nib[tuple(idx_hi)] << 4)).astype(jnp.uint8)


def _lut_decode_jnp(idx: jax.Array, lut: np.ndarray) -> jax.Array:
    """Small-LUT decode as a select chain — pure VPU ops (no per-lane LUT
    SRAM on TPU, and no gather inside Pallas kernel bodies)."""
    out = jnp.full(idx.shape, float(lut[0]), jnp.float32)
    for i in range(1, len(lut)):
        out = jnp.where(idx == i, float(lut[i]), out)
    return out


# ---------------------------------------------------------------------------
# the Codec interface
# ---------------------------------------------------------------------------

class Codec:
    """One quantization format: offline numpy codec, jittable jnp decode
    (shared by the XLA reference and the Pallas kernel bodies), KV-cache
    quantization, and the static metadata the geometry/roofline layers need.

    Weight-path array shapes (group-packed along K):
      encode/decode(codes):  (ng, k_cap[*bits/8], N)
      scales:                (ng, N) — uint8 E8M0 or uint16 bf16-bits
    KV-path shapes (quantize over the head dim):
      kv_encode(x (..., Dh)) -> (codes (..., kv_code_width(Dh)),
                                 scales (..., ) bf16 or None)
    """

    name: str = ""
    bits: int = 0               # stored bits per kept value
    scale_kind: str = "none"    # 'none' | 'e8m0' | 'bf16'
    is_identity: bool = False   # True: no dequant stage (LUT array bypassed)
    kv_capable: bool = True     # usable as a kv_quant format

    # -- metadata ----------------------------------------------------------
    @property
    def scale_bits(self) -> int:
        return _SCALE_BITS[self.scale_kind]

    @property
    def has_scale(self) -> bool:
        return self.scale_bits > 0

    @property
    def kv_dtype(self):
        return jnp.uint8

    def kv_code_width(self, dh: int) -> int:
        """Stored code elements per Dh-wide KV head vector."""
        if self.bits == 4:
            if dh % 2:
                raise ValueError(f"{self.name}: head dim {dh} not nibble-packable")
            return dh // 2
        return dh

    # -- offline numpy codec ----------------------------------------------
    def encode(self, vals: np.ndarray) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """(ng, k_cap, N) f32 packed nonzeros -> (codes, scales|None)."""
        raise NotImplementedError

    def decode(self, codes: np.ndarray, scales: Optional[np.ndarray]) -> np.ndarray:
        """Numpy mirror of decode_values (+ scaling); offline reference."""
        raise NotImplementedError

    # -- jittable decode (XLA ref + Pallas kernel bodies) ------------------
    def decode_values(self, codes: jax.Array) -> jax.Array:
        """(ng, packed_k, N) stored codes -> (ng, k_cap, N) f32 values."""
        raise NotImplementedError

    @property
    def n_planes(self) -> int:
        """Value planes a stored code row holds: 2 for nibble-packed codes
        (slot 2j in the low nibble of byte j, 2j+1 in the high), else 1."""
        return 2 if self.bits == 4 else 1

    def decode_planes(self, codes: jax.Array) -> Tuple[jax.Array, ...]:
        """Stored codes -> `n_planes` f32 value planes in stored layout:
        plane p holds slots p, p + n_planes, ... of each group, one per
        stored code row, so nibble codes decode without the sublane
        interleave of `decode_values` (the fused decode GEMV contracts
        each plane with its own columns of x)."""
        if self.n_planes == 1:
            return (self.decode_values(codes),)
        c = _widen(codes)
        return self.decode_nibbles(c & 0xF), self.decode_nibbles(c >> 4)

    def decode_nibbles(self, nib: jax.Array) -> jax.Array:
        """int32 nibble codes -> f32 values (4-bit codecs)."""
        raise NotImplementedError

    def decode_scales(self, scales: jax.Array) -> jax.Array:
        """(ng, N) stored scales -> (ng, N) f32 multipliers, built from
        bits (exact; no `exp2`, no 8- or 16-bit arithmetic)."""
        s = scales.astype(jnp.int32)
        if self.scale_kind == "e8m0":
            # 2^(e-127) is the f32 with exponent field e; e == 0 is the
            # subnormal 2^-127 (mantissa bit 22)
            return _f32_from_bits(jnp.where(s == 0, 1 << 22, s << 23))
        return _f32_from_bits(s << 16)  # bf16 bits are the f32's top half

    # -- KV-cache path -----------------------------------------------------
    def kv_encode(self, x: jax.Array) -> Tuple[jax.Array, Optional[jax.Array]]:
        raise NotImplementedError

    def kv_decode(
        self, codes: jax.Array, scales: Optional[jax.Array]
    ) -> jax.Array:
        """Codes (+ scales) -> values. Returned in the decode compute dtype
        (f32 for scaled codecs, bf16 for bf8); cache readers cast to their
        compute dtype, full-precision consumers (grad compression) do not."""
        raise NotImplementedError

    # shared helper: one bf16 scale per (..., head) vector over the last axis
    def _kv_scale(self, x: jax.Array, qmax: float) -> Tuple[jax.Array, jax.Array]:
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
        scale = (amax / qmax).astype(jnp.bfloat16)  # the *stored* scale
        safe = jnp.maximum(scale.astype(jnp.float32), 1e-12)
        return scale, safe


class BF16Codec(Codec):
    """No quantization (sparsity only): codes are bf16 bit pairs."""

    name, bits, scale_kind = "bf16", 16, "none"
    is_identity = True
    kv_capable = False  # the unquantized cache path covers this

    def encode(self, vals):
        ng, kc, n = vals.shape
        b = _f32_to_bf16_bits(vals)  # (ng, k_cap, N) uint16
        codes = np.stack([b & 0xFF, b >> 8], axis=2).reshape(ng, -1, n)
        return codes.astype(np.uint8), None

    def decode(self, codes, scales):
        lo = codes[:, 0::2, :].astype(np.uint16)
        hi = codes[:, 1::2, :].astype(np.uint16)
        return _bf16_bits_to_f32(lo | (hi << 8))

    def decode_values(self, codes):
        ng, packed, n = codes.shape
        pairs = _widen(codes).reshape(ng, packed // 2, 2, n)
        lo, hi = pairs[:, :, 0, :], pairs[:, :, 1, :]
        return _f32_from_bits((hi << 24) | (lo << 16))


class BF8Codec(Codec):
    """E5M2 — the high byte of IEEE binary16. Decode = `<< 8` + bitcast."""

    name, bits, scale_kind = "bf8", 8, "none"

    def encode(self, vals):
        return quantize_bf8(vals), None

    def decode(self, codes, scales):
        return dequantize_bf8(codes)

    def decode_values(self, codes):
        return _bf8_decode_jnp(codes)

    def kv_encode(self, x):
        return quantize_bf8_jnp(x), None

    def kv_decode(self, codes, scales):
        return dequantize_bf8_jnp(codes)


def _bf8_decode_jnp(codes: jax.Array) -> jax.Array:
    """E5M2 code -> f32, built from bits: the f16 -> f32 widening of the
    code's high-byte f16 without 16-bit arithmetic (Mosaic has none)."""
    c = _widen(codes)
    e, m = (c >> 2) & 0x1F, c & 0x3
    normal = ((e + (127 - 15)) << 23) | (m << 21)
    # e == 31: inf (m == 0) or a quiet NaN, as the f16 -> f32 cast gives
    special = jnp.where(m == 0, 0x7F800000, 0x7FC00000 | (m << 21))
    # e == 0: subnormal m * 2^-16; exact and a normal f32
    sub = jax.lax.bitcast_convert_type(
        m.astype(jnp.float32) * (2.0 ** -16), jnp.int32
    )
    mag = jnp.where(e == 0, sub, jnp.where(e == 31, special, normal))
    return _f32_from_bits(mag | ((c >> 7) << 31))


def _fp4_decode_jnp(nib: jax.Array) -> jax.Array:
    """E2M1 nibble -> f32, built from bits, pure ALU (no LUT).

    The magnitude code c = nib & 7 is (e, m) = (c >> 1, c & 1). For c >= 2
    the value (1 + m/2) * 2^(e-1) is the f32 with exponent field e + 126
    and mantissa bit m, i.e. bits (c + 252) << 22; c == 1 is 0.5
    (0x3F000000), c == 0 is zero; bit 3 is the sign bit."""
    c = nib & 7
    sub = jnp.where(c == 1, 0x3F000000, 0)
    mag = jnp.where(c >= 2, (c + 252) << 22, sub)
    return _f32_from_bits(mag | ((nib & 8) << 28))


class MXFP4Codec(Codec):
    """OCP MX FP4 (E2M1) with a shared E8M0 scale per group.

    The single jnp decoder is the ALU remap (`_fp4_decode_jnp`): exact in
    f32 for every grid value, so it is bit-identical to the `FP4_GRID` LUT
    (asserted over all 16 nibbles in tests/test_codecs.py). This is the
    reconciliation of the former ref-LUT / Pallas-ALU fork.
    """

    name, bits, scale_kind = "mxfp4", 4, "e8m0"

    def encode(self, vals):
        amax = np.abs(vals).max(axis=1)  # (ng, N)
        e = np.floor(np.log2(np.maximum(amax, 2.0 ** -126)))
        scale_exp = np.clip(e - 2.0, -127, 127)  # E2M1 emax = 2 (max elem 6.0)
        scales = (scale_exp + 127).astype(np.uint8)  # E8M0
        q = vals / (2.0 ** scale_exp)[:, None, :]
        codes4 = quantize_fp4(q)  # (ng, k_cap, N) in [0,16)
        return _pack_nibbles_np(codes4, axis=1), scales

    def decode(self, codes, scales):
        vals = dequantize_fp4(_unpack_nibbles_np(codes, axis=1))
        return vals * (2.0 ** (scales.astype(np.float32) - 127.0))[:, None, :]

    def decode_values(self, codes):
        return self.decode_nibbles(_unpack_nibbles_jnp(codes, axis=1))

    def decode_nibbles(self, nib):
        return _fp4_decode_jnp(nib)

    def kv_encode(self, x):
        scale, safe = self._kv_scale(x, 6.0)  # E2M1 max magnitude
        q = x.astype(jnp.float32) / safe[..., None]
        sign = (q < 0).astype(jnp.uint8)
        mag = jnp.abs(q)
        idx = sum(
            (mag > float(t)).astype(jnp.uint8) for t in _FP4_MIDS
        )
        return _pack_nibbles_jnp((sign << 3) | idx, axis=-1), scale

    def kv_decode(self, codes, scales):
        vals = _fp4_decode_jnp(_unpack_nibbles_jnp(codes, axis=-1))
        return vals * scales.astype(jnp.float32)[..., None]


class IntCodec(Codec):
    """Symmetric integer (8 or 4 bit) with a per-group bf16 scale."""

    scale_kind = "bf16"

    def __init__(self, bits: int):
        self.name = f"int{bits}"
        self.bits = bits
        self.qmax = (1 << (bits - 1)) - 1

    def encode(self, vals):
        amax = np.abs(vals).max(axis=1)
        scale = np.maximum(amax / self.qmax, 1e-12)
        scales = _f32_to_bf16_bits(scale)  # uint16 bf16-bits
        scale = _bf16_bits_to_f32(scales)  # quantize with the *stored* scale
        q = np.clip(
            np.rint(vals / scale[:, None, :]), -self.qmax, self.qmax
        ).astype(np.int32)
        if self.bits == 8:
            return (q & 0xFF).astype(np.uint8), scales
        return _pack_nibbles_np((q & 0xF).astype(np.uint8), axis=1), scales

    def decode(self, codes, scales):
        if self.bits == 8:
            q = codes.view(np.int8).astype(np.float32)
        else:
            nib = _unpack_nibbles_np(codes, axis=1).astype(np.int32)
            q = (nib - 16 * (nib >= 8)).astype(np.float32)
        return q * _bf16_bits_to_f32(scales)[:, None, :]

    def _values(self, codes: jax.Array, axis: int) -> jax.Array:
        """Two's-complement codes (nibbles packed along `axis`) -> f32."""
        if self.bits == 8:
            c = _widen(codes)
            return (c - 256 * (c >= 128)).astype(jnp.float32)
        return self.decode_nibbles(_unpack_nibbles_jnp(codes, axis=axis))

    def decode_values(self, codes):
        return self._values(codes, axis=1)

    def decode_nibbles(self, nib):
        return (nib - 16 * (nib >= 8)).astype(jnp.float32)

    def kv_encode(self, x):
        scale, safe = self._kv_scale(x, float(self.qmax))
        q = jnp.clip(
            jnp.round(x.astype(jnp.float32) / safe[..., None]),
            -self.qmax, self.qmax,
        ).astype(jnp.int32)
        if self.bits == 8:
            return (q & 0xFF).astype(jnp.uint8), scale
        return _pack_nibbles_jnp((q & 0xF).astype(jnp.uint8), axis=-1), scale

    def kv_decode(self, codes, scales):
        q = self._values(codes, axis=-1)
        return q * scales.astype(jnp.float32)[..., None]


# midpoints between adjacent NF4 levels: nearest-level quantizer
_NF4_MIDS = (NF4_LUT[1:] + NF4_LUT[:-1]) / 2.0


class NF4Codec(Codec):
    """NormalFloat4 (QLoRA): 16 N(0,1)-quantile levels in [-1, 1], decoded
    through a LUT (select chain on the VPU), with a per-group bf16 absmax
    scale. Registered purely to prove the registry's one-file extensibility
    claim — nothing outside this class names 'nf4'."""

    name, bits, scale_kind = "nf4", 4, "bf16"

    @staticmethod
    def _quantize_np(q: np.ndarray) -> np.ndarray:
        """normalized f32 in [-1, 1] -> level index 0..15 (nearest)."""
        return np.searchsorted(_NF4_MIDS, q, side="left").astype(np.uint8)

    def encode(self, vals):
        amax = np.abs(vals).max(axis=1)
        scale = np.maximum(amax, 1e-12)
        scales = _f32_to_bf16_bits(scale)
        scale = _bf16_bits_to_f32(scales)  # quantize with the *stored* scale
        idx = self._quantize_np(vals / scale[:, None, :])
        return _pack_nibbles_np(idx, axis=1), scales

    def decode(self, codes, scales):
        nib = _unpack_nibbles_np(codes, axis=1)
        return NF4_LUT[nib] * _bf16_bits_to_f32(scales)[:, None, :]

    def decode_values(self, codes):
        return self.decode_nibbles(_unpack_nibbles_jnp(codes, axis=1))

    def decode_nibbles(self, nib):
        return _lut_decode_jnp(nib, NF4_LUT)

    def kv_encode(self, x):
        scale, safe = self._kv_scale(x, 1.0)
        q = x.astype(jnp.float32) / safe[..., None]
        idx = sum((q > float(t)).astype(jnp.uint8) for t in _NF4_MIDS)
        return _pack_nibbles_jnp(idx, axis=-1), scale

    def kv_decode(self, codes, scales):
        vals = _lut_decode_jnp(_unpack_nibbles_jnp(codes, axis=-1), NF4_LUT)
        return vals * scales.astype(jnp.float32)[..., None]


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Codec] = {}


def register(codec: Codec) -> Codec:
    if not codec.name or codec.bits <= 0:
        raise ValueError(f"codec needs a name and positive bits: {codec!r}")
    if codec.name in _REGISTRY:
        raise ValueError(f"codec {codec.name!r} already registered")
    _REGISTRY[codec.name] = codec
    return codec


def get_codec(name: str) -> Codec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown codec {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def codec_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def kv_codec_names() -> Tuple[str, ...]:
    return tuple(n for n in codec_names() if _REGISTRY[n].kv_capable)


# Stable numeric codec ids for binary headers (host-tier page payloads,
# checkpoint manifests). These are a wire format: ids are append-only and
# never reused — a new codec takes the next free id, a retired codec keeps
# its slot. Id 0 is the unquantized pool ("none" is not a registry codec).
_WIRE_IDS: Dict[str, int] = {
    "none": 0, "bf16": 1, "bf8": 2, "mxfp4": 3, "int8": 4, "int4": 5,
    "nf4": 6,
}
_WIRE_NAMES: Dict[int, str] = {v: k for k, v in _WIRE_IDS.items()}


def codec_wire_id(name: str) -> int:
    try:
        return _WIRE_IDS[name]
    except KeyError:
        raise ValueError(
            f"codec {name!r} has no wire id; known: {sorted(_WIRE_IDS)}"
        ) from None


def codec_from_wire_id(wire_id: int) -> str:
    try:
        return _WIRE_NAMES[wire_id]
    except KeyError:
        raise ValueError(f"unknown codec wire id {wire_id}") from None


register(BF16Codec())
register(BF8Codec())
register(MXFP4Codec())
register(IntCodec(8))
register(IntCodec(4))
register(NF4Codec())
