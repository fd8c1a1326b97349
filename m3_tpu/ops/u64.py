"""Vectorized uint64 emulation as (hi, lo) uint32 pairs.

TPUs have no native 64-bit integers, but the M3TSZ stream is defined over
64-bit words (float64 bit patterns, unix-nano timestamps — SURVEY.md §2.5,
reference /root/reference/src/dbnode/encoding/m3tsz/). Every 64-bit quantity
on device is a pair of uint32 arrays; all ops are elementwise and shape-
polymorphic so they vectorize over the series axis for free.

Shift amounts are data-dependent vectors; XLA leaves shifts >= bit width
undefined, so every variable shift here is clamped and masked explicitly.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as _np
from jax import lax

_np_int = _np.integer

U32 = jnp.uint32
# np scalar, NOT jnp: a module-level jnp scalar is a concrete device array
# that Pallas kernels would capture as an illegal closed-over constant.
MASK32 = _np.uint32(0xFFFFFFFF)


def u64(hi, lo):
    return jnp.asarray(hi, U32), jnp.asarray(lo, U32)


def from_u32(x):
    x = jnp.asarray(x, U32)
    return jnp.zeros_like(x), x


def from_i32(x):
    """Sign-extend an int32 vector into a 64-bit pair (two's complement)."""
    x32 = jnp.asarray(x, jnp.int32)
    hi = jnp.where(x32 < 0, MASK32, _np.uint32(0))
    return hi, x32.astype(U32)


def const(v: int, shape=(), dtype=U32):
    v &= (1 << 64) - 1
    return (
        jnp.full(shape, (v >> 32) & 0xFFFFFFFF, dtype),
        jnp.full(shape, v & 0xFFFFFFFF, dtype),
    )


def add(a, b):
    ah, al = a
    bh, bl = b
    lo = al + bl
    carry = (lo < al).astype(U32)
    hi = ah + bh + carry
    return hi, lo


def neg(a):
    ah, al = a
    return add((~ah, ~al), const(1))


def sub(a, b):
    return add(a, neg(b))


def bxor(a, b):
    return a[0] ^ b[0], a[1] ^ b[1]


def band(a, b):
    return a[0] & b[0], a[1] & b[1]


def bor(a, b):
    return a[0] | b[0], a[1] | b[1]


def eq(a, b):
    return (a[0] == b[0]) & (a[1] == b[1])


def is_zero(a):
    return (a[0] == 0) & (a[1] == 0)


def lt_u(a, b):
    """Unsigned 64-bit less-than."""
    return (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))


def is_neg(a):
    """Sign bit of a two's-complement pair."""
    return (a[0] >> 31) != 0


def shl(a, s):
    """Logical shift left by amounts s in [0, 64] (vector or Python int)."""
    hi, lo = a
    if isinstance(s, (int, _np_int)):
        s = int(s)
        if s == 0:
            return hi, lo
        if s < 32:
            return (hi << U32(s)) | (lo >> U32(32 - s)), lo << U32(s)
        if s == 32:
            return lo, jnp.zeros_like(lo)
        if s < 64:
            return lo << U32(s - 32), jnp.zeros_like(lo)
        return jnp.zeros_like(hi), jnp.zeros_like(lo)
    s = jnp.asarray(s, U32)
    # NOT jnp.minimum: unsigned vector min lowers to an i8->i1 trunc that
    # Mosaic rejects inside fori_loop bodies (Pallas kernel path).
    s1 = jnp.where(s < U32(31), s, U32(31))
    hi_a = (hi << s1) | jnp.where(s1 == 0, U32(0), lo >> (U32(32) - s1))
    lo_a = lo << s1
    s2 = jnp.clip(s.astype(jnp.int32) - 32, 0, 31).astype(U32)
    hi_b = lo << s2
    lt32 = s < 32
    ge64 = s >= 64
    out_hi = jnp.where(lt32, hi_a, jnp.where(ge64, U32(0), hi_b))
    out_lo = jnp.where(lt32, lo_a, U32(0))
    return out_hi, out_lo


def shr(a, s):
    """Logical shift right by amounts s in [0, 64] (vector or Python int)."""
    hi, lo = a
    if isinstance(s, (int, _np_int)):
        s = int(s)
        if s == 0:
            return hi, lo
        if s < 32:
            return hi >> U32(s), (lo >> U32(s)) | (hi << U32(32 - s))
        if s == 32:
            return jnp.zeros_like(hi), hi
        if s < 64:
            return jnp.zeros_like(hi), hi >> U32(s - 32)
        return jnp.zeros_like(hi), jnp.zeros_like(lo)
    s = jnp.asarray(s, U32)
    # NOT jnp.minimum: unsigned vector min lowers to an i8->i1 trunc that
    # Mosaic rejects inside fori_loop bodies (Pallas kernel path).
    s1 = jnp.where(s < U32(31), s, U32(31))
    lo_a = (lo >> s1) | jnp.where(s1 == 0, U32(0), hi << (U32(32) - s1))
    hi_a = hi >> s1
    s2 = jnp.clip(s.astype(jnp.int32) - 32, 0, 31).astype(U32)
    lo_b = hi >> s2
    lt32 = s < 32
    ge64 = s >= 64
    out_hi = jnp.where(lt32, hi_a, U32(0))
    out_lo = jnp.where(lt32, lo_a, jnp.where(ge64, U32(0), lo_b))
    return out_hi, out_lo


def sar(a, s):
    """Arithmetic shift right by vector amounts s in [0, 64]."""
    hi, lo = a
    sign = is_neg(a)
    h, l = shr(a, s)
    # Fill vacated high bits with ones when negative.
    ones = (jnp.full_like(h, 0xFFFFFFFF), jnp.full_like(l, 0xFFFFFFFF))
    fh, fl = shl(ones, jnp.asarray(64, U32) - jnp.asarray(s, U32))
    out_hi = jnp.where(sign, h | fh, h)
    out_lo = jnp.where(sign, l | fl, l)
    return out_hi, out_lo


def sign_extend(a, num_bits):
    """Sign-extend the low ``num_bits`` of a pair (encoding.go SignExtend)."""
    s = jnp.asarray(64, U32) - jnp.asarray(num_bits, U32)
    return sar(shl(a, s), s)


def clz32(x):
    return lax.clz(x.astype(jnp.int32)).astype(jnp.int32)


def ctz32(x):
    """Count trailing zeros of uint32; 32 for zero input."""
    x = jnp.asarray(x, U32)
    lowbit = x & (~x + U32(1))
    return jnp.where(x == 0, jnp.int32(32), 31 - clz32(lowbit))


def clz(a):
    hi, lo = a
    return jnp.where(hi != 0, clz32(hi), 32 + clz32(lo))


def ctz(a):
    hi, lo = a
    # Matches reference LeadingAndTrailingZeros: trailing zeros of 0 is 0 there,
    # but full-pair ctz of 0 would be 64; callers guard the zero case.
    return jnp.where(lo != 0, ctz32(lo), 32 + ctz32(hi))


def mul_u32(a, m):
    """64-bit pair times a uint32 vector (mod 2^64)."""
    hi, lo = a
    m = jnp.asarray(m, U32)
    p_hi, p_lo = umul32_wide(lo, m)
    return hi * m + p_hi, p_lo


def umul32_wide(a, b):
    """Full 32x32 -> 64 unsigned multiply as (hi, lo)."""
    a = jnp.asarray(a, U32)
    b = jnp.asarray(b, U32)
    a0 = a & U32(0xFFFF)
    a1 = a >> 16
    b0 = b & U32(0xFFFF)
    b1 = b >> 16
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = (p00 >> 16) + (p01 & U32(0xFFFF)) + (p10 & U32(0xFFFF))
    lo = (p00 & U32(0xFFFF)) | (mid << 16)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    return hi, lo


def u32_to_f32(x):
    """uint32 -> float32 value conversion via int32 halves.

    Mosaic (Pallas TPU) has no uint32->float32 convert; 16-bit halves cast
    exactly through int32 and recombine without precision loss beyond f32's
    own 24-bit mantissa."""
    x = jnp.asarray(x, U32)
    hi = (x >> U32(16)).astype(jnp.int32).astype(jnp.float32)
    lo = (x & U32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + lo


def to_f32(a):
    """Approximate signed 64-bit pair -> float32 (for on-device aggregation)."""
    # sign-magnitude: converting the two's-complement halves directly
    # cancels catastrophically for small negatives (-2 is -2^32 +
    # (2^32 - 2), and f32 rounds the second term to 2^32: result 0)
    negative = is_neg(a)
    mh, ml = select(negative, neg(a), a)
    mag = u32_to_f32(mh) * jnp.float32(4294967296.0) + u32_to_f32(ml)
    return jnp.where(negative, -mag, mag)


def f64_bits_to_f32(a):
    """Interpret a pair as float64 bits and convert the value to float32.

    Values outside float32 range become +/-inf; subnormal float64 flush toward
    zero. NaN and inf are preserved. Used only for on-device f32 aggregation —
    bit-exact results flow through the (hi, lo) pairs themselves.
    """
    hi, lo = a
    sign = jnp.where((hi >> 31) != 0, jnp.float32(-1.0), jnp.float32(1.0))
    exp = ((hi >> 20) & U32(0x7FF)).astype(jnp.int32)
    mant = (hi & U32(0xFFFFF)).astype(jnp.int32).astype(jnp.float32) * jnp.float32(
        2.0**32
    ) + u32_to_f32(lo)
    frac = mant * jnp.float32(2.0**-52)
    # Exact power-of-two scaling: bitcast (e+127)<<23 rather than jnp.exp2,
    # which is a polynomial approximation on some backends (CPU) and loses
    # ~2^-18 relative accuracy at large exponents.
    def pow2(e_int):
        bits = ((e_int + 127).astype(jnp.uint32)) << U32(23)
        return lax.bitcast_convert_type(bits, jnp.float32)

    e = jnp.clip(exp - 1023, -149, 128)
    e1 = jnp.clip(e, -126, 127)
    magnitude = (jnp.float32(1.0) + frac) * pow2(e1) * pow2(e - e1)
    magnitude = jnp.where(exp == 0, frac * pow2(jnp.full_like(exp, -126)), magnitude)
    special = exp == 0x7FF
    inf = jnp.float32(jnp.inf)
    nan = jnp.float32(jnp.nan)
    magnitude = jnp.where(special, jnp.where(mant == 0, inf, nan), magnitude)
    return sign * magnitude


def select(pred, a, b):
    """Elementwise select between two pairs."""
    return jnp.where(pred, a[0], b[0]), jnp.where(pred, a[1], b[1])
