"""Unit tests for the (hi, lo) uint32-pair 64-bit emulation."""

import random

import numpy as np
import pytest

from m3_tpu.ops import u64

MASK = (1 << 64) - 1


def pair(vals):
    vs = [v & MASK for v in vals]
    return (
        np.array([v >> 32 for v in vs], np.uint32),
        np.array([v & 0xFFFFFFFF for v in vs], np.uint32),
    )


def unpair(p):
    hi, lo = np.asarray(p[0], np.uint64), np.asarray(p[1], np.uint64)
    return [(int(h) << 32) | int(l) for h, l in zip(hi, lo)]


random.seed(0)
VALS = [0, 1, 0xFFFFFFFF, 0x100000000, MASK, 1 << 63, 0x0123456789ABCDEF] + [
    random.getrandbits(64) for _ in range(9)
]
OTHER = [random.getrandbits(64) for _ in range(len(VALS))]


def test_add_sub():
    a, b = pair(VALS), pair(OTHER)
    assert unpair(u64.add(a, b)) == [(x + y) & MASK for x, y in zip(VALS, OTHER)]
    assert unpair(u64.sub(a, b)) == [(x - y) & MASK for x, y in zip(VALS, OTHER)]


def test_bitwise():
    a, b = pair(VALS), pair(OTHER)
    assert unpair(u64.bxor(a, b)) == [x ^ y for x, y in zip(VALS, OTHER)]
    assert unpair(u64.band(a, b)) == [x & y for x, y in zip(VALS, OTHER)]
    assert unpair(u64.bor(a, b)) == [x | y for x, y in zip(VALS, OTHER)]


@pytest.mark.parametrize("s", [0, 1, 7, 31, 32, 33, 63, 64])
def test_shifts(s):
    a = pair(VALS)
    sv = np.full(len(VALS), s, np.int32)
    assert unpair(u64.shl(a, sv)) == [(x << s) & MASK for x in VALS]
    assert unpair(u64.shr(a, sv)) == [(x >> s) for x in VALS]


@pytest.mark.parametrize("s", [0, 1, 31, 32, 63])
def test_sar(s):
    a = pair(VALS)
    sv = np.full(len(VALS), s, np.int32)
    exp = []
    for x in VALS:
        sx = x - (1 << 64) if x & (1 << 63) else x
        exp.append((sx >> s) & MASK)
    assert unpair(u64.sar(a, sv)) == exp


def test_sign_extend():
    a = pair([0b0111, 0b1000, 0b1111, 0x7F, 0x80])
    n = np.array([4, 4, 4, 8, 8], np.int32)
    got = unpair(u64.sign_extend(a, n))
    exp = [7, (-8) & MASK, (-1) & MASK, 127, (-128) & MASK]
    assert got == exp


def test_clz_ctz():
    a = pair(VALS)
    clz = list(np.asarray(u64.clz(a)))
    ctz = list(np.asarray(u64.ctz(a)))
    for x, c, t in zip(VALS, clz, ctz):
        assert c == (64 - x.bit_length() if x else 64)
        if x:
            assert t == ((x & -x).bit_length() - 1)


def test_mul_u32():
    a = pair(VALS)
    for m in [1, 1000, 1_000_000, 1_000_000_000]:
        mv = np.full(len(VALS), m, np.uint32)
        assert unpair(u64.mul_u32(a, mv)) == [(x * m) & MASK for x in VALS]


def test_cmp():
    a, b = pair(VALS), pair(OTHER)
    lt = list(np.asarray(u64.lt_u(a, b)))
    for x, y, l in zip(VALS, OTHER, lt):
        assert l == (x < y)


def test_f64_bits_to_f32():
    import struct

    vals = [0.0, 1.0, -2.5, 1e30, -1e-30, float("inf"), float("nan"), 3.141592653589793]
    bits = [struct.unpack("<Q", struct.pack("<d", v))[0] for v in vals]
    got = np.asarray(u64.f64_bits_to_f32(pair(bits)))
    for v, g in zip(vals, got):
        if v != v:
            assert g != g
        elif v == 0:
            assert g == 0
        else:
            assert abs(g - np.float32(v)) <= abs(np.float32(v)) * 1e-6 or g == np.float32(v)


@pytest.mark.parametrize(
    "v", [0, 1, -1, -2, -7, -126, -381, 381, -(1 << 24), (1 << 40) + 5,
          -((1 << 40) + 5), -(1 << 62)],
)
def test_to_f32_signed(v):
    """Signed pair -> f32 is the correctly rounded value for BOTH signs:
    small negatives used to cancel to 0 (-2) or lose their low byte
    (-381 -> -256), which fed wrong int-mode values to every f32
    aggregate."""
    got = np.asarray(u64.to_f32(pair([v])))
    assert got.dtype == np.float32
    assert got[0] == np.float32(v), (v, got)
