"""Batched M3TSZ ENCODE on device: the write-path twin of ops/chunked.py.

The read path decodes chunk-parallel straight from HBM residency
(decode_chunked_lanes); this module closes the loop by ENCODING sealed
blocks lane-parallel on device, so a flush's streams are born as
resident-pool pages instead of host-encoded bytes uploaded over PCIe.
``codec/m3tsz.py`` stays the bit-exactness oracle: for every lane this
kernel accepts, its output bytes are IDENTICAL to the host encoder's
(tests/test_encode.py proves the roundtrip and fileset byte-identity),
and every lane it cannot express (annotations, time-unit changes,
non-second-aligned starts, int/float mode mixing, >i32 magnitudes)
falls back to the host codec at seal — correctness never depends on the
classifier, only throughput does.

Shape of the kernel (one jit per (T, W) bucket):

- host ``classify_lanes`` gates each lane INT-FAST (every value hits the
  ``convert_to_int_float`` quick path, |value| and |diff| fit int32) or
  FLOAT-FAST (every value probes float, so the stream is pure XOR
  records after the first) — the same two regimes ops/chunked.py's fast
  chunk bodies decode;
- per-record emission is decomposed into at most 8 fixed SLOTS of <=32
  bits each (first-timestamp hi/lo, dod opcode, dod value, value
  control, sig/meaningful header, value hi, value lo). Slot contents
  are elementwise given the sig-tracker state; the ONLY sequential
  state is the int significant-bits hysteresis (IntSigBitsTracker),
  carried by a T-step ``lax.scan`` vectorized across lanes — the XOR
  chain's prev-bits/prev-xor are a shift and a host forward-fill;
- an exclusive cumsum of slot bit-lengths turns slots into bit offsets
  (chunk boundaries fall out as every CHUNK_K-th record's offset — the
  packed side planes ride for free), and two scatter-adds per slot pack
  the bits MSB-first into big-endian uint32 words, the exact layout
  ``_fetch4_select`` reads back. Different slots never share a bit, so
  add IS or. A final 11-bit slot writes the EOS marker; truncating the
  word row at ceil(bits/8) bytes reproduces ``Encoder.stream()``'s
  canonical tail byte-for-byte.
"""

from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple

import numpy as np

NANOS_PER_SECOND = 1_000_000_000
I32_MAX = 2_147_483_647
CHUNK_K_DEFAULT = 32

# int-mode significant-bit hysteresis (codec/m3tsz.py)
_SIG_DIFF_THRESHOLD = 3
_SIG_REPEAT_THRESHOLD = 5

KIND_NONE = 0  # host-codec fallback lane
KIND_INT = 1
KIND_FLOAT = 2

_M64 = (1 << 64) - 1


def probe_is_float(v: np.ndarray) -> np.ndarray:
    """Vectorized ``convert_to_int_float(v, 0)[2]``: True where the host
    probe keeps the value in float mode. Bit-exact with the scalar probe
    (same modf/nextafter ladder, mult 0..6, MAX_OPT_INT cutoff)."""
    v = np.asarray(v, np.float64)
    frac, _ = np.modf(v)
    # quick path: already an int and below float64(MaxInt64)
    decided_int = (v < float(2**63)) & (frac == 0)
    val = np.abs(v)
    for _ in range(7):  # mult = 0..MAX_MULT
        active = ~decided_int & (val < 10.0**13)
        if not active.any():
            break
        frac, i = np.modf(val)
        hit = (
            (frac == 0)
            | ((frac < 0.1) & (np.nextafter(val, 0.0) <= i))
            | ((frac > 0.9) & (np.nextafter(val, i + 1.0) >= i + 1.0))
        )
        decided_int |= active & hit
        val = np.where(active, val * 10.0, val)
    return ~decided_int


class LaneClass(NamedTuple):
    kind: int  # KIND_NONE / KIND_INT / KIND_FLOAT
    reason: str  # why a lane fell back (counter labels / debugging)


def classify_lane(t: np.ndarray, v: np.ndarray, u: np.ndarray) -> LaneClass:
    """Gate one merged lane (times int64 nanos, values float64, unit
    ints) for the device encoder. Conservative: anything the kernel
    cannot reproduce BIT-EXACTLY against codec/m3tsz.py is KIND_NONE."""
    n = len(t)
    if n == 0:
        return LaneClass(KIND_NONE, "empty")
    if not (np.asarray(u) == 1).all():  # Unit.SECOND only
        return LaneClass(KIND_NONE, "unit")
    t = np.asarray(t, np.int64)
    if t[0] < 0 or (t % NANOS_PER_SECOND != 0).any():
        # an unaligned START makes initial_time_unit NONE (the first
        # record then emits a time-unit marker the kernel does not
        # speak); an unaligned LATER timestamp makes the dod
        # normalization lossy, so the decoder's reconstructed prev_time
        # diverges from the raw column and the side-row carries would
        # not match snapshot_stream
        return LaneClass(KIND_NONE, "unaligned")
    if n > 1 and not (t[1:] > t[:-1]).all():
        return LaneClass(KIND_NONE, "unsorted")
    deltas = np.concatenate([np.zeros(1, np.int64), np.diff(t)])
    dd = deltas - np.concatenate([np.zeros(1, np.int64), deltas[:-1]])
    dod = np.where(dd >= 0, dd // NANOS_PER_SECOND, -((-dd) // NANOS_PER_SECOND))
    if (np.abs(dod) > I32_MAX).any():
        return LaneClass(KIND_NONE, "dod_overflow")
    v = np.asarray(v, np.float64)
    frac, _ = np.modf(v)
    quick_int = (v < float(2**63)) & (frac == 0)
    if quick_int.all():
        with np.errstate(invalid="ignore"):
            if not (np.abs(v) <= I32_MAX).all():
                return LaneClass(KIND_NONE, "int_overflow")
        iv = v.astype(np.int64)
        if n > 1 and (np.abs(np.diff(iv)) > I32_MAX).any():
            return LaneClass(KIND_NONE, "diff_overflow")
        return LaneClass(KIND_INT, "")
    if probe_is_float(v).all():
        return LaneClass(KIND_FLOAT, "")
    return LaneClass(KIND_NONE, "mixed_mode")


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

_SLOTS = 8  # per-record emission slots, each <= 32 bits
# worst-case record widths (bits): rec0 float 65+1+64; later float
# 36+3+12+64; later int 36+3+9+33 — float dominates
_REC0_BITS = 130
_REC_BITS = 115
_EOS_BITS = 11


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def words_bound(T: int, round_words_to: int = 1) -> int:
    bits = _REC0_BITS + _REC_BITS * max(T - 1, 0) + _EOS_BITS + 31
    return _round_up(max(bits // 32, 1), round_words_to)


@lru_cache(maxsize=32)
def _build_kernel(T: int, W: int, K: int):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from . import u64

    U32 = jnp.uint32
    C = max((T + K - 1) // K, 1)

    def kernel(
        t0_hi, t0_lo,  # u32[M] first-timestamp nanos pair
        dod,  # i32[T, M] normalized delta-of-delta (dod[0] == 0)
        valid,  # bool[T, M]
        float_lane,  # bool[M]
        absval,  # u32[T, M] |v0| at rec0, |prev - cur| after (int lanes)
        negbit,  # u32[T, M] sign opcode bit (1 = decoder ADDS)
        int_repeat,  # bool[T, M] prev == cur (int lanes, j > 0)
        vb_hi, vb_lo,  # u32[T, M] IEEE-754 bits (float lanes)
        pxr_hi, pxr_lo,  # u32[T, M] prev_xor BEFORE record j (host ffill)
    ):
        M = t0_hi.shape[0]
        j_idx = jnp.arange(T, dtype=jnp.int32)[:, None]
        rec0 = (j_idx == 0) & valid
        later = (j_idx > 0) & valid

        # --- int sig tracker: the one truly sequential piece ---
        sig_in = (jnp.int32(32) - u64.clz32(absval)).astype(jnp.int32)
        active = valid & ~float_lane[None, :] & ~(int_repeat & (j_idx > 0))
        is_rec0_row = j_idx == 0

        def step(carry, x):
            ns, ch, nl = carry
            sig, r0, act = x
            gt = sig > ns
            low = (ns - sig) >= _SIG_DIFF_THRESHOLD
            ch_l = jnp.where(nl == 0, sig, jnp.maximum(ch, sig))
            nl_l = nl + 1
            hit = nl_l >= _SIG_REPEAT_THRESHOLD
            ns_low = jnp.where(hit, ch_l, ns)
            nl_l = jnp.where(hit, 0, nl_l)
            new_sig = jnp.where(gt, sig, jnp.where(low, ns_low, ns))
            ch_n = jnp.where(low, ch_l, ch)
            nl_n = jnp.where(gt, nl, jnp.where(low, nl_l, 0))
            # first record: write_int_sig(sig) only, counters untouched
            new_sig = jnp.where(r0, sig, new_sig)
            ch_n = jnp.where(r0, ch, ch_n)
            nl_n = jnp.where(r0, nl, nl_n)
            ns2 = jnp.where(act, new_sig, ns)
            ch2 = jnp.where(act, ch_n, ch)
            nl2 = jnp.where(act, nl_n, nl)
            return (ns2, ch2, nl2), (ns, ns2)

        z = jnp.zeros((M,), jnp.int32)
        (_, _, _), (ns_before, ns_after) = lax.scan(
            step,
            (z, z, z),
            (sig_in, jnp.broadcast_to(is_rec0_row, (T, M)), active),
        )

        # --- timestamp slots (elementwise) ---
        l_tsh = jnp.where(rec0, 32, 0)
        v_tsh = jnp.where(rec0, t0_hi[None, :], U32(0))
        l_tsl = jnp.where(rec0, 32, 0)
        v_tsl = jnp.where(rec0, t0_lo[None, :], U32(0))
        zero = dod == 0
        b7 = (dod >= -64) & (dod <= 63)
        b9 = (dod >= -256) & (dod <= 255)
        b12 = (dod >= -2048) & (dod <= 2047)
        l_op = jnp.where(zero, 1, jnp.where(b7, 2, jnp.where(b9, 3, 4)))
        v_op = jnp.where(zero, 0, jnp.where(b7, 2, jnp.where(b9, 6, jnp.where(b12, 14, 15)))).astype(U32)
        l_dv = jnp.where(zero, 0, jnp.where(b7, 7, jnp.where(b9, 9, jnp.where(b12, 12, 32))))
        dmask = jnp.where(
            l_dv == 0, U32(0), U32(0xFFFFFFFF) >> (U32(32) - l_dv.astype(U32))
        )
        v_dv = dod.astype(U32) & dmask
        l_op = jnp.where(valid, l_op, 0)
        l_dv = jnp.where(valid, l_dv, 0)

        # --- int value slots ---
        width = jnp.where(is_rec0_row, sig_in, ns_after)
        upd = later & (ns_before != ns_after)
        # ctrl: rec0 '0'; repeat '01'; update '000'; steady '1'
        i_ctrl_v = jnp.where(
            rec0, U32(0), jnp.where(int_repeat, U32(1), jnp.where(upd, U32(0), U32(1)))
        )
        i_ctrl_l = jnp.where(
            rec0, 1, jnp.where(int_repeat, 2, jnp.where(upd, 3, 1))
        )
        # sig/mult header: UPDATE_SIG+NON_ZERO+6bits(sig-1)+NO_UPDATE_MULT
        hdr9 = U32(0x180) | ((width.astype(U32) - U32(1)) << U32(1))
        i_hdr_v = jnp.where(rec0 & (sig_in > 0), hdr9, jnp.where(upd, hdr9, U32(0)))
        i_hdr_l = jnp.where(
            rec0, jnp.where(sig_in > 0, 9, 2), jnp.where(upd, 9, 0)
        )
        i_val_v = (negbit << width.astype(U32)) | absval
        i_val_l = 1 + width
        irep = int_repeat & later
        i_hdr_v = jnp.where(irep, U32(0), i_hdr_v)
        i_hdr_l = jnp.where(irep, 0, i_hdr_l)
        i_val_v = jnp.where(irep, U32(0), i_val_v)
        i_val_l = jnp.where(irep, 0, i_val_l)

        # --- float value slots ---
        pvb_hi = jnp.concatenate([vb_hi[:1], vb_hi[:-1]], axis=0)
        pvb_lo = jnp.concatenate([vb_lo[:1], vb_lo[:-1]], axis=0)
        f_rep = later & (vb_hi == pvb_hi) & (vb_lo == pvb_lo)
        x_hi = vb_hi ^ pvb_hi
        x_lo = vb_lo ^ pvb_lo
        pl = u64.clz((pxr_hi, pxr_lo))
        pt = u64.ctz((pxr_hi, pxr_lo))
        cl = u64.clz((x_hi, x_lo))
        ct = u64.ctz((x_hi, x_lo))
        contained = (cl >= pl) & (ct >= pt)
        len_c = 64 - pl - pt
        nm = 64 - cl - ct
        pay_c = u64.shr((x_hi, x_lo), pt.astype(U32))
        pay_u = u64.shr((x_hi, x_lo), ct.astype(U32))
        flen = jnp.where(contained, len_c, nm)
        pay_hi = jnp.where(contained, pay_c[0], pay_u[0])
        pay_lo = jnp.where(contained, pay_c[1], pay_u[1])
        f_ctrl_v = jnp.where(
            rec0, U32(1), jnp.where(f_rep, U32(1), jnp.where(contained, U32(6), U32(7)))
        )
        f_ctrl_l = jnp.where(rec0, 1, jnp.where(f_rep, 2, 3))
        f_hdr_v = jnp.where(
            later & ~f_rep & ~contained,
            (cl.astype(U32) << U32(6)) | (nm.astype(U32) - U32(1)),
            U32(0),
        )
        f_hdr_l = jnp.where(later & ~f_rep & ~contained, 12, 0)
        f_vhi_v = jnp.where(rec0, vb_hi, jnp.where(f_rep, U32(0), pay_hi))
        f_vhi_l = jnp.where(rec0, 32, jnp.where(f_rep, 0, jnp.maximum(flen - 32, 0)))
        f_vlo_v = jnp.where(rec0, vb_lo, jnp.where(f_rep, U32(0), pay_lo))
        f_vlo_l = jnp.where(rec0, 32, jnp.where(f_rep, 0, jnp.minimum(flen, 32)))

        # --- merge lanes, mask invalid records ---
        fl = float_lane[None, :]

        def pick(fv, iv_):
            return jnp.where(fl, fv, iv_)

        v_ctrl = pick(f_ctrl_v, i_ctrl_v)
        l_ctrl = jnp.where(valid, pick(f_ctrl_l, i_ctrl_l), 0)
        v_hdr = pick(f_hdr_v, i_hdr_v)
        l_hdr = jnp.where(valid, pick(f_hdr_l, i_hdr_l), 0)
        v_vhi = pick(f_vhi_v, i_val_v)
        l_vhi = jnp.where(valid, pick(f_vhi_l, i_val_l), 0)
        v_vlo = pick(f_vlo_v, U32(0))
        l_vlo = jnp.where(valid, pick(f_vlo_l, 0), 0)

        vals = jnp.stack([v_tsh, v_tsl, v_op, v_dv, v_ctrl, v_hdr, v_vhi, v_vlo], 1)
        lens = jnp.stack([l_tsh, l_tsl, l_op, l_dv, l_ctrl, l_hdr, l_vhi, l_vlo], 1)
        vals = vals.reshape(T * _SLOTS, M)
        lens = lens.reshape(T * _SLOTS, M).astype(jnp.int32)
        # trailing EOS marker slot (9-bit opcode 0x100 + 2-bit value 0)
        vals = jnp.concatenate([vals, jnp.full((1, M), 0x400, U32)], 0)
        lens = jnp.concatenate([lens, jnp.full((1, M), _EOS_BITS, jnp.int32)], 0)

        inc = jnp.cumsum(lens, axis=0)
        offs = inc - lens  # exclusive
        total_bits = inc[-1]
        chunk_offs = offs[:: K * _SLOTS][:C]
        chunk_sigs = ns_before[::K][:C]

        # --- emission: two scatter-adds per slot into big-endian words ---
        b = (offs & 31).astype(jnp.int32)
        end = b + lens
        shl_hi = jnp.clip(32 - end, 0, 31).astype(U32)
        shr_hi = jnp.clip(end - 32, 0, 31).astype(U32)
        hi = jnp.where(end <= 32, vals << shl_hi, vals >> shr_hi)
        shl_lo = jnp.clip(64 - end, 0, 31).astype(U32)
        lo = jnp.where(end > 32, vals << shl_lo, U32(0))
        hi = jnp.where(lens > 0, hi, U32(0))
        lo = jnp.where(lens > 0, lo, U32(0))
        w = (offs >> 5).astype(jnp.int32)
        lane = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32)[None, :], w.shape)
        flat_hi = (lane * W + w).reshape(-1)
        flat_lo = (lane * W + w + 1).reshape(-1)
        out = jnp.zeros((M * W,), U32)
        out = out.at[flat_hi].add(hi.reshape(-1), mode="drop")
        out = out.at[flat_lo].add(lo.reshape(-1), mode="drop")
        return out.reshape(M, W), total_bits, chunk_offs, chunk_sigs

    return jax.jit(kernel)


class EncodeResult(NamedTuple):
    """Device-encoded lane batch. ``words`` stays on device (the
    resident pool admits it without re-upload); everything else is
    small host metadata."""

    words: object  # device uint32[M_pad, W]; rows past M are padding lanes
    total_bits: np.ndarray  # int64[M], EOS included
    nbytes: np.ndarray  # int64[M] finalized stream length
    chunk_offs: np.ndarray  # int64[Cmax, M] bit offset at each chunk start
    chunk_sigs: np.ndarray  # int32[Cmax, M] tracker num_sig at chunk start
    n_chunks: np.ndarray  # int32[M]
    kinds: np.ndarray  # int8[M] KIND_INT / KIND_FLOAT
    counts: np.ndarray  # int32[M]
    chunk_k: int

    def streams(self) -> list[bytes]:
        """Finalized m3tsz byte streams — ONE device->host transfer for
        the whole batch (fileset persistence / oracle tests), never on
        the admission hot path."""
        host = np.asarray(self.words).astype(">u4")
        return [
            host[m].tobytes()[: int(self.nbytes[m])]
            for m in range(len(self.nbytes))
        ]


def encode_lanes(
    lanes: list,
    kinds,
    k: int = CHUNK_K_DEFAULT,
    round_words_to: int = 1,
) -> EncodeResult | None:
    """Encode classified lanes on device. ``lanes`` is a list of
    ``(times int64[N], values float64[N])``; ``kinds[i]`` must be
    KIND_INT or KIND_FLOAT (run :func:`classify_lane` first). Returns
    None for an empty batch."""
    M = len(lanes)
    if M == 0:
        return None
    kinds = np.asarray(kinds, np.int8)
    counts = np.asarray([len(t) for t, _ in lanes], np.int32)
    T = int(counts.max())
    # pad T and M to pow2 buckets so the jit cache stays small: every
    # shard seals a different lane count, and one compile of this program
    # for the TPU is ~25 s. Padding lanes are all-invalid and sliced off.
    T_pad = max(8, 1 << int(np.ceil(np.log2(T))))
    M_pad = max(8, 1 << int(np.ceil(np.log2(M))))
    W = words_bound(T_pad, round_words_to)

    t0 = np.zeros(M_pad, np.uint64)
    dod = np.zeros((T_pad, M_pad), np.int32)
    valid = np.zeros((T_pad, M_pad), bool)
    absval = np.zeros((T_pad, M_pad), np.uint32)
    negbit = np.zeros((T_pad, M_pad), np.uint32)
    int_repeat = np.zeros((T_pad, M_pad), bool)
    vb_hi = np.zeros((T_pad, M_pad), np.uint32)
    vb_lo = np.zeros((T_pad, M_pad), np.uint32)
    pxr_hi = np.zeros((T_pad, M_pad), np.uint32)
    pxr_lo = np.zeros((T_pad, M_pad), np.uint32)
    float_lane = np.zeros(M_pad, bool)
    float_lane[:M] = kinds == KIND_FLOAT

    for m, (t, v) in enumerate(lanes):
        t = np.asarray(t, np.int64)
        v = np.asarray(v, np.float64)
        n = len(t)
        t0[m] = np.uint64(t[0])
        valid[:n, m] = True
        deltas = np.concatenate([np.zeros(1, np.int64), np.diff(t)])
        dd = deltas - np.concatenate([np.zeros(1, np.int64), deltas[:-1]])
        dod[:n, m] = np.where(
            dd >= 0, dd // NANOS_PER_SECOND, -((-dd) // NANOS_PER_SECOND)
        ).astype(np.int32)
        if kinds[m] == KIND_INT:
            iv = v.astype(np.int64)
            d = np.concatenate([iv[:1], iv[:-1] - iv[1:]])
            absval[:n, m] = np.abs(d).astype(np.uint32)
            # rec0: OPCODE_NEGATIVE written when v0 >= 0 (decode adds);
            # later: when prev - cur < 0 (decode adds |d| -> cur > prev)
            nb = np.where(d < 0, 1, 0)
            nb[0] = 1 if iv[0] >= 0 else 0
            negbit[:n, m] = nb
            int_repeat[1:n, m] = d[1:] == 0
        else:
            vb = v.view(np.uint64)
            vb_hi[:n, m] = (vb >> np.uint64(32)).astype(np.uint32)
            vb_lo[:n, m] = (vb & np.uint64(0xFFFFFFFF)).astype(np.uint32)
            if n > 1:
                # prev_xor BEFORE record j: forward fill of nonzero xors,
                # seeded with the first value's bits (write_full_float)
                src = np.concatenate([vb[:1], vb[1:] ^ vb[:-1]])
                updated = np.concatenate([[True], vb[1:] != vb[:-1]])
                last = np.maximum.accumulate(np.where(updated, np.arange(n), 0))
                px_after = src[last]
                pxr = np.concatenate([np.zeros(1, np.uint64), px_after[:-1]])
                pxr_hi[:n, m] = (pxr >> np.uint64(32)).astype(np.uint32)
                pxr_lo[:n, m] = (pxr & np.uint64(0xFFFFFFFF)).astype(np.uint32)

    kern = _build_kernel(T_pad, W, k)
    words, total_bits, chunk_offs, chunk_sigs = kern(
        (t0 >> np.uint64(32)).astype(np.uint32),
        (t0 & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        dod, valid, float_lane,
        absval, negbit, int_repeat,
        vb_hi, vb_lo, pxr_hi, pxr_lo,
    )
    total_bits = np.asarray(total_bits, np.int64)[:M]
    return EncodeResult(
        words=words,
        total_bits=total_bits,
        nbytes=(total_bits + 7) // 8,
        chunk_offs=np.asarray(chunk_offs, np.int64)[:, :M],
        chunk_sigs=np.asarray(chunk_sigs, np.int32)[:, :M],
        n_chunks=((counts + k - 1) // k).astype(np.int32),
        kinds=kinds,
        counts=counts,
        chunk_k=k,
    )


def lane_max_span(result: EncodeResult, m: int) -> int:
    """Widest chunk span in bits for lane ``m`` (resident-pool window
    sizing) — matches snapshot_stream's post-hoc ``span``: offset deltas
    with the final chunk extending to the padded stream end
    (``nbytes * 8``, EOS and byte padding included)."""
    nc = int(result.n_chunks[m])
    if nc == 0:
        return 0
    offs = result.chunk_offs[:nc, m]
    ends = np.concatenate(
        [offs[1:], np.asarray([int(result.nbytes[m]) * 8], np.int64)]
    )
    return int((ends - offs).max())


def side_rows_for(
    result: EncodeResult, lanes: list, block_start: int
) -> list:
    """Packed 10-word side rows per lane, bit-identical to
    ``pack_side_rows(snapshot_stream(stream))`` for every device-encoded
    lane (None where a chunk overflows the packed ranges — that lane
    admits without side planes and decodes streamed)."""
    from .sideplane import pack_side_rows_vec

    k = result.chunk_k
    out = []
    for m, (t, v) in enumerate(lanes):
        t = np.asarray(t, np.int64)
        v = np.asarray(v, np.float64)
        n = int(result.counts[m])
        nc = int(result.n_chunks[m])
        ci = np.arange(nc)
        j = ci * k  # records consumed before each chunk
        off = result.chunk_offs[:nc, m]
        prev_time = np.where(j > 0, t[np.maximum(j - 1, 0)], 0).astype(np.uint64)
        pd = np.zeros(nc, np.uint64)
        ge2 = j >= 2
        pd[ge2] = (t[j[ge2] - 1] - t[j[ge2] - 2]).astype(np.uint64)
        full = (j + k) <= n
        if result.kinds[m] == KIND_INT:
            iv = v.astype(np.int64)
            int_val = np.where(j > 0, iv[np.maximum(j - 1, 0)], 0).astype(np.uint64)
            sig = result.chunk_sigs[:nc, m]
            rows = pack_side_rows_vec(
                off, prev_time, pd, np.ones(nc, np.uint64),
                np.zeros(nc, np.uint64), np.zeros(nc, np.uint64), int_val,
                sig, np.zeros(nc, np.uint64), np.zeros(nc, bool),
                full, np.zeros(nc, bool), block_start,
            )
        else:
            vb = v.view(np.uint64)
            pfb = np.zeros(nc, np.uint64)
            pxr = np.zeros(nc, np.uint64)
            if n > 1 or nc > 0:
                src = np.concatenate([vb[:1], vb[1:] ^ vb[:-1]])
                updated = np.concatenate([[True], vb[1:] != vb[:-1]])
                last = np.maximum.accumulate(np.where(updated, np.arange(n), 0))
                px_after = src[last]
                gt0 = j > 0
                pfb[gt0] = vb[j[gt0] - 1]
                pxr[gt0] = px_after[j[gt0] - 1]
            # chunk 0's snapshot predates the first record: is_float is
            # still False and fast_float needs float mode AT chunk start
            rows = pack_side_rows_vec(
                off, prev_time, pd, np.ones(nc, np.uint64),
                pfb, pxr, np.zeros(nc, np.uint64),
                np.zeros(nc, np.uint64), np.zeros(nc, np.uint64), j > 0,
                np.zeros(nc, bool), full & (ci > 0), block_start,
            )
        out.append(rows)
    return out


def encode_block(points: list, block_start: int, k: int = CHUNK_K_DEFAULT,
                 round_words_to: int = 1):
    """Convenience seal-path entry: classify + encode + side rows.

    ``points`` is a list of per-lane ``(times, values, units)`` triples.
    Returns ``(kinds int8[L], result EncodeResult | None, lane_index
    int32[L], side_rows list)`` where ``lane_index[i]`` is the row of
    lane i in the encode batch, or -1 for host-fallback lanes."""
    kinds = np.zeros(len(points), np.int8)
    for i, (t, v, u) in enumerate(points):
        kinds[i] = classify_lane(t, v, u).kind
    lane_index = np.full(len(points), -1, np.int32)
    eligible = [i for i in range(len(points)) if kinds[i] != KIND_NONE]
    lane_index[eligible] = np.arange(len(eligible), dtype=np.int32)
    lanes = [(points[i][0], points[i][1]) for i in eligible]
    result = encode_lanes(
        lanes, kinds[eligible], k=k, round_words_to=round_words_to
    )
    side = side_rows_for(result, lanes, block_start) if result is not None else []
    return kinds, result, lane_index, side
