"""Fused chunked decode + aggregation: the flagship TPU kernel.

Round-1 decode materialized 7 u64 [S, T] outputs from the scan and aggregated
afterwards — every step streamed a multi-hundred-MB carry plus outputs through
HBM. Here the whole K-step decode loop runs with its state resident on-chip
and only per-LANE aggregates (sum/count/min/max/last) leave the kernel: a
Pallas grid over lane tiles; each program loads its tile's window columns
into VMEM once and runs the K-record loop as a fori_loop, state in vector
registers/VMEM. HBM traffic = windows once + [N] accumulators once. Off the
chip the same kernel body runs in interpret mode; the plain-jnp reference it
is tested against is parallel/scan.chunked_scan_aggregate.

Record semantics are decode.py's branchless M3TSZ step (reference hot loop:
/root/reference/src/dbnode/encoding/m3tsz/iterator.go:64, istream.go:97);
aggregation matches parallel/scan._aggregate_decoded.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.instrument import KernelProfiler
from . import u64
from .chunked import _fetch4_select, _point_step
from .decode import (
    DecodeResult,
    DecodeState,
    _decode_timestamp,
    _decode_value,
    _decode_value_fast,
    _extract,
    _extract32,
    _int32_val_to_f32,
    _int_val_to_f32,
    _read_xor,
    _ts_consumed_fast,
)

I32 = jnp.int32
U32 = jnp.uint32
F32 = jnp.float32

# device-tier observability for the fused lane-aggregate kernel (see
# ops/chunked.PROFILER)
PROFILER_PACKED = KernelProfiler("packed_lane_agg")

LANE_TILE = (8, 128)  # native f32/i32 VPU tile
TILE_LANES = LANE_TILE[0] * LANE_TILE[1]
# rows per packed-kernel grid program: taller blocks amortize per-program
# grid/DMA overhead (measured best at 24-32); must be a multiple of 8
# (sublane tiling)
ROWS_DEFAULT = int(os.environ.get("M3_TPU_TILE_ROWS", "32"))


class LaneAggregates(NamedTuple):
    """Per-lane (= per chunk) reductions emitted by the fused kernel."""

    sum: jnp.ndarray  # f32[N]
    count: jnp.ndarray  # i32[N]
    min: jnp.ndarray  # f32[N] (+inf where empty)
    max: jnp.ndarray  # f32[N] (-inf where empty)
    last: jnp.ndarray  # f32[N] (value of last valid record in the lane)
    err: jnp.ndarray  # bool/i32[N]


def _init_state(rel_pos, num_bits, prev_time, prev_delta, prev_float_bits,
                prev_xor, int_val, time_unit, sig, mult, is_float):
    as_pair = lambda p: (jnp.asarray(p[0], U32), jnp.asarray(p[1], U32))
    shape = rel_pos.shape
    return DecodeState(
        pos=jnp.zeros(shape, I32),
        done=jnp.asarray(num_bits, I32) <= jnp.asarray(rel_pos, I32),
        err=jnp.zeros(shape, bool),
        prev_time=as_pair(prev_time),
        prev_delta=as_pair(prev_delta),
        time_unit=jnp.asarray(time_unit, I32),
        prev_float_bits=as_pair(prev_float_bits),
        prev_xor=as_pair(prev_xor),
        int_val=as_pair(int_val),
        mult=jnp.asarray(mult, I32),
        sig=jnp.asarray(sig, I32),
        is_float=jnp.asarray(is_float, bool),
    )


def _fused_step(fetch4, nb, nt0, first_chunk_i32, int_optimized, carry, idx):
    """Decode ONE record for every lane and fold it into the accumulators.

    ``first_chunk_i32`` is int32, not bool: every value closed over by the
    loop body is threaded through the while-op carry, and Mosaic cannot
    round-trip i1 vector carries (it stores them as i8 and the trunc back is
    unsupported). Mask math stays in int32 until the final compare.
    """
    state, acc = carry
    s_sum, s_cnt, s_min, s_max, s_last = acc
    first_vec = (first_chunk_i32 * jnp.where(idx == 0, I32(1), I32(0))) != 0
    was_active = ~state.done & ~state.err
    state, _ = _decode_timestamp(fetch4, nb, state, first_vec, nt=nt0)
    ts_active = ~state.done & ~state.err
    state = _decode_value(fetch4, state, first_vec, int_optimized)
    now_active = ~state.done & ~state.err
    valid = was_active & ts_active & now_active

    if int_optimized:
        point_is_float = state.is_float
        val = u64.select(point_is_float, state.prev_float_bits, state.int_val)
        v = jnp.where(
            point_is_float,
            u64.f64_bits_to_f32(val),
            _int_val_to_f32(val, state.mult),
        )
    else:
        v = u64.f64_bits_to_f32(state.prev_float_bits)
    s_sum = s_sum + jnp.where(valid, v, F32(0))
    s_cnt = s_cnt + valid.astype(I32)
    s_min = jnp.minimum(s_min, jnp.where(valid, v, F32(jnp.inf)))
    s_max = jnp.maximum(s_max, jnp.where(valid, v, F32(-jnp.inf)))
    s_last = jnp.where(valid, v, s_last)
    return state, (s_sum, s_cnt, s_min, s_max, s_last)


def _run_lane_tile(windows_cols, rel_pos, num_bits, first, prev_time, prev_delta,
                   prev_float_bits, prev_xor, int_val, time_unit, sig, mult,
                   is_float, k: int, cw: int, int_optimized: bool,
                   unroll: bool = False) -> LaneAggregates:
    """Shared body: decode K records over one set of lanes (any shape) with
    window columns already materialized, accumulating aggregates."""
    rel_pos = jnp.asarray(rel_pos, I32)
    fetch4 = functools.partial(_fetch4_select, windows_cols, cw, rel_pos)
    state = _init_state(rel_pos, num_bits, prev_time, prev_delta,
                        prev_float_bits, prev_xor, int_val, time_unit, sig,
                        mult, is_float)
    first_chunk_i32 = jnp.asarray(first).astype(I32)
    nb = jnp.asarray(num_bits, I32) - rel_pos
    zero_pos = jnp.zeros_like(rel_pos)
    nt0 = _extract(fetch4(zero_pos), 0, 64)

    shape = rel_pos.shape
    acc0 = (
        jnp.zeros(shape, F32),
        jnp.zeros(shape, I32),
        jnp.full(shape, jnp.inf, F32),
        jnp.full(shape, -jnp.inf, F32),
        jnp.full(shape, jnp.nan, F32),
    )
    step = functools.partial(
        _fused_step, fetch4, nb, nt0, first_chunk_i32, int_optimized
    )
    # Mosaic can't round-trip i1 vectors through a fori_loop carry, so
    # bool state fields travel as int32 and are re-compared each step.
    def pack(st):
        return st._replace(
            done=st.done.astype(I32), err=st.err.astype(I32),
            is_float=st.is_float.astype(I32),
        )

    def unpack(st):
        return st._replace(
            done=st.done != 0, err=st.err != 0, is_float=st.is_float != 0
        )

    def body(i, c):
        st, ac = c
        st, ac = step((unpack(st), ac), i)
        return pack(st), ac

    # fully unrolled on hardware: Mosaic schedules the straight-line
    # record bodies much better than the rolled loop (+16% measured);
    # Pallas only supports unroll=1 or unroll=num_steps. Interpret mode
    # keeps the rolled loop (the interpreter executes per-op, and the
    # 24x traced body is pathologically slow there).
    state, acc = jax.lax.fori_loop(
        0, k, body, (pack(state), acc0), unroll=k if unroll else 1
    )
    state = unpack(state)
    s_sum, s_cnt, s_min, s_max, s_last = acc
    return LaneAggregates(
        sum=s_sum, count=s_cnt, min=s_min, max=s_max, last=s_last, err=state.err
    )


def _run_lane_tile_fast(windows_cols, rel_pos, num_bits, int_val, sig, mult,
                        k: int, cw: int, unroll: bool = False) -> LaneAggregates:
    """Specialized K-record body for host-classified fast chunks (see
    ops/chunked.py prescan flags): every record is marker-free and int-mode,
    the time unit is constant in {s, ms}, the value path is int32-safe, and
    the chunk holds exactly k records (or the lane is empty).

    Skips the float-XOR path, full-float extracts, marker/time-unit logic,
    f64->f32 conversion, per-record done/err bookkeeping (the active mask is
    constant per lane) — and the TIMESTAMP VALUES themselves: aggregates are
    the kernel's only output, so a timestamp record contributes nothing but
    its consumed-bit count (_ts_consumed_fast)."""
    rel_pos = jnp.asarray(rel_pos, I32)
    shape = rel_pos.shape
    active = jnp.asarray(num_bits, I32) > rel_pos  # empty/padding lanes: False
    # minimal carry: pos + the fields fast records can change (no done/err/
    # float/timestamp planes; bool-free so the Mosaic i1 hazard never arises)
    state0 = (
        jnp.zeros(shape, I32),  # pos
        # int32-safe by classification: only the low word carries the value
        jax.lax.bitcast_convert_type(jnp.asarray(int_val[1], U32), I32),
        jnp.asarray(sig, I32),
        jnp.asarray(mult, I32),
    )
    acc0 = (
        jnp.zeros(shape, F32),
        jnp.zeros(shape, I32),
        jnp.full(shape, jnp.inf, F32),
        jnp.full(shape, -jnp.inf, F32),
        jnp.full(shape, jnp.nan, F32),
    )
    active_i = active.astype(I32)
    # a fast record consumes at most 36 (ts) + 80 (value) = 116 bits, so the
    # cursor before record j is statically bounded — early records need only
    # a shallow barrel (see _fetch4_select max_widx)
    MAX_REC_BITS = 116

    def body(c, ts_widx, val_widx):
        (pos, iv, sg, ml), acc = c
        s_sum, s_cnt, s_min, s_max, s_last = acc
        ws_ts = _fetch4_select(windows_cols, cw, rel_pos, pos, max_widx=ts_widx)
        pos = pos + _ts_consumed_fast(ws_ts)
        st = DecodeState(
            pos=pos, done=None, err=None, prev_time=None, prev_delta=None,
            time_unit=None, prev_float_bits=None, prev_xor=None,
            int_val=iv, mult=ml, sig=sg, is_float=None,
        )
        fetch_val = functools.partial(
            _fetch4_select, windows_cols, cw, rel_pos, max_widx=val_widx
        )
        st = _decode_value_fast(fetch_val, st)
        v = _int32_val_to_f32(st.int_val, st.mult)
        s_sum = s_sum + jnp.where(active, v, F32(0))
        s_cnt = s_cnt + active_i
        s_min = jnp.minimum(s_min, jnp.where(active, v, F32(jnp.inf)))
        s_max = jnp.maximum(s_max, jnp.where(active, v, F32(-jnp.inf)))
        s_last = jnp.where(active, v, s_last)
        return (
            (st.pos, st.int_val, st.sig, st.mult),
            (s_sum, s_cnt, s_min, s_max, s_last),
        )

    if unroll:
        carry = (state0, acc0)
        for j in range(k):
            ts_widx = (31 + MAX_REC_BITS * j) >> 5
            val_widx = (31 + MAX_REC_BITS * j + 36) >> 5
            carry = body(carry, ts_widx, val_widx)
        _state, acc = carry
    else:
        _state, acc = jax.lax.fori_loop(
            0, k, lambda _i, c: body(c, None, None), (state0, acc0)
        )
    s_sum, s_cnt, s_min, s_max, s_last = acc
    return LaneAggregates(
        sum=s_sum, count=s_cnt, min=s_min, max=s_max, last=s_last,
        err=jnp.zeros(shape, bool),
    )


def _run_lane_tile_fast_float(windows_cols, rel_pos, num_bits,
                              prev_float_bits, prev_xor,
                              k: int, cw: int, unroll: bool = False) -> LaneAggregates:
    """Specialized K-record body for FLOAT-MODE fast chunks (fast_float
    classification, ops/chunked.py): every record is marker-free with the
    stream in float mode at the chunk start and after every record, unit
    constant in {s, ms}. The only value formats are therefore
    "1" + Gorilla XOR (NO_UPDATE) and the 2-bit "01" repeat — no int
    paths, no mode-transition full floats, no marker peeks, no done/err
    planes.
    Timestamps contribute only their consumed width (_ts_consumed_fast)."""
    rel_pos = jnp.asarray(rel_pos, I32)
    shape = rel_pos.shape
    active = jnp.asarray(num_bits, I32) > rel_pos
    pfb0 = (jnp.asarray(prev_float_bits[0], U32), jnp.asarray(prev_float_bits[1], U32))
    pxr0 = (jnp.asarray(prev_xor[0], U32), jnp.asarray(prev_xor[1], U32))
    state0 = (jnp.zeros(shape, I32), pfb0, pxr0)
    acc0 = (
        jnp.zeros(shape, F32),
        jnp.zeros(shape, I32),
        jnp.full(shape, jnp.inf, F32),
        jnp.full(shape, -jnp.inf, F32),
        jnp.full(shape, jnp.nan, F32),
    )
    active_i = active.astype(I32)
    # ts <= 36 bits; value <= 1 + 14 + 64 = 79 bits
    MAX_REC_BITS = 36 + 79

    def body(c, ts_widx, val_widx):
        (pos, pfb, pxr), acc = c
        s_sum, s_cnt, s_min, s_max, s_last = acc
        ws_ts = _fetch4_select(windows_cols, cw, rel_pos, pos, max_widx=ts_widx)
        pos = pos + _ts_consumed_fast(ws_ts)
        ws = _fetch4_select(windows_cols, cw, rel_pos, pos, max_widx=val_widx)
        # OPCODE_UPDATE = 0: the only update record a fast_float chunk can
        # contain is "01" (update+repeat, 2 bits); NO_UPDATE = 1 prefixes
        # the Gorilla XOR record at offset 1
        repeat = _extract32(ws, 0, 1) == 0
        nb, nx, consumed = _read_xor(ws, 1, pfb, pxr)
        pfb = u64.select(repeat, pfb, nb)
        pxr = u64.select(repeat, pxr, nx)
        pos = pos + jnp.where(repeat, 2, 1 + consumed)
        v = u64.f64_bits_to_f32(pfb)
        s_sum = s_sum + jnp.where(active, v, F32(0))
        s_cnt = s_cnt + active_i
        s_min = jnp.minimum(s_min, jnp.where(active, v, F32(jnp.inf)))
        s_max = jnp.maximum(s_max, jnp.where(active, v, F32(-jnp.inf)))
        s_last = jnp.where(active, v, s_last)
        return ((pos, pfb, pxr), (s_sum, s_cnt, s_min, s_max, s_last))

    if unroll:
        carry = (state0, acc0)
        for j in range(k):
            ts_widx = (31 + MAX_REC_BITS * j) >> 5
            val_widx = (31 + MAX_REC_BITS * j + 36) >> 5
            carry = body(carry, ts_widx, val_widx)
        _state, acc = carry
    else:
        _state, acc = jax.lax.fori_loop(
            0, k, lambda _i, c: body(c, None, None), (state0, acc0)
        )
    s_sum, s_cnt, s_min, s_max, s_last = acc
    return LaneAggregates(
        sum=s_sum, count=s_cnt, min=s_min, max=s_max, last=s_last,
        err=jnp.zeros(shape, bool),
    )


# ---------------------------------------------------------------------------
# Pallas TPU kernel — packed layout (the fast path)
# ---------------------------------------------------------------------------
#
# Profiling on real TPU showed the first kernel, one array a field, DMA-issue
# bound, not compute bound: each grid program pulled 24 strided window columns + 17
# separate 4KB lane arrays + 6 outputs (~47 small DMAs, ~7us/program), while
# the decode math itself was fully hidden. The packed layout moves the same
# bytes in 3 large contiguous DMAs per program: windows [tiles, CW, 8, 128],
# all 17 per-lane state fields in one u32 plane stack [tiles, NLANE, 8, 128],
# and one f32 [tiles, 6, 8, 128] output block.

# Order of the u32 planes in the packed lane array.
PACKED_LANE_PLANES = (
    "rel_pos", "num_bits", "first",
    "prev_time_hi", "prev_time_lo", "prev_delta_hi", "prev_delta_lo",
    "prev_float_bits_hi", "prev_float_bits_lo", "prev_xor_hi", "prev_xor_lo",
    "int_val_hi", "int_val_lo",
    "time_unit", "sig", "mult", "is_float",
)
NLANE = len(PACKED_LANE_PLANES)


class PackedLanes(NamedTuple):
    """Host-packed kernel inputs (see pack_lane_inputs)."""

    windows4: np.ndarray  # u32[tiles, CW, R, 128]
    lanes4: np.ndarray  # u32[tiles, NLANE, R, 128]
    tile_flags: np.ndarray  # i32[tiles]: 0 general, 1 every lane int-fast,
    #                         2 every lane float-fast
    n: int  # true lane count (before tile padding)
    order: str  # "c" (chunk-major), "s" (series-major), "sorted"
    inv: np.ndarray | None = None  # "sorted": i32[S]; original series i's
    #                                results sit at packed row inv[i]


def pack_lane_inputs(batch, order: str = "c", rows: int = ROWS_DEFAULT) -> PackedLanes:
    """Pack a ChunkedBatch's lane arrays into the kernel's DMA-friendly
    layout on the host (numpy; one-time per batch / done at fileset load).

    ``order="c"`` lays lanes out chunk-major (lane j = chunk_idx * S +
    series_idx): a tile then holds the SAME chunk position across ~1024
    series, so host-classified fast chunks (ChunkedBatch.fast) cluster into
    homogeneous tiles and the kernel picks the specialized body per tile.
    Series-major ("s") keeps the original ordering (mixed tiles, general
    body everywhere).

    ``order="sorted"`` additionally PERMUTES THE SERIES AXIS so series rich
    in fast chunks pack first: on a MIXED workload (float-mode series
    interleaved with int gauges) chunk-major tiles would all contain some
    slow lane and the whole batch would fall to the general body; sorting
    series by fast-chunk count reclusters the fast majority into
    homogeneous tiles. Permuting whole series (not individual lanes) keeps
    the per-series reduction a plain reshape — only the [S]-sized output
    arrays need a small inverse gather (PackedLanes.inv; a full [S*C] lane
    gather measured ~325 ms at 8M lanes on TPU, 8x the decode itself)."""
    windows = np.asarray(batch.windows, np.uint32)
    n, cw = windows.shape
    s, c = batch.num_series, batch.num_chunks

    perm_series = None
    inv_series = None
    if order == "sorted":
        fast_lanes = getattr(batch, "fast", None)
        ff_lanes = getattr(batch, "fast_float", None)
        int_cnt = (
            np.asarray(fast_lanes, bool).reshape(s, c).sum(axis=1)
            if fast_lanes is not None
            else np.zeros(s, np.int64)
        )
        flt_cnt = (
            np.asarray(ff_lanes, bool).reshape(s, c).sum(axis=1)
            if ff_lanes is not None
            else np.zeros(s, np.int64)
        )
        # group series by dominant class (int-fast, then float-fast, then
        # slow) so each class's tiles stay homogeneous; stable order within
        group = np.where(
            (int_cnt > 0) & (int_cnt >= flt_cnt), 0, np.where(flt_cnt > 0, 1, 2)
        )
        perm_series = np.argsort(group, kind="stable")
        inv_series = np.argsort(perm_series).astype(np.int32)

    def reorder(x):
        if order == "s":
            return x
        xs = x.reshape((s, c) + x.shape[1:])
        if perm_series is not None:
            xs = xs[perm_series]
        return np.ascontiguousarray(xs.swapaxes(0, 1).reshape(x.shape))

    if rows <= 0 or rows % 8:
        raise ValueError(f"rows must be a positive multiple of 8, got {rows}")
    tile_lanes = rows * 128
    tiles = -(-n // tile_lanes)
    npad = tiles * tile_lanes
    r, cc = rows, 128

    wpad = np.zeros((npad, cw), np.uint32)
    wpad[:n] = reorder(windows)
    windows4 = np.ascontiguousarray(
        wpad.reshape(tiles, r, cc, cw).transpose(0, 3, 1, 2)
    )

    def u32(x):
        x = np.asarray(x)
        if x.dtype == np.bool_:
            return x.astype(np.uint32)
        return x.astype(np.int32, copy=False).view(np.uint32)

    def plane(name):
        if name.endswith("_hi") or name.endswith("_lo"):
            pair = getattr(batch, name[:-3])
            return pair[0] if name.endswith("_hi") else pair[1]
        return getattr(batch, name)

    fields = [u32(reorder(np.asarray(plane(name)))) for name in PACKED_LANE_PLANES]
    lpad = np.zeros((NLANE, npad), np.uint32)
    for i, f in enumerate(fields):
        lpad[i, :n] = f
    lanes4 = np.ascontiguousarray(
        lpad.reshape(NLANE, tiles, r, cc).transpose(1, 0, 2, 3)
    )

    # tile class: 1 = every lane int-fast, 2 = every lane float-fast,
    # 0 = mixed/slow (general body). Padding lanes are wildcard-fast.
    def _pad_flags(arr):
        if arr is None:
            return np.zeros(npad, bool)
        p = np.ones(npad, bool)  # padding lanes never force a tile slow
        p[:n] = reorder(np.asarray(arr, bool))
        return p

    int_tiles = (
        _pad_flags(getattr(batch, "fast", None))
        .reshape(tiles, tile_lanes)
        .all(axis=1)
    )
    flt_tiles = (
        _pad_flags(getattr(batch, "fast_float", None))
        .reshape(tiles, tile_lanes)
        .all(axis=1)
    )
    tile_flags = np.where(int_tiles, 1, np.where(flt_tiles, 2, 0)).astype(np.int32)
    return PackedLanes(
        windows4=windows4, lanes4=lanes4, tile_flags=tile_flags, n=n,
        order=order, inv=inv_series,
    )


def _pallas_kernel_packed(
    k, cw, int_optimized, unroll, specialize, flag_ref, win_ref, lane_ref, out_ref
):
    from jax.experimental import pallas as pl

    cols = [win_ref[0, j] for j in range(cw)]
    zero = jnp.zeros(win_ref.shape[2:], U32)
    cols = cols + [zero, zero, zero]
    ln = lambda name: lane_ref[0, PACKED_LANE_PLANES.index(name)]
    pair = lambda name: (ln(name + "_hi"), ln(name + "_lo"))
    as_i32 = lambda x: jax.lax.bitcast_convert_type(x, I32)

    def write(agg):
        out_ref[0, 0] = agg.sum
        # count <= k << 2^24, so f32 carries it exactly through the packed block
        out_ref[0, 1] = agg.count.astype(F32)
        out_ref[0, 2] = agg.min
        out_ref[0, 3] = agg.max
        out_ref[0, 4] = agg.last
        out_ref[0, 5] = agg.err.astype(F32)

    def general():
        write(
            _run_lane_tile(
                cols,
                as_i32(ln("rel_pos")),
                as_i32(ln("num_bits")),
                ln("first") != 0,
                pair("prev_time"),
                pair("prev_delta"),
                pair("prev_float_bits"),
                pair("prev_xor"),
                pair("int_val"),
                as_i32(ln("time_unit")),
                as_i32(ln("sig")),
                as_i32(ln("mult")),
                ln("is_float") != 0,
                k,
                cw,
                int_optimized,
                unroll=unroll,
            )
        )

    if not specialize:
        general()
        return

    flag = flag_ref[pl.program_id(0)]
    pl.when(flag == 0)(general)

    @pl.when(flag == 1)
    def _fast():
        write(
            _run_lane_tile_fast(
                cols,
                as_i32(ln("rel_pos")),
                as_i32(ln("num_bits")),
                pair("int_val"),
                as_i32(ln("sig")),
                as_i32(ln("mult")),
                k,
                cw,
                unroll=unroll,
            )
        )

    @pl.when(flag == 2)
    def _fast_float():
        write(
            _run_lane_tile_fast_float(
                cols,
                as_i32(ln("rel_pos")),
                as_i32(ln("num_bits")),
                pair("prev_float_bits"),
                pair("prev_xor"),
                k,
                cw,
                unroll=unroll,
            )
        )


@functools.partial(
    jax.jit,
    static_argnames=("n", "k", "int_optimized", "interpret", "specialize"),
)
def lane_aggregates_packed(
    windows4, lanes4, tile_flags=None, n: int = 0, k: int = 0,
    int_optimized: bool = True, interpret: bool = False, specialize: bool = True,
) -> LaneAggregates:
    """Fast path: 3 contiguous DMAs per grid program (see module note).

    ``tile_flags`` (i32[tiles], from pack_lane_inputs) selects the
    specialized all-int marker-free body per tile; None or
    ``specialize=False`` compiles the general body only."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    windows4 = jnp.asarray(windows4, U32)
    lanes4 = jnp.asarray(lanes4, U32)
    tiles, cw = windows4.shape[0], windows4.shape[1]
    rows = windows4.shape[2]
    npad = tiles * rows * 128
    if tile_flags is None:
        tile_flags = jnp.zeros((tiles,), I32)
        specialize = False
    tile_flags = jnp.asarray(tile_flags, I32)

    # the tile flags ride scalar prefetch (SMEM); index maps gain the scalar
    # ref as a trailing arg per PrefetchScalarGridSpec convention
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((1, cw, rows, 128), lambda i, _f: (i, 0, 0, 0)),
            pl.BlockSpec((1, NLANE, rows, 128), lambda i, _f: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 6, rows, 128), lambda i, _f: (i, 0, 0, 0)),
    )
    outs = pl.pallas_call(
        functools.partial(
            _pallas_kernel_packed, k, cw, int_optimized, not interpret, specialize
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles, 6, rows, 128), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(tile_flags, windows4, lanes4)
    s_sum, s_cnt, s_min, s_max, s_last, s_err = (
        outs[:, i].reshape(npad)[:n] for i in range(6)
    )
    return LaneAggregates(
        sum=s_sum, count=s_cnt.astype(I32), min=s_min, max=s_max,
        last=s_last, err=s_err != 0,
    )


# ---------------------------------------------------------------------------
# Pallas TPU kernel — decoded POINTS, not aggregates (query/plan.py stage 4)
# ---------------------------------------------------------------------------

# the seven per-record planes of chunked._point_step, in its order
_POINT_PLANES = 7
# the kernel's per-lane inputs, one u32 plane each of ONE packed array (a
# pad and a DMA a program where 17 arrays were 17 of each)
_POINT_LANE_PLANES = (
    "rel_pos", "num_bits", "first",
    "prev_time_hi", "prev_time_lo", "prev_delta_hi", "prev_delta_lo",
    "prev_float_bits_hi", "prev_float_bits_lo", "prev_xor_hi", "prev_xor_lo",
    "int_val_hi", "int_val_lo", "time_unit", "sig", "mult", "is_float",
)


def _points_kernel(k, cw, int_optimized, win_ref, lane_ref, *out_refs):
    """One (8, 128) lane tile: the K-record loop of decode_chunked_lanes
    with its state on-chip, every record's planes stored at row ``i`` of
    the [K, ...] output blocks. The loop stays rolled: the window columns
    are re-read from VMEM by every fetch (refs are not loop carries), so
    the body's code does not grow with ``cw`` x ``k``."""
    point_refs, err_ref = out_refs[:_POINT_PLANES], out_refs[_POINT_PLANES]
    zero = jnp.zeros(LANE_TILE, U32)
    ln = lambda name: lane_ref[_POINT_LANE_PLANES.index(name), 0]
    i32 = lambda name: jax.lax.bitcast_convert_type(ln(name), I32)
    pair = lambda name: (ln(name + "_hi"), ln(name + "_lo"))
    rel_pos, num_bits = i32("rel_pos"), i32("num_bits")

    def fetch4(pos):
        cols = [win_ref[j, 0] for j in range(cw)] + [zero, zero, zero]
        return _fetch4_select(cols, cw, rel_pos, pos)

    state = _init_state(
        rel_pos, num_bits, pair("prev_time"), pair("prev_delta"),
        pair("prev_float_bits"), pair("prev_xor"), pair("int_val"),
        i32("time_unit"), i32("sig"), i32("mult"), ln("is_float") != 0,
    )
    first_chunk_i32 = i32("first")
    nb = num_bits - rel_pos
    nt0 = _extract(fetch4(jnp.zeros_like(rel_pos)), 0, 64)

    # Mosaic can't round-trip i1 vectors through a loop carry (see
    # _run_lane_tile): bool state travels as int32
    def pack(st):
        return st._replace(
            done=st.done.astype(I32), err=st.err.astype(I32),
            is_float=st.is_float.astype(I32),
        )

    def unpack(st):
        return st._replace(
            done=st.done != 0, err=st.err != 0, is_float=st.is_float != 0
        )

    def body(i, st):
        first_vec = (first_chunk_i32 * jnp.where(i == 0, I32(1), I32(0))) != 0
        st, out = _point_step(
            fetch4, nb, nt0, first_vec, int_optimized, unpack(st)
        )
        for ref, x in zip(point_refs, out):
            ref[i, 0] = x.astype(ref.dtype)
        return pack(st)

    state = jax.lax.fori_loop(0, k, body, pack(state))
    err_ref[0] = state.err


@functools.partial(
    jax.jit, static_argnames=("k", "int_optimized", "interpret")
)
def decode_points_pallas(
    windows, rel_pos, num_bits, first, prev_time, prev_delta, prev_float_bits,
    prev_xor, int_val, time_unit, sig, mult, is_float, k: int,
    int_optimized: bool = True, interpret: bool = False,
) -> DecodeResult:
    """decode_chunked_lanes as ONE device operation: the same arguments,
    the same [N, K] planes bit for bit (both run chunked._point_step), with
    ``values_f32`` left None (the plan program never read it). The lax.scan
    form costs ~29 small XLA operations a record, whatever the lane count;
    at a few thousand lanes that overhead is the decode's whole time."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    windows = jnp.asarray(windows, U32)
    n, cw = windows.shape
    tiles = -(-n // TILE_LANES)
    npad = tiles * TILE_LANES
    planes = dict(
        rel_pos=rel_pos, num_bits=num_bits, first=first, time_unit=time_unit,
        sig=sig, mult=mult, is_float=is_float,
    )
    for name, p in (("prev_time", prev_time), ("prev_delta", prev_delta),
                    ("prev_float_bits", prev_float_bits),
                    ("prev_xor", prev_xor), ("int_val", int_val)):
        planes[name + "_hi"], planes[name + "_lo"] = p

    def u32(x):
        x = jnp.asarray(x)
        if x.dtype == jnp.bool_:
            return x.astype(U32)
        return jax.lax.bitcast_convert_type(x.astype(I32), U32) \
            if x.dtype != U32 else x

    def tiled(x):  # [..., N] -> [..., tiles, 8, 128], zero lanes appended
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, npad - n)])
        return x.reshape(*x.shape[:-1], tiles, *LANE_TILE)

    # windows transposed to [CW, tiles, 8, 128] so each column is a clean
    # tile; padding lanes have num_bits 0 <= rel_pos 0: done from the start
    w = tiled(windows.T)
    lanes = tiled(jnp.stack([u32(planes[p]) for p in _POINT_LANE_PLANES]))
    lane_spec = pl.BlockSpec((1, *LANE_TILE), lambda i: (i, 0, 0))
    block = lambda rows: pl.BlockSpec((rows, 1, *LANE_TILE), lambda i: (0, i, 0, 0))
    point_dtypes = (U32, U32, U32, U32, I32, I32, I32)
    outs = pl.pallas_call(
        functools.partial(_points_kernel, k, cw, int_optimized),
        grid=(tiles,),
        in_specs=[block(cw), block(len(_POINT_LANE_PLANES))],
        out_specs=[block(k)] * _POINT_PLANES + [lane_spec],
        out_shape=[
            jax.ShapeDtypeStruct((k, tiles, *LANE_TILE), d) for d in point_dtypes
        ] + [jax.ShapeDtypeStruct((tiles, *LANE_TILE), I32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(w, lanes)
    ts_hi, ts_lo, val_hi, val_lo, pif, mlt, valid = (
        o.reshape(k, npad)[:, :n].T for o in outs[:_POINT_PLANES]
    )
    return DecodeResult(
        ts_hi=ts_hi, ts_lo=ts_lo, val_hi=val_hi, val_lo=val_lo,
        point_is_float=pif != 0, mult=mlt, valid=valid != 0,
        err=outs[_POINT_PLANES].reshape(npad)[:n] != 0, values_f32=None,
    )
