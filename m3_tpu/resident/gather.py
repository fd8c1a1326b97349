"""Decode-from-HBM lane assembly: the read side of the resident pool's
page format (pool.py).

A scan or a query plan hands over O(series)-sized host int vectors
(``ResidentPool.plan_chunked``); the bodies here turn page rows, side-page
rows, the reserved zero page and the packed side words into decode lanes by
device gathers over the pool's page buffer and side planes, and dispatch
the same chunked / packed kernels (parallel/scan.py) the streamed path
uses. parallel/ aggregates over lanes and knows nothing of pages; this is
the one module outside pool.py that does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import device
from ..ops.fused import NLANE, PACKED_LANE_PLANES, ROWS_DEFAULT
from ..ops.sideplane import SIDE_WORDS, unpack_side_planes
from ..parallel.mesh import SHARD_AXIS
from ..parallel.scan import ScanAggregates, chunked_scan_aggregate_packed
from ..utils.instrument import KernelProfiler

# The per-chunk side tables are ALREADY device-resident (paged in at
# admission), so a scan assembles the ChunkedBatch/PackedLanes lane view —
# windows, rel_pos/num_bits, decoder-state carries, classification flags —
# by pure device gathers, chunk-parallel: decoding a resident stream whole,
# with a T-step lax.scan, ran at a fraction of the chunked kernel's rate
# even off the chip, which is why no whole-stream resident scan exists.

RESIDENT_CHUNKED_PROF = KernelProfiler("resident_chunked_assemble")


def _resident_gather(pool_words, side_words, page_rows, side_rows,
                     n_chunks, total_bits, block_hi, block_lo,
                     si, ci, cw: int, w: int, spc: int, per_lane=None):
    """Shared gather core for both lane layouts: (si, ci) lane->chunk
    coordinate vectors -> (planes dict, windows [N, CW], rel, nbits,
    valid). ``planes`` are the decoder-state lane planes unpacked from
    the packed 10-word side rows (ops/sideplane.py; prev_time re-based
    off the per-series block_start pair). Every array is built to be
    BIT-IDENTICAL to what ops/chunked.assemble_chunked produces for the
    same streams (windows zeroed on invalid lanes, all-zero state for
    padding) so the shared decode programs yield bit-identical results.

    ``per_lane(x)`` maps a per-series table [S] to its per-lane values
    and ``per_lane(x, col)`` picks column ``col`` [N] of each lane's row of
    an [S, X] table: ``x[si]`` and ``x[si, col]`` where not given. A caller
    whose ``si`` is known when it traces passes the same thing without a
    gather over the series: on the TPU ``x[si]`` is an element-wise loop,
    or (small S) a select chain over one mask a series, each mask a device
    operation of its own."""
    if per_lane is None:
        per_lane = lambda x, col=None: x[si] if col is None else x[si, col]

    page_rows = jnp.asarray(page_rows, jnp.int32)
    side_rows = jnp.asarray(side_rows, jnp.int32)
    valid = ci < per_lane(jnp.asarray(n_chunks, jnp.int32))
    # side slot: page-granular indirection (chunk ci sits at slot ci%spc
    # of side page ci//spc); invalid lanes hit reserved zero page 0
    sp = per_lane(side_rows, jnp.where(valid, ci, 0) // spc)
    slot = jnp.where(valid, sp * spc + ci % spc, 0)
    side = jnp.take(
        jnp.asarray(side_words, jnp.uint32).reshape(-1, SIDE_WORDS),
        slot, axis=0,
    )  # [N, SIDE_WORDS] packed rows
    bs = (
        per_lane(jnp.asarray(block_hi, jnp.uint32)),
        per_lane(jnp.asarray(block_lo, jnp.uint32)),
    )
    planes = unpack_side_planes(side, bs, valid)
    off = planes["off"].astype(jnp.int32)
    w0 = off >> 5
    rel = off & 31
    tb = per_lane(jnp.asarray(total_bits, jnp.int32))
    nbits = jnp.where(valid, jnp.clip(tb - (w0 << 5), 0, cw * 32), 0)
    # windows: word position -> page (tiny int table), then page*W +
    # word%W into the flat pool. Trailing zero-page columns in page_rows
    # guarantee w0 + cw - 1 stays in range and reads zeros.
    j = jnp.arange(cw, dtype=jnp.int32)[None, :]
    wabs = w0[:, None] + j  # [N, CW] absolute word index within the lane
    # 2-D indices, NOT takes over flattened tables: on the TPU the
    # reshape of the pool to 1-D is a re-layout copy of the whole pool per
    # program, and the flat take from page_rows compiled in minutes (94 s
    # against 0.9 s at 8192 series)
    # the page of each word: a window of cw words touches at most
    # ceil((cw - 1) / w) + 1 consecutive pages (two, at the deployed 512-word
    # page), so it is that many page ids a LANE and a select a word, where
    # a page id a WORD was a second element-wise gather over [N, CW] (a
    # fifth of the plan program's device time: PERF.md section 6, PR 29)
    p0 = w0 // w
    last = page_rows.shape[1] - 1
    page = per_lane(page_rows, p0)[:, None]
    for k in range(1, (w + cw - 2) // w + 1):
        page = jnp.where(wabs // w - p0[:, None] == k,
                         per_lane(page_rows, jnp.minimum(p0 + k, last))[:, None],
                         page)
    words = jnp.asarray(pool_words, jnp.uint32)[page, wabs % w]
    windows = jnp.where(valid[:, None], words, jnp.uint32(0))
    return planes, windows, rel, nbits, valid


def _assemble_resident_lanes_traced(pool_words, side_words, page_rows,
                                    side_rows, n_chunks, total_bits,
                                    block_hi, block_lo,
                                    c: int, cw: int, w: int, spc: int) -> dict:
    """Traced body: resident plan arrays -> decode_chunked_lanes kwargs
    (series-major lane order, ChunkedBatch layout)."""
    s = page_rows.shape[0]
    n = s * c
    lane = jnp.arange(n, dtype=jnp.int32)
    si = lane // c
    ci = lane % c
    # series-major lanes: a series' row c times over and a pick along
    # it, no gather over the series
    def per_lane(x, col=None):
        rows = jnp.broadcast_to(
            x[:, None], (s, c) + x.shape[1:]
        ).reshape((n,) + x.shape[1:])
        if col is None:
            return rows
        return jnp.take_along_axis(rows, col[:, None], axis=1, mode="clip")[:, 0]

    planes, windows, rel, nbits, valid = _resident_gather(
        pool_words, side_words, page_rows, side_rows, n_chunks, total_bits,
        block_hi, block_lo, si, ci, cw, w, spc, per_lane=per_lane,
    )
    return dict(
        windows=windows,
        rel_pos=rel,
        num_bits=nbits,
        first=valid & (ci == 0),
        prev_time=planes["prev_time"],
        prev_delta=planes["prev_delta"],
        prev_float_bits=planes["prev_float_bits"],
        prev_xor=planes["prev_xor"],
        int_val=planes["int_val"],
        time_unit=planes["time_unit"].astype(jnp.int32),
        sig=planes["sig"].astype(jnp.int32),
        mult=planes["mult"].astype(jnp.int32),
        is_float=planes["is_float"] != 0,
    )


# the traced body under the name a program that assembles lanes inside its
# own jit imports (query/plan.py stage 4); the def keeps its name because
# jax names the compiled program, the trace's events and the compile
# cache's key after it
assemble_lanes_traced = _assemble_resident_lanes_traced

_assemble_resident_lanes_jit = jax.jit(
    _assemble_resident_lanes_traced, static_argnames=("c", "cw", "w", "spc")
)


def assemble_resident_lanes(plan, s_pad: int | None = None) -> tuple[dict, int]:
    """Eager entry: a ResidentChunkedPlan -> (decode_chunked_lanes lane
    kwargs on device, padded series count). ``s_pad`` pads the series
    axis with empty lanes (page row 0 / side page 0 -> zero windows,
    nbits 0) exactly like the streamed path's b"" padding streams."""
    s = plan.page_rows.shape[0]
    s_pad = s if s_pad is None else max(s_pad, s)
    vecs = pad_chunked_plan(plan, s_pad)
    key = (s_pad, plan.num_chunks, plan.window_words)
    with RESIDENT_CHUNKED_PROF.dispatch(key) as d:
        lane_args = d.done(_assemble_resident_lanes_jit(
            plan.words, plan.side, *vecs,
            c=plan.num_chunks, cw=plan.window_words, w=plan.page_words,
            spc=plan.side_page_chunks,
        ))
    return lane_args, s_pad


def _assemble_resident_packed_traced(pool_words, side_words, page_rows,
                                     side_rows, n_chunks, total_bits,
                                     block_hi, block_lo,
                                     c: int, cw: int, w: int, spc: int,
                                     rows: int):
    """Traced body: resident plan arrays -> the packed kernel's layout
    (ops/fused.pack_lane_inputs, chunk-major "c" order): windows4
    u32[tiles, CW, R, 128], lanes4 u32[tiles, NLANE, R, 128], tile_flags
    i32[tiles]. Mirrors the host packer EXACTLY — chunk-major lane j maps
    to (series j%S, chunk j//S), tile-padding lanes are zero/wildcard-fast,
    first chunks are never fast — so on the same streams both packings are
    bit-identical and the kernel's specialization decisions agree."""
    s = page_rows.shape[0]
    n = s * c
    tile_lanes = rows * 128
    tiles = -(-n // tile_lanes)
    npad = tiles * tile_lanes
    j = jnp.arange(npad, dtype=jnp.int32)
    inb = j < n
    si = jnp.where(inb, j % s, 0)
    ci = jnp.where(inb, j // s, c)  # padding lanes: ci==c is never valid
    planes, windows, rel, nbits, valid = _resident_gather(
        pool_words, side_words, page_rows, side_rows, n_chunks, total_bits,
        block_hi, block_lo, si, ci, cw, w, spc,
    )
    first = valid & (ci == 0)

    def u32_plane(name):
        if name == "rel_pos":
            return rel.astype(jnp.uint32)
        if name == "num_bits":
            return nbits.astype(jnp.uint32)
        if name == "first":
            return first.astype(jnp.uint32)
        if name.endswith("_hi"):
            return planes[name[:-3]][0]
        if name.endswith("_lo"):
            return planes[name[:-3]][1]
        return planes[name]  # unpacked as uint32 already

    lanes4 = jnp.stack([u32_plane(name) for name in PACKED_LANE_PLANES])
    lanes4 = lanes4.reshape(NLANE, tiles, rows, 128).transpose(1, 0, 2, 3)
    windows4 = windows.reshape(tiles, rows, 128, cw).transpose(0, 3, 1, 2)
    # tile class from the v2 fast-chunk flags bits (packed side word 8):
    # 1 = every lane int-fast, 2 = every lane float-fast, 0 = general.
    # First chunks decode the stream head the fast bodies don't implement;
    # invalid/padding lanes are wildcard-fast — both exactly as the host
    # packer classifies.
    flags = planes["flags"]
    fast_i = jnp.where(valid, ((flags & 1) != 0) & (ci != 0), True)
    fast_f = jnp.where(valid, ((flags & 2) != 0) & (ci != 0), True)
    int_tiles = jnp.all(fast_i.reshape(tiles, tile_lanes), axis=1)
    flt_tiles = jnp.all(fast_f.reshape(tiles, tile_lanes), axis=1)
    tile_flags = jnp.where(int_tiles, 1, jnp.where(flt_tiles, 2, 0)).astype(jnp.int32)
    return windows4, lanes4, tile_flags


_assemble_resident_packed_jit = jax.jit(
    _assemble_resident_packed_traced,
    static_argnames=("c", "cw", "w", "spc", "rows"),
)


def assemble_resident_packed(plan, s_pad: int | None = None):
    """Eager entry: a ResidentChunkedPlan -> ((windows4, lanes4,
    tile_flags) on device, padded series count). The packed twin of
    assemble_resident_lanes — feeds chunked_scan_aggregate_packed, the
    same flagship kernel the streamed scan (scan.streamed_scan_totals)
    dispatches."""
    s = plan.page_rows.shape[0]
    s_pad = s if s_pad is None else max(s_pad, s)
    vecs = pad_chunked_plan(plan, s_pad)
    key = ("packed", s_pad, plan.num_chunks, plan.window_words)
    with RESIDENT_CHUNKED_PROF.dispatch(key) as d:
        packed = d.done(_assemble_resident_packed_jit(
            plan.words, plan.side, *vecs,
            c=plan.num_chunks, cw=plan.window_words, w=plan.page_words,
            spc=plan.side_page_chunks, rows=ROWS_DEFAULT,
        ))
    return packed, s_pad


def pad_chunked_plan(plan, s_pad: int):
    """Zero-pad a ResidentChunkedPlan's host vectors to ``s_pad`` series.
    Returns (page_rows, side_rows, n_chunks, total_bits, block_hi,
    block_lo) — the positional array args of the assembly bodies."""
    s = plan.page_rows.shape[0]
    if s_pad == s:
        return (plan.page_rows, plan.side_rows, plan.n_chunks,
                plan.total_bits, plan.block_hi, plan.block_lo)
    pr = np.zeros((s_pad, plan.page_rows.shape[1]), np.int32)
    pr[:s] = plan.page_rows
    sr = np.zeros((s_pad, plan.side_rows.shape[1]), np.int32)
    sr[:s] = plan.side_rows
    nc = np.zeros(s_pad, np.int32)
    nc[:s] = plan.n_chunks
    tb = np.zeros(s_pad, np.int32)
    tb[:s] = plan.total_bits
    bh = np.zeros(s_pad, np.uint32)
    bh[:s] = plan.block_hi
    bl = np.zeros(s_pad, np.uint32)
    bl[:s] = plan.block_lo
    return pr, sr, nc, tb, bh, bl


def resident_chunked_local_fn(c: int, k: int, cw: int, w: int, spc: int,
                              with_psum: bool = False):
    """The assemble-from-residency + packed-decode body: device gathers
    over the pool + side planes build the PackedLanes view, fused with
    the flagship packed kernel. ONE definition shared by the
    single-device resident scan (resident/scan._packed_scan_fn) and the
    shard_map local of make_sharded_resident_chunked_scan — the two
    dispatch paths must never diverge on assembly semantics."""

    interpret = not device.on_tpu()

    def local(pool_words, side_words, page_rows, side_rows, n_chunks,
              total_bits, block_hi, block_lo):
        windows4, lanes4, tile_flags = _assemble_resident_packed_traced(
            pool_words, side_words, page_rows, side_rows, n_chunks,
            total_bits, block_hi, block_lo, c=c, cw=cw, w=w, spc=spc,
            rows=ROWS_DEFAULT,
        )
        s_local = page_rows.shape[0]
        return chunked_scan_aggregate_packed(
            windows4, lanes4, tile_flags, n=s_local * c, s=s_local, c=c,
            k=k, with_psum=with_psum, interpret=interpret,
        )

    return local


def make_sharded_resident_chunked_scan(mesh, c: int, k: int, cw: int, w: int,
                                       spc: int):
    """Sharded decode-from-HBM CHUNKED scan: the page pool + side planes
    ride replicated (each device of a real mesh holds its placement's
    pages; on the forced CPU test mesh replication is free) while the
    per-series plan vectors shard over the mesh's series axis. Lane
    assembly AND decode run inside the shard_map, psum reduction
    unchanged."""

    local = resident_chunked_local_fn(c, k, cw, w, spc, with_psum=True)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                  P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=ScanAggregates(
            series_sum=P(SHARD_AXIS),
            series_count=P(SHARD_AXIS),
            series_min=P(SHARD_AXIS),
            series_max=P(SHARD_AXIS),
            series_last=P(SHARD_AXIS),
            total_sum=P(),
            total_count=P(),
            total_min=P(),
            total_max=P(),
            series_err=P(SHARD_AXIS),
        ),
        check_vma=False,
    )
    return jax.jit(fn)
