"""Sharded scan-and-aggregate: the framework's flagship execution path.

Reference counterpart: the coordinator fan-out query path — index query →
per-shard ReadEncoded → client-side decode → temporal functions → cross-series
aggregation (/root/reference/src/query/storage/fanout/storage.go:76,156 and
src/query/functions/). Here the whole post-index pipeline is one SPMD program:
each device decodes its slice of the series axis (BatchedSegments sharded on
axis 0), reduces within series (time axis), and cross-series aggregates ride
ICI via `jax.lax.psum` over the "shard" mesh axis.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from .. import device
from ..ops.chunked import ChunkedBatch, decode_chunked_lanes
from ..ops.chunked import PROFILER as CHUNKED_PROF
from ..ops.decode import decode_batched
from ..utils.instrument import KernelProfiler
from .mesh import SHARD_AXIS, series_mesh


# device-tier observability for the batched decode kernel: sampled
# block_until_ready-bounded dispatch wall time under
# M3_TPU_PROFILE_SAMPLE_RATE (m3tpu_kernel_dispatch_seconds), the first
# dispatch of a signature (its compile) kept out of it
_JIT_DECODE = KernelProfiler("m3tsz_decode")


class ScanAggregates(NamedTuple):
    """Per-series reductions plus replicated cross-series totals."""

    series_sum: jnp.ndarray  # f32[S] sum_over_time per series
    series_count: jnp.ndarray  # i32[S] valid datapoints per series
    series_min: jnp.ndarray  # f32[S]
    series_max: jnp.ndarray  # f32[S]
    series_last: jnp.ndarray  # f32[S]
    total_sum: jnp.ndarray  # f32[] cross-series (psum over shard axis)
    total_count: jnp.ndarray  # i32[]
    total_min: jnp.ndarray  # f32[]
    total_max: jnp.ndarray  # f32[]
    series_err: jnp.ndarray | None = None  # bool[S] device decode bailed
    #   (annotations etc.) — stitch_host_errors() recomputes those series


def _aggregate_decoded(vals, valid, with_psum):
    """Per-series + cross-series reductions over decoded [S, T] values."""
    zero = jnp.where(valid, vals, 0.0)
    s_sum = jnp.sum(zero, axis=1)
    s_count = jnp.sum(valid.astype(jnp.int32), axis=1)
    s_min = jnp.min(jnp.where(valid, vals, jnp.inf), axis=1)
    s_max = jnp.max(jnp.where(valid, vals, -jnp.inf), axis=1)
    # last valid value per series
    t = vals.shape[1]
    last_idx = jnp.max(jnp.where(valid, jnp.arange(t)[None, :], -1), axis=1)
    s_last = jnp.take_along_axis(zero, jnp.maximum(last_idx, 0)[:, None], axis=1)[:, 0]
    s_last = jnp.where(last_idx >= 0, s_last, jnp.nan)

    has = s_count > 0
    t_sum = jnp.sum(jnp.where(has, s_sum, 0.0))
    t_count = jnp.sum(s_count)
    t_min = jnp.min(jnp.where(has, s_min, jnp.inf))
    t_max = jnp.max(jnp.where(has, s_max, -jnp.inf))
    if with_psum:
        t_sum = jax.lax.psum(t_sum, SHARD_AXIS)
        t_count = jax.lax.psum(t_count, SHARD_AXIS)
        t_min = jax.lax.pmin(t_min, SHARD_AXIS)
        t_max = jax.lax.pmax(t_max, SHARD_AXIS)
    t_min = jnp.where(t_count > 0, t_min, jnp.nan)
    t_max = jnp.where(t_count > 0, t_max, jnp.nan)
    return ScanAggregates(
        series_sum=s_sum,
        series_count=s_count,
        series_min=jnp.where(has, s_min, jnp.nan),
        series_max=jnp.where(has, s_max, jnp.nan),
        series_last=s_last,
        total_sum=t_sum,
        total_count=t_count,
        total_min=t_min,
        total_max=t_max,
    )


def _is_tracing(x) -> bool:
    """True when ``x`` is an abstract tracer — i.e. this Python frame is
    running under an outer jit/shard_map trace, where wall time measures
    tracing (microseconds), not the XLA compile that happens later at the
    outer jit boundary. Compile attribution would be wrong there."""
    try:
        from jax.core import Tracer
    except ImportError:  # jax moved/renamed it: skip tracking, never break
        return True
    return isinstance(x, Tracer)


def _local_scan_aggregate(words, num_bits, initial_unit, *, max_points, with_psum):
    if _is_tracing(words):
        res = decode_batched(words, num_bits, initial_unit, max_points=max_points)
    else:
        # eager call: the first invocation per signature blocks on the jit
        # compile of decode_batched (tracked), and sampled dispatches are
        # block_until_ready-bounded for the dispatch histogram; cost=
        # captures the compiled HLO's flops/bytes once per signature when
        # profiling is on (m3tpu_kernel_flops / _bytes_accessed)
        with _JIT_DECODE.dispatch(
            (tuple(words.shape), int(max_points)),
            cost=(decode_batched, (words, num_bits, initial_unit),
                  {"max_points": max_points}),
        ) as d:
            res = d.done(decode_batched(
                words, num_bits, initial_unit, max_points=max_points
            ))
    return _aggregate_decoded(res.values_f32, res.valid, with_psum)


def scan_aggregate(words, num_bits, initial_unit, max_points: int) -> ScanAggregates:
    """Single-device decode + aggregate (no collectives)."""
    return _local_scan_aggregate(
        words, num_bits, initial_unit, max_points=max_points, with_psum=False
    )


def chunked_scan_aggregate(lane_args: dict, s: int, c: int, k: int, with_psum=False):
    """Flagship fast path: side-table chunked decode (ops/chunked.py) +
    aggregation. ``lane_args`` are ChunkedBatch fields as (device) arrays."""
    if _is_tracing(lane_args["windows"]):
        res = decode_chunked_lanes(**lane_args, k=k)
    else:
        with CHUNKED_PROF.dispatch(
            (tuple(lane_args["windows"].shape), int(k)),
            cost=(decode_chunked_lanes, (), {**lane_args, "k": k}),
        ) as d:
            res = d.done(decode_chunked_lanes(**lane_args, k=k))
    vals = res.values_f32.reshape(s, c * k)
    valid = res.valid.reshape(s, c * k)
    return _aggregate_decoded(vals, valid, with_psum)


def _aggregates_from_lanes(
    lane_agg, s: int, c: int, with_psum: bool, lane_order: str = "s",
    inv=None, precise: bool = False, unpermute_series: bool = True,
) -> ScanAggregates:
    """Reduce per-lane (per-chunk) aggregates [S*C] to ScanAggregates.

    ``lane_order``: "s" = series-major (lane = s*C + c), "c" = chunk-major
    (lane = c*S + s, the specialized packed kernel layout), "sorted" =
    chunk-major with the SERIES axis permuted fast-first; ``inv`` (i32[S])
    gathers the per-series outputs back to original order — an [S] gather,
    not an [S*C] one (TPU gathers are expensive)."""
    unperm = lambda x: x
    if lane_order == "sorted":
        rs = lambda x: x.reshape(c, s).T
        if unpermute_series:
            # [S]-sized gather (~20 ms/262k series on TPU) — callers that
            # only consume cross-series totals (order-independent) pass
            # unpermute_series=False and unpermute fetched arrays on host
            # with PackedLanes.inv when needed
            inv_d = jnp.asarray(inv)
            unperm = lambda x: x[inv_d]
    elif lane_order == "c":
        rs = lambda x: x.reshape(c, s).T
    else:
        rs = lambda x: x.reshape(s, c)
    l_sum, l_cnt = rs(lane_agg.sum), rs(lane_agg.count)
    l_min, l_max, l_last = rs(lane_agg.min), rs(lane_agg.max), rs(lane_agg.last)
    s_err = None
    if getattr(lane_agg, "err", None) is not None:
        s_err = jnp.any(rs(jnp.asarray(lane_agg.err).astype(jnp.int32)) != 0, axis=1)
    if precise:
        # float-float tree sums (ops/precise.py): per-series and the
        # cross-series total carry (hi, lo) pairs — ~1 ulp of exact vs
        # O(log n) ulp for the plain tree (TOLERANCE.md)
        from ..ops import precise as pr

        sp_hi, sp_lo = pr.compensated_sum(l_sum, axis=1)
        s_sum = sp_hi + sp_lo
    else:
        s_sum = jnp.sum(l_sum, axis=1)
    s_count = jnp.sum(l_cnt, axis=1)
    s_min = jnp.min(l_min, axis=1)
    s_max = jnp.max(l_max, axis=1)
    # last = value of the last chunk that saw any valid record
    cidx = jnp.arange(c)[None, :]
    last_c = jnp.max(jnp.where(l_cnt > 0, cidx, -1), axis=1)
    s_last = jnp.take_along_axis(l_last, jnp.maximum(last_c, 0)[:, None], axis=1)[:, 0]
    s_last = jnp.where(last_c >= 0, s_last, jnp.nan)

    has = s_count > 0
    if precise:
        from ..ops import precise as pr

        t_pair = pr.compensated_sum(jnp.where(has, sp_hi, 0.0)[None, :], axis=1)
        t_lo_pair = pr.compensated_sum(jnp.where(has, sp_lo, 0.0)[None, :], axis=1)
        t_pair = pr.dd_add(
            (t_pair[0][0], t_pair[1][0]), (t_lo_pair[0][0], t_lo_pair[1][0])
        )
        t_sum = None  # assembled below (pair form survives the psum)
    else:
        t_sum = jnp.sum(jnp.where(has, s_sum, 0.0))
    t_count = jnp.sum(s_count)
    t_min = jnp.min(jnp.where(has, s_min, jnp.inf))
    t_max = jnp.max(jnp.where(has, s_max, -jnp.inf))
    if with_psum:
        if precise:
            from ..ops import precise as pr

            # psum hi and lo separately; renormalize after the collective
            t_pair = pr.fast_two_sum(
                jax.lax.psum(t_pair[0], SHARD_AXIS),
                jax.lax.psum(t_pair[1], SHARD_AXIS),
            )
        else:
            t_sum = jax.lax.psum(t_sum, SHARD_AXIS)
        t_count = jax.lax.psum(t_count, SHARD_AXIS)
        t_min = jax.lax.pmin(t_min, SHARD_AXIS)
        t_max = jax.lax.pmax(t_max, SHARD_AXIS)
    if precise:
        t_sum = t_pair[0] + t_pair[1]
    t_min = jnp.where(t_count > 0, t_min, jnp.nan)
    t_max = jnp.where(t_count > 0, t_max, jnp.nan)
    return ScanAggregates(
        series_sum=unperm(s_sum),
        series_count=unperm(s_count),
        series_min=unperm(jnp.where(has, s_min, jnp.nan)),
        series_max=unperm(jnp.where(has, s_max, jnp.nan)),
        series_last=unperm(s_last),
        total_sum=t_sum,
        total_count=t_count,
        total_min=t_min,
        total_max=t_max,
        series_err=unperm(s_err) if s_err is not None else None,
    )


def stitch_host_errors(aggs: ScanAggregates, stream_for) -> ScanAggregates:
    """Query-layer stitch for device-erred lanes: series whose device
    decode bailed (annotations and other host-only features set the
    per-lane err flag, ops/decode.py) are recomputed through the host
    codec and patched into the aggregate block; totals are rebuilt from
    the patched per-series arrays in float64.

    ``stream_for(series_idx) -> bytes`` returns the series' encoded
    stream (the caller owns the segment source)."""
    import numpy as np

    from ..codec.m3tsz import decode

    if aggs.series_err is None:
        return aggs
    err = np.asarray(aggs.series_err).astype(bool)
    idxs = np.nonzero(err)[0]
    if idxs.size == 0:
        return aggs
    s_sum = np.asarray(aggs.series_sum).copy()
    s_cnt = np.asarray(aggs.series_count).copy()
    s_min = np.asarray(aggs.series_min).copy()
    s_max = np.asarray(aggs.series_max).copy()
    s_last = np.asarray(aggs.series_last).copy()
    for i in idxs:
        dps = decode(stream_for(int(i)))
        if not dps:
            s_sum[i] = 0.0
            s_cnt[i] = 0
            s_min[i] = s_max[i] = s_last[i] = np.nan
            continue
        vals32 = np.asarray([dp.value for dp in dps], np.float32)
        s_sum[i] = np.float32(np.sum(vals32.astype(np.float64)))
        s_cnt[i] = len(vals32)
        s_min[i] = vals32.min()
        s_max[i] = vals32.max()
        s_last[i] = vals32[-1]
    has = s_cnt > 0
    return ScanAggregates(
        series_sum=s_sum,
        series_count=s_cnt,
        series_min=s_min,
        series_max=s_max,
        series_last=s_last,
        total_sum=np.float32(np.sum(s_sum[has].astype(np.float64))),
        total_count=int(s_cnt.sum()),
        total_min=np.float32(np.min(s_min[has])) if has.any() else np.float32(np.nan),
        total_max=np.float32(np.max(s_max[has])) if has.any() else np.float32(np.nan),
        series_err=np.zeros_like(err),
    )


def chunked_scan_aggregate_fused(
    lane_args: dict, s: int, c: int, k: int, with_psum=False, backend: str = "auto"
):
    """Fused flagship path (ops/fused.py): the K-step decode runs with state
    on-chip and only per-lane aggregates leave the kernel. ``backend``:
    "pallas" (TPU kernel), "jnp" (lax.scan fallback), or "auto"."""
    from ..ops import fused

    if backend == "auto":
        # Mosaic kernels are TPU-only; every other backend (cpu, gpu) takes
        # the lax.scan fallback rather than attempting a pltpu lowering.
        backend = "pallas" if device.on_tpu() else "jnp"
    fn = fused.lane_aggregates_pallas if backend == "pallas" else fused.lane_aggregates_jnp
    if _is_tracing(lane_args["windows"]):
        lane_agg = fn(**lane_args, k=k)
    else:
        with fused.PROFILER_FUSED.dispatch(
            (backend, tuple(lane_args["windows"].shape), int(k))
        ) as d:
            lane_agg = d.done(fn(**lane_args, k=k))
    return _aggregates_from_lanes(lane_agg, s, c, with_psum)


def chunked_scan_aggregate_packed(
    windows4, lanes4, tile_flags=None, n: int = 0, s: int = 0, c: int = 0,
    k: int = 0, with_psum=False, interpret: bool = False,
    lane_order: str = "c", inv=None, precise: bool = False,
    unpermute_series: bool = True,
):
    """Packed-layout flagship path: 3 contiguous DMAs per Pallas grid program
    (ops/fused.py packed kernel). Inputs come from fused.pack_lane_inputs;
    ``tile_flags`` routes homogeneous fast tiles through the specialized
    all-int body; ``inv`` (with lane_order="sorted") gathers the fast-first
    permuted lanes back to series order."""
    from ..ops import fused

    if _is_tracing(windows4):
        lane_agg = fused.lane_aggregates_packed(
            windows4, lanes4, tile_flags, n=n, k=k, interpret=interpret
        )
    else:
        with fused.PROFILER_PACKED.dispatch(
            (tuple(windows4.shape), int(n), int(k))
        ) as d:
            lane_agg = d.done(fused.lane_aggregates_packed(
                windows4, lanes4, tile_flags, n=n, k=k, interpret=interpret
            ))
    return _aggregates_from_lanes(
        lane_agg, s, c, with_psum, lane_order=lane_order, inv=inv,
        precise=precise, unpermute_series=unpermute_series,
    )


def chunked_device_args(batch: ChunkedBatch, device_put=True) -> dict:
    """ChunkedBatch → kwargs for decode_chunked_lanes, device-resident."""
    import jax as _jax

    from ..ops.chunked import lane_kwargs

    put = (lambda x: _jax.device_put(jnp.asarray(x))) if device_put else jnp.asarray
    return lane_kwargs(batch, transform=put)


def make_sharded_chunked_scan(mesh, s: int, c: int, k: int):
    """Sharded flagship path: chunked decode + aggregate over the mesh.

    Lane arrays are [S*C] series-major, so sharding axis 0 across N devices
    keeps whole series on one device as long as S % N == 0 (pad with empty
    series otherwise). Cross-series totals psum over the shard axis.
    """
    n_dev = mesh.devices.size
    if s % n_dev != 0:
        raise ValueError(f"series count {s} not divisible by mesh size {n_dev}")

    def local(lane_args):
        return chunked_scan_aggregate(lane_args, s // n_dev, c, k, with_psum=True)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS),),
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
        ),
        check_vma=False,
    )
    return jax.jit(fn)


def make_sharded_scan(mesh, max_points: int):
    """Build a pjit'd scan-and-aggregate over ``mesh``'s shard axis.

    Inputs must have a series count divisible by the mesh size (pad with
    num_bits==0 series — zero-length streams decode to no valid points and
    drop out of every reduction).
    """
    fn = shard_map(
        functools.partial(
            _local_scan_aggregate, max_points=max_points, with_psum=True
        ),
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
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
        ),
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_scan_aggregate(
    words, num_bits, initial_unit, max_points: int, mesh=None
) -> ScanAggregates:
    mesh = mesh if mesh is not None else series_mesh()
    return make_sharded_scan(mesh, max_points)(words, num_bits, initial_unit)


# ---------------------------------------------------------------------------
# Decode-from-HBM, chunk-parallel: lane assembly by device gather over the
# resident pool's page buffer + side planes (m3_tpu/resident/pool.py)
# ---------------------------------------------------------------------------
#
# The whole-stream resident scan below decodes with a T-step lax.scan and
# measured 0.17x the chunked kernel even on CPU (PROFILE.md). Here the
# per-chunk side tables are ALREADY device-resident (paged in at
# admission), so a scan assembles the ChunkedBatch/PackedLanes lane view —
# windows, rel_pos/num_bits, decoder-state carries, classification flags —
# by pure device gathers from O(series)-sized host int vectors and
# dispatches the same chunked/packed kernels the streamed path uses.

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
    from ..ops.sideplane import SIDE_WORDS, unpack_side_planes

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
    from ..ops.fused import NLANE, PACKED_LANE_PLANES

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
    same flagship kernel the streamed pipeline (parallel/stream.py)
    dispatches."""
    from ..ops.fused import ROWS_DEFAULT

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
    import numpy as _np

    s = plan.page_rows.shape[0]
    if s_pad == s:
        return (plan.page_rows, plan.side_rows, plan.n_chunks,
                plan.total_bits, plan.block_hi, plan.block_lo)
    pr = _np.zeros((s_pad, plan.page_rows.shape[1]), _np.int32)
    pr[:s] = plan.page_rows
    sr = _np.zeros((s_pad, plan.side_rows.shape[1]), _np.int32)
    sr[:s] = plan.side_rows
    nc = _np.zeros(s_pad, _np.int32)
    nc[:s] = plan.n_chunks
    tb = _np.zeros(s_pad, _np.int32)
    tb[:s] = plan.total_bits
    bh = _np.zeros(s_pad, _np.uint32)
    bh[:s] = plan.block_hi
    bl = _np.zeros(s_pad, _np.uint32)
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

    from ..ops.fused import ROWS_DEFAULT

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
