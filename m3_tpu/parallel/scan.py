"""Scan-and-aggregate over decode lanes: the framework's flagship execution path.

Reference counterpart: the coordinator fan-out query path — index query →
per-shard ReadEncoded → client-side decode → temporal functions → cross-series
aggregation (/root/reference/src/query/storage/fanout/storage.go:76,156 and
src/query/functions/). Here the whole post-index pipeline is one SPMD program:
each device decodes its slice of the series axis (chunk lanes sharded on
axis 0), reduces within series (time axis), and cross-series aggregates ride
ICI via `jax.lax.psum` over the "shard" mesh axis.

Two entries: ``chunked_scan_aggregate_packed`` is the served kernel (what
resident/scan.py dispatches), ``chunked_scan_aggregate`` the plain-jnp
reference it is tested against. This module aggregates over lanes and knows
nothing of where they came from; the resident pool's page format and the
gather that turns pages into lanes live in resident/ (gather.py).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from ..ops.chunked import ChunkedBatch, decode_chunked_lanes
from ..ops.chunked import PROFILER as CHUNKED_PROF
from .mesh import SHARD_AXIS


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
    inv=None, precise: bool = False,
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


def chunked_scan_aggregate_packed(
    windows4, lanes4, tile_flags=None, n: int = 0, s: int = 0, c: int = 0,
    k: int = 0, with_psum=False, interpret: bool = False,
    lane_order: str = "c", inv=None, precise: bool = False,
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
        precise=precise,
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
