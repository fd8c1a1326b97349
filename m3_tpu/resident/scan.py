"""Decode-from-HBM scan orchestration over the paged resident pool.

Bridges the host page table (pool.py) and the device scan path
(gather.py, then parallel/scan.py). Since the side planes landed in the
pool (PR 11), the resident scan is CHUNK-PARALLEL: plan_chunked hands over
O(series) int vectors, gather.py builds the PackedLanes view by device
gather over page rows + side planes, and the SAME packed fused kernel the
streamed scan below dispatches decodes it — no host rebuild of chunk
tables, no T-step whole-stream scan.

Bit-exactness contract: ``resident_scan_totals`` and
``streamed_scan_totals`` funnel through ONE shared decode + aggregation
path (parallel/scan.chunked_scan_aggregate_packed) over
identically-shaped, bit-identical packed lane arrays (the device
assembly mirrors ops/fused.pack_lane_inputs exactly, tile flags
included), so on identical input streams their float32 totals match bit
for bit — the property tests assert exact equality, not tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import device
from ..ops.chunked import build_chunked, decode_chunked_lanes
from ..ops.decode import DecodeResult, finalize_decode
from ..ops.fused import pack_lane_inputs
from ..parallel.scan import chunked_scan_aggregate_packed
from ..storage.fs import CHUNK_K
from ..utils.instrument import DEFAULT as METRICS
from .gather import (
    RESIDENT_CHUNKED_PROF,
    assemble_resident_lanes,
    make_sharded_resident_chunked_scan,
    pad_chunked_plan,
    resident_chunked_local_fn,
)

# host->device block bytes moved by the STREAMED scan path (the fallback
# when matched blocks are not fully resident); warm resident scans leave
# this and resident_upload_bytes_total untouched — the zero-transfer
# acceptance test asserts on both counters
STREAMED_BYTES = METRICS.counter(
    "scan_streamed_bytes_total",
    "host->device block bytes uploaded by the streamed scan fallback",
)

_MIN_LANES = 8  # also the forced CPU test mesh size (conftest)


def _pow2(n: int, lo: int = 1) -> int:
    return max(lo, 1 << max(int(n) - 1, 0).bit_length())


def resident_scan_totals(pool, keys: list, mesh=None):
    """Scan-and-aggregate the resident lanes for ``keys`` (one lane per
    (series, block) key) through the chunk-parallel kernels. Returns a
    ScanAggregates with the series arrays sliced back to ``len(keys)``,
    or None when any key is not resident (or carries no side planes —
    the caller streams instead, keeping the parity contract trivially).

    ``mesh``: shard the lanes across a device mesh (gather.py
    make_sharded_resident_chunked_scan, psum reduction unchanged);
    None = single device."""
    with pool.read_lease():
        plan = pool.plan_chunked(keys)
        if plan is None:
            return None
        s = len(keys)
        s_pad = _pow2(s, _MIN_LANES)
        if mesh is not None:
            n_dev = mesh.devices.size
            s_pad = _pow2(max(s_pad, n_dev), _MIN_LANES)
        vecs = pad_chunked_plan(plan, s_pad)
        shape_key = (plan.num_chunks, plan.chunk_k, plan.window_words,
                     plan.page_words, plan.side_page_chunks)
        if mesh is not None:
            fn = _sharded_chunked(mesh, *shape_key)
        else:
            fn = _packed_scan_fn(*shape_key)
        with RESIDENT_CHUNKED_PROF.dispatch(
            ("scan", s_pad, *shape_key, mesh is not None)
        ) as d:
            aggs = d.done(fn(plan.words, plan.side, *vecs))
        return _slice_series(aggs, s)


@functools.lru_cache(maxsize=32)
def _packed_scan_fn(c: int, k: int, cw: int, w: int, spc: int):
    """ONE jitted program per plan shape: PackedLanes assembly (device
    gathers over the pool + side planes) fused with the packed decode
    kernel — the gathered lane arrays never materialize between
    dispatches. The body is gather.resident_chunked_local_fn, shared
    with the sharded variant so the two paths can't diverge."""
    return jax.jit(resident_chunked_local_fn(c, k, cw, w, spc))


@functools.lru_cache(maxsize=32)
def _sharded_chunked(mesh, c: int, k: int, cw: int, w: int, spc: int):
    return make_sharded_resident_chunked_scan(mesh, c, k, cw, w, spc)


def streamed_scan_totals(segments: list, k: int = CHUNK_K):
    """The streamed twin of resident_scan_totals: prescan + upload
    ``segments`` (one m3tsz stream per lane) as chunk lanes and run the
    same decode + aggregation with the same padding buckets (series_err
    carried the same way). Charges the uploaded bytes to
    scan_streamed_bytes_total. ``k`` must match the chunk size the
    resident path decodes with (the fileset's chunkK) for the bit-exact
    parity contract — the chunk decomposition sets the f32 reduction
    order."""
    s = len(segments)
    s_pad = _pow2(s, _MIN_LANES)
    batch = build_chunked(list(segments) + [b""] * (s_pad - s), k=k)
    packed = pack_lane_inputs(batch)
    windows4 = jax.device_put(packed.windows4)
    lanes4 = jax.device_put(packed.lanes4)
    tile_flags = jax.device_put(packed.tile_flags)
    # counter semantics: compressed BLOCK bytes the fallback had to move
    # off-pool (the quantity residency eliminates, matching the metric
    # name/help, shard heat, and the upload_bytes comparison) — NOT the
    # packed lane arrays, which duplicate overlapping window words
    # across chunks and would silently rescale dashboards several-fold
    STREAMED_BYTES.inc(sum(len(seg) for seg in segments))
    aggs = chunked_scan_aggregate_packed(
        windows4, lanes4, tile_flags,
        n=packed.n, s=s_pad, c=batch.num_chunks, k=k,
        lane_order=packed.order, interpret=not device.on_tpu(),
    )
    return _slice_series(aggs, s)


_SERIES_FIELDS = (
    "series_sum", "series_count", "series_min", "series_max",
    "series_last", "series_err",
)


def _slice_series(aggs, s: int):
    out = {}
    for name in _SERIES_FIELDS:
        v = getattr(aggs, name)
        # m3lint: disable=M3L010 -- sanctioned end-of-scan host finalize: the one device->host copy after the fused dispatch
        out[name] = np.asarray(v)[:s] if v is not None else None
    return aggs._replace(**out)


def resident_fetch_arrays(pool, keys: list):
    """Exact datapoint reconstruction from HBM: decode the resident lanes
    for ``keys`` through the chunked kernel and return
    ``([(times i64[n], values f64[n])], err bool[S])`` — bit-exact vs the
    host codec (ops/decode.finalize_decode), with ``err[i]`` flagging
    lanes the device decoder bailed on (annotated streams) so the caller
    can re-read those through the host path.

    Returns None when any key is not resident."""
    with pool.read_lease():
        plan = pool.plan_chunked(keys)
        if plan is None:
            return None
        s = len(keys)
        s_pad = _pow2(s, _MIN_LANES)
        lane_args, s_pad = assemble_resident_lanes(plan, s_pad)
        c, k = plan.num_chunks, plan.chunk_k
        with RESIDENT_CHUNKED_PROF.dispatch(
            ("fetch", tuple(lane_args["windows"].shape), int(k))
        ) as d:
            res = d.done(decode_chunked_lanes(**lane_args, k=k))

    rs = lambda x: x.reshape(s_pad, c * k)
    res = DecodeResult(
        ts_hi=rs(res.ts_hi),
        ts_lo=rs(res.ts_lo),
        val_hi=rs(res.val_hi),
        val_lo=rs(res.val_lo),
        point_is_float=rs(res.point_is_float),
        mult=rs(res.mult),
        valid=rs(res.valid),
        err=jnp.any(res.err.reshape(s_pad, c), axis=1),
        values_f32=rs(res.values_f32),
    )
    timestamps, values, valid = finalize_decode(res)
    err = np.asarray(res.err, bool)[:s]
    out = []
    for i in range(s):
        m = valid[i]
        out.append((timestamps[i][m], values[i][m]))
    return out, err
