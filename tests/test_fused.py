"""Parity tests for the fused decode+aggregate kernel (ops/fused.py).

The packed kernel is what the served scan dispatches; these tests pin it to
the chunked oracle (ops/chunked.py + parallel/scan.chunked_scan_aggregate)
in two tiers:

  1. Pallas interpret-mode vs oracle (always, CPU mesh) — exercises the exact
     kernel body Mosaic compiles, catching i1-vector hazards before hardware
  2. Mosaic compile for a described v5e (tests/test_tpu_compile.py) and
     compile+run vs oracle on the chip (chip_smoke.py, kernel-parity phase)
"""

import functools

import jax
import numpy as np
import pytest

from m3_tpu.ops.chunked import build_chunked, tile_chunked
from m3_tpu.parallel.scan import (
    chunked_device_args,
    chunked_scan_aggregate,
)
from m3_tpu.utils.synthetic import synthetic_streams


def _batch(k=16, n_series=96, n_points=97, seed=7):
    streams = synthetic_streams(32, n_points, seed=seed)
    return tile_chunked(build_chunked(streams, k=k), n_series)


def _oracle(batch, args):
    fn = jax.jit(
        functools.partial(
            chunked_scan_aggregate,
            s=batch.num_series,
            c=batch.num_chunks,
            k=batch.k,
        )
    )
    return fn(args)


def _assert_matches(got, want, rtol=1e-6):
    np.testing.assert_array_equal(np.asarray(got.series_count), np.asarray(want.series_count))
    np.testing.assert_allclose(np.asarray(got.series_sum), np.asarray(want.series_sum), rtol=rtol)
    np.testing.assert_allclose(np.asarray(got.series_min), np.asarray(want.series_min), rtol=rtol)
    np.testing.assert_allclose(np.asarray(got.series_max), np.asarray(want.series_max), rtol=rtol)
    np.testing.assert_allclose(np.asarray(got.series_last), np.asarray(want.series_last), rtol=rtol)
    assert int(got.total_count) == int(want.total_count)
    np.testing.assert_allclose(float(got.total_sum), float(want.total_sum), rtol=rtol)


@pytest.mark.parametrize("k", [8, 16, 24, 32])
def test_packed_pallas_interpret_matches_oracle(k):
    """Packed-layout kernel (3-DMA fast path) in interpret mode vs oracle,
    CHUNK_K (32, the served chunk size) among the sizes."""
    from m3_tpu.ops import fused
    from m3_tpu.parallel.scan import chunked_scan_aggregate_packed

    batch = _batch(k=k)
    args = chunked_device_args(batch, device_put=False)
    packed = fused.pack_lane_inputs(batch)
    got = chunked_scan_aggregate_packed(
        packed.windows4, packed.lanes4, n=packed.n,
        s=batch.num_series, c=batch.num_chunks, k=batch.k, interpret=True,
    )
    _assert_matches(got, _oracle(batch, args))


@pytest.mark.parametrize("kind", ["gauge", "counter", "float"])
def test_packed_specialized_interpret_matches_oracle(kind):
    """Specialized fast-tile body (all-int marker-free chunks) vs oracle in
    interpret mode, across workloads that classify differently."""
    from m3_tpu.ops import fused
    from m3_tpu.parallel.scan import chunked_scan_aggregate_packed

    streams = synthetic_streams(32, 97, seed=13, kind=kind)
    batch = tile_chunked(build_chunked(streams, k=16), 96)
    if kind in ("gauge", "counter"):
        # middle chunks of int-optimizable data must classify fast,
        # otherwise the specialization never executes
        assert np.asarray(batch.fast).sum() > 0
    args = chunked_device_args(batch, device_put=False)
    packed = fused.pack_lane_inputs(batch)
    got = chunked_scan_aggregate_packed(
        packed.windows4, packed.lanes4, packed.tile_flags, n=packed.n,
        s=batch.num_series, c=batch.num_chunks, k=batch.k, interpret=True,
    )
    # rtol covers the chunk-major reduction's different f32 sum order
    _assert_matches(got, _oracle(batch, args), rtol=1e-5)


def test_sorted_packed_interpret_matches_oracle_on_mixed():
    """order="sorted" (fast-first lane permutation + inv output gather) on a
    MIXED workload — float-mode, counters, time-unit changes, annotations —
    must match the oracle exactly per series."""
    from m3_tpu.ops import fused
    from m3_tpu.parallel.scan import chunked_scan_aggregate_packed
    from m3_tpu.utils.synthetic import synthetic_mixed_streams

    streams = synthetic_mixed_streams(48, 97, seed=5)
    batch = tile_chunked(build_chunked(streams, k=16), 96)
    assert 0.2 < np.asarray(batch.fast).mean() < 0.95  # genuinely mixed
    args = chunked_device_args(batch, device_put=False)
    packed = fused.pack_lane_inputs(batch, order="sorted")
    assert packed.inv is not None
    got = chunked_scan_aggregate_packed(
        packed.windows4, packed.lanes4, packed.tile_flags, n=packed.n,
        s=batch.num_series, c=batch.num_chunks, k=batch.k, interpret=True,
        lane_order="sorted", inv=packed.inv,
    )
    _assert_matches(got, _oracle(batch, args), rtol=1e-5)


def test_sorted_pack_tile_flags_recover_fast_majority():
    """On an interleaved mixed batch large enough for several tiles, the
    chunk-major layout yields ~zero fast tiles while sorted recovers a
    fast-tile fraction close to the fast-lane fraction."""
    from m3_tpu.ops import fused
    from m3_tpu.utils.synthetic import synthetic_mixed_streams

    streams = synthetic_mixed_streams(64, 193, seed=9)
    batch = tile_chunked(build_chunked(streams, k=16), 4096)
    fast_frac = float(np.asarray(batch.fast).mean())
    packed_c = fused.pack_lane_inputs(batch, order="c", rows=8)
    packed_s = fused.pack_lane_inputs(batch, order="sorted", rows=8)
    frac_c = (packed_c.tile_flags == 1).mean()
    frac_s = (packed_s.tile_flags == 1).mean()
    # series-granularity sorting can't reclaim a fast-rich series' own slow
    # boundary chunks (chunk 0 + EOS tail, ~2/C of its lanes) — the bound
    # is fast_frac minus that structural loss, not fast_frac itself
    c = batch.num_chunks
    assert frac_s >= fast_frac - 2.5 / c
    assert frac_s > frac_c


def test_float_fast_tiles_interpret_match_oracle():
    """fast_float tiles (class 2) route through the float-specialized body:
    all-float batch large enough for homogeneous float tiles must match the
    oracle, including repeated values (the 2-bit '01' repeat record)."""
    from m3_tpu.codec.m3tsz import Encoder
    from m3_tpu.ops import fused
    from m3_tpu.ops.chunked import lane_kwargs
    from m3_tpu.parallel.scan import chunked_scan_aggregate_packed

    NANOS = 1_000_000_000
    T0 = 1_600_000_000 * NANOS
    rng = np.random.RandomState(3)
    streams = []
    for s in range(32):
        enc = Encoder(T0)
        v = 0.12345
        for j in range(97):
            if rng.rand() < 0.3:
                pass  # repeat the previous value → '01' repeat records
            else:
                v = float(rng.lognormal(0, 2))
            enc.encode(T0 + j * NANOS, v)
        streams.append(enc.stream())
    batch = tile_chunked(build_chunked(streams, k=16), 2048)
    assert np.asarray(batch.fast_float).mean() > 0.5
    packed = fused.pack_lane_inputs(batch, order="sorted", rows=8)
    assert (packed.tile_flags == 2).sum() >= 5
    got = chunked_scan_aggregate_packed(
        packed.windows4, packed.lanes4, packed.tile_flags, n=packed.n,
        s=batch.num_series, c=batch.num_chunks, k=batch.k, interpret=True,
        lane_order="sorted", inv=packed.inv,
    )
    args = chunked_device_args(batch, device_put=False)
    _assert_matches(got, _oracle(batch, args), rtol=1e-5)


def test_three_class_sorted_mixed_interpret():
    """Mixed workload through all three bodies at once (general + int fast
    + float fast) with the series-sorted layout."""
    from m3_tpu.ops import fused
    from m3_tpu.parallel.scan import chunked_scan_aggregate_packed
    from m3_tpu.utils.synthetic import synthetic_mixed_streams

    streams = synthetic_mixed_streams(64, 97, seed=5, frac_float=0.5)
    batch = tile_chunked(build_chunked(streams, k=16), 4096)
    packed = fused.pack_lane_inputs(batch, order="sorted", rows=8)
    classes = np.bincount(packed.tile_flags, minlength=3)
    assert classes[1] > 0 and classes[2] > 0, classes
    got = chunked_scan_aggregate_packed(
        packed.windows4, packed.lanes4, packed.tile_flags, n=packed.n,
        s=batch.num_series, c=batch.num_chunks, k=batch.k, interpret=True,
        lane_order="sorted", inv=packed.inv,
    )
    args = chunked_device_args(batch, device_put=False)
    _assert_matches(got, _oracle(batch, args), rtol=1e-5)


def test_err_lane_host_stitch_on_mixed_batch():
    """A MIXED batch where some lanes err on device (annotation streams):
    the query layer stitches host-decoded results back in
    (stitch_host_errors) and the final block matches a full host oracle
    for EVERY series, annotated ones included."""
    from m3_tpu.codec.m3tsz import decode
    from m3_tpu.ops import fused
    from m3_tpu.parallel.scan import chunked_scan_aggregate_packed, stitch_host_errors
    from m3_tpu.utils.synthetic import synthetic_mixed_streams

    streams = synthetic_mixed_streams(
        32, 97, seed=31, frac_annotation=0.2  # plenty of err lanes
    )
    n_series = 64
    batch = tile_chunked(build_chunked(streams, k=16), n_series)
    packed = fused.pack_lane_inputs(batch, order="sorted")
    got = chunked_scan_aggregate_packed(
        packed.windows4, packed.lanes4, packed.tile_flags, n=packed.n,
        s=batch.num_series, c=batch.num_chunks, k=batch.k, interpret=True,
        lane_order="sorted", inv=packed.inv,
    )
    err = np.asarray(got.series_err)
    assert err.any(), "annotation streams must err on device"

    stitched = stitch_host_errors(got, lambda i: streams[i % len(streams)])
    assert not np.asarray(stitched.series_err).any()

    # full host oracle over every series
    per = []
    for srm in streams:
        vals = np.asarray([dp.value for dp in decode(srm)], np.float32)
        per.append((
            float(np.sum(vals.astype(np.float64))), len(vals),
            float(vals.min()), float(vals.max()), float(vals[-1]),
        ))
    want = [per[i % len(streams)] for i in range(n_series)]
    np.testing.assert_allclose(
        np.asarray(stitched.series_sum, np.float64),
        [w[0] for w in want], rtol=1e-5,
    )
    np.testing.assert_array_equal(
        np.asarray(stitched.series_count), [w[1] for w in want]
    )
    np.testing.assert_allclose(
        np.asarray(stitched.series_min), [w[2] for w in want], rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(stitched.series_max), [w[3] for w in want], rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(stitched.series_last), [w[4] for w in want], rtol=1e-6
    )
    assert float(stitched.total_count) == sum(w[1] for w in want)
    assert float(stitched.total_sum) == pytest.approx(
        sum(w[0] for w in want), rel=1e-5
    )


def test_fast_classification_boundaries():
    """First chunks, EOS chunks, float records, and annotations must
    classify slow; clean middle chunks fast."""
    from m3_tpu.codec.m3tsz import Encoder
    from m3_tpu.ops.chunked import snapshot_stream

    NANOS = 1_000_000_000
    # 40 int-mode points, k=8 -> 5 chunks; EOS consumed beyond chunk 5
    enc = Encoder(10 * NANOS)
    for i in range(40):
        enc.encode((10 + i) * NANOS, float(i))
    snaps = snapshot_stream(enc.stream(), 8)
    assert [p["fast"] for p in snaps] == [True] * 5  # chunk 0 slowed later
    from m3_tpu.ops.chunked import assemble_chunked

    batch = assemble_chunked([enc.stream()], [snaps], 8)
    assert list(np.asarray(batch.fast)) == [False, True, True, True, True]

    # a float value mid-chunk de-classifies that chunk only
    enc2 = Encoder(10 * NANOS)
    for i in range(24):
        v = 0.1234567890123 if i == 12 else float(i)  # not int-optimizable
        enc2.encode((10 + i) * NANOS, v)
    snaps2 = snapshot_stream(enc2.stream(), 8)
    assert [p["fast"] for p in snaps2] == [True, False, True]

    # an annotation mid-chunk de-classifies
    enc3 = Encoder(10 * NANOS)
    for i in range(24):
        ann = b"x" if i == 12 else None
        enc3.encode((10 + i) * NANOS, float(i), annotation=ann)
    snaps3 = snapshot_stream(enc3.stream(), 8)
    assert [p["fast"] for p in snaps3] == [True, False, True]

    # partial trailing chunk (not k records) is slow
    enc4 = Encoder(10 * NANOS)
    for i in range(20):
        enc4.encode((10 + i) * NANOS, float(i))
    snaps4 = snapshot_stream(enc4.stream(), 8)
    assert [p["fast"] for p in snaps4] == [True, True, False]


def test_native_prescan_fast_flags_match_python():
    from m3_tpu import native
    from m3_tpu.ops.chunked import snapshot_stream

    if not native.available():
        pytest.skip("native codec unavailable")
    streams = synthetic_streams(16, 97, seed=3)
    for k in (8, 16):
        got = native.prescan_batch(streams, k=k)
        for data, per_native in zip(streams, got):
            per_py = snapshot_stream(data, k)
            assert [bool(p["fast"]) for p in per_native] == [
                bool(p["fast"]) for p in per_py
            ]


@pytest.mark.parametrize("kind,k", [("gauge", 16), ("float", 32), ("counter", 32)])
def test_point_kernel_interpret_bit_identical_to_the_scan(kind, k):
    """ops/fused.decode_points_pallas (the plan program's decode on the
    chip, ONE device operation) against ops/chunked.decode_chunked_lanes
    (the lax.scan it stands in for): every plane of every record, bit for
    bit, over int-optimised, full-precision float and counter lanes, with
    a lane count that is no multiple of the 1,024-lane tile."""
    from m3_tpu.ops import fused
    from m3_tpu.ops.chunked import decode_chunked_lanes, lane_kwargs

    streams = synthetic_streams(24, 97, seed=11, kind=kind)
    batch = tile_chunked(build_chunked(streams, k=k), 56)
    kw = lane_kwargs(batch)
    want = decode_chunked_lanes(**kw, k=k)
    got = fused.decode_points_pallas(**kw, k=k, interpret=True)
    assert int(np.asarray(want.valid).sum()) == 56 * 97
    for field in want._fields:
        if field == "values_f32":  # not computed: the plan never read it
            continue
        a, b = np.asarray(getattr(want, field)), np.asarray(getattr(got, field))
        assert a.dtype == b.dtype and a.shape == b.shape, field
        np.testing.assert_array_equal(a, b, err_msg=field)
