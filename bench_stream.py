"""Sustained streaming benchmark: host→device upload + fused decode at 1M
series (BASELINE config-5 direction: working set larger than one transfer).

Unlike bench.py (device-resident arrays, pure kernel throughput), every
timed iteration re-uploads each packed batch from host memory, so the
number includes the host→device pipeline (parallel/stream.py double
buffering).

Not re-measured on the directly attached v5e yet (ROADMAP S1/D4).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

from __future__ import annotations

import json
import os
import time

NORTH_STAR = 10e9  # datapoints/sec/chip, same scale as bench.py


def main() -> None:
    import jax

    from m3_tpu import device

    device.configure_compile_cache()

    from m3_tpu.ops.chunked import build_chunked, tile_chunked
    from m3_tpu.parallel.stream import packed_batches, stream_aggregate
    from m3_tpu.utils.synthetic import synthetic_streams

    n_points = 720
    k = 24
    # 1M series (BENCH_SERIES=1048576) works but takes ~10 GB of host
    # batches.
    n_series = int(os.environ.get("BENCH_SERIES", 262144))
    batch_series = int(os.environ.get("BENCH_BATCH", 65536))
    platform = jax.devices()[0].platform
    if platform == "cpu":
        # enough batches that the median interval is a real steady-state
        # statistic: with only 2, the single drain interval lands in the
        # pipeline-fill phase and over-reports throughput ~25% (measured)
        n_series = min(n_series, 32768)
        batch_series = min(batch_series, 4096)

    import numpy as np

    base = build_chunked(synthetic_streams(64, n_points, seed=3), k=k)
    n_batches = -(-n_series // batch_series)
    # ONE host-side packed batch, cycled: every iteration is still a full
    # host→device upload + fused decode of batch_series series (the device
    # cannot tell repeated bytes from fresh ones), so cycling measures the
    # identical pipeline while keeping host memory flat — which is what
    # lets this bench run at 10M+ series (n_batches in the hundreds).
    one = next(iter(packed_batches([tile_chunked(base, batch_series)])))
    host = [one] * n_batches

    # Steady-state measurement within ONE pass: the first drain absorbs
    # compile + pipeline fill; per-batch intervals are summarized by their
    # MEDIAN, which is robust to burst variance on a shared host.
    marks = stream_aggregate(host, prefetch=2, drain_times=(times := []))
    total_points = int(marks.total_count)
    per_batch = total_points // n_batches
    diffs = np.diff(np.asarray(times))
    if not len(diffs):  # single batch: no steady-state intervals to report
        diffs = np.asarray([float("nan")])
    med = float(np.median(diffs))
    wall = times[-1] - times[0] if len(times) > 1 else float("nan")

    dps = per_batch / med
    # ---- resident side-by-side: the same bytes decoded FROM HBM ----
    # Streamed above re-uploads every batch; here the compressed streams
    # sit in the paged resident pool (m3_tpu/resident/) and each scan is a
    # device page gather + decode — the transfer term drops out entirely.
    resident = {}
    try:
        resident = _resident_side(n_points, platform, k=k)
    except Exception as exc:  # never cost the streamed line
        import sys

        print(f"WARN resident side failed: {exc}", file=sys.stderr)
    print(
        json.dumps(
            {
                "metric": "m3tsz_streamed_decode_aggregate_datapoints_per_sec",
                "value": round(dps, 1),
                "unit": "datapoints/s",
                "vs_baseline": round(dps / NORTH_STAR, 6),
                "series": n_series,
                "batches": n_batches,
                "per_batch_s_p10": round(float(np.percentile(diffs, 10)), 4),
                "per_batch_s_p50": round(med, 4),
                "per_batch_s_p90": round(float(np.percentile(diffs, 90)), 4),
                "steady_state_wall_s": round(wall, 2),
                "scan_wall_dps": round(total_points / (wall + med), 1),
                **{
                    ("resident_" + k if not k.startswith("resident") else k): v
                    for k, v in resident.items()
                },
                **(
                    {"resident_vs_streamed": round(resident["dps"] / dps, 3)}
                    if resident.get("dps")
                    else {}
                ),
            }
        )
    )


def _resident_side(n_points: int, platform: str, k: int = 24) -> dict:
    """Warm decode-from-HBM scan over pool-resident synthetic streams.

    EQUAL SETTINGS with the streamed line above: same chunk size ``k``,
    same per-scan series count as one streamed batch, and the SAME packed
    fused kernel — the side planes paged in at admission let the resident
    scan assemble PackedLanes by device gather, so the only difference
    left is assembly-from-HBM vs host-pack + upload. Also reports the
    zero-transfer contract: warm scans move no block bytes host->device
    (upload/streamed counters flat across the timed iterations)."""
    import time as _time

    from m3_tpu.cache.block_cache import BlockKey
    from m3_tpu.resident import ResidentOptions, ResidentPool, resident_scan_totals
    from m3_tpu.utils.synthetic import synthetic_streams

    # Deliberately NOT bench.py's BENCH_RESIDENT_SERIES: sizing one bench
    # must not silently resize the other's recorded metric.
    n_resident = int(
        os.environ.get(
            "BENCH_STREAM_RESIDENT_SERIES", 65536 if platform == "tpu" else 4096
        )
    )
    uniq = synthetic_streams(64, n_points, seed=3)
    pool = ResidentPool(
        ResidentOptions(max_bytes=max(64 << 20, n_resident * 4096 * 4))
    )
    bound = n_points + 8
    t0 = 0
    for start in range(0, n_resident, 4096):
        n = min(4096, n_resident - start)
        pool.admit_block(
            "bench",
            0,
            t0,
            start,  # one synthetic "volume" per admission batch
            [(b"s%07d" % (start + i), uniq[i % len(uniq)], bound) for i in range(n)],
            chunk_k=k,
        )
    keys = [
        BlockKey("bench", 0, b"s%07d" % i, t0, (i // 4096) * 4096)
        for i in range(n_resident)
    ]
    warm = resident_scan_totals(pool, keys)  # compile + warm
    total = int(warm.total_count)
    before = pool.stats()["upload_bytes"]
    # SAME steady-state methodology as the streamed line: an inflight
    # window of 2 scans with a hard scalar-fetch drain per result, timed
    # by drain intervals — dispatch of scan N+1 overlaps compute of scan
    # N exactly as stream_aggregate pipelines its batches.
    import collections

    import numpy as _np

    iters = 6
    inflight: collections.deque = collections.deque()
    times: list[float] = []
    for _ in range(iters):
        inflight.append(resident_scan_totals(pool, keys, device_out=True))
        if len(inflight) > 2:
            _np.asarray(inflight.popleft().total_count)
            times.append(_time.perf_counter())
    while inflight:
        _np.asarray(inflight.popleft().total_count)
        times.append(_time.perf_counter())
    diffs = _np.diff(_np.asarray(times))
    dt = float(_np.median(diffs)) if len(diffs) else float("nan")
    return {
        "dps": round(total / dt, 1),
        "series": n_resident,
        "scan_s": round(dt, 4),
        "pool_occupancy": round(pool.stats()["occupancy"], 6),
        # zero-transfer contract: warm scans admit/upload nothing
        "warm_block_bytes_transferred": pool.stats()["upload_bytes"] - before,
    }


if __name__ == "__main__":
    main()
