"""Driver benchmark: batched M3TSZ decode + aggregate throughput on one chip.

Measures datapoints decoded+aggregated per second (BASELINE.md config 2/3
shape: S series x 720 points, gauge workload, scan decode + sum/count/min/max
reductions). Baseline for vs_baseline is the north-star target of 10B
datapoints/sec/chip (BASELINE.json); the reference itself publishes no
comparable hard number.

Prints FOUR JSON lines (FIVE with BENCH_SELFMON=1):
  1. {"metric": "m3tsz_decode_aggregate_datapoints_per_sec_per_chip", ...}
     — the raw kernel scan-and-aggregate number.
  2. {"metric": "m3tsz_decode_aggregate_warm_cache_datapoints_per_sec_per_chip",
     ..., "hit_rate", "cold_value", "speedup_vs_cold"} — the repeated-query
     storage path (query/m3_storage.py fetch over sealed filesets) with the
     decoded-block cache (m3_tpu/cache/) warm, vs the same query cold.
  3. {"metric": "m3tsz_resident_scan_datapoints_per_sec_per_chip", ...,
     "pool_occupancy", "pool_bytes", "path"} — the compressed-residency
     mode (m3_tpu/resident/): sealed blocks admitted to the HBM pool at
     flush, warm scan_totals decoding from HBM with zero block-byte
     transfer.
  4. {"metric": "process_metrics_snapshot", ...} — the benched process's own
     m3tpu_* metrics (query latency histogram summary, per-stage latency,
     decoded bytes, jit compile count/seconds per kernel) so BENCH_*.json
     rounds can attribute a regression to the layer that actually moved.
  5. (BENCH_SELFMON=1 only) {"metric": "selfmon_overhead", ...} — what the
     self-scrape collector cost while the phases ran (m3_tpu/selfmon/):
     scrapes, datapoints written, scrape errors, sampled kernel dispatches.
  6. (BENCH_PROFILE=1 only) {"metric": "profile_overhead", ...} — the
     continuous wall-clock stack sampler (m3_tpu/profiling/) running at
     its default hz DURING the phases: samples taken, distinct stacks,
     measured sampler seconds and overhead ratio — the PROFILE.md
     continuous-profiling acceptance row (<2% median regression) is one
     env-var A/B away.
"""

from __future__ import annotations

import json
import os
import sys
import time

NORTH_STAR = 10e9  # datapoints/sec/chip


def main() -> int:
    from m3_tpu import device

    device.configure_compile_cache()
    device.install_compile_counters()
    # BENCH_SELFMON=1: run the self-monitoring pipeline DURING the bench —
    # the collector stores this process's registry into a local reserved
    # namespace every BENCH_SELFMON_INTERVAL (default 10s) while the
    # phases run, and a sampled KernelProfiler is enabled via
    # M3_TPU_PROFILE_SAMPLE_RATE — so the PROFILE.md self-scrape overhead
    # row (acceptance: decode-aggregate dp/s regresses < 2%) is one
    # env-var A/B away
    selfmon = maybe_start_selfmon()
    profiler = maybe_start_profiler()
    # the phases are independent, so one that fails does not cost the
    # others their lines — but it does cost the run its exit code
    failed = []
    for phase in (kernel_phase, bench_warm_cache, bench_resident):
        try:
            phase()
        except Exception as exc:
            failed.append(phase.__name__)
            print(f"FAIL bench phase {phase.__name__}: {exc!r}", file=sys.stderr)
    metrics_snapshot_line()
    if selfmon is not None:
        selfmon_overhead_line(selfmon)
    if profiler is not None:
        profile_overhead_line(profiler)
    return 1 if failed else 0


def maybe_start_selfmon():
    if os.environ.get("BENCH_SELFMON", "0") != "1":
        return None
    import tempfile

    from m3_tpu.selfmon import RESERVED_NS, DatabaseSink, SelfMonCollector
    from m3_tpu.storage.database import Database, NamespaceOptions

    db = Database(
        tempfile.mkdtemp(prefix="m3tpu-bench-selfmon-"), num_shards=1
    )
    db.create_namespace(RESERVED_NS, NamespaceOptions())
    db.bootstrap()
    interval = float(os.environ.get("BENCH_SELFMON_INTERVAL", "10"))
    return SelfMonCollector(
        DatabaseSink(db, RESERVED_NS), interval=interval,
        instance="bench", component="bench",
    ).start()


def _snap_total(snap: dict, name: str) -> float:
    """Sum of a counter/gauge family's children in a collect() snapshot."""
    fam = snap.get(name)
    return sum(c["value"] for c in fam["children"]) if fam else 0.0


def selfmon_overhead_line(selfmon) -> None:
    """Fifth JSON line (BENCH_SELFMON=1): what the self-scrape cost."""
    selfmon.stop()
    selfmon.scrape_once()  # short runs still report a real tick
    from m3_tpu.utils.instrument import DEFAULT as METRICS

    snap = METRICS.collect()

    def total(name):
        return _snap_total(snap, name)

    scrapes = total("m3tpu_selfmon_scrapes_total")
    dps = total("m3tpu_selfmon_datapoints_total")
    print(
        json.dumps(
            {
                "metric": "selfmon_overhead",
                "interval_secs": selfmon.interval,
                "scrapes": scrapes,
                "datapoints_written": dps,
                "datapoints_per_scrape": round(dps / scrapes, 1) if scrapes else 0.0,
                "scrape_errors": total("m3tpu_selfmon_scrape_errors_total"),
                "profile_sample_rate": os.environ.get(
                    "M3_TPU_PROFILE_SAMPLE_RATE", "0"
                ),
                "kernel_dispatches_sampled": sum(
                    c["count"]
                    for c in snap.get(
                        "m3tpu_kernel_dispatch_seconds", {}
                    ).get("children", ())
                ),
            }
        )
    )


def maybe_start_profiler():
    """BENCH_PROFILE=1: run the always-on stack sampler during the bench
    at its default rate (M3_TPU_PROFILE_HZ to override) — the A/B for the
    PROFILE.md continuous-profiling overhead row."""
    if os.environ.get("BENCH_PROFILE", "0") != "1":
        return None
    from m3_tpu.profiling import start_sampler

    return start_sampler(instance="bench")


def profile_overhead_line(profiler) -> None:
    """Sixth JSON line (BENCH_PROFILE=1): what the sampler saw and cost."""
    profiler.stop()
    prof = profiler.profile()
    from m3_tpu.utils.instrument import DEFAULT as METRICS

    snap = METRICS.collect()

    def total(name):
        return _snap_total(snap, name)

    def gauge(name):
        fam = snap.get(name)
        return fam["children"][0]["value"] if fam and fam["children"] else 0.0

    print(
        json.dumps(
            {
                "metric": "profile_overhead",
                "hz": profiler.hz,
                "samples": total("m3tpu_profile_samples_total"),
                "distinct_stacks": len(prof["folded"]),
                "sampler_seconds": round(
                    total("m3tpu_profile_overhead_seconds_total"), 6
                ),
                "overhead_ratio": round(gauge("m3tpu_profile_overhead_ratio"), 6),
                "frames_truncated": total("m3tpu_profile_frames_truncated_total"),
                "stacks_truncated": total("m3tpu_profile_stacks_truncated_total"),
                "errors": total("m3tpu_profile_errors_total"),
            }
        )
    )


def kernel_phase() -> None:
    import functools

    import jax

    from m3_tpu.ops import fused
    from m3_tpu.ops.chunked import build_chunked, tile_chunked
    from m3_tpu.parallel.scan import (
        chunked_device_args,
        chunked_scan_aggregate_fused,
        chunked_scan_aggregate_packed,
    )
    from m3_tpu.utils.synthetic import synthetic_streams

    n_points = 720
    k = 24
    n_series = int(os.environ.get("BENCH_SERIES", 524288))
    platform = jax.devices()[0].platform
    if platform == "cpu":
        n_series = min(n_series, 4096)

    streams = synthetic_streams(64, n_points, seed=3)
    batch = tile_chunked(build_chunked(streams, k=k), n_series)

    if platform == "tpu":
        # packed-layout Pallas kernel: 3 contiguous DMAs per grid program;
        # chunk-major tiles route through the specialized all-int body
        packed = fused.pack_lane_inputs(batch)
        w4 = jax.device_put(packed.windows4)
        l4 = jax.device_put(packed.lanes4)
        tf = jax.device_put(packed.tile_flags)
        fn0 = jax.jit(
            functools.partial(
                chunked_scan_aggregate_packed,
                n=packed.n,
                s=batch.num_series,
                c=batch.num_chunks,
                k=batch.k,
                lane_order=packed.order,
            )
        )
        fn = lambda _args: fn0(w4, l4, tf)
        args = None
    else:
        args = chunked_device_args(batch)
        fn = jax.jit(
            functools.partial(
                chunked_scan_aggregate_fused,
                s=batch.num_series,
                c=batch.num_chunks,
                k=batch.k,
            )
        )
    # compile + warm; device.install_compile_counters() lands the compile
    # time in m3tpu_jit_compile_seconds_total so the metrics snapshot line
    # can separate warmup from steady-state
    out = fn(args)
    jax.block_until_ready(out)
    total_points = int(out.total_count)

    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters

    dps = total_points / dt
    print(
        json.dumps(
            {
                "metric": "m3tsz_decode_aggregate_datapoints_per_sec_per_chip",
                "value": round(dps, 1),
                "unit": "datapoints/s",
                "vs_baseline": round(dps / NORTH_STAR, 6),
            }
        )
    )


def bench_warm_cache() -> None:
    """Repeated-query storage path: the same PromQL-matcher fetch over
    sealed blocks, cold (decode from fileset bytes) vs warm (decoded-block
    cache resident). Emits warm throughput + hit rate so BENCH rounds
    track cache effectiveness."""
    import shutil
    import tempfile

    import numpy as np

    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher
    from m3_tpu.storage.database import Database, NamespaceOptions

    NANOS = 1_000_000_000
    n_series = int(os.environ.get("BENCH_CACHE_SERIES", 256))
    n_points = 720
    t0 = 1_600_000_000 * NANOS  # block-aligned
    step = 10 * NANOS  # 720 points stay inside one 2h block
    base = tempfile.mkdtemp(prefix="m3tpu-bench-cache-")
    try:
        db = Database(base, num_shards=8, commitlog_enabled=False)
        db.create_namespace("bench", NamespaceOptions())
        rng = np.random.default_rng(7)
        for i in range(n_series):
            tags = ((b"__name__", b"bench_gauge"), (b"series", b"%06d" % i))
            sid = db.write_tagged("bench", tags, t0, float(rng.standard_normal()))
            vals = rng.standard_normal(n_points - 1)
            db.write_batch(
                "bench",
                [
                    (sid, t0 + (j + 1) * step, float(vals[j]))
                    for j in range(n_points - 1)
                ],
            )
        db.flush("bench", t0 + 4 * 3600 * NANOS)  # seal everything
        storage = M3Storage(db, "bench")
        matchers = [Matcher("__name__", "=", "bench_gauge")]
        span = (t0, t0 + n_points * step)

        def fetch_aggregate():
            total, agg = 0, 0.0
            for _tags, _times, vals in storage.fetch(matchers, *span):
                total += len(vals)
                agg += float(vals.sum())
            return total, agg

        tc0 = time.perf_counter()
        total_points, _ = fetch_aggregate()  # cold: decodes + populates
        cold_dt = time.perf_counter() - tc0
        assert total_points == n_series * n_points, total_points

        # a few PromQL passes over the same data so the snapshot line has a
        # real query latency histogram + per-stage breakdown to report
        from m3_tpu.query.engine import Engine

        engine = Engine(storage)
        for _ in range(3):
            engine.query_range(
                "sum(bench_gauge)", t0, t0 + (n_points - 1) * step, step
            )

        before = db.block_cache.stats()
        tw0 = time.perf_counter()
        fetch_aggregate()  # second pass: hit-rate measurement
        warm_dt = time.perf_counter() - tw0
        after = db.block_cache.stats()
        lookups = (after["hits"] - before["hits"]) + (
            after["misses"] - before["misses"]
        )
        hit_rate = (after["hits"] - before["hits"]) / max(lookups, 1)

        iters = 4
        tw1 = time.perf_counter()
        for _ in range(iters):
            fetch_aggregate()
        warm_dt = min(warm_dt, (time.perf_counter() - tw1) / iters)

        cold_dps = total_points / cold_dt
        warm_dps = total_points / warm_dt
        db.close()
        print(
            json.dumps(
                {
                    "metric": "m3tsz_decode_aggregate_warm_cache_datapoints_per_sec_per_chip",
                    "value": round(warm_dps, 1),
                    "unit": "datapoints/s",
                    "vs_baseline": round(warm_dps / NORTH_STAR, 6),
                    "cold_value": round(cold_dps, 1),
                    "speedup_vs_cold": round(warm_dps / cold_dps, 3),
                    "hit_rate": round(hit_rate, 4),
                }
            )
        )
    finally:
        shutil.rmtree(base, ignore_errors=True)


def bench_resident() -> None:
    """Compressed-residency mode: seal blocks into the HBM-resident pool
    (admission happens at flush), then measure the warm decode-from-HBM
    scan (query/m3_storage.py scan_totals, resident path) — zero block
    bytes cross host->device per scan, asserted via the pool counters."""
    import shutil
    import tempfile

    import numpy as np

    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher
    from m3_tpu.resident import ResidentOptions
    from m3_tpu.storage.database import Database, NamespaceOptions

    NANOS = 1_000_000_000
    n_series = int(os.environ.get("BENCH_RESIDENT_SERIES", 256))
    n_points = 720
    t0 = 1_600_000_000 * NANOS
    step = 10 * NANOS
    base = tempfile.mkdtemp(prefix="m3tpu-bench-resident-")
    try:
        db = Database(
            base,
            num_shards=8,
            commitlog_enabled=False,
            resident_options=ResidentOptions(max_bytes=1 << 30),
        )
        db.create_namespace("bench", NamespaceOptions())
        rng = np.random.default_rng(11)
        for i in range(n_series):
            tags = ((b"__name__", b"bench_gauge"), (b"series", b"%06d" % i))
            sid = db.write_tagged("bench", tags, t0, float(rng.standard_normal()))
            vals = rng.standard_normal(n_points - 1)
            db.write_batch(
                "bench",
                [
                    (sid, t0 + (j + 1) * step, float(vals[j]))
                    for j in range(n_points - 1)
                ],
            )
        db.flush("bench", t0 + 4 * 3600 * NANOS)  # seal + admit
        storage = M3Storage(db, "bench")
        matchers = [Matcher("__name__", "=", "bench_gauge")]
        span = (t0, t0 + n_points * step)

        first = storage.scan_totals(matchers, *span)  # compile + warm
        assert first["count"] == n_series * n_points, first
        before = db.resident_stats()
        iters = 5
        t_start = time.perf_counter()
        for _ in range(iters):
            out = storage.scan_totals(matchers, *span)
        dt = (time.perf_counter() - t_start) / iters
        after = db.resident_stats()
        transferred = (after["upload_bytes"] - before["upload_bytes"]) + (
            after["streamed_bytes"] - before["streamed_bytes"]
        )
        dps = out["count"] / dt
        db.close()
        print(
            json.dumps(
                {
                    "metric": "m3tsz_resident_scan_datapoints_per_sec_per_chip",
                    "value": round(dps, 1),
                    "unit": "datapoints/s",
                    "vs_baseline": round(dps / NORTH_STAR, 6),
                    "path": out["path"],
                    "series": n_series,
                    "pool_bytes": after["bytes"],
                    "pool_occupancy": round(after["occupancy"], 6),
                    "warm_block_bytes_transferred": transferred,
                }
            )
        )
    finally:
        shutil.rmtree(base, ignore_errors=True)


def metrics_snapshot_line() -> None:
    """Final JSON line: the benched process's own metrics registry, reduced
    to the families BENCH rounds attribute regressions with."""
    from m3_tpu.utils.instrument import DEFAULT as METRICS

    snap = METRICS.collect()

    def family_total(name: str) -> float:
        fam = snap.get(name)
        if not fam:
            return 0.0
        return sum(c["value"] for c in fam["children"])

    def by_label(name: str, label: str) -> dict:
        fam = snap.get(name)
        if not fam:
            return {}
        return {
            c["labels"].get(label, ""): round(c["value"], 6)
            for c in fam["children"]
        }

    def hist_summary(name: str, label: str | None = None) -> dict | None:
        fam = snap.get(name)
        if not fam or not fam["children"]:
            return None
        if label is None:
            count = sum(c["count"] for c in fam["children"])
            total = sum(c["sum"] for c in fam["children"])
            return {
                "count": count,
                "sum_secs": round(total, 6),
                "avg_secs": round(total / count, 6) if count else 0.0,
            }
        return {
            c["labels"].get(label, ""): {
                "count": c["count"],
                "sum_secs": round(c["sum"], 6),
            }
            for c in fam["children"]
        }

    print(
        json.dumps(
            {
                "metric": "process_metrics_snapshot",
                "query_latency": hist_summary("m3tpu_query_duration_seconds"),
                "query_stage_latency": hist_summary(
                    "m3tpu_query_stage_duration_seconds", label="stage"
                ),
                "decoded_bytes_total": family_total("m3tpu_decoded_bytes_total"),
                "query_datapoints_scanned_total": family_total(
                    "m3tpu_query_datapoints_scanned_total"
                ),
                "jit_compiles_total": by_label("m3tpu_jit_compiles_total", "kernel"),
                "jit_compile_seconds_total": by_label(
                    "m3tpu_jit_compile_seconds_total", "kernel"
                ),
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
