"""Full benchmark suite: BASELINE.md configs 1-5, the mixed-workload bench,
and the scan p50 latency — the honest numbers the round-3 verdict asked for
(bench.py stays the single headline line; this writes --out, default
bench_suite_out.json).

Run:  python bench_suite.py [--configs 1,2,3,4,5,mixed,scan] [--series N]

Each config prints one BENCH-style JSON line and all records land in
--out. On CPU the workloads shrink (sanity only — real numbers come from
the TPU chip). ``--configs soak`` starts a fleet of child processes and
runs alone: this process then never imports jax (one process per chip).
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import numpy as np

NANOS = 1_000_000_000
NORTH_STAR = 10e9
T0 = 1_600_000_000 * NANOS


def _rec(metric, value, unit, **extra):
    rec = {
        "metric": metric,
        "value": round(float(value), 4),
        "unit": unit,
        "vs_baseline": round(float(value) / NORTH_STAR, 6)
        if unit == "datapoints/s"
        else None,
        **extra,
    }
    print(json.dumps(rec), flush=True)
    return rec


def _fetch(out):
    """Device→host sync by materializing one scalar (a data fetch is
    ordered after the compute that produced it). Indexes a single element
    so big outputs are not transferred."""
    leaf = out
    if hasattr(out, "total_count"):
        leaf = out.total_count
    elif isinstance(out, (tuple, list)):
        leaf = out[0]
    if getattr(leaf, "ndim", 0):
        leaf = leaf[(0,) * leaf.ndim]
    return float(leaf)


def _timeit(fn, args, iters=10):
    """Self-validating timing: pipelined (block-at-end, amortizes the
    per-dispatch overhead) cross-checked against synchronous
    fetch-per-iter. A pipelined number >20x faster than sync means the
    block didn't block — report sync instead."""
    import jax

    out = fn(args)
    jax.block_until_ready(out)
    _fetch(out)
    n_sync = max(3, iters // 3)
    t0 = time.perf_counter()
    for _ in range(n_sync):
        _fetch(fn(args))
    dt_sync = (time.perf_counter() - t0) / n_sync
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(args)
    jax.block_until_ready(out)
    dt_pipe = (time.perf_counter() - t0) / iters
    dt = dt_sync if dt_pipe < dt_sync / 20 else dt_pipe
    return dt, out


def _timeit_chain(scalar_step, args, k_lo=4, k_hi=16, reps=3):
    """Per-application time of a kernel via the K-slope method.

    ``scalar_step(carry, *args) -> f32 scalar`` applies the kernel once with
    a data dependency on ``carry`` (so XLA cannot CSE/DCE the chain). We jit
    a lax.scan of K applications, synchronously time (result fetch) K_hi and
    K_lo dispatches, and divide the difference by (K_hi - K_lo): fixed costs
    — per-dispatch overhead, result transfer — cancel exactly.
    This measures sustained throughput, which is what a streaming flush/query
    pipeline sees; sub-ms kernels are otherwise swamped by dispatch latency
    (the r04 config3/config4 numbers were RTT-bound, not compute-bound).
    Falls back to plain sync timing if the slope is non-positive (noise)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def chained(k):
        @jax.jit
        def f(*a):
            def body(c, _):
                return scalar_step(c, *a) * 1e-30, None

            c, _ = lax.scan(body, jnp.float32(0), None, length=k)
            return c

        return f

    f_lo, f_hi = chained(k_lo), chained(k_hi)
    _fetch(f_lo(*args))
    _fetch(f_hi(*args))  # compile + residency settle
    lo_ts, hi_ts = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        _fetch(f_lo(*args))
        lo_ts.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _fetch(f_hi(*args))
        hi_ts.append(time.perf_counter() - t0)
    slope = (np.median(hi_ts) - np.median(lo_ts)) / (k_hi - k_lo)
    if slope <= 0:  # noise floor: report the conservative sync latency
        return np.median(hi_ts) / k_hi
    return slope


def _latencies(fn, args, iters=20):
    for _ in range(4):  # compile + argument residency settle
        _fetch(fn(args))
    lats = []
    for _ in range(iters):
        t0 = time.perf_counter()
        _fetch(fn(args))
        lats.append(time.perf_counter() - t0)
    return np.asarray(lats)


# --- config 1: CPU codec round trip (m3tsz_benchmark_test.go role) ---


def bench_config1():
    from m3_tpu import native
    from m3_tpu.codec.m3tsz import decode
    from m3_tpu.utils.synthetic import synthetic_streams

    streams = synthetic_streams(1000, 720, seed=1)
    nbytes = sum(map(len, streams))
    npts = 1000 * 720
    # native batch decoder (native/m3tsz.cc m3tsz_decode_batch — the Go
    # iterator's role, single-core number reported for /core parity)
    native.decode_batch(streams[:4])  # lazy build + warm
    t0 = time.perf_counter()
    out = native.decode_batch(streams, n_threads=1, max_points=720)
    dt = time.perf_counter() - t0
    assert sum(len(t) for t, _, _ in out) == npts
    # pure-Python reference decoder (annotation-capable fallback)
    t0 = time.perf_counter()
    total = sum(len(decode(s)) for s in streams[:50])
    dt_py = (time.perf_counter() - t0) * (len(streams) / 50)
    assert total == 50 * 720
    return _rec(
        "config1_cpu_decode_roundtrip",
        npts / dt,
        "datapoints/s",
        bytes_per_datapoint=round(nbytes / npts, 3),
        series=1000,
        python_decode_dps=round(npts / dt_py, 1),
    )


# --- config 2: S x 720 packed decode+aggregate (the headline shape) ---


def _packed_fn(batch, order="c"):
    import jax

    from m3_tpu.ops import fused
    from m3_tpu.parallel.scan import chunked_scan_aggregate_packed

    packed = fused.pack_lane_inputs(batch, order=order)
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
            # cross-series totals are order-independent; per-series arrays
            # come back in sorted order and unpermute on host via inv
            unpermute_series=False,
        )
    )
    fn = lambda _: fn0(w4, l4, tf)
    return fn, packed


def _jnp_fn(batch):
    import jax

    from m3_tpu.parallel.scan import chunked_device_args, chunked_scan_aggregate_fused

    args = chunked_device_args(batch)
    fn0 = jax.jit(
        functools.partial(
            chunked_scan_aggregate_fused,
            s=batch.num_series,
            c=batch.num_chunks,
            k=batch.k,
        )
    )
    return lambda _: fn0(args)


def _build(streams, n_series, k=24):
    from m3_tpu.ops.chunked import build_chunked, tile_chunked

    return tile_chunked(build_chunked(streams, k=k), n_series)


def bench_config2(n_series, on_tpu):
    from m3_tpu.utils.synthetic import synthetic_streams

    batch = _build(synthetic_streams(64, 720, seed=3), n_series)
    fn = _packed_fn(batch)[0] if on_tpu else _jnp_fn(batch)
    dt, out = _timeit(fn, None)
    pts = int(out.total_count)
    return _rec(
        "config2_decode_aggregate",
        pts / dt,
        "datapoints/s",
        series=n_series,
        points=720,
    )


def bench_mixed(n_series, on_tpu):
    """Mixed workload: >=30% float-mode + counters + time-unit changes +
    annotations + varied gauge entropy, interleaved (not 64 tiled uniques).
    Sorted lane packing routes the fast majority to the specialized body."""
    from m3_tpu.utils.synthetic import synthetic_mixed_streams

    batch = _build(synthetic_mixed_streams(256, 720, seed=11), n_series)
    fast_frac = float(np.asarray(batch.fast).mean())
    ff_frac = float(np.asarray(batch.fast_float).mean())
    int_tiles = float_tiles = 0.0
    if on_tpu:
        fn, packed = _packed_fn(batch, order="sorted")
        int_tiles = float((packed.tile_flags == 1).mean())
        float_tiles = float((packed.tile_flags == 2).mean())
    else:
        fn = _jnp_fn(batch)
    dt, out = _timeit(fn, None)
    pts = int(out.total_count)
    return _rec(
        "mixed_workload_decode_aggregate",
        pts / dt,
        "datapoints/s",
        series=n_series,
        fast_lane_fraction=round(fast_frac, 4),
        fast_float_lane_fraction=round(ff_frac, 4),
        int_tile_fraction=round(int_tiles, 4),
        float_tile_fraction=round(float_tiles, 4),
        composition="30% float, 8% counter, 5% tu-change, 2% annotation, 55% gauge",
    )


def bench_scan_p50(n_series, on_tpu):
    """1M->50M scan p50: per-dispatch latency of the full decode+aggregate
    at the given series count (the second half of the north-star metric)."""
    from m3_tpu.utils.synthetic import synthetic_streams

    batch = _build(synthetic_streams(64, 720, seed=3), n_series)
    fn = _packed_fn(batch)[0] if on_tpu else _jnp_fn(batch)
    lats = _latencies(fn, None)
    return _rec(
        "scan_latency_p50",
        float(np.percentile(lats, 50)),
        "seconds",
        series=n_series,
        p90=round(float(np.percentile(lats, 90)), 6),
        p99=round(float(np.percentile(lats, 99)), 6),
    )


# --- config 3: temporal functions over a decoded block ---


def bench_config3(n_series):
    import jax
    import jax.numpy as jnp

    from m3_tpu.query.functions import temporal

    t = 720
    rng = np.random.default_rng(0)
    vals = rng.normal(100, 10, (n_series, t)).astype(np.float32)
    vals[rng.random((n_series, t)) < 0.01] = np.nan  # missing samples
    x = jax.device_put(jnp.asarray(vals))
    window = 7  # 1m range at 10s step

    from m3_tpu.query.functions.temporal_fused import fused_temporal

    def step(carry, v):
        r, a = fused_temporal(
            v + carry, window, 10.0, ("rate", "avg_over_time")
        )
        return jnp.nansum(r) + jnp.nansum(a)

    dt = _timeit_chain(step, (x,))
    # two functions over S*T points each
    return _rec(
        "config3_temporal_functions",
        2 * n_series * t / dt,
        "datapoints/s",
        series=n_series,
        functions="rate+avg_over_time",
    )


# --- config 4: 10M active series 10s->1m rollups ---


def bench_config4(n_series):
    import jax
    import jax.numpy as jnp

    from m3_tpu import native
    from m3_tpu.aggregator.kernels import aggregate_dense, dense_quantiles

    per = 6  # datapoints per series in the 1m window (10s resolution)
    n = n_series * per
    rng = np.random.default_rng(2)
    ids = np.repeat(np.arange(n_series, dtype=np.int64), per)
    times = T0 + np.tile((np.arange(per) * 10 * NANOS), n_series) + rng.integers(
        0, 10 * NANOS, n
    )
    values = rng.lognormal(0, 1, n).astype(np.float32)
    # fused native densify (m3agg_* in native/m3tsz.cc): window bucketing +
    # counts + arrival-order-exact dense scatter, memory-bound C++ passes
    t0 = time.perf_counter()
    dv, dt_, dvalid = native.pack_windowed_dense(
        ids, times, values, T0, 60 * NANOS, 1, n_series
    )
    pack_s = time.perf_counter() - t0
    dvd = jax.device_put(dv)
    dtd = jax.device_put(dt_)
    dvld = jax.device_put(dvalid)

    def agg_step(carry, vals, torder, valid):
        out = aggregate_dense(vals + carry, torder, valid)
        return out.sum.sum() + out.last.sum() + out.min.sum() + out.max.sum()

    dt_agg = _timeit_chain(agg_step, (dvd, dtd, dvld))

    # timer quantiles on a 10% timer population (p50/p95/p99)
    n_t = max(n_series // 10, 1)
    vq = jax.device_put(dv[:n_t])
    vlq = jax.device_put(dvalid[:n_t])

    def q_step(carry, vals, valid):
        return jnp.nansum(dense_quantiles(vals + carry, valid, qs=(0.5, 0.95, 0.99)))

    # the timer slice is 10x smaller: longer chains keep the slope above the
    # dispatch-jitter noise floor
    dt_q = _timeit_chain(q_step, (vq, vlq), k_lo=32, k_hi=256)

    tmask = n_t * per
    total_dps = n + tmask
    return _rec(
        "config4_rollup_10s_to_1m",
        total_dps / (dt_agg + dt_q),
        "datapoints/s",
        active_series=n_series,
        agg_dps=round(n / dt_agg, 1),
        timer_quantile_dps=round(tmask / dt_q, 1),
        host_densify_s=round(pack_s, 3),
    )


# --- config 5: regexp index query -> decode -> aggregate (fan-out) ---


def bench_config5(n_series, on_tpu):
    from m3_tpu.index.query import RegexpQuery, search_segment
    from m3_tpu.index.segment import Document, MutableSegment
    from m3_tpu.ops.chunked import select_series
    from m3_tpu.utils.synthetic import synthetic_streams

    # index S series: name=metric_{i%100}, dc, host
    seg = MutableSegment()
    t_ix0 = time.perf_counter()
    for i in range(n_series):
        seg.insert(
            Document(
                id=str(i).encode(),
                fields=(
                    (b"name", f"metric_{i % 100}".encode()),
                    (b"dc", f"dc{i % 4}".encode()),
                ),
            )
        )
    sealed = seg.seal()
    index_build_s = time.perf_counter() - t_ix0

    q = RegexpQuery(b"name", b"metric_1[0-9]")  # ~10% of series
    t_q0 = time.perf_counter()
    postings = search_segment(sealed, q)
    query_s = time.perf_counter() - t_q0
    sel = np.asarray(postings, np.int64)

    # the synthetic population tiles 64 unique streams across n_series, so
    # selecting from the tiled batch == selecting (i % 64) from the base —
    # composing the two skips materializing a multi-GB copy of REPEATED
    # data that no real deployment would hold (real series are gathered
    # from their own storage); the gather below still moves the full
    # matched-series byte volume
    base = _build(synthetic_streams(64, 720, seed=3), 64)
    t_s0 = time.perf_counter()
    sub = select_series(base, sel % 64)
    select_s = time.perf_counter() - t_s0

    fn = _packed_fn(sub)[0] if on_tpu else _jnp_fn(sub)
    dt, out = _timeit(fn, None)
    pts = int(out.total_count)
    return _rec(
        "config5_regexp_fanout_decode_aggregate",
        pts / dt,
        "datapoints/s",
        indexed_series=n_series,
        matched_series=int(sel.size),
        index_query_ms=round(query_s * 1e3, 2),
        index_build_s=round(index_build_s, 2),
        select_pack_s=round(select_s, 2),
    )


def bench_multitenant(rate=400.0, duration=5.0):
    """Mixed multi-tenant read+write bench (ROADMAP open item 3's success
    metric): an in-process coordinator behind its real HTTP surface, a
    two-tenant open-loop fixed-rate workload (services/loadgen.py
    --tenants mode; ticks the loop can't take are counted, not absorbed —
    no coordinated omission), reporting sustained QPS and per-tenant
    p50/p95/p99."""
    import argparse

    from m3_tpu.services import loadgen
    from m3_tpu.services.coordinator import Coordinator, serve

    coord = Coordinator()
    srv, port = serve(coord, 0)
    try:
        args = argparse.Namespace(
            node="", coordinator=f"127.0.0.1:{port}", aggregator="",
            namespace="default", series=200, rate=rate, duration=duration,
            workers=8, batch=10, read_fraction=0.3, series_offset=0,
            listen=None, agents="", tenants="alpha:3,beta:1",
        )
        out = loadgen.run_multitenant(
            args, loadgen.make_tenant_client_factory(args)
        )
    finally:
        srv.shutdown()
        coord.db.close()
    return _rec(
        "multitenant_sustained_qps",
        out["sustained_ops_per_sec"],
        "ops/s",
        target_ops_per_sec=out["target_ops_per_sec"],
        missed_ticks=out["missed_ticks"],
        errors=out["errors"],
        rejected=out["rejected"],
        per_tenant={
            name: {
                k: t[k]
                for k in ("ops_per_sec", "p50_ms", "p95_ms", "p99_ms")
            }
            for name, t in out["tenants"].items()
        },
    )


def bench_hedging(reads=150, delay=0.4, delay_prob=0.12):
    """Hedging column for the tenants row (PR 14): the SAME 3-replica
    tagged-read workload against a cluster whose node1 read path
    straggles (seeded jittered lognormal delay on fetch_tagged),
    measured closed-loop with hedged backup requests OFF then ON. An
    unhedged read that draws the straggler pays the full
    ``straggler_grace`` wait; a hedged one gets a backup twin at the
    p95 trigger and returns as soon as every host is settled. The
    headline is p99_ratio (hedged/unhedged); hedge counters prove the
    backup path actually carried the wins."""
    from m3_tpu.index.query import term
    from m3_tpu.net.faults import FaultPlan, FaultRule
    from m3_tpu.testing.cluster import LocalCluster
    from m3_tpu.testing.faults import wrap_nodes
    from m3_tpu.utils.instrument import DEFAULT as METRICS

    def hedge_counter(kind):
        fam = METRICS.collect().get(f"m3tpu_session_hedges_{kind}_total")
        return sum(c["value"] for c in fam["children"]) if fam else 0.0

    nanos = 1_000_000_000
    t0 = 1_600_000_000 * nanos
    plan = FaultPlan(
        [FaultRule(op="fetch_tagged", peer="node1", delay=delay,
                   delay_prob=delay_prob, jitter=0.1,
                   delay_dist="lognormal")],
        seed=11,
    )
    cluster = LocalCluster(num_nodes=3, num_shards=4, replica_factor=3)
    modes = {}
    issued = won = 0.0
    try:
        seed_session = cluster.session()
        for i in range(16):
            tags = ((b"__name__", b"bench_hedge"), (b"i", b"%d" % i))
            seed_session.write_tagged(tags, t0 + i * nanos, float(i))
        seed_session.close()
        q = term(b"__name__", b"bench_hedge")
        for mode, hedged in (("unhedged", False), ("hedged", True)):
            s = cluster.session()
            s.nodes = wrap_nodes(s.nodes, plan)
            s.hedge_enabled = hedged
            i0, w0 = hedge_counter("issued"), hedge_counter("won")
            lats = []
            bench_t0 = time.perf_counter()
            for _ in range(reads):
                r0 = time.perf_counter()
                res = s.fetch_tagged(q, t0 - 1, t0 + 3600 * nanos)
                lats.append(time.perf_counter() - r0)
                assert len(list(res)) == 16
            elapsed = time.perf_counter() - bench_t0
            lats.sort()
            modes[mode] = {
                "reads_per_sec": round(reads / elapsed, 1),
                "p50_ms": round(lats[len(lats) // 2] * 1e3, 2),
                "p99_ms": round(lats[int(len(lats) * 0.99) - 1] * 1e3, 2),
            }
            if hedged:
                issued = hedge_counter("issued") - i0
                won = hedge_counter("won") - w0
            s.close()
    finally:
        import shutil

        shutil.rmtree(cluster.base_dir, ignore_errors=True)
    return _rec(
        "hedged_read_tail_latency",
        round(modes["hedged"]["p99_ms"] / max(modes["unhedged"]["p99_ms"], 1e-9), 3),
        "p99 ratio (hedged/unhedged)",
        straggler={"peer": "node1", "delay_s": delay,
                   "delay_prob": delay_prob, "dist": "lognormal"},
        hedges_issued=issued,
        hedges_won=won,
        **modes,
    )


def bench_pipeline(n_series=None, on_tpu=False):
    """Staged-vs-fused device-query-plan sweep (query/plan.py): an
    in-process Database (resident pool + device index) seeded with the
    dispatch-bound temporal shape — MANY short series, the monitoring
    fleet profile where per-stage host overhead dominates device compute
    — then the SAME ``rate(metric{job=~...}[w])`` query timed warm
    through the fused one-dispatch plan and the staged executor
    (plan.force_staged). Plan-compile/build time is excluded from the
    steady-state percentiles and reported separately
    (``plan_warmup_ms``). Acceptance: fused p50 <= 0.5x staged p50 on
    CPU, with per-query profiled dispatch counts reported for both."""
    import statistics
    import tempfile
    import time as _time

    import numpy as _np

    from m3_tpu.index.device.store import IndexDeviceOptions
    from m3_tpu.query import plan as qplan
    from m3_tpu.query import stats as qstats
    from m3_tpu.query.engine import Engine
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.resident.pool import ResidentOptions
    from m3_tpu.rules.rules import encode_tags_id
    from m3_tpu.storage.database import Database, NamespaceOptions

    n_series = n_series or (65536 if on_tpu else 8192)
    n_points = 16
    NANOS_ = 1_000_000_000
    t0 = 1_600_000_000 * NANOS_
    step = 10 * NANOS_
    db = Database(
        tempfile.mkdtemp(prefix="m3tpu-bench-pipe-"), num_shards=4,
        commitlog_enabled=False,
        resident_options=ResidentOptions(max_bytes=256 << 20),
        index_device_options=IndexDeviceOptions(max_bytes=256 << 20),
    )
    db.create_namespace("bench", NamespaceOptions(block_size_nanos=3600 * NANOS_))
    rng = _np.random.default_rng(0)
    for i in range(n_series):
        tags = ((b"__name__", b"bp"), (b"job", b"app%d" % (i % 4)),
                (b"s", b"%06d" % i))
        sid = encode_tags_id(tags)
        db.write_tagged("bench", tags, t0, float(i % 7))
        db.write_batch(
            "bench",
            [(sid, t0 + (j + 1) * step,
              float(rng.integers(0, 50)) / 4.0) for j in range(n_points - 1)],
        )
    db.flush("bench", t0 + 4 * 3600 * NANOS_)
    eng = Engine(M3Storage(db, "bench"))
    query = 'rate(bp{job=~"app.*"}[2m])'
    span = (t0 + 30 * NANOS_, t0 + (n_points - 1) * step, 30 * NANOS_)

    def run(staged: bool):
        st = qstats.start("bench")
        try:
            if staged:
                with qplan.force_staged():
                    eng.query_range(query, *span)
            else:
                eng.query_range(query, *span)
        finally:
            qstats.finish(st, 0.0)
        return st

    # warmup: plan build + every jit compile on BOTH paths, reported
    # apart from steady state
    w0 = _time.perf_counter()
    run(staged=False)
    plan_warmup_s = _time.perf_counter() - w0
    w0 = _time.perf_counter()
    run(staged=True)
    staged_warmup_s = _time.perf_counter() - w0

    def p50(staged: bool, iters=9):
        ts = []
        st = None
        for _ in range(iters):
            a = _time.perf_counter()
            st = run(staged)
            ts.append(_time.perf_counter() - a)
        return statistics.median(ts), st

    fused_p50, fused_st = p50(staged=False)
    staged_p50, staged_st = p50(staged=True)
    db.close()
    return _rec(
        "pipeline_fused_vs_staged",
        staged_p50 / max(fused_p50, 1e-12),
        "speedup",
        series=n_series,
        points=n_points,
        fused_p50_ms=round(fused_p50 * 1e3, 3),
        staged_p50_ms=round(staged_p50 * 1e3, 3),
        ratio=round(fused_p50 / staged_p50, 4),
        fused_dispatches=fused_st.device_dispatches,
        staged_dispatches=staged_st.device_dispatches,
        plan_hits=fused_st.plan_hits,
        plan_warmup_ms=round(plan_warmup_s * 1e3, 1),
        staged_warmup_ms=round(staged_warmup_s * 1e3, 1),
    )


def bench_ingest(on_tpu):
    """Device ingest suite (BENCH_r06 — the write-path twin of bench.py's
    read headline). Three records:

    1. ``ingest_device_write_plane`` (headline): sustained writes/s into
       the per-shard (series_lane, slot) column planes, device syncs
       riding along at the default IngestOptions.sync_batch cadence —
       the client-visible write plane, the apples-to-apples twin of
       PROFILE.md's 291k writes/s/core host BufferBucket ceiling (both
       exclude seal-time encode, which is lazy on both paths).
    2. ``ingest_encode_seal_kernel``: the seal-time chunk-parallel
       m3tsz encode (ops/encode.py) in datapoints/s.
    3. ``ingest_born_resident_seal``: end-to-end Database write->flush
       through device ingest — proves zero upload bytes on the device
       admissions while reporting the full-path rate.
    """
    import tempfile

    from m3_tpu.ingest import IngestOptions
    from m3_tpu.ingest.buffer import ColumnWriteBuffer
    from m3_tpu.ops import encode as dev_encode
    from m3_tpu.utils.instrument import Registry

    HOST_CEILING = 291_000.0  # writes/s/core, PROFILE.md round 5
    rng = np.random.default_rng(21)

    # --- 1) write plane: sustained append+sync ---
    B = 16384
    lanes = 8192 if on_tpu else 2048
    iters = 120 if on_tpu else 60
    opts = IngestOptions(lanes=lanes, slots=1024, sync_batch=B)
    buf = ColumnWriteBuffer(opts, 2 * 3600 * NANOS, registry=Registry("bi_"))
    sids = [b"s%05d" % (i % lanes) for i in range(B)]
    vals = (np.arange(B, dtype=np.float64) % 97) / 4.0
    units = np.ones(B, np.int8)
    base = (np.arange(B) // lanes).astype(np.int64)
    per = B // lanes
    buf.append_batch(sids, T0 + base * NANOS, vals, units)
    buf.sync()  # jit compile + plane residency settle
    t0 = time.perf_counter()
    n = 0
    for k in range(iters):
        ts = T0 + (base + per * (k + 1)) * NANOS
        buf.append_batch(sids, ts, vals, units)
        n += B
    dt = time.perf_counter() - t0
    assert buf.spills == dict.fromkeys(buf.spills, 0), buf.spills
    plane_rec = _rec(
        "ingest_device_write_plane",
        n / dt,
        "writes/s",
        vs_host_ceiling=round(n / dt / HOST_CEILING, 2),
        batch=B,
        lanes=lanes,
        device_syncs=buf.device_syncs,
        device_sync_bytes=buf.device_sync_bytes,
    )

    # --- 2) seal-time batched encode kernel ---
    M, N = (4096, 720) if on_tpu else (512, 720)
    enc_lanes = []
    for m in range(M):
        t = T0 + np.cumsum(rng.integers(1, 30, N)).astype(np.int64) * NANOS
        v = (
            rng.integers(-5000, 5000, N).astype(np.float64)
            if m % 2
            else rng.normal(0, 10, N)
        )
        enc_lanes.append((t, v))
    kinds = [
        dev_encode.classify_lane(t, v, np.ones(N, np.int8)).kind
        for t, v in enc_lanes
    ]
    dev_encode.encode_lanes(enc_lanes, kinds, k=32)  # compile warm
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        res = dev_encode.encode_lanes(enc_lanes, kinds, k=32)
    dt_enc = (time.perf_counter() - t0) / reps
    enc_rec = _rec(
        "ingest_encode_seal_kernel",
        M * N / dt_enc,
        "datapoints/s",
        lanes=M,
        points=N,
        bytes_per_datapoint=round(float(res.nbytes.sum()) / (M * N), 3),
    )

    # --- 3) end-to-end born-resident seal ---
    from m3_tpu.resident.pool import ResidentOptions
    from m3_tpu.storage.database import Database, NamespaceOptions

    bsz = 2 * 3600 * NANOS
    S, P = (4096, 128) if on_tpu else (512, 64)
    db = Database(
        tempfile.mkdtemp(prefix="m3tpu-bench-ingest-"),
        num_shards=4,
        commitlog_enabled=False,
        resident_options=ResidentOptions(enabled=True, max_bytes=256 << 20),
        ingest_options=IngestOptions(),
    )
    db.create_namespace("bench", NamespaceOptions(block_size_nanos=bsz))
    db.bootstrapped = True
    entries = []
    for s in range(S):
        sid = b"ser%05d" % s
        for p in range(P):
            entries.append((sid, bsz + (p * 20 + s % 17) * NANOS, float(s % 100)))
    t0 = time.perf_counter()
    db.write_batch("bench", entries)
    dt_w = time.perf_counter() - t0
    t0 = time.perf_counter()
    db.flush("bench", 2 * bsz)
    dt_f = time.perf_counter() - t0
    st = db.resident_pool.stats()
    db.close()
    npts = S * P
    seal_rec = _rec(
        "ingest_born_resident_seal",
        npts / (dt_w + dt_f),
        "writes/s",
        series=S,
        points=P,
        write_s=round(dt_w, 3),
        seal_s=round(dt_f, 3),
        device_admissions=st["device_admissions"],
        admissions=st["admissions"],
        upload_bytes=st["upload_bytes"],
        side_stage_bytes=st["ingest_side_stage_bytes"],
    )
    assert st["upload_bytes"] == 0, st
    assert st["device_admissions"] == st["admissions"] > 0, st
    return [plane_rec, enc_rec, seal_rec]


def bench_compression(n_series=2000, n_points=720):
    """bytes/datapoint on a PRODUCTION-LIKE trace, next to the reference's
    1.45 bytes/dp production claim (docs/m3db/architecture/engine.md:11).
    Composition modeled on a typical Prometheus scrape: regular 10s
    timestamps; step-y monotone request counters; low-cardinality gauges
    that mostly repeat (memory/queue sizes); one-decimal utilization
    gauges; a tail of higher-entropy latency floats."""
    from m3_tpu import native

    rng = np.random.default_rng(9)
    times = (T0 + np.arange(n_points) * 10 * NANOS).astype(np.int64)
    all_t, all_v, lens = [], [], []
    comp = {"counter": 0.4, "repeat_gauge": 0.3, "decimal_gauge": 0.2, "latency": 0.1}
    for i in range(n_series):
        r = i / n_series
        if r < comp["counter"]:
            # ~5 req/s with bursts; cumulative counter
            vals = np.cumsum(rng.poisson(50, n_points)).astype(float)
        elif r < comp["counter"] + comp["repeat_gauge"]:
            # changes rarely (queue depth, memory pages)
            base = float(rng.integers(100, 10000))
            steps = rng.choice([0, 0, 0, 0, 0, 0, 0, 1, -1], n_points)
            vals = base + np.cumsum(steps).astype(float)
        elif r < comp["counter"] + comp["repeat_gauge"] + comp["decimal_gauge"]:
            # one-decimal utilization percentage
            vals = np.round(rng.normal(55, 6, n_points), 1)
        else:
            # latency seconds, 3 decimals
            vals = np.round(rng.lognormal(-3, 0.4, n_points), 3)
        all_t.append(times)
        all_v.append(vals)
        lens.append(n_points)
    streams = native.encode_batch(
        np.concatenate(all_t), np.concatenate(all_v), np.asarray(lens, np.int32)
    )
    nbytes = sum(map(len, streams))
    npts = n_series * n_points
    return _rec(
        "compression_production_trace",
        nbytes / npts,
        "bytes/datapoint",
        series=n_series,
        reference_production_claim=1.45,
        composition="40% counters, 30% repeat gauges, 20% 1-decimal gauges, 10% latency",
    )


def bench_index(n_series, tmpdir="/tmp/m3tpu-index-bench"):
    """Index-at-scale microbench: build an n_series namespace index, persist
    to the mmap segment format, reopen zero-copy, and serve term + regexp
    queries (segment/fst/segment.go role + postings_list_cache.go)."""
    import shutil

    from m3_tpu.index.disk_segment import DiskSegment
    from m3_tpu.index.ns_index import NamespaceIndex
    from m3_tpu.index.query import regexp as regexp_q
    from m3_tpu.index.query import term as term_q

    HOUR = 3600 * NANOS
    shutil.rmtree(tmpdir, ignore_errors=True)
    ix = NamespaceIndex(block_size_nanos=HOUR)
    t0 = time.perf_counter()
    batch = [
        (
            f"s{i}".encode(),
            (
                (b"dc", b"dc%d" % (i % 4)),
                (b"host", b"h%d" % (i % 50021)),
                (b"name", b"metric_%d" % (i % 100)),
            ),
            T0,
        )
        for i in range(n_series)
    ]
    ix.write_batch(batch)
    build_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ix.persist_before(tmpdir, "bench", T0 + 2 * HOUR)
    persist_s = time.perf_counter() - t0

    ix2 = NamespaceIndex(block_size_nanos=HOUR)
    t0 = time.perf_counter()
    ix2.load_persisted(tmpdir, "bench")
    open_s = time.perf_counter() - t0

    def lat(q, iters=5):
        out = []
        n = 0
        for _ in range(iters):
            t0 = time.perf_counter()
            r = ix2.query(q, T0 - HOUR, T0 + HOUR)
            out.append(time.perf_counter() - t0)
            n = len(r.docs)
        return out, n

    term_lats, term_n = lat(term_q(b"name", b"metric_42"))
    re_lats, re_n = lat(regexp_q(b"name", b"metric_1[0-9]"))
    # query results are lazy (index/query.py MatchedDocs); report the
    # full-materialization and ids-only costs separately so the latency
    # numbers above can't hide per-doc decode work downstream would pay
    r = ix2.query(regexp_q(b"name", b"metric_1[0-9]"), T0 - HOUR, T0 + HOUR)
    t0 = time.perf_counter()
    n_mat = len(list(r.docs))
    mat_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ids = r.docs.ids() if hasattr(r.docs, "ids") else [d.id for d in r.docs]
    ids_s = time.perf_counter() - t0
    assert n_mat == len(ids) == re_n
    shutil.rmtree(tmpdir, ignore_errors=True)
    return _rec(
        "index_5m_mmap_segment",
        n_series / build_s,
        "docs_indexed/s",
        series=n_series,
        persist_s=round(persist_s, 2),
        mmap_open_ms=round(open_s * 1e3, 2),
        term_query_ms_cold=round(term_lats[0] * 1e3, 3),
        term_query_ms_warm=round(float(np.median(term_lats[1:])) * 1e3, 3),
        term_matched=term_n,
        regexp_query_ms_cold=round(re_lats[0] * 1e3, 3),
        regexp_query_ms_cached=round(float(np.median(re_lats[1:])) * 1e3, 3),
        regexp_matched=re_n,
        regexp_materialize_ms=round(mat_s * 1e3, 1),
        regexp_ids_only_ms=round(ids_s * 1e3, 1),
    )


def bench_index_device(series_counts, tmpdir="/tmp/m3tpu-index-device-bench"):
    """Device-vs-host index_resolve sweep (ISSUE 10's flatness claim,
    measured): for each series count build a namespace index with the
    device tier on, seal (admitting the segment into HBM), and report
    p50 resolve latency for a regexp + a conjunction query through the
    device executor vs the SAME index host-forced — plus matched
    docs/sec through the device path. ``index_resolve`` staying flat as
    the series count grows is the success metric; the sweep makes it a
    number instead of an assertion."""
    import shutil

    from m3_tpu.index.device import DeviceIndexStore, IndexDeviceOptions
    from m3_tpu.index.ns_index import NamespaceIndex
    from m3_tpu.index.query import conj, regexp as regexp_q, term as term_q

    HOUR = 3600 * NANOS
    shutil.rmtree(tmpdir, ignore_errors=True)
    queries = [
        ("regexp", regexp_q(b"name", b"metric_1[0-9]")),
        ("conj", conj(term_q(b"dc", b"dc1"), regexp_q(b"name", b"metric_.*"))),
    ]
    sweep = []
    last_docs_per_s = 0.0
    for n_series in series_counts:
        store = DeviceIndexStore(IndexDeviceOptions(max_bytes=1 << 30))
        ix = NamespaceIndex(block_size_nanos=HOUR, device_store=store)
        ix.write_batch(
            [
                (
                    f"s{i}".encode(),
                    (
                        (b"dc", b"dc%d" % (i % 4)),
                        (b"host", b"h%d" % (i % 50021)),
                        (b"name", b"metric_%d" % (i % 100)),
                    ),
                    T0,
                )
                for i in range(n_series)
            ]
        )
        ix.seal_before(T0 + 2 * HOUR)
        assert store.stats()["admissions"] == 1, store.stats()
        row = {"series": n_series}
        for qname, q in queries:
            # ids() materializes doc ids only — the executor's own cost,
            # not per-doc tag decode
            def run(force_host, iters=7):
                lats = []
                matched = 0
                for _ in range(iters):
                    t0 = time.perf_counter()
                    r = ix.query(q, T0 - HOUR, T0 + HOUR, force_host=force_host)
                    matched = len(r.docs.ids())
                    lats.append(time.perf_counter() - t0)
                return float(np.median(lats)), matched

            run(False, iters=2)  # device warmup: jit compiles excluded
            dev_p50, matched = run(False)
            host_p50, matched_h = run(True)
            assert matched == matched_h, (qname, matched, matched_h)
            row[f"{qname}_device_p50_ms"] = round(dev_p50 * 1e3, 3)
            row[f"{qname}_host_p50_ms"] = round(host_p50 * 1e3, 3)
            row[f"{qname}_matched"] = matched
            if qname == "regexp":
                last_docs_per_s = matched / max(dev_p50, 1e-9)
                row["matched_docs_per_s"] = round(last_docs_per_s)
        sweep.append(row)
        assert store.stats()["errors"] == 0
    # growth factors across the sweep, normalized to the series growth:
    # 1.0 = perfectly linear, < host = the device path flattens the curve
    # (CPU runs are sanity only — the kernels are built for TPU vector
    # units, where the host python/numpy walk is the one that can't keep
    # up; see BASELINE.md's platform note)
    growth = {}
    if len(sweep) >= 2:
        s_growth = sweep[-1]["series"] / sweep[0]["series"]
        for qname, _ in queries:
            for side in ("device", "host"):
                k = f"{qname}_{side}_p50_ms"
                growth[f"{qname}_{side}_growth"] = round(
                    (sweep[-1][k] / max(sweep[0][k], 1e-9)) / s_growth, 3
                )
    return _rec(
        "index_device_resolve",
        last_docs_per_s,
        "matched_docs/s",
        sweep=sweep,
        **growth,
    )


def bench_soak():
    """Composed production-soak SLO gate (tools/check_soak.py): a seeded
    multi-process RF=3 cluster + cluster-mode coordinator + aggregator HA
    pair under overlapping acts (diurnal load, write storm, tenant flood,
    node add+drain, aggregator leader SIGKILL, backfill burst, seeded
    stragglers), with the SLO engine as the verdict. The headline is the
    availability error budget still standing after ~90s of that. NOT in
    the default config set — it spawns a fleet and owns the box while it
    runs; invoke it deliberately (``--configs soak``, the CI gate)."""
    import os
    import subprocess
    import sys

    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "check_soak.py"
    )
    proc = subprocess.run(
        [sys.executable, script, "--json"],
        capture_output=True, text=True, timeout=900,
    )
    summary = None
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            summary = json.loads(line)
    assert summary is not None, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert proc.returncode == 0 and not summary.get("failures"), summary
    return _rec(
        "soak_slo_gate",
        summary["availability_budget_remaining"],
        "availability budget remaining",
        elapsed_secs=summary["elapsed_secs"],
        total_ops=summary["total_ops"],
        client_errors=summary["client_errors"],
        sheds=summary["sheds"],
        availability_sli=summary["availability_sli"],
        latency_sli=summary["latency_sli"],
        durability_probes=summary["durability_probes"],
        freshness_probes=summary["freshness_probes"],
        rollup_windows=summary["rollup_windows"],
    )


def _write_soak() -> None:
    """``--configs soak`` alone: the fleet's processes are children of
    tools/check_soak.py, which puts them on the CPU — so the record says
    cpu, and this parent stays off jax (it would otherwise hold the chip
    its children could not then reach)."""
    soak_record = bench_soak()
    with open("BENCH_r07.json", "w") as f:
        json.dump(
            {
                "platform": "cpu (child processes, tools/check_soak.py)",
                "parsed": soak_record,
                "records": [soak_record],
            },
            f,
            indent=1,
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--configs",
        default="1,2,3,4,5,mixed,scan,index,compression,tenants,pipeline,ingest",
    )
    ap.add_argument("--series", type=int, default=0, help="override config-2 series")
    ap.add_argument("--out", default="bench_suite_out.json")
    args = ap.parse_args()
    want = set(args.configs.split(","))
    if "soak" in want:
        if want != {"soak"}:
            ap.error("--configs soak starts a fleet of child processes and "
                     "cannot be combined with in-process configs")
        return _write_soak()

    import jax

    from m3_tpu import device

    device.configure_compile_cache()
    on_tpu = jax.devices()[0].platform == "tpu"
    big = on_tpu
    s2 = args.series or (1048576 if big else 2048)
    s_mixed = 524288 if big else 2048
    s3 = 102400 if big else 4096
    s4 = 10_000_000 if big else 100_000
    s5 = 10_000_000 if big else 20_000

    records = []
    if "1" in want:
        records.append(bench_config1())
    if "2" in want:
        records.append(bench_config2(s2, on_tpu))
    if "mixed" in want:
        records.append(bench_mixed(s_mixed, on_tpu))
    if "scan" in want:
        records.append(bench_scan_p50(s2, on_tpu))
    if "3" in want:
        records.append(bench_config3(s3))
    if "4" in want:
        records.append(bench_config4(s4))
    if "5" in want:
        records.append(bench_config5(s5, on_tpu))
    if "index" in want:
        records.append(bench_index(5_000_000 if big else 100_000))
        records.append(
            bench_index_device(
                [65536, 262144, 1048576] if big else [65536, 262144]
            )
        )
    if "compression" in want:
        records.append(bench_compression())
    if "tenants" in want:
        records.append(bench_multitenant())
        records.append(bench_hedging())
    if "pipeline" in want:
        records.append(bench_pipeline(on_tpu=on_tpu))
    ingest_records = None
    if "ingest" in want:
        ingest_records = bench_ingest(on_tpu)
        records.extend(ingest_records)

    # merge into an existing results file: re-running a subset of configs
    # replaces those records and keeps the rest
    merged: dict[str, dict] = {}
    try:
        with open(args.out) as f:
            for r in json.load(f).get("records", []):
                merged[r["metric"]] = r
    except (OSError, ValueError):
        pass
    for r in records:
        merged[r["metric"]] = r
    with open(args.out, "w") as f:
        json.dump(
            {
                "platform": jax.devices()[0].device_kind,
                "records": list(merged.values()),
            },
            f,
            indent=1,
        )
    if ingest_records is not None:
        # BENCH_r06: the ingest round's headline (write-plane writes/s
        # vs the PROFILE.md 291k/s/core host ceiling) + its satellites
        with open("BENCH_r06.json", "w") as f:
            json.dump(
                {
                    "platform": jax.devices()[0].device_kind,
                    "parsed": ingest_records[0],
                    "records": ingest_records,
                },
                f,
                indent=1,
            )


if __name__ == "__main__":
    main()
