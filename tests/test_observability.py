"""Observability substrate units: tracer context propagation + thread
safety, Prometheus exposition correctness, per-query stats records, and the
RPC middleware metrics (reference: x/instrument, x/context opentracing
wiring, Dapper-style propagation)."""

import json
import threading
import urllib.request

import pytest

from m3_tpu.query.stats import QueryStats, SlowQueryRing
from m3_tpu.utils.instrument import Registry
from m3_tpu.utils.trace import Tracer

NANOS = 1_000_000_000
T0 = 1_600_000_000 * NANOS


# --- tracer: cross-thread + cross-process semantics ---


def test_cross_thread_span_does_not_adopt_other_threads_stack():
    """A span started on a worker thread must NOT silently become a child
    of whatever span happens to be open on another thread."""
    tr = Tracer()

    def worker():
        with tr.span("worker.child"):
            pass

    with tr.span("main.parent"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    spans = {s["name"]: s for s in tr.dump()}
    child, parent = spans["worker.child"], spans["main.parent"]
    assert child["parentId"] is None  # own root, not parent's child
    assert child["traceId"] != parent["traceId"]


def test_cross_thread_explicit_context_joins_trace():
    """Explicit propagation (current_context -> span_from_context) is the
    supported way to join a trace across threads/processes."""
    tr = Tracer()
    ctx_holder = {}

    def worker(ctx):
        with tr.span_from_context("worker.child", ctx):
            with tr.span("worker.grandchild"):
                pass

    with tr.span("main.parent"):
        ctx = tr.current_context()
        t = threading.Thread(target=worker, args=(ctx,))
        t.start()
        t.join()
    spans = {s["name"]: s for s in tr.dump()}
    parent = spans["main.parent"]
    child = spans["worker.child"]
    grand = spans["worker.grandchild"]
    assert child["traceId"] == parent["traceId"]
    assert child["parentId"] == parent["spanId"]
    assert grand["traceId"] == parent["traceId"]
    assert grand["parentId"] == child["spanId"]


def test_span_from_context_unsampled_is_noop():
    """The upstream chose not to sample: downstream must not root a fresh
    local trace (that would orphan one-span trees on every replica)."""
    tr = Tracer()
    with tr.span_from_context("s", {"trace_id": 1, "span_id": 2, "sampled": False}):
        pass
    assert tr.dump() == []
    assert tr.started == 1
    # a missing context still falls back to a normal local span
    with tr.span_from_context("local", None):
        pass
    (span,) = tr.dump()
    assert span["name"] == "local" and span["parentId"] is None


def test_tracer_counters_thread_safe():
    tr = Tracer()
    n_threads, per_thread = 8, 200

    def worker():
        for _ in range(per_thread):
            with tr.span("w"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr.started == n_threads * per_thread
    assert tr.sampled == n_threads * per_thread


def test_tracer_from_env(monkeypatch):
    monkeypatch.setenv("M3_TPU_TRACE_SAMPLE_RATE", "0.25")
    monkeypatch.setenv("M3_TPU_TRACE_CAPACITY", "7")
    tr = Tracer.from_env()
    assert tr.sample_rate == 0.25
    assert tr.finished.maxlen == 7
    # malformed values fall back to defaults instead of raising at import
    monkeypatch.setenv("M3_TPU_TRACE_SAMPLE_RATE", "lots")
    monkeypatch.setenv("M3_TPU_TRACE_CAPACITY", "big")
    tr = Tracer.from_env()
    assert tr.sample_rate == 1.0
    assert tr.finished.maxlen == 4096


# --- wire-level trace context helpers ---


def test_wire_trace_inject_extract_roundtrip():
    from m3_tpu.net import wire

    req = wire.inject_trace(
        {"op": "fetch"}, {"trace_id": 11, "span_id": 22, "sampled": True}
    )
    # survives the wire codec
    decoded = wire.loads(wire.dumps(req))
    ctx = wire.extract_trace(decoded)
    assert ctx == {"trace_id": 11, "span_id": 22, "sampled": True}
    assert wire.TRACE_KEY not in decoded  # popped so op handlers never see it
    # absent / malformed contexts read as None, not an error
    assert wire.extract_trace({"op": "fetch"}) is None
    assert wire.extract_trace({wire.TRACE_KEY: "bogus", "op": "x"}) is None
    assert wire.extract_trace({wire.TRACE_KEY: [1, "x", True], "op": "x"}) is None


# --- prometheus exposition ---


def test_exposition_label_escaping():
    reg = Registry(prefix="t_")
    reg.counter(
        "matched_total",
        labels={"regex": 'env=~"prod.*"', "path": "a\\b", "note": "line1\nline2"},
    ).inc()
    text = reg.expose()
    line = next(l for l in text.splitlines() if l.startswith("t_matched_total"))
    assert '\\"prod.*\\"' in line  # quotes escaped
    assert "a\\\\b" in line  # backslash escaped
    assert "line1\\nline2" in line  # newline escaped
    assert "\n" not in line  # the sample stays one line


def test_exposition_histogram_cumulative_and_inf():
    reg = Registry(prefix="t_")
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    text = reg.expose()
    assert 't_lat_bucket{le="0.1"} 2' in text
    assert 't_lat_bucket{le="1.0"} 3' in text  # cumulative, not per-bucket
    assert 't_lat_bucket{le="10.0"} 4' in text
    assert 't_lat_bucket{le="+Inf"} 5' in text
    assert "t_lat_count 5" in text
    assert "t_lat_sum 55.6" in text


def test_registry_concurrent_registration_stress():
    reg = Registry(prefix="t_")
    errors = []

    def worker(i):
        try:
            for j in range(200):
                reg.counter("shared_total", labels={"w": str(j % 10)}).inc()
                reg.histogram("shared_lat", labels={"w": str(j % 10)}).observe(0.01)
                reg.gauge("shared_gauge").add(1)
        except Exception as exc:  # registration races must not raise
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    collected = reg.collect()
    total = sum(c["value"] for c in collected["t_shared_total"]["children"])
    assert total == 8 * 200
    assert collected["t_shared_gauge"]["children"][0]["value"] == 8 * 200
    # kind conflicts still surface
    with pytest.raises(ValueError):
        reg.gauge("shared_total")


def test_registry_collect_matches_expose():
    reg = Registry(prefix="t_")
    reg.counter("c_total").inc(2)
    h = reg.histogram("h", buckets=(1.0,))
    h.observe(0.5)
    h.observe(2.0)
    snap = reg.collect()
    assert snap["t_c_total"]["children"][0]["value"] == 2.0
    hrow = snap["t_h"]["children"][0]
    assert hrow["count"] == 2 and hrow["buckets"][0] == [1.0, 1]
    assert hrow["buckets"][-1][1] == 2  # +Inf cumulative == count


# --- per-query stats ---


def test_query_stats_record_and_ring(tmp_path):
    from m3_tpu.block.core import make_tags
    from m3_tpu.query import stats
    from m3_tpu.query.engine import Engine
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.storage.database import Database, NamespaceOptions

    db = Database(str(tmp_path), num_shards=2, commitlog_enabled=False)
    db.create_namespace("default", NamespaceOptions())
    for i in range(4):
        tags = make_tags({"__name__": "qs_gauge", "i": str(i)})
        for j in range(10):
            db.write_tagged("default", tags, T0 + j * 10 * NANOS, float(i + j))
    engine = Engine(M3Storage(db, "default"))
    engine.query_range("qs_gauge", T0, T0 + 90 * NANOS, 10 * NANOS)
    # the global ring may hold records from other tests — find ours
    rec = next(
        r for r in reversed(stats.RING.dump()) if r["query"] == "qs_gauge"
    )
    assert rec["seriesScanned"] == 4
    assert rec["datapointsScanned"] == 40
    assert rec["bytesScanned"] == 40 * 16  # i64 times + f64 values
    assert rec["durationSecs"] > 0
    for stage in ("parse", "fetch", "index_resolve", "decode", "exec"):
        assert stage in rec["stages"], rec["stages"]
    assert rec["stages"]["fetch"] > 0
    assert rec["error"] is None
    db.close()


def test_query_stats_error_recorded(tmp_path):
    from m3_tpu.query import stats
    from m3_tpu.query.engine import Engine
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.storage.database import Database, NamespaceOptions

    db = Database(str(tmp_path), num_shards=1, commitlog_enabled=False)
    db.create_namespace("default", NamespaceOptions())
    engine = Engine(M3Storage(db, "default"))
    with pytest.raises(ValueError):
        engine.query_range("this is not promql {{", T0, T0 + NANOS, NANOS)
    rec = stats.RING.dump()[-1]
    assert rec["error"] is not None
    db.close()


def test_slow_query_ring_bounded():
    ring = SlowQueryRing(capacity=3)
    for i in range(10):
        ring.record(QueryStats(query=f"q{i}"))
    dumped = ring.dump()
    assert [r["query"] for r in dumped] == ["q7", "q8", "q9"]
    assert [r["query"] for r in ring.dump(limit=2)] == ["q8", "q9"]


# --- coordinator /debug/slow_queries route ---


def test_debug_slow_queries_route():
    from m3_tpu.services.coordinator import Coordinator, serve

    coord = Coordinator()
    srv, port = serve(coord)
    try:
        coord.db.write_tagged(
            "default",
            ((b"__name__", b"route_gauge"),),
            T0,
            1.0,
        )
        base = f"http://127.0.0.1:{port}"
        urllib.request.urlopen(
            f"{base}/api/v1/query_range?query=route_gauge"
            f"&start={T0 // NANOS}&end={T0 // NANOS + 60}&step=15"
        ).read()
        out = json.loads(
            urllib.request.urlopen(f"{base}/debug/slow_queries").read()
        )
        recs = [r for r in out["queries"] if r["query"] == "route_gauge"]
        assert recs, out["queries"]
        assert recs[-1]["seriesScanned"] == 1
        assert recs[-1]["stages"]["fetch"] > 0
    finally:
        srv.shutdown()


# --- rpc middleware: per-op metrics + universal metrics op ---


def test_rpc_middleware_metrics_and_universal_scrape(tmp_path):
    from m3_tpu.net.client import RpcClient
    from m3_tpu.net.server import DebugService, RpcServer
    from m3_tpu.utils.instrument import DEFAULT as METRICS

    server = RpcServer(DebugService({"role": "test"}), component="testsvc")
    server.start()
    client = RpcClient("127.0.0.1", server.port)
    try:
        assert client._call("health")["ok"] is True
        # DebugService has no op_metrics: the middleware answers the scrape
        text = client._call("metrics")
        assert "m3tpu_rpc_requests_total" in text
        with pytest.raises(Exception):
            client._call("bogus_op")
        snap = METRICS.collect()
        reqs = {
            tuple(sorted(c["labels"].items())): c["value"]
            for c in snap["m3tpu_rpc_requests_total"]["children"]
        }
        key = (("component", "testsvc"), ("op", "health"))
        assert reqs[key] >= 1
        errs = {
            tuple(sorted(c["labels"].items())): c["value"]
            for c in snap["m3tpu_rpc_errors_total"]["children"]
        }
        assert errs[(("component", "testsvc"), ("op", "bogus_op"))] >= 1
        hist = {
            tuple(sorted(c["labels"].items())): c
            for c in snap["m3tpu_rpc_request_duration_seconds"]["children"]
        }
        assert hist[key]["count"] >= 1
        # in-flight gauge returned to zero after the calls completed
        inflight = {
            tuple(sorted(c["labels"].items())): c["value"]
            for c in snap["m3tpu_rpc_inflight"]["children"]
        }
        assert inflight[key] == 0
    finally:
        client.close()
        server.stop()


def test_rpc_middleware_op_label_cardinality_capped():
    """Op names arrive off the wire: unique bogus ops must not grow the
    metric registry without bound (they collapse to one _overflow label)."""
    from m3_tpu.net.server import DebugService, RpcMiddleware

    mw = RpcMiddleware(DebugService(), component="captest")
    for i in range(3 * mw._MAX_OPS):
        try:
            mw.handle({"op": f"bogus_{i}"})
        except ValueError:
            pass
    assert len(mw._per_op) <= mw._MAX_OPS + 1
    assert "_overflow" in mw._per_op


# --- stages: one mechanism inside the dbnode's served paths ---

HOUR = 3600 * NANOS
STEP = 10 * NANOS
B0 = T0 // HOUR * HOUR  # a block's start: the data below stays in one block

# what each served path opens, as PERF.md section 3 and README.md name them
WRITE_STAGES = {
    "rpc.server.write_batch", "write.route", "write.shard_lock_wait",
    "write.buffer", "write.ingest_append", "ingest.sync",
    "write.cache_invalidate", "write.commitlog_enqueue",
}
QUERY_STAGES = {
    "rpc.server.query_range", "query.eval", "parse", "fetch", "plan.lookup",
    "plan.enqueue", "plan.device_wait", "plan.finalize", "reply.build",
}
# a range function on the device path (float32-exact samples): its
# dispatch and its read-back, inside the engine's query.temporal
TEMPORAL_STAGES = QUERY_STAGES | {
    "query.temporal", "query.temporal.dispatch", "query.temporal.wait",
}


SERVED_PATHS = pytest.mark.parametrize("kind,op,want", [
    ("write", "write_batch", WRITE_STAGES),
    ("query", "query_range", QUERY_STAGES),
    ("temporal", "query_range", TEMPORAL_STAGES),
])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One device-tier database behind the RPC middleware, a sealed block
    the plan serves, and one request of each served path."""
    from m3_tpu.index.device.store import IndexDeviceOptions
    from m3_tpu.ingest import IngestOptions
    from m3_tpu.net.server import NodeService, RpcMiddleware
    from m3_tpu.resident.pool import ResidentOptions
    from m3_tpu.rules.rules import encode_tags_id
    from m3_tpu.storage.database import Database, NamespaceOptions

    db = Database(
        str(tmp_path_factory.mktemp("served")),
        num_shards=2,
        resident_options=ResidentOptions(max_bytes=16 << 20),
        index_device_options=IndexDeviceOptions(max_bytes=64 << 20),
        # every batch of the 96 series passes sync_batch in both shards,
        # so each write request runs ingest.sync
        ingest_options=IngestOptions(lanes=64, slots=256, sync_batch=32),
    )
    db.create_namespace("ns", NamespaceOptions(block_size_nanos=HOUR))
    sids = []
    for i in range(96):
        tags = ((b"__name__", b"pm"), (b"s", b"%03d" % i))
        sids.append(encode_tags_id(tags))
        db.write_tagged("ns", tags, B0, float(i))
    # a block large enough that a request's fixed costs (parse, the
    # record's publication) stay under a twentieth of it on the CPU
    for j in range(1, 240):
        db.write_batch("ns", [(sid, B0 + j * STEP, float(j % 7)) for sid in sids])
    db.flush("ns", B0 + HOUR)
    mw = RpcMiddleware(NodeService(db), component="dbnode")
    requests = {
        # into the open block, the sealed one resident: every write stage runs
        "write": lambda k: {
            "op": "write_batch", "ns": "ns",
            "entries": [[sid, B0 + HOUR + k * STEP, 1.0] for sid in sids],
        },
        "query": lambda k: {
            "op": "query_range", "ns": "ns", "query": "pm",
            "start": B0 + 60 * NANOS, "end": B0 + 2300 * NANOS, "step": 4 * NANOS,
        },
        # the plan serves the range's grid over all 96 series (at least a
        # block of the fused kernel's rows), the engine applies the function
        "temporal": lambda k: {
            "op": "query_range", "ns": "ns", "query": "max_over_time(pm[5m])",
            "start": B0 + 360 * NANOS, "end": B0 + 2300 * NANOS, "step": 4 * NANOS,
        },
    }
    # first sight compiles the plan program and the ingest scatter
    for kind in requests:
        mw.handle(requests[kind](0))
    yield mw, requests
    db.close()


def _stage_calls(op):
    from m3_tpu.utils.trace import TRACER

    return {stage: calls for (o, stage), (_w, _c, calls)
            in TRACER.stage_table().items() if o == op}


@SERVED_PATHS
def test_sampled_request_yields_one_stage_tree(served, kind, op, want):
    from m3_tpu.net import wire
    from m3_tpu.utils.trace import TRACER

    mw, requests = served
    trace_id, parent = 0x5EED0000 + sum(kind.encode()), 77
    req = wire.inject_trace(
        requests[kind](1), {"trace_id": trace_id, "span_id": parent, "sampled": True})
    mw.handle(req)
    spans = [s for s in TRACER.dump() if s["traceId"] == f"{trace_id:016x}"]
    assert {s["name"] for s in spans} == want
    by_id = {s["spanId"]: s for s in spans}
    roots = [s for s in spans if s["parentId"] not in by_id]
    assert [s["name"] for s in roots] == [f"rpc.server.{op}"]
    assert roots[0]["parentId"] == f"{parent:016x}"
    slack = 1_000_000  # a span starts and ends on the wall clock
    for s in spans:
        p = by_id.get(s["parentId"])
        if p is not None:
            assert s["startNanos"] >= p["startNanos"] - slack
            assert (s["startNanos"] + s["durationNanos"]
                    <= p["startNanos"] + p["durationNanos"] + slack)
    # every name follows the rule profiling/gaps.py tells stages by
    from m3_tpu.utils.trace import is_stage_name

    assert all(is_stage_name(n) for n in want)


@SERVED_PATHS
def test_unsampled_request_counts_stages_and_builds_no_span(served, kind, op, want):
    from m3_tpu.utils.instrument import DEFAULT as METRICS
    from m3_tpu.utils.trace import TRACER

    mw, requests = served
    before, sampled, started = _stage_calls(op), TRACER.sampled, TRACER.started
    mw.handle(requests[kind](2))
    after = _stage_calls(op)
    assert (TRACER.sampled, TRACER.started) == (sampled, started)
    assert {s for s in after if after[s] > before.get(s, 0)} == want
    expo = METRICS.expose()
    for family in ("stage_seconds_total", "stage_cpu_seconds_total", "stage_calls_total"):
        assert f'm3tpu_{family}{{op="{op}",stage="rpc.server.{op}"}}' in expo


def _best_stage_share(mw, request, names) -> float:
    """The largest share of the handler's wall time that the stages
    ``names`` cover, over five plan-served requests: one pre-empted
    request on a loaded test machine does not decide it."""
    import time

    best = None
    for k in range(3, 8):
        req = request(k)
        reply = None  # the last reply is freed outside the timed region
        t0 = time.perf_counter()
        reply = mw.handle(req)
        wall = time.perf_counter() - t0
        st = reply["stats"]
        assert st["planHits"] == 1 and st["planFallbacks"] == 0
        stages = st["stages"]
        assert stages["query.eval"] == st["durationSecs"]
        share = sum(stages[n] for n in names) / wall
        best = share if best is None else max(best, share)
    return best


PLAN_SERVED_STAGES = (
    "plan.lookup", "plan.enqueue", "plan.device_wait", "plan.finalize", "reply.build")


def test_plan_served_reply_stages_sum_to_the_handlers_wall_time(served):
    mw, requests = served
    best = _best_stage_share(mw, requests["query"], PLAN_SERVED_STAGES)
    # the five name the handler's time: parse, the engine's own steps and
    # the middleware are what is left
    assert 0.95 <= best <= 1.0, best


def test_plan_served_temporal_reply_stages_sum_to_the_handlers_wall_time(served):
    """``max_over_time`` over the 96 series the plan serves: with the
    engine's range function as ``query.temporal`` the same stages name
    the handler's time."""
    mw, requests = served
    best = _best_stage_share(
        mw, requests["temporal"], PLAN_SERVED_STAGES + ("query.temporal",))
    assert 0.95 <= best <= 1.0, best


def test_stages_are_profiler_annotations_nested_in_the_request(served, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    mw, requests = served
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        mw.handle(requests["write"](8))
        mw.handle(requests["query"](8))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [p for p in ProfileData.from_file(path).planes if p.name.startswith("/host:")]
    for op, want in (("write_batch", WRITE_STAGES), ("query_range", QUERY_STAGES)):
        root_name = f"rpc.server.{op}"
        found = False
        for plane in host:
            for line in plane.lines:
                events = [(e.name, int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
                          for e in line.events]
                roots = [e for e in events if e[0] == root_name]
                if not roots:
                    continue
                found = True
                _, r0, r1 = roots[0]
                inside = {n for n, s, e in events if r0 <= s and e <= r1}
                assert want <= inside, (op, want - inside)
        assert found, f"no {root_name} annotation on any host thread"


def test_commitlog_counters_match_the_files_and_the_fsyncs(tmp_path, monkeypatch):
    import os

    from m3_tpu.storage import commitlog as cl_mod
    from m3_tpu.storage.commitlog import CommitLog, CommitLogEntry
    from m3_tpu.utils.instrument import DEFAULT as METRICS

    def value(name):
        return sum(c["value"] for c in METRICS.collect()[f"m3tpu_{name}"]["children"])

    def files():
        return sum(os.path.getsize(os.path.join(tmp_path, n)) for n in os.listdir(tmp_path))

    fsyncs = []
    real = cl_mod.DISK.fsync
    monkeypatch.setattr(
        cl_mod.DISK, "fsync", lambda f, path: (fsyncs.append(path), real(f, path))[1])
    names = ("commitlog_bytes_total", "commitlog_entries_total", "commitlog_fsyncs_total")
    b0, e0, f0 = (value(n) for n in names)
    log = CommitLog(str(tmp_path), flush_every=64, flush_interval=60.0)
    try:
        for k in range(5):
            log.write_batch([CommitLogEntry(b"series-%d" % i, T0 + k, float(i))
                             for i in range(100)])
        log.write(CommitLogEntry(b"one", T0, 1.0, annotation=b"note"))
        log.rotate()
        log.write_batch([CommitLogEntry(b"after", T0 + 9, 2.0)])
        log.flush()
        b1, e1, f1 = (value(n) for n in names)
        assert b1 - b0 == files()
        assert e1 - e0 == 502
        assert f1 - f0 == len(fsyncs) >= 3
        assert value("commitlog_fsync_seconds_total") > 0
    finally:
        log.close()


def test_compile_counters_count_what_jax_counts():
    import jax
    import jax.numpy as jnp
    from jax import monitoring

    from m3_tpu import device
    from m3_tpu.utils.instrument import DEFAULT as METRICS

    device.install_compile_counters()
    device.install_compile_counters()  # a second call registers nothing more
    seen = []
    monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: seen.append(kw.get("fun_name"))
        if event == "/jax/core/compile/backend_compile_duration" else None)

    def total():
        fam = METRICS.collect().get("m3tpu_jit_compiles_total", {"children": []})
        return sum(c["value"] for c in fam["children"])

    def fresh_program_for_the_compile_counter(x):
        return (x * 3 + 1).sum()

    before = total()
    fn = jax.jit(fresh_program_for_the_compile_counter)
    x = jnp.arange(37, dtype=jnp.float32)
    fn(x).block_until_ready()
    first = total() - before
    assert first == len(seen) >= 1
    assert "fresh_program_for_the_compile_counter" in " ".join(map(str, seen))
    fn(x).block_until_ready()  # the repeat compiles nothing
    assert total() - before == first == len(seen)
    kernels = {c["labels"]["kernel"] for c in
               METRICS.collect()["m3tpu_jit_compiles_total"]["children"]}
    assert any("fresh_program_for_the_compile_counter" in k for k in kernels)


def test_device_profile_op_over_the_wire(tmp_path):
    import glob

    from m3_tpu.net.client import RemoteNode
    from m3_tpu.testing.proc_cluster import ProcCluster

    cluster = ProcCluster(num_nodes=1, num_shards=2, replica_factor=1,
                          base_dir=str(tmp_path / "data"))
    node = RemoteNode.connect(cluster.nodes["node0"].endpoint, timeout=120.0)
    try:
        assert node.device_profile("stat")["capturing"] is False
        out = str(tmp_path / "capture")
        started = node.device_profile("start", dir=out)
        assert started["capturing"] is True and "peak_bytes_in_use" in started
        # duplicate-safe: the same start again changes nothing, another
        # directory is refused while this capture runs
        assert node.device_profile("start", dir=out)["dir"] == out
        with pytest.raises(Exception, match="already running"):
            node.device_profile("start", dir=out + "-other")
        node.write_batch("default", [(b"sid-%d" % i, T0 + NANOS, 1.0) for i in range(8)])
        # while a capture runs every request is sampled
        assert any(s["name"] == "rpc.server.write_batch" for s in node.traces())
        assert node.device_profile("stat")["dir"] == out
        assert node.device_profile("stop")["capturing"] is False
        assert node.device_profile("stop")["dir"] is None  # nothing running
        assert glob.glob(out + "/**/*.xplane.pb", recursive=True)
        assert "m3tpu_stage_calls_total" in node.metrics()
    finally:
        node.close()
        cluster.close()
