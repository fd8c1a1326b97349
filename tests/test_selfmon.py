"""Self-monitoring pipeline (m3_tpu/selfmon/): the fleet's own telemetry
ingested through the normal write path and queryable via PromQL.

Covers the PR's acceptance surface in-process — conversion goldens, the
reserved-namespace guard, KernelProfiler sampling determinism, the
exemplar→trace join, EXPLAIN, the collector loop against a real Database,
and the aggregator's m3msg push leg — plus one spawned dbnode+coordinator
end-to-end test where the coordinator answers a PromQL query over its own
RPC-pulled, store-ingested telemetry.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

from m3_tpu.block.core import make_tags
from m3_tpu.index.query import term
from m3_tpu.query.engine import Engine
from m3_tpu.query.m3_storage import M3Storage
from m3_tpu.selfmon import (
    RESERVED_NS,
    DatabaseSink,
    MsgSink,
    ReservedNamespaceError,
    SelfMonCollector,
    selfmon_writer,
    snapshot_to_datapoints,
)
from m3_tpu.storage.database import Database, NamespaceOptions
from m3_tpu.utils.instrument import KernelProfiler, Registry

NANOS = 1_000_000_000
T0 = 1_600_000_000 * NANOS


@pytest.fixture()
def db(tmp_path):
    db = Database(str(tmp_path), num_shards=2)
    db.create_namespace("default", NamespaceOptions())
    db.create_namespace(RESERVED_NS, NamespaceOptions())
    db.bootstrap()
    yield db
    db.close()


# --- histogram/counter/gauge -> datapoint conversion (golden) ---


def test_conversion_golden():
    reg = Registry(prefix="m3tpu_")
    reg.counter("writes_total", labels={"op": "w"}).inc(3)
    reg.gauge("pool_bytes").set(12.5)
    h = reg.histogram("lat_seconds", labels={"op": "q"}, buckets=(0.1, 1))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    entries, truncated = snapshot_to_datapoints(
        reg.collect(), 123, instance="i0", role="dbnode"
    )
    assert truncated == 0
    got = {tags: v for tags, t, v in entries}
    assert all(t == 123 for _, t, _ in entries)
    ident = {"instance": "i0", "role": "dbnode"}
    expected = {
        make_tags({**ident, "__name__": "m3tpu_writes_total", "op": "w"}): 3.0,
        make_tags({**ident, "__name__": "m3tpu_pool_bytes"}): 12.5,
        make_tags({**ident, "__name__": "m3tpu_lat_seconds_bucket",
                   "op": "q", "le": "0.1"}): 1.0,
        make_tags({**ident, "__name__": "m3tpu_lat_seconds_bucket",
                   "op": "q", "le": "1.0"}): 2.0,
        make_tags({**ident, "__name__": "m3tpu_lat_seconds_bucket",
                   "op": "q", "le": "+Inf"}): 3.0,
        make_tags({**ident, "__name__": "m3tpu_lat_seconds_sum",
                   "op": "q"}): 5.55,
        make_tags({**ident, "__name__": "m3tpu_lat_seconds_count",
                   "op": "q"}): 3.0,
    }
    assert got == pytest.approx(expected)


def test_conversion_skips_reserved_namespace_children():
    """Feedback-loop guard: write-path counters labeled with the reserved
    namespace never re-enter the stored telemetry."""
    reg = Registry(prefix="m3tpu_")
    reg.counter("db_writes_total", labels={"ns": "default"}).inc(7)
    reg.counter("db_writes_total", labels={"ns": RESERVED_NS}).inc(99)
    entries, _ = snapshot_to_datapoints(reg.collect(), T0, instance="n")
    vals = [v for tags, _, v in entries
            if (b"__name__", b"m3tpu_db_writes_total") in tags]
    assert vals == [7.0]


def test_conversion_cardinality_cap_is_loud():
    reg = Registry(prefix="m3tpu_")
    for i in range(10):
        reg.counter("many_total", labels={"op": f"op{i}"}).inc()
    entries, truncated = snapshot_to_datapoints(
        reg.collect(), T0, max_datapoints=4
    )
    assert len(entries) == 4 and truncated == 6


# --- reserved-namespace rule (runtime assertion) ---


def test_reserved_namespace_guard(db):
    tags = ((b"__name__", b"m3tpu_x"),)
    with pytest.raises(ReservedNamespaceError):
        db.write_tagged(RESERVED_NS, tags, T0, 1.0)
    with pytest.raises(ReservedNamespaceError):
        db.write_batch(RESERVED_NS, [(b"sid", T0, 1.0)])
    # the collector's sink context is the sanctioned path
    with selfmon_writer():
        db.write_tagged(RESERVED_NS, tags, T0, 1.0)
    assert len(db.fetch_tagged(RESERVED_NS, term(b"__name__", b"m3tpu_x"),
                               T0 - 1, T0 + 1)) == 1
    # ...and it does not leak outside the context
    with pytest.raises(ReservedNamespaceError):
        db.write_tagged(RESERVED_NS, tags, T0 + 1, 1.0)


def test_reserved_namespace_wire_marker(db):
    """The cluster write plane re-establishes the writer context from the
    wire `selfmon` marker (the coordinator collector's remote hop)."""
    from m3_tpu.net.server import NodeService

    svc = NodeService(db, node_id="n0")
    req = {"op": "write_tagged", "ns": RESERVED_NS,
           "tags": [[b"__name__", b"m3tpu_remote"]], "t": T0, "v": 2.0}
    with pytest.raises(ReservedNamespaceError):
        svc.handle(dict(req))
    svc.handle(dict(req, selfmon=True))
    res = db.fetch_tagged(RESERVED_NS, term(b"__name__", b"m3tpu_remote"),
                          T0 - 1, T0 + 1)
    assert len(res) == 1 and res[0][2][0].value == 2.0


def test_peer_bootstrap_carries_reserved_namespace(tmp_path):
    """Replication is not ingest: peer-streamed `_m3tpu` telemetry (which
    a sanctioned collector admitted on the source replica) must survive a
    shard handoff instead of being silently dropped by the guard."""
    from m3_tpu.codec.m3tsz import Datapoint
    from m3_tpu.utils.hash import shard_for
    from m3_tpu.utils.xtime import Unit

    db = Database(str(tmp_path), num_shards=4)
    db.create_namespace(RESERVED_NS, NamespaceOptions())
    db.bootstrap(now_nanos=T0)
    try:
        tags = make_tags({"__name__": "m3tpu_peer_gauge"})
        from m3_tpu.rules.rules import encode_tags_id

        sid = encode_tags_id(tags)
        shard = shard_for(sid, 4)
        peer_data = [
            (sid, tags,
             [Datapoint(T0 + i * NANOS, float(i), Unit.SECOND) for i in range(3)])
        ]
        res = db.bootstrap_shards(
            [shard],
            lambda ns, s: peer_data if s == shard else [],
            has_peer_with_shard=lambda s: True,
        )
        src = res["sources"][RESERVED_NS]
        assert src["fulfilled"].get("peers", 0) > 0
        rows = db.fetch_tagged(RESERVED_NS, term(b"__name__", b"m3tpu_peer_gauge"),
                               T0 - 1, T0 + 10 * NANOS)
        assert len(rows) == 1
        assert [dp.value for dp in rows[0][2]] == [0.0, 1.0, 2.0]
    finally:
        db.close()


# --- KernelProfiler ---


def test_kernel_profiler_sampling_determinism():
    reg = Registry(prefix="m3tpu_")
    prof = KernelProfiler("k1", registry=reg, sample_rate=0.25)
    sampled = []
    for _ in range(100):
        with prof.dispatch() as d:
            d.done(np.zeros(3))
        sampled.append(d.sampled)
    assert sum(sampled) == 25  # exactly rate * n, deterministically
    # a second profiler at the same rate samples the SAME dispatch indices
    prof2 = KernelProfiler("k2", registry=reg, sample_rate=0.25)
    sampled2 = []
    for _ in range(100):
        with prof2.dispatch() as d2:
            d2.done(np.zeros(1))
        sampled2.append(d2.sampled)
    assert sampled2 == sampled
    fam = reg.collect()["m3tpu_kernel_dispatch_seconds"]
    by_kernel = {c["labels"]["kernel"]: c["count"] for c in fam["children"]}
    assert by_kernel == {"k1": 25, "k2": 25}
    disp = reg.collect()["m3tpu_kernel_dispatches_total"]
    assert {c["labels"]["kernel"]: c["value"] for c in disp["children"]} == {
        "k1": 100.0, "k2": 100.0
    }


def test_kernel_profiler_rate_zero_and_one():
    reg = Registry(prefix="m3tpu_")
    off = KernelProfiler("off", registry=reg, sample_rate=0.0)
    on = KernelProfiler("on", registry=reg, sample_rate=1.0)
    for _ in range(5):
        with off.dispatch() as d:
            d.done(np.zeros(1))
        assert not d.sampled
        with on.dispatch() as d:
            d.done(np.zeros(1))
        assert d.sampled
    fam = reg.collect()["m3tpu_kernel_dispatch_seconds"]
    by_kernel = {c["labels"]["kernel"]: c["count"] for c in fam["children"]}
    assert by_kernel.get("off", 0) == 0 and by_kernel["on"] == 5


def test_kernel_profiler_excludes_compiles_from_dispatch_histogram():
    reg = Registry(prefix="m3tpu_")
    prof = KernelProfiler("kc", registry=reg, sample_rate=1.0)
    with prof.dispatch(key=("sig", 1)) as d:
        d.done(np.zeros(1))
    snap = reg.collect()
    # the first call's wall time is compile time -> not a dispatch sample
    assert snap["m3tpu_kernel_dispatch_seconds"]["children"][0]["count"] == 0
    with prof.dispatch(key=("sig", 1)) as d:
        d.done(np.zeros(1))
    snap = reg.collect()
    assert snap["m3tpu_kernel_dispatch_seconds"]["children"][0]["count"] == 1
    assert snap["m3tpu_kernel_dispatches_total"]["children"][0]["value"] == 2.0
    # the first-sight rule no longer feeds the compile counters: those
    # count jax's own events (device.install_compile_counters)
    assert "m3tpu_jit_compiles_total" not in snap


def test_scan_dispatch_profiled(monkeypatch):
    """The served decode path actually feeds the dispatch counters."""
    from m3_tpu.codec.m3tsz import Encoder
    from m3_tpu.ops.fused import PROFILER_PACKED
    from m3_tpu.resident.scan import streamed_scan_totals
    from m3_tpu.utils.instrument import DEFAULT as METRICS

    enc = Encoder(T0)
    for i in range(4):
        enc.encode(T0 + i * NANOS, float(i))
    before = PROFILER_PACKED._n
    monkeypatch.setattr(PROFILER_PACKED, "sample_rate", 1.0)
    # twice: the first call per signature is compile-attributed and
    # deliberately excluded from the dispatch histogram
    for _ in range(2):
        aggs = streamed_scan_totals([enc.stream()])
    assert int(aggs.total_count) == 4
    assert PROFILER_PACKED._n == before + 2
    fam = METRICS.collect()["m3tpu_kernel_dispatch_seconds"]
    counts = {c["labels"]["kernel"]: c["count"] for c in fam["children"]}
    assert counts.get("packed_lane_agg", 0) >= 1


# --- exemplars: slow bucket -> stitched trace -> slow-query record ---


def test_exemplar_joins_trace_and_slow_query_record(db):
    from m3_tpu.query.stats import RING
    from m3_tpu.utils.instrument import DEFAULT as METRICS
    from m3_tpu.utils.trace import TRACER

    db.write_tagged("default", make_tags({"__name__": "exemplar_gauge"}),
                    T0, 4.0)
    eng = Engine(M3Storage(db, "default"))
    with TRACER.span("test.exemplar_root"):
        r = eng.query_range("exemplar_gauge", T0, T0 + NANOS, NANOS)
    assert len(r.metas) == 1

    rec = next(
        rec for rec in reversed(RING.dump()) if rec["query"] == "exemplar_gauge"
    )
    assert rec["traceId"] is not None
    fam = METRICS.collect()["m3tpu_query_duration_seconds"]
    exemplars = [
        ex for child in fam["children"] for ex in child.get("exemplars", ())
    ]
    assert rec["traceId"] in {ex["traceId"] for ex in exemplars}
    # the exemplar's trace id resolves to a real recorded span tree
    assert any(
        s["traceId"] == rec["traceId"] and s["name"] == "test.exemplar_root"
        for s in TRACER.dump()
    )


# --- EXPLAIN ---


def test_explain_reports_stages_and_routing(db):
    db.write_tagged("default", make_tags({"__name__": "explain_gauge"}),
                    T0, 1.0)
    eng = Engine(M3Storage(db, "default"))
    out = eng.explain("explain_gauge", T0, T0 + 2 * NANOS, NANOS)
    assert out["query"] == "EXPLAIN explain_gauge"
    for stage in ("parse", "fetch", "exec"):
        assert out["stages"].get(stage, 0) > 0
    assert out["seriesScanned"] == 1
    assert out["result"]["series"] == 1
    # no resident pool on this db: both the device-plan gate (PR 12) and
    # the residency router record exactly that cause, in decision order
    assert out["routing"] == [
        {"series": "*", "block": None, "path": "staged",
         "reason": "plan:resident-pool-disabled"},
        {"series": "*", "block": None, "path": "streamed",
         "reason": "resident pool disabled"},
    ]
    assert out["routingDropped"] == 0
    # a plain query does NOT pay routing recording
    eng.query_range("explain_gauge", T0, T0 + NANOS, NANOS)
    from m3_tpu.query.stats import RING

    rec = next(r for r in reversed(RING.dump()) if r["query"] == "explain_gauge")
    assert "routing" not in rec


def test_explain_routing_resident(tmp_path):
    """With a resident pool, EXPLAIN records the per-block resident
    decision (and streamed fallbacks name their cause)."""
    from m3_tpu.resident import ResidentOptions

    db = Database(
        str(tmp_path), num_shards=1,
        resident_options=ResidentOptions(enabled=True, max_bytes=1 << 20),
    )
    db.create_namespace("default", NamespaceOptions())
    db.bootstrap()
    try:
        tags = make_tags({"__name__": "res_gauge"})
        for i in range(4):
            db.write_tagged("default", tags, T0 + i * NANOS, float(i))
        bsz = db.namespaces["default"].opts.block_size_nanos
        db.flush("default", ((T0 // bsz) + 1) * bsz)
        eng = Engine(M3Storage(db, "default"))
        out = eng.explain("res_gauge", T0, T0 + 4 * NANOS, NANOS)
        paths = {r["path"] for r in out["routing"]}
        assert "resident" in paths, out["routing"]
        assert out["residentHits"] >= 1
    finally:
        db.close()


# --- the collector against a real Database + PromQL readback ---


def test_collector_scrape_to_promql(db):
    reg = Registry(prefix="m3tpu_")
    reg.counter("rpc_requests_total",
                labels={"component": "dbnode", "op": "fetch"}).inc(5)
    coll = SelfMonCollector(
        DatabaseSink(db), interval=3600, instance="node0",
        component="dbnode", registry=reg, clock=lambda: T0,
    )
    written, errors = coll.scrape_once()
    assert errors == 0 and written > 0
    eng = Engine(M3Storage(db, RESERVED_NS))
    r = eng.query_instant("m3tpu_rpc_requests_total", T0 + NANOS)
    assert len(r.metas) == 1
    tags = dict(r.metas[0].tags)
    assert tags[b"instance"] == b"node0" and tags[b"op"] == b"fetch"
    assert float(np.asarray(r.values)[0, -1]) == 5.0


def test_collector_pulls_peers(db):
    """The coordinator-side pull: peers' snapshots land tagged with the
    peer's instance id, and a dead peer is counted, not fatal."""
    peer_reg = Registry(prefix="m3tpu_")
    peer_reg.gauge("resident_pool_bytes").set(42.0)

    class FakePeer:
        def metrics_snapshot(self):
            return peer_reg.collect()

    class DeadPeer:
        def metrics_snapshot(self):
            raise ConnectionError("down")

    coll = SelfMonCollector(
        DatabaseSink(db), interval=3600, instance="coord0",
        component="coordinator", registry=Registry(prefix="m3tpu_"),
        peers=lambda: {"node7": FakePeer(), "node8": DeadPeer()},
        clock=lambda: T0,
    )
    written, errors = coll.scrape_once()
    assert errors == 1 and written >= 1
    eng = Engine(M3Storage(db, RESERVED_NS))
    r = eng.query_instant('m3tpu_resident_pool_bytes{instance="node7"}',
                          T0 + NANOS)
    assert len(r.metas) == 1
    assert dict(r.metas[0].tags)[b"role"] == b"peer"
    assert float(np.asarray(r.values)[0, -1]) == 42.0


# --- aggregator push leg: MsgSink -> bus -> coordinator ingest ---


def test_msg_sink_routes_to_reserved_namespace():
    from m3_tpu.metrics.encoding import decode_aggregated_batch
    from m3_tpu.services.coordinator import Coordinator

    produced = []

    class FakeProducer:
        def produce(self, shard, payload):
            produced.append((shard, payload))

    sink = MsgSink(FakeProducer(), num_shards=4)
    sink.write([
        (make_tags({"__name__": "m3tpu_agg_messages_total",
                    "instance": "agg0"}), T0, 9.0),
    ])
    assert produced
    msgs = [m for _, payload in produced
            for m in decode_aggregated_batch(payload)]
    coord = Coordinator()
    try:
        assert coord.ingest_aggregated(msgs) == 1
        assert RESERVED_NS in coord.db.namespaces
        res = coord.db.fetch_tagged(
            RESERVED_NS, term(b"__name__", b"m3tpu_agg_messages_total"),
            T0 - 1, T0 + 1,
        )
        assert len(res) == 1
        tags = dict(res[0][1])
        assert b"__selfmon__" not in tags  # marker stripped
        assert b"agg" not in tags  # not suffixed like user rollups
        assert res[0][2][0].value == 9.0
    finally:
        coord.db.close()


# --- end-to-end: spawned dbnode + coordinator answer PromQL over their
# own ingested telemetry ---


def _get_json(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return json.loads(r.read())


def test_e2e_self_scrape(tmp_path):
    from m3_tpu.net.client import RemoteNode
    from m3_tpu.testing.proc_cluster import _spawn_listening
    import sys

    dbnode = coordinator = None
    try:
        dbnode, dh, dport = _spawn_listening(
            [sys.executable, "-m", "m3_tpu.services.dbnode",
             "--base-dir", str(tmp_path / "dbnode"),
             "--shards", "0,1", "--num-shards", "2",
             "--no-mediator", "--selfmon-interval", "1"],
            "dbnode",
        )
        coordinator, ch, cport = _spawn_listening(
            [sys.executable, "-m", "m3_tpu.services.coordinator",
             "--base-dir", str(tmp_path / "coord"),
             "--selfmon-interval", "1",
             "--selfmon-peer", f"{dh}:{dport}"],
            "coordinator",
        )
        base = f"http://{ch}:{cport}"

        # the coordinator answers a PromQL query over its own ingested
        # telemetry: m3tpu_rpc_* series exist because the coordinator's
        # scrape of the dbnode peer is itself RPC traffic
        deadline = time.monotonic() + 30
        result = []
        while time.monotonic() < deadline and not result:
            out = _get_json(
                f"{base}/api/v1/query?query=m3tpu_rpc_requests_total"
                f"&time={time.time()}&namespace={RESERVED_NS}"
            )
            assert out["status"] == "success"
            result = out["data"]["result"]
            if not result:
                time.sleep(0.2)
        assert result, "no self telemetry queryable after 30s"
        roles = {row["metric"].get("role") for row in result}
        assert "peer" in roles  # the dbnode's registry, pulled over RPC
        insts = {row["metric"].get("instance") for row in result}
        assert f"{dh}:{dport}" in insts

        # coordinator-local families are stored too
        out = _get_json(
            f"{base}/api/v1/query?query=m3tpu_selfmon_scrapes_total"
            f'{{role="coordinator"}}&time={time.time()}'
            f"&namespace={RESERVED_NS}"
        )
        assert out["data"]["result"], "coordinator's own registry missing"

        # zero client-visible scrape errors
        out = _get_json(
            f"{base}/api/v1/query?query=m3tpu_selfmon_scrape_errors_total"
            f'{{role="coordinator"}}&time={time.time()}'
            f"&namespace={RESERVED_NS}"
        )
        for row in out["data"]["result"]:
            assert float(row["value"][1]) == 0.0

        # EXPLAIN over the self telemetry reports stages + routing
        out = _get_json(
            f"{base}/api/v1/explain?query=m3tpu_rpc_requests_total"
            f"&start={time.time() - 60}&end={time.time()}&step=15"
            f"&namespace={RESERVED_NS}"
        )
        assert out["stages"].get("fetch", 0) > 0
        assert out["routing"], "EXPLAIN carries routing decisions"

        # exemplars surface on /debug/exemplars with trace ids that
        # resolve in /debug/traces (query_duration histograms get them
        # from the queries this test just ran)
        ex = _get_json(f"{base}/debug/exemplars")["exemplars"]
        dur = ex.get("m3tpu_query_duration_seconds")
        assert dur, f"no query duration exemplars: {list(ex)}"
        tid = dur[0]["exemplars"][-1]["traceId"]
        spans = _get_json(f"{base}/debug/traces?limit=512")["spans"]
        assert any(s["traceId"] == tid for s in spans)

        # the dbnode stores its OWN registry in its local reserved
        # namespace through its own write path
        node = RemoteNode(dh, dport)
        try:
            deadline = time.monotonic() + 15
            rows = []
            while time.monotonic() < deadline and not rows:
                rows = node.fetch_tagged(
                    RESERVED_NS,
                    term(b"__name__", b"m3tpu_selfmon_scrapes_total"),
                    0, 2**62,
                )
                if not rows:
                    time.sleep(0.2)
            assert rows, "dbnode local self-scrape stored nothing"
            # the dbnode's own write-path counter for the reserved
            # namespace must NOT have been re-ingested (feedback guard)
            assert not node.fetch_tagged(
                RESERVED_NS,
                term(b"ns", RESERVED_NS.encode()),
                0, 2**62,
            )
        finally:
            node.close()
    finally:
        for proc in (dbnode, coordinator):
            if proc is not None:
                proc.kill()
                proc.wait(timeout=10)
