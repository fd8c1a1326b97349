"""The per-host drill-down (TSBS ``single-groupby-1-1-1``): one host's
``max_over_time`` after another, as a Grafana dashboard with a
``$hostname`` variable asks while an operator clicks across the fleet.

Every new host is a plan-cache miss (the plan key holds the matcher's
value, and the fleet outnumbers the cache). A miss builds only the
matchers' part of the plan over the segment's part, which holds the lane
tables of the segment and block set and is built once (query/plan.py
``_SegmentPlan``). A small fleet of the benchmark's own deployments
(cpu-only: integer lanes; devops: float64 percents and int64 counters) is
written, sealed and made device-resident, with the plan cache below the
host count. Every answer must equal numpy over the generator's matrix
(``benchmark/reference.py``: the comparison that decides the cell's
``correct``) and the staged executor, bit for bit.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import fleet  # noqa: E402  (benchmark/fleet.py)
import reference  # noqa: E402
import traffic  # noqa: E402

from m3_tpu.query import plan as qplan  # noqa: E402
from m3_tpu.query import stats  # noqa: E402
from m3_tpu.utils.instrument import DEFAULT as METRICS  # noqa: E402

SEED = 3_900_000_017
# (configuration, hosts, the metrics asked for one host at a time): every
# lane class the two deployments hold on a timed path
FLEETS = {
    "cpu-only": ("tsbs-cpu-only-400-drilldown-1node", 6, ("cpu_usage_user",)),
    "devops": ("tsbs-devops-1node", 3, ("mem_used_percent", "net_bytes_recv")),
}
PLAN_CACHE = "2"  # plans kept: fewer than the hosts, so every host misses


def needle(metric: str) -> dict:
    return {"fn": "max_over_time", "metric": metric, "range_secs": 300,
            "step_secs": 60, "span_secs": 3600, "hosts": 1}


def counter(name: str) -> float:
    fam = METRICS.collect().get("m3tpu_" + name, {"children": []})
    return sum(c["value"] for c in fam["children"])


class Fleet:
    """One device-tier database holding one sealed block of a small fleet
    of ``config``, and an engine over it."""

    def __init__(self, base: str, config: str, n_hosts: int) -> None:
        from m3_tpu.index.device.store import IndexDeviceOptions
        from m3_tpu.query.engine import Engine
        from m3_tpu.query.m3_storage import M3Storage
        from m3_tpu.resident.pool import ResidentOptions
        from m3_tpu.storage.database import Database, NamespaceOptions

        cfg = fleet.load_config(config)
        cfg["hosts"] = n_hosts
        self.cfg = cfg
        self.ns = cfg["namespace"]
        self.t0 = fleet.t0_nanos(cfg)
        self.n = fleet.points_per_block(cfg)
        self.table = fleet.series_table(cfg)
        self.vals = fleet.values(cfg, SEED, self.n)
        self.row_of = {(h, m): i for i, (h, m, _) in enumerate(self.table)}
        hosts = fleet.hosts(cfg)
        dt = cfg["interval_secs"] * fleet.NANOS
        self.db = Database(
            base, num_shards=cfg["dbnode"]["num_shards"], commitlog_enabled=False,
            resident_options=ResidentOptions(max_bytes=64 << 20),
            index_device_options=IndexDeviceOptions(max_bytes=64 << 20),
        )
        self.db.create_namespace(
            self.ns, NamespaceOptions(block_size_nanos=cfg["block_secs"] * fleet.NANOS))
        sids = [self.db.write_tagged(self.ns, fleet.series_tags(hosts[h], m), self.t0,
                                     float(self.vals[i, 0]))
                for i, (h, m, _) in enumerate(self.table)]
        for k in range(1, self.n):
            self.db.write_batch(self.ns, list(zip(
                sids, [self.t0 + k * dt] * len(sids), self.vals[:, k].tolist())))
        self.db.flush(self.ns, self.t0 + self.n * dt)
        self.storage = M3Storage(self.db, self.ns)
        self.engine = Engine(self.storage)
        starts = traffic._start_slots(cfg, self.n, needle("x"), 10)
        self.starts = np.random.default_rng(SEED).choice(starts, n_hosts)

    def request(self, metric: str, host: int) -> dict:
        return traffic._query_request(self.cfg, self.t0, needle(metric), host,
                                      int(self.starts[host]))

    def ask(self, req: dict, staged: bool = False):
        """(float64 [series, steps], metas, sealed QueryStats, with the
        route of every series)."""
        st = stats.start(req["query"])
        st.record_routing = True
        try:
            if staged:
                with qplan.force_staged():
                    r = self.engine.query_range(req["query"], req["start"], req["end"],
                                                req["step"])
            else:
                r = self.engine.query_range(req["query"], req["start"], req["end"],
                                            req["step"])
        finally:
            stats.finish(st, 0.0)
        return np.asarray(r.values), [m.tags for m in r.metas], st

    def want(self, req: dict) -> np.ndarray:
        return reference.answer(self.vals, np.asarray([self.row_of[(req["host"], req["metric"])]]),
                                req)[0]

    def close(self) -> None:
        self.db.close()


@pytest.fixture(scope="module", params=sorted(FLEETS))
def small_fleet(request, tmp_path_factory):
    from m3_tpu import device

    device.install_compile_counters()  # jit_compiles_total, as a dbnode counts it
    config, n_hosts, metrics = FLEETS[request.param]
    f = Fleet(str(tmp_path_factory.mktemp(request.param)), config, n_hosts)
    f.metrics = metrics
    yield f
    f.close()


def assert_exact(f: Fleet, req: dict, got: np.ndarray, metas: list) -> None:
    assert got.shape == (1, req["n_steps"]), (req["query"], got.shape)
    assert dict(metas[0])[b"hostname"] == b"host_%d" % req["host"]
    want = f.want(req)
    assert np.array_equal(got[0], want), (req["query"], np.argwhere(got[0] != want)[:5])


def plan_served(st) -> None:
    """One plan served the request, and the device decoded every series:
    none fell back staged or was re-read by the host after the decoder
    bailed on its lane (which a lane table pointing at another lane's
    pages can make it do)."""
    assert st.plan_fallbacks == 0 and st.plan_hits + st.plan_misses == 1, st.to_dict()
    fused = {r["reason"] for r in st.routing if r["path"] == "fused"}
    assert fused == {"device-plan"}, st.routing


def test_every_host_is_a_plan_miss_over_one_segment_part(small_fleet, monkeypatch):
    """(a) and (b): every host of the fleet, one at a time, plan-served,
    equal to numpy and to the staged executor; the segment's tables are
    built once for all of them, and no host after the first compiles."""
    f = small_fleet
    monkeypatch.setenv("M3_TPU_QUERY_PLAN_CACHE", PLAN_CACHE)
    f.storage.planner._cache.clear()
    f.storage.planner._parts.clear()
    hosts = range(f.cfg["hosts"])
    tables = counter("query_plan_table_builds_total")
    answers = []
    for metric in f.metrics:
        for h in hosts:
            req = f.request(metric, h)
            got, metas, st = f.ask(req)
            plan_served(st)
            assert st.plan_misses == 1  # the fleet outnumbers the cache
            if not answers:
                compiles = counter("jit_compiles_total")
            answers.append((req, got, metas))
    assert counter("query_plan_table_builds_total") == tables + 1
    assert counter("jit_compiles_total") == compiles
    assert len(f.storage.planner._cache) == int(PLAN_CACHE)
    assert len(f.storage.planner._parts) == 1
    for req, got, metas in answers:
        assert_exact(f, req, got, metas)
        staged, staged_metas, _ = f.ask(req, staged=True)
        assert staged_metas == metas
        assert np.array_equal(staged, got)


def readmit_all(f: Fleet) -> None:
    """Evict every block of the pool and admit them again, shards in
    reverse order: the pages each lane lands on move."""
    f.db.resident_pool.clear()
    ns = f.db.namespaces[f.ns]
    for shard in reversed(ns.shards):
        for fid in shard.filesets():
            shard.readmit_fileset(fid)


def new_volume(f: Fleet, host: int, metric: str, value: float) -> None:
    """Supersede the block of the shard that holds ``host``'s ``metric``
    with a new volume in which that series reads ``value`` at every
    sample (the cold-flush supersession shape); the reference's matrix
    follows."""
    from m3_tpu.codec.m3tsz import Encoder
    from m3_tpu.storage.fs import FilesetID, write_fileset

    i = f.row_of[(host, metric)]
    ns = f.db.namespaces[f.ns]
    sid = next(bytes(d.id) for d in ns.index.blocks[f.t0].sealed[0].docs
               if dict(d.fields)[b"hostname"] == b"host_%d" % host
               and dict(d.fields)[b"__name__"] == metric.encode())
    shard = ns.shard_for(sid)
    (fid0,) = [fid for fid in shard.filesets() if fid.block_start == f.t0]
    reader = shard.reader(fid0)
    series = {s: reader.stream(s) for s in reader.series_ids}
    dt = f.cfg["interval_secs"] * fleet.NANOS
    enc = Encoder(f.t0)
    for k in range(f.n):
        enc.encode(f.t0 + k * dt, value)
    series[sid] = enc.stream()
    fid = FilesetID(f.ns, shard.id, f.t0, fid0.volume + 1)
    with shard.lock:
        write_fileset(f.db.base, fid, series, ns.opts.block_size_nanos)
        shard._invalidate_filesets()
        shard._readers.pop(f.t0, None)
        payload = shard._collect_admission_locked([fid])
    f.db.resident_pool.invalidate_block(f.ns, shard.id, f.t0, below_volume=fid.volume)
    shard._admit_payload(payload)
    f.vals[i, :] = value


@pytest.mark.parametrize("change", ["eviction", "new-volume"])
def test_a_stale_segment_part_is_rebuilt_once(small_fleet, monkeypatch, change):
    """(c): after the pool's pages move under the plan, or a block gains a
    new volume, the next host's request rebuilds the segment's part once,
    is plan-served and answers exactly; the hosts after it reuse the new
    part. A part served without its stamp's check would hand the program
    page numbers the pool has since given to other lanes."""
    f = small_fleet
    monkeypatch.setenv("M3_TPU_QUERY_PLAN_CACHE", PLAN_CACHE)
    metric = f.metrics[0]
    for h in range(2):  # a part and plans stamped before the change
        _, _, st = f.ask(f.request(metric, h))
        plan_served(st)
    if change == "eviction":
        readmit_all(f)
    else:
        new_volume(f, 2, metric, 4242.0)
    tables = counter("query_plan_table_builds_total")
    for h in range(2, f.cfg["hosts"]):
        req = f.request(metric, h)
        got, metas, st = f.ask(req)
        plan_served(st)
        assert_exact(f, req, got, metas)
        if change == "new-volume" and h == 2:
            assert (got[0] == 4242.0).all()
    assert counter("query_plan_table_builds_total") == tables + 1


def test_a_haystack_plan_is_the_one_it_was(small_fleet, monkeypatch):
    """(d): the split changes no program. A plan over every host has the
    same AST and dims whether it is built over the segment part a needle
    built or over one of its own, and the AST is the one-piece build's
    shape of its one matcher."""
    from m3_tpu.query.m3_storage import matchers_to_index_query
    from m3_tpu.query.promql import Matcher

    f = small_fleet
    monkeypatch.setenv("M3_TPU_QUERY_PLAN_CACHE", PLAN_CACHE)
    metric = f.metrics[0]
    f.storage.planner._cache.clear()
    f.storage.planner._parts.clear()
    f.ask(f.request(metric, 0))  # a needle's segment part
    (needle_part,) = f.storage.planner._parts.values()
    cls = dict(needle(metric), hosts="all")
    req = traffic._query_request(f.cfg, f.t0, cls, None, int(f.starts[0]))

    def haystack_entry():
        got, _, st = f.ask(req)
        plan_served(st)
        want = reference.answer(
            f.vals, np.asarray([f.row_of[(h, metric)] for h in range(f.cfg["hosts"])]), req)
        assert np.array_equal(got, want)
        planner = f.storage.planner
        (entry,) = [e for k, e in planner._cache.items()
                    if any(m[0] == "__name__" for m in k[1]) and len(k[1]) == 1]
        return entry

    shared = haystack_entry()
    assert shared.part is needle_part
    f.storage.planner._cache.clear()
    f.storage.planner._parts.clear()
    own = haystack_entry()
    assert own.part is not needle_part
    assert (shared.ast, shared.dims) == (own.ast, own.dims)
    leaves, ranges = [], []
    q = matchers_to_index_query([Matcher("__name__", "=", metric)])
    assert own.ast == qplan._ast_shape(q, own.part.arrays, leaves, ranges)
    assert own.ast[0] == "terms" and leaves == [(b"__name__", metric.encode())]
