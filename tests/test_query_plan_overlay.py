"""The live edge served from the device: a range that reaches into the open
block is ONE plan program that reads the shards' ingest planes beside the
sealed pages (query/plan.py, the overlay stage).

A small fleet of the benchmark's own live deployment
(``tsbs-cpu-only-400-live-1node`` at a few hosts) is written through the
served ops, its first block sealed and device-resident, then ticks of the
open block written and asked back through ``query_range`` as a dashboard
asks: ranges that end at the newest tick. Every answer must equal numpy
over the generator's matrix bit for bit (``benchmark/reference.py``: the
comparison that decides the cell's ``correct``) and be served by the plan
with no fallback; what the planes cannot stand for (an out-of-order lane,
a row they refused) must fall back staged with its counted reason and
answer exactly all the same.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import fleet  # noqa: E402  (benchmark/fleet.py)
import reference  # noqa: E402
import traffic  # noqa: E402

from m3_tpu.utils.instrument import DEFAULT as METRICS  # noqa: E402

CONFIG = "tsbs-cpu-only-400-live-1node"
HOSTS = 4
SEED = 3_700_000_011
OPEN_TICKS = 80  # the open block's ticks before the first question: more than the
# lastpoint selector's range and its lookback, so that it lies in the open block alone
# the last tick a panel may end on and fetch nothing of the open block (its
# fetch ends a step past its last step)
SEALED_END = 720 - 7
PANEL = {"fn": "max_over_time", "metric": "cpu_usage_user", "range_secs": 300,
         "step_secs": 60, "span_secs": 3600, "hosts": "all"}
LASTPOINT = {"fn": "selector", "metric": "cpu_usage_user", "step_secs": 10,
             "span_secs": 300, "hosts": "all"}


def unsynced(buf) -> bool:
    """Rows the buffer acknowledged that are not on the device yet."""
    return any((f.synced < f.counts).any() for f in buf._frames.values())


def counter(name: str, **labels) -> float:
    fam = METRICS.collect().get("m3tpu_" + name, {"children": []})
    return sum(c["value"] for c in fam["children"]
               if all(c["labels"].get(k) == v for k, v in labels.items()))


class Live:
    """One device-tier database behind the RPC middleware: a block of
    ticks written and flushed (where ``sealed``), then ``open_ticks`` of
    the next block, the first of them tagged as the sealed block's."""

    def __init__(self, base: str, sealed: bool = True, open_ticks: int = OPEN_TICKS,
                 slots: int = 1024) -> None:
        from m3_tpu.index.device.store import IndexDeviceOptions
        from m3_tpu.ingest import IngestOptions
        from m3_tpu.net.server import NodeService, RpcMiddleware
        from m3_tpu.resident.pool import ResidentOptions
        from m3_tpu.storage.database import Database, NamespaceOptions

        cfg = fleet.load_config(CONFIG)
        cfg["hosts"] = HOSTS
        self.cfg = cfg
        self.ns = cfg["namespace"]
        self.t0 = fleet.t0_nanos(cfg)
        self.dt = cfg["interval_secs"] * fleet.NANOS
        self.n = fleet.points_per_block(cfg)
        self.table = fleet.series_table(cfg)
        self.vals = fleet.values(cfg, SEED, 2 * self.n)
        self.row_of = {(h, m): i for i, (h, m, _) in enumerate(self.table)}
        self.hosts = fleet.hosts(cfg)
        dbn = cfg["dbnode"]
        self.db = Database(
            base, num_shards=dbn["num_shards"], commitlog_enabled=False,
            resident_options=ResidentOptions(max_bytes=64 << 20),
            index_device_options=IndexDeviceOptions(max_bytes=64 << 20),
            ingest_options=IngestOptions(lanes=64, slots=slots,
                                         sync_batch=dbn["ingest_sync_batch"]),
        )
        self.db.create_namespace(
            self.ns, NamespaceOptions(block_size_nanos=cfg["block_secs"] * fleet.NANOS))
        self.mw = RpcMiddleware(NodeService(self.db), component="dbnode")
        if sealed:
            self.sids = self.tagged(0)
            self.write(1, self.n)
            self.call(op="flush", ns=self.ns, flush_before=self.t0 + self.n * self.dt)
        self.sids = self.tagged(self.n)
        self.last = self.n
        self.write(self.n + 1, self.n + open_ticks)

    def call(self, **req):
        return self.mw.handle(req)

    def tagged(self, k: int) -> list[bytes]:
        sids = []
        for i, (h, metric, _) in enumerate(self.table):
            tags = [[a, b] for a, b in fleet.series_tags(self.hosts[h], metric)]
            sids.append(bytes(self.call(
                op="write_tagged", ns=self.ns, tags=tags, t=self.t0 + k * self.dt,
                v=float(self.vals[i, k]))))
        return sids

    def write(self, lo: int, hi: int) -> None:
        """Ticks [lo, hi) of the fleet, one write_batch a tick."""
        for k in range(lo, hi):
            self.call(op="write_batch", ns=self.ns, entries=[
                [sid, self.t0 + k * self.dt, v]
                for sid, v in zip(self.sids, self.vals[:, k].tolist())])
            self.last = k

    def request(self, cls: dict, end_tick: int | None = None) -> dict:
        """``cls`` over every host, its last step on ``end_tick`` (the
        newest tick written)."""
        end_tick = self.last if end_tick is None else end_tick
        return traffic.at_end_tick(traffic._query_request(self.cfg, self.t0, cls, None, 0),
                                   self.t0, self.dt, end_tick)

    def send(self, req: dict, **extra) -> dict:
        return self.call(op="query_range", ns=self.ns, query=req["query"],
                         start=req["start"], end=req["end"], step=req["step"], **extra)

    def ask(self, cls: dict, end_tick: int | None = None, **extra) -> tuple[dict, int]:
        """One request of ``cls`` (``request``) and the cells of its reply
        that differ from the reference."""
        req = self.request(cls, end_tick)
        reply = self.send(req, **extra)
        idx = np.asarray([self.row_of[(h, cls["metric"])] for h in range(HOSTS)])
        bad = reference.mismatches(
            reference.rows_by_host(reply), [f"host_{h}" for h in range(HOSTS)],
            reference.answer(self.vals, idx, req))
        return reply, bad

    def close(self) -> None:
        self.db.close()


@pytest.fixture(scope="module")
def live(tmp_path_factory):
    from m3_tpu import device

    device.install_compile_counters()  # jit_compiles_total, as a dbnode counts it
    s = Live(str(tmp_path_factory.mktemp("live")))
    yield s
    s.close()


def served(reply: dict) -> dict:
    st = reply["stats"]
    assert st["planFallbacks"] == 0 and st["deviceDispatches"] >= 1, st
    return st


def test_a_panel_across_the_seal_boundary_is_one_plan_dispatch(live):
    reply, bad = live.ask(PANEL)
    assert bad == 0
    st = served(reply)
    # the sealed half decoded on the device, the open half read from the
    # planes: every matched series has a lane in the open block
    assert st["planSeriesMatched"] == HOSTS
    assert st["planLanesDecoded"] >= HOSTS
    assert st["planOverlayLanes"] == HOSTS
    assert "plan.overlay" in st["stages"]


def test_a_selector_wholly_in_the_open_block_needs_no_sealed_stage(live):
    reply, bad = live.ask(LASTPOINT)
    assert bad == 0
    st = served(reply)
    assert st["planLanesDecoded"] == 0 and st["planOverlayLanes"] == HOSTS


@pytest.mark.parametrize("cls", [PANEL, LASTPOINT], ids=["panel", "lastpoint"])
def test_acknowledged_rows_below_the_sync_batch_are_read(live, cls):
    # a tick is a few dozen rows, far under sync_batch: the write path
    # leaves them staged, and the plan syncs them itself before it reads
    live.write(live.last + 1, live.last + 2)
    ns = live.db.namespaces[live.ns]
    bufs = {ns.shard_for(live.sids[live.row_of[(h, cls["metric"])]]).ingest
            for h in range(HOSTS)}  # the shards of the series asked for
    assert all(unsynced(b) for b in bufs)
    rows0 = counter("query_plan_overlay_sync_rows_total")
    reply, bad = live.ask(cls)
    assert bad == 0
    served(reply)
    assert counter("query_plan_overlay_sync_rows_total") > rows0
    assert not any(unsynced(b) for b in bufs)


def test_a_query_waits_for_the_sync_in_flight_of_the_rows_it_must_read(live, monkeypatch):
    # two queries at one tick: the first holds its sync of the tick's rows
    # mid-scatter, and the second must not read the planes without them
    from m3_tpu.ingest import buffer as ibuf

    live.ask(LASTPOINT)  # warm
    live.write(live.last + 1, live.last + 2)
    scattering, release = threading.Event(), threading.Event()

    def held(scatter):
        def run(*a):
            if threading.current_thread().name == "first":
                scattering.set()
                assert release.wait(120)
            return scatter(*a)
        return run

    for name in ("_scatter_tile4", "_scatter_tile4_donate"):
        monkeypatch.setattr(ibuf, name, held(getattr(ibuf, name)))
    got = {}

    def ask(name):
        got[name] = live.ask(LASTPOINT)

    first = threading.Thread(target=ask, args=("first",), name="first")
    second = threading.Thread(target=ask, args=("second",), name="second")
    first.start()
    assert scattering.wait(120)
    second.start()
    second.join(timeout=2.0)
    waited = second.is_alive()
    release.set()
    first.join(120)
    second.join(120)
    assert waited, "the second query answered while the tick's rows were in flight"
    for reply, bad in got.values():
        assert bad == 0
        served(reply)


def test_a_plan_built_at_one_tick_serves_the_next_without_a_rebuild(live):
    for cls in (PANEL, LASTPOINT):
        live.ask(cls)  # built, compiled and warm at this tick
    live.write(live.last + 1, live.last + 2)
    builds = counter("query_plan_builds_total")
    compiles = counter("query_plan_compiles_total")
    jit = counter("jit_compiles_total")
    for cls in (PANEL, LASTPOINT):
        reply, bad = live.ask(cls)
        assert bad == 0
        st = served(reply)
        assert st["planHits"] == 1 and st["planMisses"] == 0
    assert counter("query_plan_builds_total") == builds
    assert counter("query_plan_compiles_total") == compiles
    assert counter("jit_compiles_total") == jit


def test_a_query_compiles_nothing_whether_or_not_another_holds_the_planes(live):
    # another query between its read of the planes and its dispatch holds
    # their lease; a query's own sync must run the one program it always
    # runs (a donated scatter, possible only without a lease, is another)
    for cls in (PANEL, LASTPOINT):
        live.ask(cls)  # warm at this tick, no lease held
    ns = live.db.namespaces[live.ns]
    jit = counter("jit_compiles_total")
    for hold in (True, False):
        live.write(live.last + 1, live.last + 2)
        with contextlib.ExitStack() as held:
            if hold:
                for shard in ns.shards:
                    held.enter_context(shard.ingest.lease())
            for cls in (PANEL, LASTPOINT):
                reply, bad = live.ask(cls)
                assert bad == 0
                served(reply)
    assert counter("jit_compiles_total") == jit


def test_a_sealed_only_request_runs_the_sealed_program(live, monkeypatch):
    from m3_tpu.query import plan as qplan

    seen = []
    build = qplan._build_program
    monkeypatch.setattr(qplan, "_build_program",
                        lambda *a: seen.append(a) or build(*a))
    # an hour wholly inside the sealed block while the open one ingests
    reply, bad = live.ask(PANEL, end_tick=SEALED_END)
    assert bad == 0
    st = served(reply)
    assert st["planOverlayLanes"] == 0 and "plan.overlay" not in st["stages"]
    # the plan the parent built: its program was made once at build,
    # with no overlay dimensions, and is the one the request ran
    storage = live.mw.service._query_engine(live.ns).storage
    entries = [e for k, e in storage.planner._cache.items() if len(k) == 4]
    assert entries and all(e.overlay is None for e in entries)
    assert all(len(a) == 2 or a[2] is None for a in seen), seen
    compiles = counter("query_plan_compiles_total")
    reply, _ = live.ask(PANEL, end_tick=SEALED_END)
    assert reply["stats"]["planHits"] == 1
    assert counter("query_plan_compiles_total") == compiles


def test_a_series_only_the_open_block_knows_is_served_from_the_planes(live):
    from m3_tpu.query import plan as qplan

    tags = [[a, b] for a, b in fleet.series_tags(dict(live.hosts[0], hostname="host_new"),
                                                   "cpu_usage_user")]
    live.call(op="write_tagged", ns=live.ns, tags=tags, t=live.t0 + live.last * live.dt,
              v=61.0)
    for cls in (PANEL, LASTPOINT):
        reply, bad = live.ask(cls)
        st = served(reply)
        rows = reference.rows_by_host(reply)
        # every fleet series exact, the new one beside them with its sample
        assert bad == max(len(rows["host_new"]), 1)
        assert st["planSeriesMatched"] == HOSTS + 1
        assert st["planOverlayLanes"] == HOSTS + 1
        assert rows["host_new"][-1] == 61.0
        want = reference.rows_by_host(live.send(live.request(cls), force_staged=True))
        assert set(want) == set(rows)
        for host, row in want.items():
            both_nan = np.isnan(row) & np.isnan(rows[host])
            assert ((row == rows[host]) | both_nan).all(), host
    assert qplan.OVERLAY_FALLBACKS  # the closed enum the counter's label takes


@pytest.mark.parametrize("fault,reason", [("dirty", "dirty-lane"), ("spill", "spilled-row")])
def test_what_the_planes_cannot_stand_for_falls_back_with_its_reason(tmp_path, fault, reason):
    # the open block alone, its lanes OPEN_TICKS + 1 slots deep where the
    # planes refuse a row
    s = Live(str(tmp_path / fault), sealed=0, open_ticks=OPEN_TICKS,
             slots=OPEN_TICKS + 1 if fault == "spill" else 1024)
    try:
        reply, bad = s.ask(LASTPOINT)
        assert bad == 0
        served(reply)
        before = counter("query_plan_overlay_fallbacks_total", reason=reason)
        if fault == "dirty":
            # one sample of one series again, out of order: only the
            # SeriesBuffer's merge has that lane right
            s.call(op="write_batch", ns=s.ns, entries=[
                [s.sids[0], s.t0 + (s.n + 3) * s.dt, float(s.vals[0, s.n + 3])]])
        else:
            s.write(s.last + 1, s.last + 3)  # a lane past its slots
        reply, bad = s.ask(LASTPOINT, explain=True)
        assert bad == 0
        st = reply["stats"]
        assert st["planFallbacks"] == 1 and st["planOverlayLanes"] == 0
        assert counter("query_plan_overlay_fallbacks_total", reason=reason) == before + 1
        reasons = {r["reason"] for r in st["routing"] if r["path"] == "staged"}
        assert f"plan:overlay:{reason}" in reasons, reasons
    finally:
        s.close()
