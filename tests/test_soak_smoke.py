"""Tier-1 soak smoke: a ~15s in-process miniature of tools/check_soak.py.

The full composed soak (multi-process RF=3 cluster, aggregator HA pair,
node churn) is a CI gate, not a tier-1 test. This smoke keeps tier-1
coverage of the same closed loop: live query traffic → selfmon scrape →
compiled SLO recordings → status/probe ticks — on real threads and real
clocks, with lenient assertions (the shared-core CI box sets the floor,
not the ceiling)."""

import threading
import time

import pytest

from m3_tpu.selfmon import RESERVED_NS
from m3_tpu.services.coordinator import Coordinator
from m3_tpu.storage.database import Database, NamespaceOptions

# 2s scrape / 10s-floor windows: at 1s nominal spacing, scheduling
# jitter on a loaded CI box produces sub-second deltas that the m3tsz
# SECOND-unit encoding collapses onto one timestamp, flattening every
# rate() over the stored telemetry (the same rationale as the check_*
# tools' SCRAPE_INTERVAL = 2.0)
SLO_YML = """\
eval_interval: 2s
probe_interval: 2s
windows:
  fast: [10s, 20s]
  slow: [20s, 40s]
slos:
  - name: smoke_availability
    sli: availability
    objective: 0.99
    window: 60s
  - name: smoke_durability
    sli: durability
    objective: 0.9
    window: 60s
"""


@pytest.fixture()
def db(tmp_path):
    db = Database(str(tmp_path), num_shards=2)
    db.create_namespace("default", NamespaceOptions())
    db.create_namespace(RESERVED_NS, NamespaceOptions())
    db.bootstrap()
    yield db
    db.close()


def test_soak_smoke(db, tmp_path):
    slo_path = tmp_path / "slo.yml"
    slo_path.write_text(SLO_YML)

    coord = Coordinator(db=db)
    coord.start_selfmon(2.0, instance="smoke0")
    coord.start_slo(str(slo_path), instance="smoke0", jitter=False)
    try:
        eng = coord.engine_for("default")
        stop = threading.Event()
        errors: list = []

        def act_queries():
            # steady read load: every query lands in the availability SLI
            now = time.time_ns()
            while not stop.is_set():
                try:
                    eng.query_instant("up", now)
                except Exception as exc:  # smoke verdict, not silence
                    errors.append(f"query: {exc!r}")
                time.sleep(0.2)

        def act_backfill():
            # overlapping ingest churn: hours-old timestamps
            t0 = time.time_ns() - 4 * 3600 * 10**9
            for i in range(60):
                if stop.is_set():
                    return
                try:
                    db.write("default", b"smoke_backfill_%d" % (i % 4),
                             t0 + i * 10**9, float(i))
                except Exception as exc:
                    errors.append(f"backfill: {exc!r}")
                time.sleep(0.1)

        acts = [threading.Thread(target=act_queries, daemon=True),
                threading.Thread(target=act_backfill, daemon=True)]
        for t in acts:
            t.start()

        # the loop is closed when availability has a recorded ratio and
        # the probes have run: poll the live status surface
        # leaves as soon as both hold (about 10 s alone); the room is for a
        # machine shared with five other test workers
        deadline = time.monotonic() + 90
        avail = dura = None
        while time.monotonic() < deadline:
            rows = {r["name"]: r
                    for r in coord.slo.status_dict()["objectives"]}
            avail = rows.get("smoke_availability")
            dura = rows.get("smoke_durability")
            probes = (dura or {}).get("probes") or {}
            if (avail and avail.get("sliRatio") is not None
                    and probes.get("good", 0) >= 2):
                break
            time.sleep(0.5)
        stop.set()
        for t in acts:
            t.join(timeout=10)

        assert not errors, errors[:3]
        assert avail is not None and avail["sliRatio"] is not None, avail
        # every query completed: the budget must not have burned
        assert avail["sliRatio"] == pytest.approx(1.0)
        assert avail["budgetRemaining"] == pytest.approx(1.0)
        assert not avail["stale"]
        probes = dura["probes"]
        assert probes["good"] >= 2 and probes["good"] == probes["total"], probes
        # the compiled recording plane materialized in _m3tpu
        r = coord.engine_for(RESERVED_NS).query_instant(
            "slo:smoke_availability:ratio_rate10s", time.time_ns()
        )
        assert r.values is not None and r.values.size > 0
    finally:
        coord.slo.stop()
        coord.ruler.stop()
        coord.selfmon.stop()
