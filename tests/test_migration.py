"""Elastic placement: warm residency migration + zero-downtime resharding.

Covers the raw-fileset migration surface in storage/fs.py (manifest,
chunked resumable fetch, checkpoint-last commit, digest verification),
Database.admit_imported_fileset warm admission, the decoded peers stream's
exclude_blocks dedupe, the resident pool's heat-driven rebalance and
source-side drop_shard, the O(1) buffered-block summary behind
buffered_blocks, and the ClusterDatabase handoff orchestration
end-to-end over fake peers — including source death mid-stream falling
back to the decoded rebuild without wedging INITIALIZING.
"""

from __future__ import annotations

import random
import time
from types import SimpleNamespace

import pytest

from m3_tpu.cluster.kv import KVStore
from m3_tpu.cluster.placement import (
    PlacementService,
    ShardState,
    add_instance,
    build_initial_placement,
)
from m3_tpu.resident import ResidentOptions, ResidentPool
from m3_tpu.storage import fs
from m3_tpu.storage.cluster_db import ClusterDatabase
from m3_tpu.storage.database import Database, NamespaceOptions
from m3_tpu.utils.instrument import DEFAULT as METRICS

NANOS = 1_000_000_000
HOUR = 3600 * NANOS
T0 = 1_600_000_000 * NANOS


def _counter_total(name: str, **label_filter) -> float:
    fam = METRICS.collect().get(f"m3tpu_{name}")
    if fam is None:
        return 0.0
    return sum(
        c["value"]
        for c in fam["children"]
        if all(c["labels"].get(k) == v for k, v in label_filter.items())
    )


def _mkdb(path, num_shards=2, resident=True, **ns_kw):
    db = Database(
        str(path),
        num_shards=num_shards,
        commitlog_enabled=False,
        resident_options=ResidentOptions(max_bytes=8 << 20) if resident else None,
    )
    db.create_namespace("ns", NamespaceOptions(**ns_kw))
    return db


def _ingest(db, n_series=8, n_points=30, seed=0):
    from m3_tpu.rules.rules import encode_tags_id

    rng = random.Random(seed)
    sids = []
    for i in range(n_series):
        tags = ((b"__name__", b"g"), (b"s", b"%03d" % i))
        sid = encode_tags_id(tags)
        db.write_tagged("ns", tags, T0, float(i))
        for j in range(n_points - 1):
            db.write(
                "ns", sid, T0 + (j + 1) * 10 * NANOS, rng.uniform(-100, 100)
            )
        sids.append(sid)
    return sids


# ---------- fs migration surface ----------


def _migrate_fileset(src_base, dst_base, fid, chunk=97, stop_after=None):
    """Chunk-copy one fileset's streamable roles; returns chunks moved.
    ``stop_after`` aborts mid-transfer (simulated source death)."""
    moved = 0
    for suffix in fs.MIGRATION_SUFFIXES:
        offset = fs.migration_file_size(dst_base, fid, suffix)
        while True:
            data, eof = fs.read_fileset_chunk(src_base, fid, suffix, offset, chunk)
            if data:
                fs.append_fileset_chunk(dst_base, fid, suffix, offset, data)
                offset += len(data)
                moved += 1
                if stop_after is not None and moved >= stop_after:
                    return moved
            if eof:
                break
    return moved


def test_manifest_and_chunked_fetch_roundtrip(tmp_path):
    src = _mkdb(tmp_path / "src", resident=False)
    _ingest(src)
    src.flush("ns", T0 + 4 * HOUR)
    manifest = fs.migration_manifest(src.base, "ns", 0)
    assert manifest, "flushed shard must list at least one fileset"
    for entry in manifest:
        assert set(entry["files"]) == set(fs.MIGRATION_SUFFIXES)
        fid = fs.FilesetID("ns", 0, entry["blockStart"], entry["volume"])
        # the checkpoint never rides the manifest: commit writes it locally
        assert "checkpoint" not in entry["files"]
        _migrate_fileset(src.base, str(tmp_path / "dst"), fid)
        assert not fs.fileset_complete(str(tmp_path / "dst"), fid)  # pre-commit
        fs.commit_imported_fileset(str(tmp_path / "dst"), fid)
        assert fs.fileset_complete(str(tmp_path / "dst"), fid)
        for suffix in fs.MIGRATION_SUFFIXES:
            with open(fs._path(src.base, fid, suffix), "rb") as f:
                want = f.read()
            with open(fs._path(str(tmp_path / "dst"), fid, suffix), "rb") as f:
                assert f.read() == want, f"{suffix} bytes differ"
    src.close()


def test_fetch_resumes_at_partial_offset(tmp_path):
    src = _mkdb(tmp_path / "src", resident=False)
    _ingest(src)
    src.flush("ns", T0 + 4 * HOUR)
    entry = fs.migration_manifest(src.base, "ns", 0)[0]
    fid = fs.FilesetID("ns", 0, entry["blockStart"], entry["volume"])
    dst = str(tmp_path / "dst")
    # source dies after 3 chunks ...
    _migrate_fileset(src.base, dst, fid, chunk=31, stop_after=3)
    partial = sum(
        fs.migration_file_size(dst, fid, s) for s in fs.MIGRATION_SUFFIXES
    )
    assert 0 < partial < sum(entry["files"].values())
    # ... the next attempt resumes at the local byte offsets, no re-fetch
    _migrate_fileset(src.base, dst, fid, chunk=31)
    fs.commit_imported_fileset(dst, fid)
    assert fs.fileset_complete(dst, fid)
    # resume offset mismatch is an importer race, not silent corruption
    with pytest.raises(ValueError):
        fs.append_fileset_chunk(dst, fid, "data", 1, b"x")
    src.close()


def test_commit_digest_mismatch_deletes_partial(tmp_path):
    src = _mkdb(tmp_path / "src", resident=False)
    _ingest(src)
    src.flush("ns", T0 + 4 * HOUR)
    entry = fs.migration_manifest(src.base, "ns", 0)[0]
    fid = fs.FilesetID("ns", 0, entry["blockStart"], entry["volume"])
    dst = str(tmp_path / "dst")
    _migrate_fileset(src.base, dst, fid)
    # flip one payload byte: commit must refuse and start the retry clean
    path = fs._path(dst, fid, "data")
    with open(path, "r+b") as f:
        b = f.read(1)
        f.seek(0)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(ValueError):
        fs.commit_imported_fileset(dst, fid)
    assert not fs.fileset_complete(dst, fid)
    assert fs.migration_file_size(dst, fid, "data") == 0  # deleted, not kept
    src.close()


# ---------- warm admission + stream dedupe ----------


def test_admit_imported_fileset_warms_pool_and_reads_bit_exact(tmp_path):
    src = _mkdb(tmp_path / "src")
    sids = _ingest(src)
    src.flush("ns", T0 + 4 * HOUR)
    dst = _mkdb(tmp_path / "dst")
    dst.bootstrap()
    for entry in fs.migration_manifest(src.base, "ns", 0):
        fid = fs.FilesetID("ns", 0, entry["blockStart"], entry["volume"])
        _migrate_fileset(src.base, dst.base, fid)
        fs.commit_imported_fileset(dst.base, fid)
        assert dst.admit_imported_fileset("ns", 0, fid) > 0
    st = dst.resident_stats()
    assert st["entries"] > 0, "import must warm the resident pool"
    sh = dst.namespaces["ns"].shards[0]
    assert not sh.buffered_blocks(T0, T0 + 4 * HOUR)  # nothing re-buffered
    span = (T0 - HOUR, T0 + 4 * HOUR)
    moved = 0
    for sid in sids:
        want = src.read("ns", sid, *span)
        if src.namespaces["ns"].shard_for(sid).id != 0:
            continue
        moved += 1
        got = dst.read("ns", sid, *span)
        assert [(d.timestamp, d.value) for d in got] == [
            (d.timestamp, d.value) for d in want
        ]
    assert moved > 0
    # the imported series are queryable by tags: the reindex step ran
    from m3_tpu.index.query import TermQuery

    res = dst.query_ids("ns", TermQuery(b"__name__", b"g"), *span)
    assert len(res.docs) >= moved
    src.close()
    dst.close()


def test_stream_shard_excludes_migrated_blocks_but_keeps_buffered(tmp_path):
    db = _mkdb(tmp_path / "db", resident=False)
    sids = _ingest(db)
    db.flush("ns", T0 + 4 * HOUR)
    shard0 = {s for s in sids if db.namespaces["ns"].shard_for(s).id == 0}
    bs = (T0 // (2 * HOUR)) * (2 * HOUR)
    # a cold write lands a buffered overlay INSIDE the excluded block
    cold_sid = sorted(shard0)[0]
    db.write("ns", cold_sid, T0 + 5 * NANOS, 12345.0)
    full = {sid: dps for sid, _t, dps in db.stream_shard("ns", 0)}
    excl = {sid: dps for sid, _t, dps in db.stream_shard("ns", 0, exclude_blocks=[bs])}
    assert set(full) == shard0
    # sealed content of the excluded block is deduped away ...
    assert len(excl.get(cold_sid, [])) < len(full[cold_sid])
    # ... but the buffered overlay still streams: it is NOT in the fileset
    assert any(
        d.timestamp == T0 + 5 * NANOS and d.value == 12345.0
        for d in excl.get(cold_sid, [])
    )
    for sid in shard0 - {cold_sid}:
        assert sid not in excl or not excl[sid]
    db.close()


# ---------- O(1) buffered-block summary (plan eligibility) ----------


def test_buffered_summary_tracks_fill_flush_and_expiry(tmp_path):
    db = _mkdb(tmp_path / "db", resident=False)
    sh = db.namespaces["ns"].shards[0]
    assert not sh.buffered_blocks(T0, T0 + 24 * HOUR)
    sids = _ingest(db)
    assert sh.buffered_blocks(T0, T0 + HOUR)
    assert not sh.buffered_blocks(T0 + 4 * HOUR, T0 + 6 * HOUR)
    db.flush("ns", T0 + 4 * HOUR)  # warm+cold flush evicts every bucket
    assert not sh.buffered_blocks(T0, T0 + 24 * HOUR)
    assert sh._buffered_blocks == {}
    # a cold write re-fills exactly one block's summary entry
    cold_sid = next(s for s in sids if db.namespaces["ns"].shard_for(s).id == 0)
    db.write("ns", cold_sid, T0 + 7 * NANOS, 1.0)
    assert sh.buffered_blocks(T0, T0 + HOUR)
    assert len(sh._buffered_blocks) == 1
    db.flush("ns", T0 + 4 * HOUR)  # cold flush bumps the volume, evicts
    assert sh._buffered_blocks == {}
    # retention tick expiry decrements the summary too
    db.write("ns", cold_sid, T0 + 6 * HOUR, 2.0)
    assert sh.buffered_blocks(T0 + 6 * HOUR, T0 + 8 * HOUR)
    db.tick(T0 + 6 * HOUR + db.namespaces["ns"].opts.retention_nanos + 4 * HOUR)
    assert sh._buffered_blocks == {}
    db.close()


def test_plan_eligibility_flips_as_buffers_fill_and_flush(tmp_path):
    """The fused-plan gate (plan:buffer-overlay) must flip OFF when a live
    write overlays the span and back ON once the overlay seals — driven
    by the O(1) summary, not a walk of every series buffer."""
    import numpy as np

    from m3_tpu.index.device import IndexDeviceOptions
    from m3_tpu.query.engine import Engine
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query import stats as query_stats

    db = Database(
        str(tmp_path / "db"),
        num_shards=2,
        commitlog_enabled=False,
        resident_options=ResidentOptions(max_bytes=16 << 20),
        index_device_options=IndexDeviceOptions(max_bytes=64 << 20),
    )
    db.create_namespace("ns", NamespaceOptions())
    _ingest(db, n_series=4, n_points=40)
    db.flush("ns", T0 + 4 * HOUR)
    eng = Engine(M3Storage(db, "ns"))
    span = (T0 + 60 * NANOS, T0 + 290 * NANOS, 10 * NANOS)

    def run(explain=False):
        qs = query_stats.start('sum(rate(g[1m]))')
        assert qs is not None
        if explain:
            qs.record_routing = True
        try:
            res = eng.query_range('sum(rate(g[1m]))', *span)
        finally:
            query_stats.finish(qs, 0.0)
        return np.asarray(res.values), qs

    v0, qs0 = run()
    assert qs0.plan_fallbacks == 0  # sealed span: fused plan eligible
    db.write("ns", b"overlay-sid", T0 + 120 * NANOS, 7.0)  # buffer fills
    v1, qs1 = run(explain=True)
    assert qs1.plan_fallbacks >= 1
    assert any(
        r["reason"] == "plan:buffer-overlay"
        for r in qs1.routing
        if r["path"] == "staged"
    )
    db.flush("ns", T0 + 4 * HOUR)  # overlay seals: eligibility returns
    _v2, qs2 = run()
    assert qs2.plan_fallbacks == 0
    db.close()


# ---------- pool rebalance + source-side drop ----------


def _pool():
    return ResidentPool(
        ResidentOptions(
            max_bytes=1 << 14, page_words=16, side_bytes=1 << 20,
            side_page_chunks=4,
        )
    )


def _admit(pool, shard, n, nbytes=512, ns="ns"):
    from m3_tpu.codec.m3tsz import Encoder

    for i in range(n):
        enc = Encoder(T0)
        t = T0
        for j in range(nbytes // 10):
            t += NANOS
            enc.encode(t, float(i * 1000 + j))
        pool.admit_block(
            ns, shard, T0 + i * 2 * HOUR, 0,
            [(b"s%d-%d" % (shard, i), enc.stream(), 64)],
        )


def test_rebalance_sheds_cold_shard_toward_heat(tmp_path):
    pool = _pool()
    _admit(pool, 0, 6)
    _admit(pool, 1, 6)
    before = pool.stats()
    usage0 = pool.shard_usage()
    assert set(usage0) == {("ns", 0), ("ns", 1)}
    # all observed demand on shard 1: shard 0 is over its weighted share
    evicted = pool.rebalance({"1": {"hits": 1000.0, "misses": 0.0}})
    assert evicted > 0
    after = pool.stats()
    assert after["rebalance_evictions"] == before["rebalance_evictions"] + evicted
    usage = pool.shard_usage()
    assert usage.get(("ns", 0), 0) < usage0[("ns", 0)]
    assert usage.get(("ns", 1), 0) == usage0[("ns", 1)]  # hot shard untouched
    # idempotent at the fixpoint: a second pass with the same heat is ~quiet
    assert pool.rebalance({"1": {"hits": 1000.0, "misses": 0.0}}) == 0


def test_rebalance_single_shard_is_noop():
    pool = _pool()
    _admit(pool, 0, 4)
    assert pool.rebalance({"0": {"hits": 10.0}}) == 0


def test_drop_shard_frees_only_that_shard():
    pool = _pool()
    _admit(pool, 0, 3)
    _admit(pool, 1, 3)
    n = pool.drop_shard(None, 0)
    assert n == 3
    usage = pool.shard_usage()
    assert ("ns", 0) not in usage and ("ns", 1) in usage
    assert pool.drop_shard(None, 0) == 0  # idempotent


# ---------- ClusterDatabase handoff orchestration (fake peers) ----------


class _FakePeer:
    """In-process stand-in for net.client.RemoteNode over one source db."""

    def __init__(self, db, log, fail_fetch=False):
        self.db = db
        self.log = log
        self.fail_fetch = fail_fetch

    def resident_stats(self):
        return self.db.resident_stats()

    def migrate_manifest(self, ns, shard):
        return fs.migration_manifest(self.db.base, ns, shard)

    def migrate_fetch(self, ns, shard, block_start, volume, suffix, offset,
                      max_bytes, _timeout=None):
        if self.fail_fetch:
            raise ConnectionError("source died mid-stream")
        fid = fs.FilesetID(ns, shard, block_start, volume)
        data, eof = fs.read_fileset_chunk(
            self.db.base, fid, suffix, offset, max_bytes
        )
        self.log.setdefault("fetches", []).append((suffix, offset))
        return {"data": data, "eof": eof}

    def stream_shard(self, ns, shard, exclude_blocks=None):
        self.log.setdefault("streams", []).append(
            (shard, tuple(exclude_blocks or ()))
        )
        return self.db.stream_shard(ns, shard, exclude_blocks or ())

    def close(self):
        pass


def _wait_available(svc, node_id, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        p = svc.get()
        inst = p.instances.get(node_id)
        if inst and inst.shards and all(
            a.state == ShardState.AVAILABLE for a in inst.shards.values()
        ):
            return p
        time.sleep(0.05)
    raise AssertionError(f"{node_id} never reached AVAILABLE: {svc.get().to_dict()}")


def _handoff_fixture(tmp_path, fail_fetch=False):
    src = _mkdb(tmp_path / "src")
    sids = _ingest(src)
    src.flush("ns", T0 + 4 * HOUR)
    src.bootstrap()
    dst = _mkdb(tmp_path / "dst")
    dst.bootstrap()
    kv = KVStore()
    svc = PlacementService(kv)
    p = build_initial_placement(["src"], 2, 1)
    p.instances["src"].endpoint = "src"
    svc.set(p)
    log: dict = {}
    peers = {"src": _FakePeer(src, log, fail_fetch=fail_fetch)}
    cdb = ClusterDatabase(
        dst, "dst", svc,
        node_service=SimpleNamespace(assigned_shards=set()),
        peer_factory=lambda ep: peers[ep],
        retry_secs=0.2,
        migration_chunk_bytes=113,  # force many resumable chunks
    )
    return src, dst, svc, cdb, log, sids


def test_cluster_handoff_migrates_warm_then_cuts_over(tmp_path):
    src, dst, svc, cdb, log, sids = _handoff_fixture(tmp_path)
    base_filesets = _counter_total("migration_filesets_total")
    base_failures = _counter_total("migration_stream_failures_total")
    cdb.start()
    try:
        p = svc.get()
        p = add_instance(p, "dst")
        p.instances["dst"].endpoint = "dst"
        svc.set(p)
        final = _wait_available(svc, "dst")
        moved = sorted(final.instances["dst"].shards)
        assert moved, "add_instance must hand shards to the new node"
        # sealed filesets arrived as raw bytes and were committed
        for shard in moved:
            for entry in fs.migration_manifest(src.base, "ns", shard):
                fid = fs.FilesetID(
                    "ns", shard, entry["blockStart"], entry["volume"]
                )
                assert fs.fileset_complete(dst.base, fid)
        assert _counter_total("migration_filesets_total") > base_filesets
        assert _counter_total("migration_stream_failures_total") == base_failures
        assert _counter_total("migration_streamed_bytes_total", peer="src") > 0
        # the decoded stream ran WITH the migrated blocks excluded ...
        assert log["streams"], "peers bootstrap must still stream buffers"
        assert all(excl for _s, excl in log["streams"])
        # ... so nothing sealed re-buffered: the new owner's first scan of
        # a migrated block is resident-eligible (warm before cutover)
        for shard in moved:
            sh = dst.namespaces["ns"].shards[shard]
            assert not sh.buffered_blocks(T0, T0 + 4 * HOUR)
        assert dst.resident_stats()["entries"] > 0
        # bit-identical reads on the new owner
        span = (T0 - HOUR, T0 + 4 * HOUR)
        checked = 0
        for sid in sids:
            if src.namespaces["ns"].shard_for(sid).id not in moved:
                continue
            want = [(d.timestamp, d.value) for d in src.read("ns", sid, *span)]
            got = [(d.timestamp, d.value) for d in dst.read("ns", sid, *span)]
            assert got == want
            checked += 1
        assert checked > 0
    finally:
        cdb.stop()
        src.close()
        dst.close()


def test_source_death_mid_stream_falls_back_counted(tmp_path):
    """Every migrate_fetch fails: the shard must still reach AVAILABLE via
    the decoded fileset-driven rebuild, and the fallback is counted."""
    src, dst, svc, cdb, log, sids = _handoff_fixture(tmp_path, fail_fetch=True)
    base_failures = _counter_total("migration_stream_failures_total")
    cdb.start()
    try:
        p = svc.get()
        p = add_instance(p, "dst")
        p.instances["dst"].endpoint = "dst"
        svc.set(p)
        final = _wait_available(svc, "dst")
        moved = sorted(final.instances["dst"].shards)
        assert _counter_total("migration_stream_failures_total") > base_failures
        # nothing was committed, so nothing is excluded: full decoded rebuild
        assert log["streams"] and all(excl == () for _s, excl in log["streams"])
        span = (T0 - HOUR, T0 + 4 * HOUR)
        checked = 0
        for sid in sids:
            if src.namespaces["ns"].shard_for(sid).id not in moved:
                continue
            want = [(d.timestamp, d.value) for d in src.read("ns", sid, *span)]
            got = [(d.timestamp, d.value) for d in dst.read("ns", sid, *span)]
            assert got == want
            checked += 1
        assert checked > 0
        # a partially-admitted block is never visible: either the import
        # committed (excluded) or left no trace (checkpoint-last)
        for shard in moved:
            assert fs.migration_manifest(dst.base, "ns", shard) == [] or all(
                fs.fileset_complete(
                    dst.base,
                    fs.FilesetID("ns", shard, e["blockStart"], e["volume"]),
                )
                for e in fs.migration_manifest(dst.base, "ns", shard)
            )
    finally:
        cdb.stop()
        src.close()
        dst.close()


def test_source_side_drops_residency_on_shards_lost(tmp_path):
    """The donor's ClusterDatabase must free the handed-off shard's
    residency once the placement stops assigning it."""
    db = _mkdb(tmp_path / "db")
    _ingest(db)
    db.flush("ns", T0 + 4 * HOUR)
    assert db.resident_stats()["entries"] > 0
    kv = KVStore()
    svc = PlacementService(kv)
    p = build_initial_placement(["src"], 2, 1)
    p.instances["src"].endpoint = "src"
    svc.set(p)
    cdb = ClusterDatabase(
        db, "src", svc, node_service=SimpleNamespace(assigned_shards=set())
    )
    cdb.start()
    try:
        shards_with_entries = {
            s for (_ns, s) in db.resident_pool.shard_usage()
        }
        lost = sorted(shards_with_entries)[0]
        p = svc.get()
        del p.instances["src"].shards[lost]
        svc.set(p)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if all(
                s != lost for (_ns, s) in db.resident_pool.shard_usage()
            ):
                break
            time.sleep(0.05)
        assert all(s != lost for (_ns, s) in db.resident_pool.shard_usage())
    finally:
        cdb.stop()
        db.close()
