"""HBM-resident compressed series store (m3_tpu/resident/).

Covers the paged pool (allocator, LRU/budget eviction, page-table
safety), seal-time admission, invalidation coherence with the
decoded-block cache, the decode-from-HBM scan's bit-exactness vs the
streamed path, query routing (resident hit vs streamed fallback), and
the zero-transfer contract (warm resident scans move no block bytes
host->device).
"""

from __future__ import annotations

import numpy as np
import pytest

from m3_tpu.cache.block_cache import BlockKey
from m3_tpu.codec.m3tsz import Encoder, decode
from m3_tpu.resident import ResidentOptions, ResidentPool, ResidentPoolError
from m3_tpu.resident.scan import (
    resident_fetch_arrays,
    resident_scan_totals,
    streamed_scan_totals,
)

NANOS = 1_000_000_000
T0 = 1_600_000_000 * NANOS


def _stream(values, t0=T0, step=NANOS):
    enc = Encoder(t0)
    t = t0
    for v in values:
        t += step
        enc.encode(t, float(v))
    return enc.stream()


def _random_series(rng, n_series, max_points=50):
    """Property-style mixed workload: int-ish gauges, true floats, big
    magnitudes, negatives, irregular steps, varied lengths."""
    streams, bounds, expect = [], [], []
    for i in range(n_series):
        n = int(rng.integers(1, max_points))
        kind = i % 4
        if kind == 0:
            vals = rng.integers(-1000, 1000, n).astype(np.float64)
        elif kind == 1:
            vals = rng.standard_normal(n)
        elif kind == 2:
            vals = (rng.standard_normal(n) * 1e9).round(2)
        else:
            vals = np.round(rng.standard_normal(n), 3) * 10.0 ** rng.integers(-2, 3)
        enc = Encoder(T0)
        t = T0
        for v in vals:
            t += int(rng.integers(1, 60)) * NANOS
            enc.encode(t, float(v))
        streams.append(enc.stream())
        bounds.append(-(-n // 32) * 32)  # the n_chunks * chunk_k shape both
        expect.append(vals)  # scan paths derive from fileset indexes
    return streams, bounds, expect


def _pool(max_bytes=1 << 20, page_words=16, **kw):
    # tiny data budgets drive the eviction/accounting tests; give the
    # side planes their own ample budget (with small side pages) so the
    # DATA pages stay the binding constraint, as before PR 11
    kw.setdefault("side_bytes", 1 << 20)
    kw.setdefault("side_page_chunks", 4)
    return ResidentPool(ResidentOptions(max_bytes=max_bytes, page_words=page_words, **kw))


# ---------- pool mechanics ----------


def test_admission_page_accounting_and_zero_page():
    pool = _pool()
    streams = [_stream(range(10)), _stream(range(200)), b""]
    res = pool.admit_block(
        "ns", 0, T0, 0, [(b"a", streams[0], 32), (b"b", streams[1], 224), (b"c", b"", 0)]
    )
    assert res.admitted == 2 and res.complete  # empty stream: not a lane
    st = pool.stats()
    assert st["entries"] == 2
    assert st["bytes"] == len(streams[0]) + len(streams[1])
    # page 0 is reserved: never handed to an entry
    for key in (BlockKey("ns", 0, b"a", T0, 0), BlockKey("ns", 0, b"b", T0, 0)):
        entry = pool.get(key)
        assert entry is not None and 0 not in entry.pages
    # multi-page lane: pages cover the stream
    b_entry = pool.get(BlockKey("ns", 0, b"b", T0, 0))
    assert len(b_entry.pages) == -(-len(streams[1]) // (16 * 4))
    assert pool.is_complete("ns", 0, T0, 0)


def test_lru_eviction_under_byte_budget_and_free_list_reuse():
    # room for ~4 one-page lanes (5 pages incl. reserved zero page)
    pool = _pool(max_bytes=5 * 16 * 4)
    for i in range(4):
        assert pool.admit_block("ns", 0, T0 + i, 0, [(b"s", _stream([i]), 32)]).admitted
    assert len(pool) == 4
    # a fifth lane evicts the LRU entry and reuses its page
    assert pool.admit_block("ns", 0, T0 + 9, 0, [(b"s", _stream([9]), 32)]).admitted
    assert len(pool) == 4
    assert pool.evictions == 1
    assert pool.get(BlockKey("ns", 0, b"s", T0 + 0, 0)) is None  # LRU gone
    assert pool.get(BlockKey("ns", 0, b"s", T0 + 9, 0)) is not None
    # eviction voids the evicted block's complete marker
    assert not pool.is_complete("ns", 0, T0 + 0, 0)
    assert pool.is_complete("ns", 0, T0 + 9, 0)


def test_batch_larger_than_pool_never_cannibalizes_itself():
    """A pool smaller than one admission batch must not evict its own
    batch's early lanes (pending pages stay off the free list): later
    lanes are budget-rejected instead, the scatter's page indices stay
    unique, and every admitted entry decodes to its OWN bytes."""
    pool = _pool(max_bytes=4 * 16 * 4)  # 3 usable pages for 8 lanes
    values = [[float(i), float(i * 10)] for i in range(8)]
    res = pool.admit_block(
        "ns", 0, T0, 0,
        [(b"c%d" % i, _stream(v), 32) for i, v in enumerate(values)],
    )
    assert not res.complete
    assert res.rejected_budget > 0
    assert 0 < len(pool) <= 3
    seen = 0
    for i in range(8):
        key = BlockKey("ns", 0, b"c%d" % i, T0, 0)
        if key not in pool:
            continue
        seen += 1
        (ts_vs,), err = resident_fetch_arrays(pool, [key])
        assert not err.any()
        assert np.array_equal(ts_vs[1], values[i])  # its OWN bytes
    assert seen == len(pool)


def test_page_span_limit_rejects_oversized_lane():
    pool = _pool(max_bytes=1 << 20, page_words=16, max_lane_pages=2)
    big = _stream(np.random.default_rng(0).standard_normal(500))
    assert len(big) > 2 * 16 * 4
    res = pool.admit_block("ns", 0, T0, 0, [(b"big", big, 512), (b"ok", _stream([1]), 32)])
    assert res.rejected_span == 1 and res.admitted == 1
    assert not res.complete and not pool.is_complete("ns", 0, T0, 0)
    assert pool.get(BlockKey("ns", 0, b"big", T0, 0)) is None


def test_corrupt_page_table_raises_not_out_of_bounds():
    pool = _pool()
    pool.admit_block("ns", 0, T0, 0, [(b"s", _stream([1, 2, 3]), 32)])
    key = BlockKey("ns", 0, b"s", T0, 0)
    entry = pool._od[key]
    # out-of-extent page index must raise, never clamp/wrap into a gather
    pool._od[key] = entry._replace(pages=(10**6,))
    with pytest.raises(ResidentPoolError):
        pool.plan_chunked([key])
    # num_bits exceeding the page span is equally corrupt
    pool._od[key] = entry._replace(num_bits=10**9)
    with pytest.raises(ResidentPoolError):
        pool.plan_chunked([key])


def test_plan_chunked_misses_return_none():
    pool = _pool()
    pool.admit_block("ns", 0, T0, 0, [(b"s", _stream([1]), 32)])
    assert pool.plan_chunked([BlockKey("ns", 0, b"other", T0, 0)]) is None


# ---------- decode-from-HBM vs streamed: bit-exactness ----------


def test_scan_totals_bit_exact_vs_streamed_property():
    rng = np.random.default_rng(42)
    streams, bounds, _ = _random_series(rng, 24)
    pool = _pool(max_bytes=4 << 20)
    keys = []
    for i, (s, b) in enumerate(zip(streams, bounds)):
        sid = b"s%03d" % i
        pool.admit_block("ns", 0, T0, 0, [(sid, s, b)])
        keys.append(BlockKey("ns", 0, sid, T0, 0))
    got = resident_scan_totals(pool, keys)
    want = streamed_scan_totals(streams)
    # identical kernel + identical padded reduction shapes => bit equality
    assert np.array_equal(got.series_sum, want.series_sum)
    assert np.array_equal(got.series_count, want.series_count)
    assert np.array_equal(got.series_min, want.series_min, equal_nan=True)
    assert np.array_equal(got.series_max, want.series_max, equal_nan=True)
    assert np.array_equal(got.series_last, want.series_last, equal_nan=True)
    assert float(got.total_sum) == float(want.total_sum)
    assert int(got.total_count) == int(want.total_count)
    assert float(got.total_min) == float(want.total_min)
    assert float(got.total_max) == float(want.total_max)


def test_resident_fetch_arrays_bit_exact_vs_host_codec():
    rng = np.random.default_rng(7)
    streams, bounds, _ = _random_series(rng, 12)
    pool = _pool(max_bytes=4 << 20)
    keys = []
    for i, (s, b) in enumerate(zip(streams, bounds)):
        sid = b"f%03d" % i
        pool.admit_block("ns", 1, T0, 0, [(sid, s, b)])
        keys.append(BlockKey("ns", 1, sid, T0, 0))
    arrays, err = resident_fetch_arrays(pool, keys)
    assert not err.any()
    for i, (ts, vs) in enumerate(arrays):
        dps = decode(streams[i])
        assert np.array_equal(ts, np.asarray([d.timestamp for d in dps]))
        assert np.array_equal(vs, np.asarray([d.value for d in dps]))


def test_annotated_stream_flags_err_lane():
    enc = Encoder(T0)
    enc.encode(T0 + NANOS, 1.0, annotation=b"meta")
    enc.encode(T0 + 2 * NANOS, 2.0)
    pool = _pool()
    pool.admit_block("ns", 0, T0, 0, [(b"ann", enc.stream(), 32)])
    arrays, err = resident_fetch_arrays(pool, [BlockKey("ns", 0, b"ann", T0, 0)])
    # device decode bails on annotations; the router must host-fallback
    assert err[0]


def test_scan_totals_err_lanes_stitch_to_host_codec():
    """Annotated streams (device decoder bails) must not silently
    truncate totals: both scan paths surface series_err, and the host
    stitch rebuilds exact per-lane aggregates."""
    from m3_tpu.parallel.scan import stitch_host_errors

    enc = Encoder(T0)
    enc.encode(T0 + NANOS, 10.0, annotation=b"meta")
    enc.encode(T0 + 2 * NANOS, 20.0)
    streams = [_stream([1.0, 2.0, 3.0]), enc.stream()]
    bounds = [32, 32]
    pool = _pool()
    keys = []
    for i, (s, b) in enumerate(zip(streams, bounds)):
        sid = b"e%d" % i
        pool.admit_block("ns", 3, T0, 0, [(sid, s, b)])
        keys.append(BlockKey("ns", 3, sid, T0, 0))
    agg_r = resident_scan_totals(pool, keys)
    agg_s = streamed_scan_totals(streams)
    assert agg_r.series_err is not None and agg_r.series_err[1]
    assert agg_s.series_err is not None and agg_s.series_err[1]
    fixed_r = stitch_host_errors(agg_r, lambda i: streams[i])
    fixed_s = stitch_host_errors(agg_s, lambda i: streams[i])
    for fixed in (fixed_r, fixed_s):
        assert int(fixed.total_count) == 5  # 3 + the 2 annotated points
        assert float(fixed.series_sum[1]) == 30.0
        assert float(fixed.total_max) == 20.0
    assert float(fixed_r.total_sum) == float(fixed_s.total_sum)


def test_db_scan_totals_counts_annotated_fileset(resident_db):
    """End-to-end err-lane handling: a fileset holding an annotated
    stream scans to FULL counts on both paths (stitched through the host
    codec), not silently truncated ones."""
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher
    from m3_tpu.storage.fs import FilesetID, write_fileset

    db = resident_db
    sids = _ingest(db, n_points=10)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    ns = db.namespaces["ns"]
    bsz = ns.opts.block_size_nanos
    bs2 = (T0 // bsz) * bsz + bsz  # the next block
    enc = Encoder(bs2 + NANOS)
    enc.encode(bs2 + NANOS, 100.0, annotation=b"x")
    enc.encode(bs2 + 2 * NANOS, 200.0)
    shard = ns.shard_for(sids[0])
    fid = FilesetID("ns", shard.id, bs2, 0)
    with shard.lock:
        write_fileset(db.base, fid, {sids[0]: enc.stream()}, bsz)
        shard._flushed_blocks.add(bs2)
        shard._invalidate_filesets()
        payload = shard._collect_admission_locked([fid])
    shard._admit_payload(payload)
    st = M3Storage(db, "ns")
    m = [Matcher("__name__", "=", "g")]
    span = (T0, bs2 + bsz)
    tot_resident = st.scan_totals(m, *span)
    assert tot_resident["path"] == "resident"
    assert tot_resident["count"] == 8 * 10 + 2  # annotated points included
    assert tot_resident["max"] == 200.0
    db.resident_pool.clear()
    tot_streamed = st.scan_totals(m, *span)
    assert tot_streamed["path"] == "streamed"
    assert tot_streamed == {**tot_resident, "path": "streamed"}


def test_db_scan_totals_parity_with_nondefault_chunk_k(resident_db):
    """Bit-for-bit parity must survive a fileset persisted with a
    non-default chunkK: the streamed fallback prescans with the
    FILESET's chunk size (scan_segments reports it alongside each
    stream), so its chunk decomposition — and hence the f32
    partial-sum reduction order behind the totals — matches the
    resident path's side-plane decode exactly. Regression: the default
    CHUNK_K here would group the 40 points into 2 chunks instead of 3
    and drift the sum's low bits (verified to discriminate for this
    value pattern)."""
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher
    from m3_tpu.rules.rules import encode_tags_id
    from m3_tpu.storage.fs import FilesetID, write_fileset

    db = resident_db
    tags = ((b"__name__", b"g"), (b"s", b"000"))
    sid = encode_tags_id(tags)
    rng = np.random.default_rng(1)  # seed chosen: k=16 vs k=32 sums differ
    db.write_tagged("ns", tags, T0, 1.0)
    db.write_batch(
        "ns",
        [
            # magnitudes spanning 1e-3..1e7 with sign flips: any change
            # in the chunk grouping shows in the f32 sum's bit pattern
            (sid, T0 + (j + 1) * NANOS, (-1.0) ** j * float(10.0 ** rng.integers(-3, 8)))
            for j in range(39)
        ],
    )
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    ns = db.namespaces["ns"]
    bsz = ns.opts.block_size_nanos
    bs = (T0 // bsz) * bsz
    shard = ns.shard_for(sid)
    # supersede the sealed chunkK=32 volume with a bit-identical stream
    # persisted at chunkK=16 (the cold-flush volume-bump shape)
    fid0 = next(f for f in shard.filesets() if f.block_start == bs)
    stream = shard.reader(fid0).stream(sid)
    fid1 = FilesetID("ns", shard.id, bs, fid0.volume + 1)
    with shard.lock:
        write_fileset(db.base, fid1, {sid: stream}, bsz, 16)
        shard._invalidate_filesets()
        shard.invalidator.on_flush("ns", shard.id, [fid1])
        payload = shard._collect_admission_locked([fid1])
    shard._admit_payload(payload)
    st = M3Storage(db, "ns")
    m = [Matcher("__name__", "=", "g")]
    span = (bs, bs + bsz)
    tot_resident = st.scan_totals(m, *span)
    assert tot_resident["path"] == "resident"
    assert tot_resident["count"] == 40
    db.resident_pool.clear()
    tot_streamed = st.scan_totals(m, *span)
    assert tot_streamed["path"] == "streamed"
    assert tot_streamed == {**tot_resident, "path": "streamed"}


def test_sharded_resident_scan_matches_single_device():
    from m3_tpu.parallel.mesh import series_mesh

    rng = np.random.default_rng(3)
    streams, bounds, _ = _random_series(rng, 16)
    pool = _pool(max_bytes=4 << 20)
    keys = []
    for i, (s, b) in enumerate(zip(streams, bounds)):
        sid = b"m%03d" % i
        pool.admit_block("ns", 2, T0, 0, [(sid, s, b)])
        keys.append(BlockKey("ns", 2, sid, T0, 0))
    single = resident_scan_totals(pool, keys)
    sharded = resident_scan_totals(pool, keys, mesh=series_mesh())
    # per-series reductions agree to the ulp (different XLA tilings may
    # round row sums differently); integer counts agree exactly and the
    # psum'd totals agree within reduction-order tolerance
    assert np.array_equal(single.series_count, sharded.series_count)
    assert np.allclose(single.series_sum, sharded.series_sum, rtol=1e-6)
    assert int(single.total_count) == int(sharded.total_count)
    assert np.isclose(float(single.total_sum), float(sharded.total_sum), rtol=1e-5)
    assert float(single.total_min) == float(sharded.total_min)
    assert float(single.total_max) == float(sharded.total_max)


@pytest.mark.slow  # per-class sweep; the mixed-lane property above stays tier-1
def test_scan_totals_bit_exact_per_lane_class_property():
    """Seeded per-class property sweep: the resident-chunked scan must be
    bit-exact vs the streamed twin for EVERY lane class the classifier
    emits — int-fast, float-fast, mixed, and annotated/err — not just the
    mixed aggregate of the suite above (a specialization bug that flips
    one class's kernel body would hide in a mixed batch)."""
    from m3_tpu.codec.m3tsz import Encoder as Enc

    rng = np.random.default_rng(1234)

    def int_fast(n):  # steady int gauge: int-fast chunks
        return _stream(rng.integers(0, 100, n).astype(np.float64))

    def float_fast(n):  # true float series: float-fast chunks
        return _stream(rng.standard_normal(n))

    def annotated(n):
        enc = Enc(T0)
        t = T0
        for j in range(n):
            t += NANOS
            enc.encode(t, float(j), annotation=b"a" if j == 1 else None)
        return enc.stream()

    for name, mk in (("int", int_fast), ("float", float_fast), ("ann", annotated)):
        streams = [mk(int(rng.integers(2, 80))) for _ in range(9)]
        bounds = [-(-len(decode(s)) // 32) * 32 for s in streams]
        pool = _pool(max_bytes=4 << 20)
        keys = []
        for i, (s, b) in enumerate(zip(streams, bounds)):
            sid = b"%s%03d" % (name.encode(), i)
            pool.admit_block("ns", 0, T0, 0, [(sid, s, b)])
            keys.append(BlockKey("ns", 0, sid, T0, 0))
        got = resident_scan_totals(pool, keys)
        want = streamed_scan_totals(streams)
        assert np.array_equal(got.series_sum, want.series_sum), name
        assert np.array_equal(got.series_count, want.series_count), name
        assert np.array_equal(got.series_err, want.series_err), name
        assert float(got.total_sum) == float(want.total_sum), name


def test_eviction_mid_plan_scan_stays_consistent():
    """A key evicted between two scans must flip the SECOND plan to None
    (streamed fallback) while the first scan's lease-held snapshot stays
    valid — never a half-resident result."""
    pool = _pool(max_bytes=4 << 20)
    streams = [_stream([1.0, 2.0]), _stream([3.0, 4.0])]
    keys = []
    for i, s in enumerate(streams):
        sid = b"v%d" % i
        pool.admit_block("ns", 0, T0, 0, [(sid, s, 32)])
        keys.append(BlockKey("ns", 0, sid, T0, 0))
    with pool.read_lease():
        plan = pool.plan_chunked(keys)
        assert plan is not None
        # eviction lands while the scan's lease is active: the planned
        # arrays (host int vectors + device buffer refs) stay usable
        pool.invalidate_series_block("ns", 0, b"v1", T0)
        from m3_tpu.resident.gather import assemble_resident_packed

        (w4, l4, tf), s_pad = assemble_resident_packed(plan, 8)
        assert w4.shape[0] >= 1  # assembly from the snapshot still works
    assert pool.plan_chunked(keys) is None  # next scan must re-route
    got = resident_scan_totals(pool, keys)
    assert got is None


def test_side_planes_live_and_die_with_pages():
    """Side-plane lifecycle: admission allocates side pages, every drop
    path (evict, invalidate, clear) frees them with the data pages, and
    the allocator balances back to zero."""
    pool = _pool(max_bytes=1 << 20)
    st0 = pool.stats()
    assert st0["side_pages_used"] == 0 and st0["pages_used"] == 0
    for i in range(6):
        pool.admit_block("ns", 0, T0 + i, 0, [(b"s", _stream(range(40)), 64)])
    st = pool.stats()
    assert st["side_pages_used"] > 0 and st["pages_used"] > 0
    entry = pool.get(BlockKey("ns", 0, b"s", T0 + 0, 0))
    assert entry.side_pages and entry.n_chunks > 0
    # invalidation drops side planes with the entry
    pool.invalidate_series_block("ns", 0, b"s", T0 + 0)
    st2 = pool.stats()
    assert st2["side_pages_used"] < st["side_pages_used"]
    # clear() balances the allocator to zero — pages AND side pages
    pool.clear()
    st3 = pool.stats()
    assert st3["pages_used"] == 0
    assert st3["side_pages_used"] == 0
    assert st3["bytes"] == 0
    assert len(pool._free) == pool.options.num_pages - 1
    assert len(pool._free_side) == pool.options.num_side_pages - 1


def test_admission_donates_inplace_unless_scan_lease_active():
    """Scan/admit epoch fencing (carried from PR 3): an admission with no
    active scan lease donates the buffers into the scatter (true
    in-place); one racing an active lease falls back to the functional
    copy so the lease holder's snapshot stays bit-stable."""
    pool = _pool(max_bytes=1 << 20)
    pool.admit_block("ns", 0, T0, 0, [(b"a", _stream([1.0]), 32)])
    base = pool.stats()
    assert base["inplace_admissions"] >= 1
    assert base["copy_admissions"] == 0
    key_a = BlockKey("ns", 0, b"a", T0, 0)
    with pool.read_lease():
        plan = pool.plan_chunked([key_a])
        # admission racing the scan: must take the copy path
        pool.admit_block("ns", 0, T0 + 1, 0, [(b"b", _stream([2.0]), 32)])
        st = pool.stats()
        assert st["copy_admissions"] == 1
        assert st["inplace_admissions"] == base["inplace_admissions"]
        # the leased snapshot still decodes scan-consistent totals
        from m3_tpu.resident.gather import assemble_resident_packed

        assert plan is not None
        assemble_resident_packed(plan, 8)
    # lease released: admissions donate again
    pool.admit_block("ns", 0, T0 + 2, 0, [(b"c", _stream([3.0]), 32)])
    st2 = pool.stats()
    assert st2["inplace_admissions"] == base["inplace_admissions"] + 1
    # epoch bumps on every publish, fenced or copied
    assert st2["epoch"] >= 3
    # every path produced a readable entry
    for sid in (b"a", b"b", b"c"):
        ts_vs, err = resident_fetch_arrays(
            pool, [BlockKey("ns", 0, sid, T0 + (sid[0] - ord("a")), 0)]
        )
        assert not err.any()


def test_failed_upload_reclaims_pages_and_recovers(monkeypatch):
    """A scatter that throws must not strand the batch's pages off the
    free lists (functional path) nor leave entries pointing at a
    donated, possibly-deleted buffer (donate path resets the pool
    loudly). Either way the pool keeps working afterwards."""
    import m3_tpu.resident.pool as pool_mod

    real_scatter = pool_mod._scatter

    def boom(*a, **kw):
        raise RuntimeError("injected scatter failure")

    # functional-copy path (lease active): batch pages reclaimed,
    # published entries survive
    pool = _pool(max_bytes=1 << 20)
    pool.admit_block("ns", 0, T0, 0, [(b"a", _stream([1.0]), 32)])
    st0 = pool.stats()
    with pool.read_lease():
        monkeypatch.setattr(pool_mod, "_scatter", boom)
        with pytest.raises(RuntimeError):
            pool.admit_block("ns", 0, T0 + 1, 0, [(b"b", _stream([2.0]), 32)])
        monkeypatch.setattr(pool_mod, "_scatter", real_scatter)
    st = pool.stats()
    assert len(pool) == 1  # prior entry intact
    assert st["pages_used"] == st0["pages_used"]  # batch pages reclaimed
    assert st["side_pages_used"] == st0["side_pages_used"]
    assert BlockKey("ns", 0, b"b", T0 + 1, 0) not in pool
    pool.admit_block("ns", 0, T0 + 2, 0, [(b"c", _stream([3.0]), 32)])
    _ts_vs, err = resident_fetch_arrays(pool, [BlockKey("ns", 0, b"c", T0 + 2, 0)])
    assert not err.any()

    # donated path (no lease): the old buffer may already be deleted by
    # the failed scatter — the pool resets (allocator rebuilt, table
    # dropped) instead of bricking, and re-admission repopulates
    monkeypatch.setattr(pool_mod, "_scatter", boom)
    with pytest.raises(RuntimeError):
        pool.admit_block("ns", 0, T0 + 3, 0, [(b"d", _stream([4.0]), 32)])
    monkeypatch.setattr(pool_mod, "_scatter", real_scatter)
    st2 = pool.stats()
    assert len(pool) == 0
    assert st2["pages_used"] == 0 and st2["side_pages_used"] == 0
    assert len(pool._free) == pool.options.num_pages - 1
    assert len(pool._free_side) == pool.options.num_side_pages - 1
    res = pool.admit_block("ns", 0, T0 + 4, 0, [(b"e", _stream([5.0]), 32)])
    assert res.admitted == 1 and res.complete
    _ts_vs, err = resident_fetch_arrays(pool, [BlockKey("ns", 0, b"e", T0 + 4, 0)])
    assert not err.any()


def test_span_rejected_fileset_marked_never_completable():
    """Read-through re-admission consults never_completable: a fileset
    with a lane over max_lane_pages can never reach the complete marker,
    so re-admitting it would re-upload the whole fileset on every
    streamed query. A volume bump (new tuple) retries; invalidation
    clears the marker."""
    pool = _pool(max_bytes=1 << 20, page_words=16, max_lane_pages=2)
    big = _stream(np.random.default_rng(0).standard_normal(500))
    res = pool.admit_block(
        "ns", 0, T0, 0, [(b"big", big, 512), (b"ok", _stream([1]), 32)]
    )
    assert res.rejected_span == 1
    assert pool.never_completable("ns", 0, T0, 0)
    assert not pool.never_completable("ns", 0, T0, 1)  # other volume
    pool.invalidate_block("ns", 0, T0)
    assert not pool.never_completable("ns", 0, T0, 0)


def test_streamed_scan_bytes_counts_block_bytes():
    """scan_streamed_bytes_total promises BLOCK bytes (the transfer the
    resident path eliminates) — not the packed lane expansion, which
    duplicates window words across chunks and would silently rescale
    dashboards and heat comparisons several-fold."""
    from m3_tpu.resident.scan import STREAMED_BYTES, streamed_scan_totals

    streams, _bounds, _ = _random_series(np.random.default_rng(5), 6)
    before = STREAMED_BYTES.value
    streamed_scan_totals(streams)
    assert STREAMED_BYTES.value - before == sum(len(s) for s in streams)


def test_streamed_scan_totals_over_fileset_segments(tmp_path):
    """Disk -> FilesetReader segments -> the served streamed scan: count
    and sum equal the arithmetic totals, at the chunk size the fileset
    was written with (the scan's default)."""
    from m3_tpu.codec.m3tsz import encode_series
    from m3_tpu.storage.fs import CHUNK_K, FilesetID, FilesetReader, write_fileset

    series = {
        f"s{i}".encode(): encode_series(
            [T0 + j * NANOS for j in range(40)],
            [float(i + j) for j in range(40)],
        )
        for i in range(20)
    }
    fid = FilesetID("ns", 0, T0, 0)
    write_fileset(str(tmp_path), fid, series, 2 * 3600 * NANOS, CHUNK_K)
    reader = FilesetReader(str(tmp_path), fid)

    aggs = streamed_scan_totals([reader.stream(sid) for sid in reader.series_ids])
    assert int(aggs.total_count) == 20 * 40
    assert np.asarray(aggs.series_count).tolist() == [40] * 20
    want = sum(float(i + j) for i in range(20) for j in range(40))
    np.testing.assert_allclose(float(aggs.total_sum), want, rtol=1e-6)


def test_explain_never_claims_resident_when_chunked_plan_fails(resident_db, monkeypatch):
    """EXPLAIN routing must describe the path that actually served the
    query: if the chunked plan fails AFTER the resident plan was built
    (raced eviction / side-plane mismatch), the streamed fallback runs
    and no 'resident-chunked' record may survive."""
    import m3_tpu.resident.scan as rscan
    from m3_tpu.query import stats as query_stats
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher

    db = resident_db
    _ingest(db, seed=9)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    st = M3Storage(db, "ns")
    m = [Matcher("__name__", "=", "g")]
    monkeypatch.setattr(rscan, "resident_scan_totals", lambda *a, **kw: None)
    qs = query_stats.start("explain-fallback-test")
    qs.record_routing = True
    tot = st.scan_totals(m, T0, T0 + 3600 * NANOS)
    routing = [dict(r) for r in qs.routing]
    query_stats.finish(qs, 0.0)
    assert tot["path"] == "streamed"
    assert all(r["path"] != "resident" for r in routing)
    assert any("resident-plan-failed" in r["reason"] for r in routing)


# ---------- storage integration: admit on seal, invalidation ----------


@pytest.fixture
def resident_db(tmp_path):
    from m3_tpu.storage.database import Database, NamespaceOptions

    db = Database(
        str(tmp_path / "db"),
        num_shards=4,
        commitlog_enabled=False,
        resident_options=ResidentOptions(max_bytes=8 << 20),
    )
    db.create_namespace("ns", NamespaceOptions())
    yield db
    db.close()


def _ingest(db, n_series=8, n_points=40, seed=0, name=b"g"):
    from m3_tpu.rules.rules import encode_tags_id

    rng = np.random.default_rng(seed)
    step = 10 * NANOS
    sids = []
    for i in range(n_series):
        tags = ((b"__name__", name), (b"s", b"%03d" % i))
        sid = encode_tags_id(tags)
        db.write_tagged("ns", tags, T0, float(i))
        db.write_batch(
            "ns",
            [
                (sid, T0 + (j + 1) * step, float(rng.standard_normal()))
                for j in range(n_points - 1)
            ],
        )
        sids.append(sid)
    return sids


def test_database_admits_on_seal(resident_db):
    db = resident_db
    sids = _ingest(db)
    assert db.resident_pool.stats()["admissions"] == 0  # nothing sealed yet
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    st = db.resident_pool.stats()
    assert st["admissions"] == len(sids)
    assert st["entries"] == len(sids)
    assert st["complete_blocks"] >= 1
    # resident bytes equal the persisted streams exactly
    for sid in sids:
        shard = db.namespaces["ns"].shard_for(sid)
        keys, buffered = shard.scan_block_keys(sid, T0, T0 + 3600 * NANOS)
        assert not buffered and len(keys) == 1
        entry = db.resident_pool.get(keys[0])
        fid = next(f for f in shard.filesets() if f.block_start == keys[0].block_start)
        assert entry.num_bits == len(shard.reader(fid).stream(sid)) * 8


def test_write_after_seal_invalidates_and_cold_flush_readmits(resident_db):
    db = resident_db
    sids = _ingest(db)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    pool = db.resident_pool
    shard = db.namespaces["ns"].shard_for(sids[0])
    key0 = shard.scan_block_keys(sids[0], T0, T0 + 3600 * NANOS)[0][0]
    assert key0 in pool
    # cold write into the sealed block: entry dropped, block incomplete
    db.write("ns", sids[0], T0 + 5 * NANOS, 123.0)
    assert key0 not in pool
    assert not pool.is_complete("ns", shard.id, key0.block_start, key0.volume)
    # cold flush merges into a NEW volume: it admits, the old volume stays gone
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    keys, buffered = shard.scan_block_keys(sids[0], T0, T0 + 3600 * NANOS)
    assert not buffered
    assert keys[0].volume == key0.volume + 1
    assert keys[0] in pool
    assert key0 not in pool


def test_cache_and_pool_invalidate_coherently(resident_db):
    db = resident_db
    sids = _ingest(db)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    # populate the decoded-block cache alongside the resident pool
    db.read_arrays("ns", sids[1], T0, T0 + 3600 * NANOS)
    assert len(db.block_cache) > 0 and len(db.resident_pool) > 0
    shard = db.namespaces["ns"].shard_for(sids[1])
    key = shard.scan_block_keys(sids[1], T0, T0 + 3600 * NANOS)[0][0]
    assert key in db.resident_pool and key in db.block_cache
    # ONE write drops the block from BOTH resident tiers
    db.write("ns", sids[1], T0 + 7 * NANOS, 9.0)
    assert key not in db.resident_pool
    assert key not in db.block_cache


def test_write_batch_invalidates_resident_entry(resident_db):
    """Batched ingest into a sealed block must drop the resident entry
    even when the decoded-block cache is empty (the batched path's
    collect-keys fast path must consider BOTH tiers)."""
    db = resident_db
    sids = _ingest(db)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    assert db.block_cache is None or len(db.block_cache) == 0
    shard = db.namespaces["ns"].shard_for(sids[3])
    key = shard.scan_block_keys(sids[3], T0, T0 + 3600 * NANOS)[0][0]
    assert key in db.resident_pool
    db.write_batch("ns", [(sids[3], T0 + 13 * NANOS, 4.5)])
    assert key not in db.resident_pool


def test_repair_hook_drops_resident_entry(resident_db):
    db = resident_db
    sids = _ingest(db)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    shard = db.namespaces["ns"].shard_for(sids[2])
    key = shard.scan_block_keys(sids[2], T0, T0 + 3600 * NANOS)[0][0]
    assert key in db.resident_pool
    db.cache_invalidator.on_repair("ns", shard.id, sids[2], key.block_start)
    assert key not in db.resident_pool


def test_tick_retention_expiry_drops_resident_entries(resident_db):
    db = resident_db
    _ingest(db)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    assert len(db.resident_pool) > 0
    retention = db.namespaces["ns"].opts.retention_nanos
    db.tick(T0 + retention + 8 * 3600 * NANOS)
    assert len(db.resident_pool) == 0


# ---------- query routing ----------


def test_fetch_routes_resident_and_matches_plain_db(tmp_path):
    from m3_tpu.query import stats as query_stats
    from m3_tpu.query.engine import Engine
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher
    from m3_tpu.storage.database import Database, NamespaceOptions

    dbs = []
    for name, ropts in (
        ("resident", ResidentOptions(max_bytes=8 << 20)),
        ("plain", None),
    ):
        db = Database(
            str(tmp_path / name),
            num_shards=4,
            commitlog_enabled=False,
            resident_options=ropts,
        )
        db.create_namespace("ns", NamespaceOptions())
        _ingest(db, seed=5)
        db.flush("ns", T0 + 4 * 3600 * NANOS)
        dbs.append(db)
    db_r, db_p = dbs
    m = [Matcher("__name__", "=", "g")]
    span = (T0, T0 + 3600 * NANOS)
    st_r, st_p = M3Storage(db_r, "ns"), M3Storage(db_p, "ns")

    qs = query_stats.start("routing-test")
    got = st_r.fetch(m, *span)
    assert qs.resident_hits == 1 and qs.resident_misses == 0
    query_stats.finish(qs, 0.0)
    want = st_p.fetch(m, *span)
    assert len(got) == len(want) == 8
    by_tags = {t: (ts, vs) for t, ts, vs in want}
    for tags, ts, vs in got:
        wts, wvs = by_tags[tags]
        assert np.array_equal(ts, wts)
        assert np.array_equal(vs, wvs)  # f64 bit-exact reconstruction

    # warm resident fetch + scan: zero block bytes host->device
    before = db_r.resident_stats()
    st_r.fetch(m, *span)
    tot = st_r.scan_totals(m, *span)
    after = db_r.resident_stats()
    assert tot["path"] == "resident"
    assert after["upload_bytes"] == before["upload_bytes"]
    assert after["streamed_bytes"] == before["streamed_bytes"]

    # scan totals: bit-exact across the two databases' paths
    tot_p = st_p.scan_totals(m, *span)
    assert tot_p["path"] == "streamed"
    assert tot == {**tot_p, "path": "resident"}

    # engine surface + PromQL equality over both storages
    eng_r, eng_p = Engine(st_r), Engine(st_p)
    assert eng_r.scan_totals("g", *span)["path"] == "resident"
    with pytest.raises(ValueError):
        eng_r.scan_totals("sum(g)", *span)
    q_r = eng_r.query_range("sum(g)", T0, T0 + 390 * NANOS, 10 * NANOS)
    q_p = eng_p.query_range("sum(g)", T0, T0 + 390 * NANOS, 10 * NANOS)
    assert np.array_equal(np.asarray(q_r.values), np.asarray(q_p.values), equal_nan=True)
    for db in dbs:
        db.close()


def test_bootstrap_readmits_sealed_blocks_after_restart(tmp_path):
    """Blocks sealed by a previous process must re-admit at bootstrap —
    otherwise a restarted node streams historical data forever."""
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher
    from m3_tpu.storage.database import Database, NamespaceOptions

    ropts = ResidentOptions(max_bytes=8 << 20)
    db = Database(
        str(tmp_path / "node"), num_shards=4, commitlog_enabled=False,
        resident_options=ropts,
    )
    db.create_namespace("ns", NamespaceOptions())
    _ingest(db)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    db.close()

    db2 = Database(
        str(tmp_path / "node"), num_shards=4, commitlog_enabled=False,
        resident_options=ropts,
    )
    db2.create_namespace("ns", NamespaceOptions())
    assert len(db2.resident_pool) == 0
    db2.bootstrap(now_nanos=T0 + 5 * 3600 * NANOS)
    st = db2.resident_pool.stats()
    assert st["entries"] == 8 and st["complete_blocks"] >= 1
    tot = M3Storage(db2, "ns").scan_totals(
        [Matcher("__name__", "=", "g")], T0, T0 + 3600 * NANOS
    )
    assert tot["path"] == "resident"
    db2.close()


def test_pooled_fetch_keeps_storage_trace_span(resident_db):
    """The pooled fetch paths replace fetch_tagged_arrays, so they must
    emit the same storage.fetch_tagged span — stitched traces must not
    lose their storage node when residency is on (hit OR fallback)."""
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher
    from m3_tpu.utils.trace import TRACER

    db = resident_db
    sids = _ingest(db)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    st = M3Storage(db, "ns")
    m = [Matcher("__name__", "=", "g")]

    def spans_of(fn):
        with TRACER.span("test.root"):
            fn()
        return [s["name"] for s in TRACER.dump(limit=16)]

    # resident hit
    names = spans_of(lambda: st.fetch(m, T0, T0 + 3600 * NANOS))
    assert "storage.fetch_tagged" in names
    # streamed fallback (buffered overlay) still carries the span
    db.write("ns", sids[0], T0 + 3 * NANOS, 1.0)
    names = spans_of(lambda: st.fetch(m, T0, T0 + 3600 * NANOS))
    assert "storage.fetch_tagged" in names


def test_buffered_overlay_forces_streamed_fallback(resident_db):
    from m3_tpu.query import stats as query_stats
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher

    db = resident_db
    sids = _ingest(db)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    st = M3Storage(db, "ns")
    m = [Matcher("__name__", "=", "g")]
    span = (T0, T0 + 3600 * NANOS)
    assert st.scan_totals(m, *span)["path"] == "resident"
    # live buffer data overlapping the range: resident-only results would
    # miss it — the router must stream (which overlays the buffer)
    db.write("ns", sids[0], T0 + 11 * NANOS, 5.5)
    qs = query_stats.start("fallback-test")
    tot = st.scan_totals(m, *span)
    assert qs.resident_misses == 1
    query_stats.finish(qs, 0.0)
    assert tot["path"] == "streamed"
    # the streamed totals see the buffered point
    fetched = st.fetch(m, *span)
    assert tot["count"] == sum(len(ts) for _, ts, _ in fetched)


def test_eviction_forces_streamed_fallback_with_correct_results(tmp_path):
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher
    from m3_tpu.storage.database import Database, NamespaceOptions

    # pool big enough to admit, then shrink by clearing: router must not
    # claim residency for evicted blocks
    db = Database(
        str(tmp_path / "evict"),
        num_shards=4,
        commitlog_enabled=False,
        resident_options=ResidentOptions(max_bytes=8 << 20),
    )
    db.create_namespace("ns", NamespaceOptions())
    _ingest(db, seed=9)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    st = M3Storage(db, "ns")
    m = [Matcher("__name__", "=", "g")]
    span = (T0, T0 + 3600 * NANOS)
    resident = st.scan_totals(m, *span)
    db.resident_pool.clear()
    streamed = st.scan_totals(m, *span)
    assert resident["path"] == "resident" and streamed["path"] == "streamed"
    assert streamed == {**resident, "path": "streamed"}
    db.close()


def test_streamed_fallback_readmits_sealed_blocks(resident_db):
    """Read-through re-admission (carried from PR 3): a streamed-fallback
    hit on sealed, complete blocks pulls them back into the pool —
    counted in resident_readmissions_total — so the NEXT scan of the hot
    set is resident again; buffered series stay out (their blocks would
    stream regardless)."""
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher

    db = resident_db
    sids = _ingest(db)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    pool = db.resident_pool
    st = M3Storage(db, "ns")
    m = [Matcher("__name__", "=", "g")]
    span = (T0, T0 + 3600 * NANOS)
    assert st.scan_totals(m, *span)["path"] == "resident"
    # eviction churn: the whole hot set falls out of the pool
    pool.clear()
    assert pool.stats()["readmissions"] == 0
    tot = st.scan_totals(m, *span)  # cold: streams, then re-admits
    assert tot["path"] == "streamed"
    assert pool.stats()["readmissions"] == len(sids)
    # the hot set is resident again: next scan decodes from HBM, and
    # repeated scans do not re-admit (already resident = no churn)
    tot2 = st.scan_totals(m, *span)
    assert tot2["path"] == "resident"
    assert tot2 == {**tot, "path": "resident"}
    assert pool.stats()["readmissions"] == len(sids)
    # fetch-path fallback re-admits too
    pool.clear()
    st.fetch(m, *span)
    assert pool.stats()["readmissions"] == 2 * len(sids)
    # a buffered series does NOT trigger re-admission (its blocks would
    # stream again regardless — the buffer-overlay rule); query ONLY the
    # buffered series so no shard-mate doc re-admits its fileset
    pool.clear()
    db.write("ns", sids[0], T0 + 13 * NANOS, 7.0)
    only = [Matcher("__name__", "=", "g"), Matcher("s", "=", "000")]
    assert st.scan_totals(only, *span)["path"] == "streamed"
    assert pool.stats()["readmissions"] == 2 * len(sids)
    db.close()


def test_readmission_skips_already_resident_lanes():
    """Re-admission is fileset-granular (the complete marker needs the
    whole group), but one evicted lane must NOT re-stage and re-upload
    its still-resident shard-mates' bytes — those lanes are skipped in
    place (LRU-touched, counted toward completeness)."""
    pool = _pool(max_bytes=4 << 20)
    items = [(b"r%d" % i, _stream([float(i), 2.0, 3.0]), 32) for i in range(3)]
    res = pool.admit_block("ns", 0, T0, 0, items)
    assert res.admitted == 3 and res.complete
    up0 = pool.stats()["upload_bytes"]
    # all three resident: a re-admission uploads NOTHING and still
    # reports the group complete
    res2 = pool.admit_block("ns", 0, T0, 0, items, readmission=True)
    assert res2.admitted == 0 and res2.complete
    assert pool.stats()["upload_bytes"] == up0
    assert pool.stats()["readmissions"] == 0
    # one lane evicted: only ITS bytes go back up
    pool.invalidate_series_block("ns", 0, b"r1", T0)
    res3 = pool.admit_block("ns", 0, T0, 0, items, readmission=True)
    assert res3.admitted == 1 and res3.complete
    delta = pool.stats()["upload_bytes"] - up0
    assert 0 < delta < up0  # strictly less than re-uploading the fileset
    assert pool.stats()["readmissions"] == 1
    assert pool.is_complete("ns", 0, T0, 0)


def test_budget_deferred_readmission_cooldown():
    """A budget-rejected re-admission marks the fileset deferred until
    pages free up: _maybe_readmit callers skip the whole-fileset disk
    re-read while a retry is a guaranteed rejection, and the marker
    self-heals on eviction (free list grows) or full re-admission."""
    # random floats defeat the XOR compressor, so the lane spans several
    # 64-byte pages; budget = page 0 (reserved) + one lane + ONE spare
    # page, so a second identical lane can never fit without eviction
    big = _stream(np.random.default_rng(0).standard_normal(40))
    n_pages = -(-len(big) // 64)
    assert n_pages >= 2
    pool = _pool(max_bytes=(n_pages + 2) * 64, page_words=16)
    ok = pool.admit_block("ns", 0, T0, 0, [(b"a", big, 64)])
    assert ok.admitted == 1
    # free list now too small for another 2-page lane; a re-admission
    # rejects for budget and records the watermark
    rej = pool.admit_block("ns", 0, T0 + 1, 0, [(b"b", big, 64)], readmission=True)
    assert rej.rejected_budget == 1
    assert pool.budget_deferred("ns", 0, T0 + 1, 0)
    assert not pool.budget_deferred("ns", 0, T0, 0)  # only the rejected one
    # eviction frees pages past the watermark: the cooldown lifts
    pool.invalidate_block("ns", 0, T0)
    assert not pool.budget_deferred("ns", 0, T0 + 1, 0)
    # retry now succeeds and drops the marker for good
    ok2 = pool.admit_block("ns", 0, T0 + 1, 0, [(b"b", big, 64)], readmission=True)
    assert ok2.admitted == 1
    assert not pool.budget_deferred("ns", 0, T0 + 1, 0)


def test_resident_options_rejects_sub_page_budgets():
    """A small positive budget in EITHER plane would pass a >=0 check
    but leave the pool silently disabled (enabled needs >1 page per
    plane, page 0 being reserved) — validate() must reject it loudly;
    0 stays the explicit disable/derive convention."""
    from m3_tpu.utils.config import ConfigError

    ResidentOptions(max_bytes=1 << 20).validate()  # side 0 = derived: fine
    with pytest.raises(ConfigError):
        ResidentOptions(max_bytes=100).validate()
    with pytest.raises(ConfigError):
        ResidentOptions(max_bytes=1 << 20, side_bytes=100).validate()


def test_readmission_failure_never_fails_the_query(resident_db, monkeypatch):
    """Read-through re-admission is opportunistic: by the time it runs,
    the streamed result is already computed. An admission failure (device
    OOM near the pool budget is the realistic case, and on the
    donated-scatter path it also resets the pool) must be counted — not
    raised into a query whose answer is in hand."""
    from m3_tpu.query import m3_storage as m3s
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher
    from m3_tpu.storage.database import Shard

    db = resident_db
    sids = _ingest(db)
    db.flush("ns", T0 + 4 * 3600 * NANOS)
    pool = db.resident_pool
    st = M3Storage(db, "ns")
    m = [Matcher("__name__", "=", "g")]
    span = (T0, T0 + 3600 * NANOS)
    pool.clear()

    def boom(self, fid):
        raise RuntimeError("RESOURCE_EXHAUSTED: device OOM")

    monkeypatch.setattr(Shard, "readmit_fileset", boom)
    before = m3s._M_READMIT_FAILURES.value
    tot = st.scan_totals(m, *span)  # must serve, not raise
    assert tot["path"] == "streamed"
    assert tot["count"] == 8 * 40
    assert m3s._M_READMIT_FAILURES.value == before + 1
    assert pool.stats()["readmissions"] == 0
    # fetch-path fallback takes the same guard
    rows = st.fetch(m, *span)
    assert len(rows) == len(sids)
    assert m3s._M_READMIT_FAILURES.value == before + 2
