"""Device-resident inverted index (m3_tpu/index/device/).

The gating contract: for ANY query AST and ANY segment state (mutable,
sealed+admitted, persisted, evicted, rejected), the device executor
returns doc-id sequences BIT-IDENTICAL to the host executor, with
transparent host fallback whenever the device tier is absent. The
property suite here drives randomized corpora and randomized ASTs
through both executors (seeded random — the environment has no
hypothesis) across seal/persist/evict boundaries.
"""

import random

import numpy as np
import pytest

from m3_tpu.index.device import (
    DeviceIndexStore,
    IndexDeviceOptions,
    classify_regexp,
)
from m3_tpu.index.device import kernels
from m3_tpu.index.ns_index import NamespaceIndex
from m3_tpu.index.query import (
    AllQuery,
    FieldQuery,
    conj,
    disj,
    neg,
    regexp,
    term,
)
from m3_tpu.index.segment import Document, MutableSegment

NANOS = 1_000_000_000
HOUR = 3600 * NANOS
T0 = 1_600_000_000 * NANOS
SPAN = (T0 - HOUR, T0 + 4 * HOUR)


def make_store(max_bytes=64 << 20, **kw):
    return DeviceIndexStore(IndexDeviceOptions(max_bytes=max_bytes, **kw))


def make_index(store=None, **kw):
    return NamespaceIndex(HOUR, device_store=store, **kw)


def corpus_batch(n, seed=0, t=T0):
    rng = random.Random(seed)
    batch = []
    for i in range(n):
        tags = [
            (b"name", b"metric_%d" % (i % max(n // 40, 7))),
            (b"host", b"h%04d" % rng.randrange(max(n // 5, 10))),
            (b"dc", b"dc%d" % (i % 3)),
        ]
        if rng.random() < 0.5:
            tags.append((b"role", rng.choice(
                [b"db", b"db-replica", b"web", b"w\x00eird", b"", b"ab", b"abc"]
            )))
        batch.append((b"s%d" % i, tuple(tags), t))
    return batch


def ids(result):
    return [d.id for d in result.docs]


def assert_parity(ix, q, span=SPAN, limit=None):
    dev = ids(ix.query(q, *span, limit=limit))
    host = ids(ix.query(q, *span, limit=limit, force_host=True))
    assert dev == host, (q, len(dev), len(host))
    return dev


# ---------- kernel-level properties ----------


def test_key_ordering_matches_bytes_order():
    """(zero-padded big-endian words, length) must compare exactly like
    raw bytes — including embedded NULs and prefix pairs."""
    rng = random.Random(7)
    terms = [b"", b"a", b"ab", b"abc", b"ab\x00", b"ab\x00x", b"ab\x01", b"b"]
    for _ in range(200):
        n = rng.randrange(1, 9)
        terms.append(bytes(rng.randrange(0, 256) for _ in range(n)))
    terms = sorted(set(terms))
    k = kernels.key_width_words(max(len(t) for t in terms))
    keys, lens = kernels.build_term_keys(terms, k)
    for _ in range(500):
        i, j = rng.randrange(len(terms)), rng.randrange(len(terms))
        expect = terms[i] < terms[j]
        got = kernels.host_key_lt(keys[i], int(lens[i]), keys[j], int(lens[j]))
        assert got == expect, (terms[i], terms[j])


def test_host_lower_bound_matches_bisect():
    import bisect

    rng = random.Random(11)
    terms = sorted({bytes(rng.randrange(97, 123) for _ in range(rng.randrange(1, 6)))
                    for _ in range(300)})
    k = kernels.key_width_words(max(len(t) for t in terms))
    keys, lens = kernels.build_term_keys(terms, k)
    probes = list(terms) + [b"a", b"zzzz", b"m", b"", b"mm\x00"]
    for p in probes:
        pk, pl = kernels.build_term_keys([p], k)
        got = kernels.host_lower_bound(keys, lens, 0, len(terms), pk[0], int(pl[0]))
        assert got == bisect.bisect_left(terms, p), p


def test_bitmap_to_docids_roundtrip():
    rng = random.Random(3)
    for n_docs in (1, 31, 32, 33, 1000):
        docs = sorted(rng.sample(range(n_docs), k=max(n_docs // 3, 1)))
        words = np.zeros(-(-n_docs // 32), np.uint32)
        for d in docs:
            words[d // 32] |= np.uint32(1 << (d % 32))
        out = kernels.bitmap_to_docids(words)
        assert out.tolist() == docs
        assert out.dtype == np.int32


def test_all_docs_words_tail_masked():
    for n in (1, 31, 32, 33, 95, 96):
        w = kernels.all_docs_words(n)
        assert kernels.bitmap_to_docids(w).tolist() == list(range(n))


def test_classify_regexp():
    assert classify_regexp(b"metric_1") == ("literal", b"metric_1")
    assert classify_regexp(b"^metric_1$") == ("literal", b"metric_1")
    assert classify_regexp(b"metric_.*") == ("prefix", b"metric_")
    assert classify_regexp(b"a|b|c") == ("alternation", [b"a", b"b", b"c"])
    assert classify_regexp(b"(a|bc)") == ("alternation", [b"a", b"bc"])
    assert classify_regexp(b"metric_[0-9]")[0] == "general"
    assert classify_regexp(b"a|b*")[0] == "general"
    assert classify_regexp(b"(a|b)c")[0] == "general"
    assert classify_regexp(b"")[0] == "literal"


# ---------- executor parity ----------


BASE_QUERIES = [
    term(b"name", b"metric_3"),
    term(b"name", b"nope"),
    term(b"missing_field", b"x"),
    term(b"role", b""),
    term(b"role", b"w\x00eird"),
    regexp(b"name", b"metric_1[0-9]"),
    regexp(b"name", b"metric_1.*"),
    regexp(b"name", b"metric_1|metric_2"),
    regexp(b"host", b"h00.*"),
    regexp(b"role", b"db.*"),
    regexp(b"role", b"db"),
    regexp(b"name", b"met+ric_4"),
    FieldQuery(b"role"),
    FieldQuery(b"absent"),
    AllQuery(),
    neg(AllQuery()),
    conj(term(b"dc", b"dc1"), regexp(b"name", b"metric_.*")),
    conj(term(b"dc", b"dc0"), neg(term(b"host", b"h0001"))),
    conj(neg(term(b"dc", b"dc2"))),
    disj(term(b"dc", b"dc0"), term(b"dc", b"dc2"), term(b"name", b"metric_1")),
    disj(neg(FieldQuery(b"role")), regexp(b"host", b"h000.*")),
    conj(
        disj(term(b"dc", b"dc0"), term(b"dc", b"dc1")),
        neg(regexp(b"name", b"metric_[0-3]")),
        FieldQuery(b"host"),
    ),
]


def test_sealed_parity_fixed_queries():
    store = make_store()
    ix = make_index(store)
    ix.write_batch(corpus_batch(4000, seed=1))
    ix.seal_before(T0 + 2 * HOUR)
    assert store.stats()["admissions"] == 1
    for q in BASE_QUERIES:
        assert_parity(ix, q)
    st = store.stats()
    assert st["search_hits"] > 0 and st["errors"] == 0


def test_parity_across_seal_boundary():
    """Mixed mutable + device-sealed segments in one block union: the
    executor routes per segment and still dedupes across them."""
    store = make_store()
    ix = make_index(store)
    ix.write_batch(corpus_batch(1500, seed=2))
    ix.seal_before(T0 + 2 * HOUR)
    # same ids re-written (cross-segment dedupe) plus fresh ones, into
    # the SAME block: the mutable segment stays host-side
    ix.write_batch(corpus_batch(500, seed=3))
    ix.write_batch(
        [(b"x%d" % i, ((b"name", b"metric_3"), (b"dc", b"dc9")), T0)
         for i in range(50)]
    )
    for q in BASE_QUERIES + [term(b"dc", b"dc9")]:
        assert_parity(ix, q)


def test_random_ast_property_suite():
    """Randomized corpora x randomized ASTs, device vs host bit-identical."""
    for seed in range(5):
        rng = random.Random(100 + seed)
        store = make_store()
        ix = make_index(store)
        ix.write_batch(corpus_batch(800 + 700 * seed, seed=seed))
        # half the rounds also leave a mutable remainder in a later block
        if seed % 2:
            ix.write_batch(corpus_batch(300, seed=seed + 50, t=T0 + HOUR))
        ix.seal_before(T0 + HOUR)  # seals block 0 only

        fields = [b"name", b"host", b"dc", b"role", b"absent"]

        def rand_value():
            return rng.choice(
                [b"metric_%d" % rng.randrange(25), b"h%04d" % rng.randrange(200),
                 b"dc%d" % rng.randrange(4), b"db", b"", b"ab", b"abc"]
            )

        def rand_pattern():
            return rng.choice(
                [b"metric_1[0-9]", b"metric_.*", b"h00.*", b"dc(0|2)",
                 b"db.*", b"metric_1|metric_2|h0001", b".*_3", b"[dw]b.*",
                 b"metric_%d" % rng.randrange(25)]
            )

        def rand_query(depth):
            roll = rng.random()
            if depth <= 0 or roll < 0.45:
                leaf = rng.random()
                if leaf < 0.4:
                    return term(rng.choice(fields), rand_value())
                if leaf < 0.8:
                    return regexp(rng.choice(fields), rand_pattern())
                if leaf < 0.9:
                    return FieldQuery(rng.choice(fields))
                return AllQuery()
            subs = [rand_query(depth - 1) for _ in range(rng.randrange(2, 4))]
            if roll < 0.65:
                return conj(*subs)
            if roll < 0.85:
                return disj(*subs)
            return neg(subs[0])

        for _ in range(25):
            q = rand_query(2)
            limit = rng.choice([None, None, 10, 100])
            assert_parity(ix, q, limit=limit)
        assert store.stats()["errors"] == 0


def test_multichip_dryrun_regexp_parity():
    """The multi-chip dry run's parity surface: a 65k-series index, regexp
    matching a ~5% slice (__graft_entry__.dryrun_multichip's query),
    resolved by the device executor bit-identically to the host."""
    n_series = 65536 + 3
    seg = MutableSegment()
    for i in range(n_series):
        seg.insert(Document(
            id=b"s%d" % i,
            fields=((b"name", b"metric_%d" % (i % 97)), (b"dc", b"dc%d" % (i % 3))),
        ))
    store = make_store()
    ix = make_index(store)
    blk = ix._block_for(T0)
    blk.mutable = seg
    ix.seal_before(T0 + 2 * HOUR)
    assert store.stats()["admissions"] == 1
    q = regexp(b"name", b"metric_1[0-4]")
    dev = assert_parity(ix, q)
    assert len(dev) >= 3000  # the dry-run's own floor
    assert store.stats()["search_hits"] >= 1


def test_newline_term_prefix_regexp_parity():
    """Host `.` does not match \\n: a term containing a newline must NOT
    match `pre.*` — the device prefix fast-class downgrades to the
    host-matched general path for segments carrying such terms."""
    store = make_store()
    ix = make_index(store)
    ix.write_batch([
        (b"a", ((b"name", b"metric_1"),), T0),
        (b"b", ((b"name", b"metric_\nodd"),), T0),
        (b"c", ((b"name", b"metric_2"),), T0),
    ])
    ix.seal_before(T0 + 2 * HOUR)
    assert store.stats()["admissions"] == 1
    dev = assert_parity(ix, regexp(b"name", b"metric_.*"))
    assert dev == [b"a", b"c"]  # the \n term is excluded on BOTH paths
    assert_parity(ix, regexp(b"name", b".*"))
    # exact matching still covers the newline term on both paths
    assert assert_parity(ix, term(b"name", b"metric_\nodd")) == [b"b"]


# ---------- residency lifecycle: eviction, rejection, persistence ----------


def test_eviction_falls_back_seamlessly():
    store = make_store()
    ix = make_index(store)
    ix.write_batch(corpus_batch(600, seed=4, t=T0))
    ix.write_batch(corpus_batch(600, seed=5, t=T0 + HOUR))
    ix.seal_before(T0 + 3 * HOUR)
    assert store.stats()["admissions"] == 2
    # shrink the budget to one segment and admit a third block: LRU evicts
    first_bytes = store.stats()["bytes"]
    store.options.max_bytes = first_bytes // 2 + 64
    ix.write_batch(corpus_batch(600, seed=6, t=T0 + 2 * HOUR))
    ix.seal_before(T0 + 4 * HOUR)
    st = store.stats()
    assert st["evictions"] >= 1
    for q in BASE_QUERIES[:8]:
        assert_parity(ix, q)
    st = store.stats()
    assert st["search_misses"] > 0, "evicted segments must fall back"
    assert st["errors"] == 0


def test_term_too_long_rejected_not_wrong():
    store = make_store(max_term_bytes=16)
    ix = make_index(store)
    long_val = b"v" * 40
    ix.write_batch(
        [(b"s%d" % i, ((b"name", b"metric_1"), (b"blob", long_val)), T0)
         for i in range(20)]
    )
    ix.seal_before(T0 + 2 * HOUR)
    st = store.stats()
    assert st["rejections"] == 1 and st["admissions"] == 0
    assert_parity(ix, term(b"blob", long_val))
    assert_parity(ix, term(b"name", b"metric_1"))
    assert store.stats()["search_misses"] > 0


def test_over_budget_segment_rejected():
    store = make_store(max_bytes=128)  # far too small for any segment
    ix = make_index(store)
    ix.write_batch(corpus_batch(500, seed=7))
    ix.seal_before(T0 + 2 * HOUR)
    st = store.stats()
    assert st["admissions"] == 0 and st["rejections"] == 1
    for q in BASE_QUERIES[:5]:
        assert_parity(ix, q)


def test_persist_reload_parity(tmp_path):
    base = str(tmp_path)
    store = make_store()
    ix = make_index(store)
    ix.write_batch(corpus_batch(1200, seed=8))
    ix.seal_before(T0 + 2 * HOUR)
    ix.persist_before(base, "ns", T0 + 2 * HOUR)
    # the persisted DiskSegment replaced the in-memory one and was
    # re-admitted; the replaced segment's device tier was released
    st = store.stats()
    assert st["admissions"] == 2 and st["invalidations"] >= 1
    assert st["segments"] == 1
    for q in BASE_QUERIES:
        assert_parity(ix, q)

    # a fresh index restoring from disk admits at load
    store2 = make_store()
    ix2 = make_index(store2)
    assert ix2.load_persisted(base, "ns")
    assert store2.stats()["admissions"] == 1
    for q in BASE_QUERIES:
        a = ids(ix2.query(q, *SPAN))
        b = ids(ix.query(q, *SPAN))
        assert a == b, q


def test_admission_racing_retention_never_publishes(monkeypatch):
    """A block expired between seal and admission publish must NOT pin a
    device tier in the store (CONTRIBUTING's identity-swap guarantee:
    the whole block being gone counts as 'the segment is gone')."""
    store = make_store()
    ix = make_index(store)
    ix.write_batch(corpus_batch(200, seed=20))

    real_admit = store.admit

    def race_admit(host_seg, **kw):
        # retention expiry lands while the upload is in flight
        ix.evict_before(T0 + 2 * HOUR)
        return real_admit(host_seg, **kw)

    monkeypatch.setattr(store, "admit", race_admit)
    ix.seal_before(T0 + 2 * HOUR)
    st = store.stats()
    assert st["segments"] == 0, "orphaned block's tier must be dropped"
    assert st["bytes"] == 0
    assert ids(ix.query(AllQuery(), *SPAN)) == []


def test_device_error_counts_as_miss(monkeypatch):
    """An evaluation fault must degrade to host fallback AND count as a
    search miss (hits + misses == total searches) plus an error."""
    from m3_tpu.index.device import kernels as k

    store = make_store()
    ix = make_index(store)
    ix.write_batch(corpus_batch(200, seed=21))
    ix.seal_before(T0 + 2 * HOUR)

    def boom(*a, **kw):
        raise RuntimeError("injected device fault")

    monkeypatch.setattr(k, "match_terms", boom)
    dev = ids(ix.query(term(b"dc", b"dc1"), *SPAN))
    host = ids(ix.query(term(b"dc", b"dc1"), *SPAN, force_host=True))
    assert dev == host, "fault must fall back to a correct host answer"
    st = store.stats()
    # exactly one device search ran (force_host never reaches the
    # wrapper): it must be accounted as BOTH an error and a miss
    assert st["errors"] == 1
    assert st["search_misses"] == 1
    assert st["search_hits"] == 0


def test_retention_eviction_releases_device_tier(tmp_path):
    store = make_store()
    ix = make_index(store)
    ix.write_batch(corpus_batch(400, seed=9))
    ix.seal_before(T0 + 2 * HOUR)
    assert len(store) == 1
    ix.evict_before(T0 + 2 * HOUR)
    st = store.stats()
    assert st["invalidations"] == 1 and st["segments"] == 0
    assert st["bytes"] == 0
    assert ids(ix.query(AllQuery(), *SPAN)) == []


# ---------- postings cache coherence (satellite) ----------


def test_postings_cache_counters_and_invalidation(tmp_path):
    from m3_tpu.index.postings_cache import _M_HITS, _M_MISSES

    ix = make_index()  # host-only: the cache serves the host executor
    ix.write_batch(corpus_batch(800, seed=10))
    ix.seal_before(T0 + 2 * HOUR)
    q = regexp(b"name", b"metric_1[0-9]")
    h0, m0 = _M_HITS.value, _M_MISSES.value
    first = ids(ix.query(q, *SPAN))
    assert _M_MISSES.value > m0
    again = ids(ix.query(q, *SPAN))
    assert again == first
    assert _M_HITS.value > h0, "repeat regexp must serve from the cache"
    assert ix.postings_cache.stats()["entries"] > 0

    # persisting the block supersedes the sealed segment: its cached
    # postings are dropped explicitly, not left to squat capacity
    ix.persist_before(str(tmp_path), "ns", T0 + 2 * HOUR)
    st = ix.postings_cache.stats()
    assert st["invalidations"] > 0
    assert st["entries"] == 0


def test_postings_cache_invalidate_on_retention():
    ix = make_index()
    ix.write_batch(corpus_batch(300, seed=11))
    ix.seal_before(T0 + 2 * HOUR)
    ids(ix.query(FieldQuery(b"host"), *SPAN))
    assert ix.postings_cache.stats()["entries"] > 0
    ix.evict_before(T0 + 2 * HOUR)
    assert ix.postings_cache.stats()["entries"] == 0


# ---------- stats / routing / observability ----------


def test_query_stats_and_routing_reasons():
    from m3_tpu.query import stats

    store = make_store()
    ix = make_index(store)
    ix.write_batch(corpus_batch(400, seed=12, t=T0))
    ix.write_batch(corpus_batch(400, seed=13, t=T0 + HOUR))
    ix.seal_before(T0 + 2 * HOUR)
    # evict the LRU segment so one block routes host with reason=evicted
    store.options.max_bytes = 1
    store._evict_one_locked()

    st = stats.start("index-routing-test")
    assert st is not None
    st.record_routing = True
    ix.query(regexp(b"name", b"met+ric_2"), *SPAN)
    stats.finish(st, 0.0)
    assert st.index_device_hits == 1
    assert st.index_device_misses == 1
    d = st.to_dict()
    assert d["indexDeviceHits"] == 1 and d["indexDeviceMisses"] == 1
    paths = {(r["path"], r["reason"]) for r in st.routing}
    assert ("index-host", "evicted") in paths
    assert ("index-device", "regexp-host-fallback") in paths


def test_device_hit_routing_reason_empty():
    from m3_tpu.query import stats

    store = make_store()
    ix = make_index(store)
    ix.write_batch(corpus_batch(300, seed=14))
    ix.seal_before(T0 + 2 * HOUR)
    st = stats.start("index-routing-device")
    st.record_routing = True
    ix.query(term(b"dc", b"dc1"), *SPAN)
    stats.finish(st, 0.0)
    assert [r for r in st.routing if r["path"] == "index-device"]
    assert all(r["reason"] == "" for r in st.routing
               if r["path"] == "index-device")


# ---------- Database-level integration ----------


def test_database_flush_admits_and_resolves(tmp_path):
    from m3_tpu.storage.database import Database, NamespaceOptions

    db = Database(
        str(tmp_path), num_shards=2, commitlog_enabled=False,
        index_device_options=IndexDeviceOptions(max_bytes=64 << 20),
    )
    db.create_namespace("idx", NamespaceOptions(block_size_nanos=HOUR))
    for i in range(64):
        tags = ((b"__name__", b"idx_gauge"), (b"series", b"%04d" % i),
                (b"dc", b"dc%d" % (i % 3)))
        db.write_tagged("idx", tags, T0 + i * NANOS, float(i))
    st = db.index_stats()
    assert st["enabled"] and st["admissions"] == 0
    db.flush("idx", T0 + 2 * HOUR)
    st = db.index_stats()
    assert st["admissions"] >= 1, "segments admit at seal time"
    assert st["bytes"] > 0
    ns_stats = st["namespaces"]["idx"]
    assert ns_stats["device_resident_segments"] >= 1
    assert "postings_cache" in ns_stats

    q = regexp(b"series", b"00[0-3][0-9]")
    dev = [d.id for d in db.query_ids("idx", q, T0 - HOUR, T0 + HOUR).docs]
    host = [
        d.id
        for d in db.query_ids(
            "idx", q, T0 - HOUR, T0 + HOUR, force_host=True
        ).docs
    ]
    assert dev == host and len(dev) == 40
    assert db.index_device_store.stats()["search_hits"] >= 1

    # the host consumers of the sealed surface run on wrappers unchanged:
    # aggregate (labels endpoints) and peer streaming (seg.docs walk)
    agg = db.aggregate_query("idx", None, T0 - HOUR, T0 + HOUR)
    assert agg[b"dc"] == {b"dc0", b"dc1", b"dc2"}
    streamed = db.stream_shard("idx", 0)
    assert streamed and all(tags for _, tags, _ in streamed)

    # device-memory accounting includes the index tier
    from m3_tpu.profiling import collect_device_memory

    mem = collect_device_memory(db)
    assert mem["index"] > 0
    db.close()


def test_index_device_disabled_by_default(tmp_path):
    from m3_tpu.storage.database import Database, NamespaceOptions

    db = Database(str(tmp_path), num_shards=1, commitlog_enabled=False)
    db.create_namespace("d", NamespaceOptions(block_size_nanos=HOUR))
    assert db.index_device_store is None
    db.write_tagged("d", ((b"a", b"b"),), T0, 1.0)
    db.flush("d", T0 + 2 * HOUR)
    st = db.index_stats()
    assert st["enabled"] is False
    assert [d.id for d in db.query_ids("d", AllQuery(), T0, T0 + HOUR).docs]
    db.close()
