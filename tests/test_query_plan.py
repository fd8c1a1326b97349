"""One-dispatch fused query pipeline (query/plan.py) property suite.

The gating contract: an eligible query served by a device plan is
bit-IDENTICAL to the staged executor — values AND doc ids — across
query shapes (conj/disj/regexp matchers x rate/increase/avg_over_time)
and residency states (fully resident, partially resident, buffered
overlay), with exactly ONE profiled device dispatch once the plan cache
is warm, and the cache invalidating on segment swap, volume bump, and
resident eviction.
"""

from __future__ import annotations

import numpy as np
import pytest

from m3_tpu.index.device.store import IndexDeviceOptions
from m3_tpu.query import plan as qplan
from m3_tpu.query import stats
from m3_tpu.query.engine import Engine
from m3_tpu.query.m3_storage import M3Storage
from m3_tpu.query.promql import Matcher
from m3_tpu.resident.pool import ResidentOptions
from m3_tpu.rules.rules import encode_tags_id
from m3_tpu.storage.database import Database, NamespaceOptions
from m3_tpu.utils.instrument import DEFAULT as METRICS

NANOS = 1_000_000_000
HOUR = 3600 * NANOS
T0 = 1_600_000_000 * NANOS
STEP = 10 * NANOS


@pytest.fixture
def plan_db(tmp_path):
    db = Database(
        str(tmp_path / "db"),
        num_shards=2,
        commitlog_enabled=False,
        resident_options=ResidentOptions(max_bytes=16 << 20),
        index_device_options=IndexDeviceOptions(max_bytes=64 << 20),
    )
    db.create_namespace("ns", NamespaceOptions(block_size_nanos=HOUR))
    yield db
    db.close()


def _seed(db, n_series=24, n_points=48, seed=0, name=b"pm"):
    """Mixed value modes: float-mode (random), int-mode (integers), and
    scaled-decimal int-mode (the encoder's mult path) — the finalize
    arithmetic differs per mode and parity must hold for all of them."""
    rng = np.random.default_rng(seed)
    sids = []
    for i in range(n_series):
        tags = (
            (b"__name__", name),
            (b"job", b"app%d" % (i % 3)),
            (b"s", b"%03d" % i),
        )
        sid = encode_tags_id(tags)
        db.write_tagged("ns", tags, T0, float(i))
        if i % 3 == 0:
            vals = [float(j % 9) for j in range(n_points - 1)]
        elif i % 3 == 1:
            vals = [round(float(rng.standard_normal()), 2) for _ in range(n_points - 1)]
        else:
            vals = [float(rng.standard_normal()) for _ in range(n_points - 1)]
        db.write_batch(
            "ns",
            [(sid, T0 + (j + 1) * STEP, v) for j, v in enumerate(vals)],
        )
        sids.append(sid)
    db.flush("ns", T0 + 4 * HOUR)
    return sids


def _run(eng, query, span, staged=False, explain=False):
    """(values, metas, sealed QueryStats) for one evaluation."""
    st = stats.start(query)
    assert st is not None
    if explain:
        st.record_routing = True
    try:
        if staged:
            with qplan.force_staged():
                r = eng.query_range(query, *span)
        else:
            r = eng.query_range(query, *span)
    finally:
        stats.finish(st, 0.0)
    return np.asarray(r.values), [m.tags for m in r.metas], st


def _assert_bitexact(eng, query, span, expect_fused=True):
    vf, mf, stf = _run(eng, query, span)
    vs, ms, _sts = _run(eng, query, span, staged=True)
    assert mf == ms, f"meta mismatch for {query}"
    assert vf.shape == vs.shape
    eq = (vf == vs) | (np.isnan(vf) & np.isnan(vs))
    assert eq.all(), (
        f"value mismatch for {query}: {np.argwhere(~eq)[:5]}"
    )
    if expect_fused:
        assert stf.plan_hits + stf.plan_misses >= 1, f"not fused: {query}"
        assert stf.plan_fallbacks == 0
    return stf


SPAN = (T0 + 60 * NANOS, T0 + 460 * NANOS, 20 * NANOS)

QUERIES = [
    # regexp (prefix class) x rate
    'rate(pm{job=~"app.*"}[2m])',
    # exact conjunction x increase
    'increase(pm{job="app0"}[90s])',
    # negation in the conjunction x avg_over_time
    'avg_over_time(pm{job=~"app.*",s!="003"}[2m])',
    # alternation (disjunction on device) x rate
    'rate(pm{job=~"app0|app2"}[2m])',
    # negated regexp
    'sum_over_time(pm{job!~"app1.*"}[2m])',
    # plain selector (consolidation only)
    'pm{job="app1"}',
    # aggregation on top — engine layers are identical either way, but
    # the grid underneath must be too
    'sum(rate(pm{job=~"app.*"}[2m]))',
]


def test_fused_vs_staged_bitexact_smoke(plan_db):
    # one shape that composes most of the plan surface (prefix regexp +
    # negated conjunction + temporal fn); the full per-shape sweep below
    # is @slow — each shape pays its own fused+staged compile, and the
    # seven together were the single largest line item in tier-1
    _seed(plan_db)
    eng = Engine(M3Storage(plan_db, "ns"))
    _assert_bitexact(eng, 'avg_over_time(pm{job=~"app.*",s!="003"}[2m])', SPAN)


@pytest.mark.slow
def test_fused_vs_staged_bitexact_across_shapes(plan_db):
    _seed(plan_db)
    eng = Engine(M3Storage(plan_db, "ns"))
    for query in QUERIES:
        _assert_bitexact(eng, query, SPAN)


# -- stage 5 alone: the fused grid against engine.consolidate_row ------------

_LB = 50  # lookback of the hand-built lanes, in their own small time unit
_X = None  # an invalid slot


def _lane_case(rows, grid, window=(-(10**6), 10**6)):
    return rows, grid, window


# each row: (ts, value) per slot, or _X for an invalid slot. Values alternate
# float-mode and scaled int-mode so every rider plane is checked.
_LANE_CASES = {
    "step_on_timestamp": _lane_case(
        [[(100, 1.5), (110, 2.5), (120, 3.5)]], [99, 100, 110, 119, 120, 121]),
    "steps_before_first_point": _lane_case(
        [[(500, 1.0), (510, 2.0)]], [100, 200, 499, 500, 505]),
    "gap_longer_than_lookback": _lane_case(
        [[(100, 1.0), (110, 2.0), (300, 3.0)]],
        [110, 159, 160, 161, 250, 299, 300, 349, 350]),
    "invalid_head": _lane_case(
        [[_X, _X, (120, 3.0), (130, 4.0)]], [100, 119, 120, 125, 130, 140]),
    "invalid_middle": _lane_case(
        [[(100, 1.0), _X, _X, (130, 4.0), _X, (150, 6.0)]],
        [100, 110, 120, 129, 130, 140, 149, 150, 160]),
    "invalid_tail": _lane_case(
        [[(100, 1.0), (110, 2.0), _X, _X]], [105, 110, 120, 159, 160, 170]),
    "all_invalid_row": _lane_case(
        [[_X, _X, _X, _X], [(100, 1.0), (110, 2.0), _X, (130, 9.0)]],
        [90, 100, 115, 130, 200]),
    # the compaction sentinel: an all-zero lane block (ts 0, nothing valid),
    # under a grid that starts inside its lookback of zero
    "sentinel_row": _lane_case(
        [[_X, _X, _X], [(5, 7.0), (20, 8.0), _X]], [0, 1, 10, 49, 50, 60]),
    "padded_grid_steps": _lane_case(
        [[(100, 1.0), (110, 2.0), (120, 3.0)]],
        [100, 110, 115, 115, 115, 115, 115, 115]),
    # two blocks of four slots, the first block's tail and the second's
    # head empty, and a hole longer than the lookback between them
    "two_blocks_with_hole": _lane_case(
        [[(100, 1.0), (110, 2.0), _X, _X, _X, (400, 5.0), (410, 6.0), _X],
         [_X, _X, _X, _X, (400, 5.5), (410, 6.5), (420, 7.5), (430, 8.5)]],
        [100, 120, 159, 160, 300, 399, 400, 405, 415, 440, 479, 480]),
    "window_cuts_both_ends": _lane_case(
        [[(100, 1.0), (110, 2.0), (120, 3.0), (130, 4.0), (140, 5.0)],
         [(90, 1.0), (119, 2.0), (131, 3.0), _X, (150, 5.0)]],
        [100, 110, 115, 120, 130, 135, 140, 150, 180], window=(110, 131)),
}


@pytest.mark.parametrize("case", sorted(_LANE_CASES))
def test_consolidate_last_bitexact_vs_host_rule(case):
    """query/plan._consolidate_last (the program's stage 5, compare-and-
    reduce) against engine.consolidate_row on hand-built lanes."""
    import jax

    from m3_tpu.query.engine import consolidate_row

    rows, grid, (flo, fhi) = _LANE_CASES[case]
    base = T0  # real unix nanos: both u32 halves of every pair are in play
    unit = NANOS
    cap, t_pts = len(rows), len(rows[0])
    ts = np.zeros((cap, t_pts), np.uint64)
    raw = np.zeros((cap, t_pts), np.uint64)
    pif = np.zeros((cap, t_pts), np.int32)
    mlt = np.zeros((cap, t_pts), np.int32)
    valid = np.zeros((cap, t_pts), bool)
    for r, row in enumerate(rows):
        for i, slot in enumerate(row):
            if slot is _X:
                continue
            t, v = slot
            valid[r, i] = True
            ts[r, i] = base + t * unit
            if i % 2:
                pif[r, i] = 1
                raw[r, i] = np.float64(v).view(np.uint64)
            else:  # int mode, two decimals
                mlt[r, i] = 2
                raw[r, i] = np.int64(round(v * 100)).view(np.uint64)
    grid_ns = base + np.asarray(grid, np.int64) * unit
    t_grid = qplan.pad_pow2(len(grid_ns), qplan._SENTINEL_GRID)
    g = np.full(t_grid, grid_ns[-1], np.int64)
    g[: len(grid_ns)] = grid_ns

    def pair(x):
        x = np.asarray(x).astype(np.uint64)
        return (
            (x >> np.uint64(32)).astype(np.uint32),
            (x & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        )

    w_lo, w_hi = base + flo * unit, base + fhi * unit
    counts, planes, ok = jax.jit(qplan._consolidate_last)(
        pair(ts), (*pair(raw), pif, mlt), valid, pair(g),
        pair(w_lo), pair(w_hi), pair(_LB * unit),
    )
    got = qplan._finalize_grid(*planes, ok)[:, : len(grid_ns)]
    vals = qplan._finalize_grid(*pair(raw), pif, mlt, np.ones_like(valid))
    tsi = ts.astype(np.int64)
    for r in range(cap):
        keep = valid[r] & (tsi[r] >= w_lo) & (tsi[r] < w_hi)
        want = consolidate_row(tsi[r][keep], vals[r][keep], grid_ns, _LB * unit)
        assert int(counts[r]) == int(keep.sum())
        np.testing.assert_array_equal(
            got[r].view(np.uint64), want.view(np.uint64), err_msg=f"row {r}"
        )
    assert not np.isnan(got).all(), "the case consolidates nothing"


@pytest.mark.parametrize("steps,t_grid", [(0, 8), (5, 8), (61, 128), (128, 128)])
def test_pack_request_is_the_program_s_eight_arguments_in_one(steps, t_grid):
    """The plan program takes a request's grid, fetch bounds and lookback
    as one u32 array (one host-to-device transfer): the words are those the
    eight separate arguments carried, padded steps repeating the last."""
    grid = np.int64(1_700_000_000_000_000_000) + np.arange(steps, dtype=np.int64) * 10**10
    lo, hi, lb = int(grid[0]) - 5 * 10**9 if steps else 0, (1 << 63) + 7, 300 * 10**9
    got = qplan._pack_request(grid, t_grid, lo, hi, lb)
    assert got.dtype == np.uint32 and got.shape == (2 * t_grid + 6,)
    padded = np.concatenate([grid, np.full(t_grid - steps, grid[-1] if steps else 0)])
    assert (got[:t_grid].astype(np.uint64) << np.uint64(32) | got[t_grid:2 * t_grid]
            == padded.astype(np.uint64)).all()
    pairs = got[2 * t_grid:].astype(object)
    assert [(int(pairs[i]) << 32) | int(pairs[i + 1]) for i in (0, 2, 4)] == [lo, hi, lb]


@pytest.mark.parametrize("n_words,cap,t_grid", [(1, 8, 8), (127, 64, 128), (4, 512, 16)])
def test_unpack_reply_is_the_program_s_nine_outputs(n_words, cap, t_grid):
    """The plan program hands back ONE u32 array (one read-back): the
    count, the bitmap, the per-slot counts and error flags, then the five
    [cap, t_grid] planes, signed and boolean ones bit-cast. What comes out
    is what went in, dtype for dtype where the finalize reads one."""
    rng = np.random.default_rng(n_words)
    u32 = lambda *shape: rng.integers(0, 1 << 32, shape, dtype=np.uint64).astype(np.uint32)
    want = (u32(n_words), 37, rng.integers(0, 736, cap).astype(np.int32),
            rng.random(cap) < 0.1, u32(cap, t_grid), u32(cap, t_grid),
            rng.integers(0, 2, (cap, t_grid)).astype(np.int32),
            rng.integers(-6, 7, (cap, t_grid)).astype(np.int32),  # mult may be negative
            rng.random((cap, t_grid)) < 0.7)
    n, bitmap, *rest = want[1], want[0], *want[2:]
    packed = np.concatenate(
        [np.asarray([n], np.int32).view(np.uint32), bitmap]
        + [np.asarray(x).astype(np.int32).view(np.uint32).reshape(-1) for x in rest])
    got = qplan._unpack_reply(packed, n_words, cap, t_grid)
    assert len(got) == 9 and got[1] == 37
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    assert got[2].dtype == np.int32 and got[7].dtype == np.int32
    assert got[3].dtype == bool and got[8].dtype == bool


def test_fused_matches_doc_ids_and_order(plan_db):
    _seed(plan_db)
    eng = Engine(M3Storage(plan_db, "ns"))
    vf, mf, st = _run(eng, 'pm{job=~"app.*"}', SPAN)
    assert st.plan_misses + st.plan_hits >= 1
    _vs, ms, _ = _run(eng, 'pm{job=~"app.*"}', SPAN, staged=True)
    assert mf == ms and len(mf) == 24  # same docs, same order


def test_warm_plan_is_one_device_dispatch(plan_db):
    _seed(plan_db)
    eng = Engine(M3Storage(plan_db, "ns"))
    q = 'rate(pm{job=~"app.*"}[2m])'
    _run(eng, q, SPAN)  # compile + build
    _vf, _mf, st = _run(eng, q, SPAN)
    assert st.plan_hits == 1 and st.plan_misses == 0
    assert st.device_dispatches == 1, st.to_dict()
    _vs, _ms, sts = _run(eng, q, SPAN, staged=True)
    assert sts.device_dispatches > 1  # staged pays per-stage dispatches


def test_host_regexp_leaf_falls_back_with_reason(plan_db):
    _seed(plan_db)
    eng = Engine(M3Storage(plan_db, "ns"))
    q = 'rate(pm{job=~"app.*[02]"}[2m])'  # general class: host automaton
    vf, mf, st = _run(eng, q, SPAN, explain=True)
    assert st.plan_fallbacks >= 1 and st.plan_hits == 0
    reasons = [r["reason"] for r in st.routing if r["path"] == "staged"]
    assert "plan:host-regexp-leaf" in reasons
    # still correct (both evaluations are staged now, but prove it)
    vs, ms, _ = _run(eng, q, SPAN, staged=True)
    assert mf == ms
    assert ((vf == vs) | (np.isnan(vf) & np.isnan(vs))).all()


def test_buffer_overlay_falls_back(plan_db):
    _seed(plan_db)
    eng = Engine(M3Storage(plan_db, "ns"))
    q = 'rate(pm{job=~"app.*"}[2m])'
    _assert_bitexact(eng, q, SPAN)
    # a live write into the query range overlays the sealed blocks —
    # an UNINDEXED series id: the write touches neither the mutable
    # index nor any resident entry, isolating the buffer-overlay cause
    # (an indexed-series write would ALSO invalidate its resident block
    # and fire non-resident-block first, equally correctly)
    plan_db.write("ns", b"unindexed-overlay", T0 + 200 * NANOS, 123.0)
    vf, mf, st = _run(eng, q, SPAN, explain=True)
    assert st.plan_fallbacks >= 1
    reasons = [r["reason"] for r in st.routing if r["path"] == "staged"]
    assert "plan:buffer-overlay" in reasons
    vs, ms, _ = _run(eng, q, SPAN, staged=True)
    assert mf == ms
    assert ((vf == vs) | (np.isnan(vf) & np.isnan(vs))).all()


def test_partially_resident_falls_back_never_lies(plan_db):
    _seed(plan_db)
    eng = Engine(M3Storage(plan_db, "ns"))
    q = 'rate(pm{job=~"app.*"}[2m])'
    _assert_bitexact(eng, q, SPAN)
    pool = plan_db.resident_pool
    # drop ONE lane (the write-hook invalidation shape): the block's
    # complete marker goes with it, so the plan must stop serving
    ns = plan_db.namespaces["ns"]
    sid = encode_tags_id(
        ((b"__name__", b"pm"), (b"job", b"app0"), (b"s", b"000"))
    )
    shard = ns.shard_for(sid)
    keys, _ = shard.scan_block_keys(sid, SPAN[0] - 5 * 60 * NANOS, SPAN[1])
    assert keys
    pool.invalidate_series_block("ns", shard.id, sid, keys[0].block_start)
    vf, mf, st = _run(eng, q, SPAN, explain=True)
    assert st.plan_hits == 0  # stale plan must NOT serve
    assert st.plan_fallbacks >= 1
    reasons = [r["reason"] for r in st.routing if r["path"] == "staged"]
    assert "plan:non-resident-block" in reasons
    vs, ms, _ = _run(eng, q, SPAN, staged=True)
    assert mf == ms
    assert ((vf == vs) | (np.isnan(vf) & np.isnan(vs))).all()


def test_annotated_err_lane_stitches_through_host(plan_db):
    from m3_tpu.codec.m3tsz import Encoder
    from m3_tpu.storage.fs import FilesetID, write_fileset

    # the annotated doc is written BEFORE the seed's flush so it lands
    # in the SEALED index segment (a mutable-index doc would correctly
    # force the whole query staged before the err lane even mattered)
    tags = ((b"__name__", b"pm"), (b"job", b"ann"), (b"s", b"ann"))
    sid = encode_tags_id(tags)
    plan_db.write_tagged("ns", tags, T0 + 30 * NANOS, 1.0)
    _seed(plan_db, n_series=8)
    ns = plan_db.namespaces["ns"]
    bsz = ns.opts.block_size_nanos
    bs = (T0 // bsz) * bsz
    # supersede the ann series' fileset with an annotated stream at a
    # NEW volume (device decoder bails on annotations -> err lane ->
    # batched host stitch)
    shard = ns.shard_for(sid)
    reader = shard.reader(FilesetID("ns", shard.id, bs, 0))
    series = {s: reader.stream(s) for s in reader.series_ids}
    enc = Encoder(T0)
    enc.encode(T0 + 60 * NANOS, 100.0, annotation=b"x")
    enc.encode(T0 + 120 * NANOS, 200.0)
    series[sid] = enc.stream()
    fid = FilesetID("ns", shard.id, bs, 1)
    with shard.lock:
        write_fileset(plan_db.base, fid, series, bsz)
        shard._invalidate_filesets()
        shard._readers.pop(bs, None)
        payload = shard._collect_admission_locked([fid])
    plan_db.resident_pool.invalidate_block("ns", shard.id, bs, below_volume=1)
    shard._admit_payload(payload)
    eng = Engine(M3Storage(plan_db, "ns"))
    q = 'pm{job=~"a.*"}'  # matches app* and ann
    vf, mf, st = _run(eng, q, SPAN, explain=True)
    assert st.plan_hits + st.plan_misses >= 1, st.to_dict()
    fused_reasons = {
        r["series"]: r["reason"] for r in st.routing if r["path"] == "fused"
    }
    assert any("annotated-err-lane" in v for v in fused_reasons.values())
    vs, ms, _ = _run(eng, q, SPAN, staged=True)
    assert mf == ms
    assert ((vf == vs) | (np.isnan(vf) & np.isnan(vs))).all()
    # the annotated values are really there
    row = vf[[m for m in mf].index(tuple(sorted(tags)))]
    assert 100.0 in row and 200.0 in row


# ---------------------------------------------------------------------------
# plan-cache keying / invalidation
# ---------------------------------------------------------------------------


def test_plan_cache_hits_and_lru(plan_db):
    _seed(plan_db)
    storage = M3Storage(plan_db, "ns")
    eng = Engine(storage)
    q = 'rate(pm{job=~"app.*"}[2m])'
    _run(eng, q, SPAN)
    before = storage.planner.hits
    _run(eng, q, SPAN)
    _run(eng, q, SPAN)
    assert storage.planner.hits == before + 2
    assert len(storage.planner._cache) == 1


# ---------------------------------------------------------------------------
# decode capacity: the power-of-two bucket of what matched
# ---------------------------------------------------------------------------

# (value of the ``grp`` tag, series carrying it): disjoint groups of one
# field, so matchers that differ only in the value share an AST shape
GROUPS = (("n1", 1), ("n7", 7), ("n8", 8), ("n9", 9), ("n40", 40), ("rest", 5))
N_GROUPED = sum(n for _, n in GROUPS)  # 70 docs: a 96-bit bitmap, not a power of two


@pytest.fixture(scope="module")
def bucket_db(tmp_path_factory):
    db = Database(
        str(tmp_path_factory.mktemp("buckets") / "db"),
        num_shards=2,
        commitlog_enabled=False,
        resident_options=ResidentOptions(max_bytes=16 << 20),
        index_device_options=IndexDeviceOptions(max_bytes=64 << 20),
    )
    db.create_namespace("ns", NamespaceOptions(block_size_nanos=HOUR))
    rng = np.random.default_rng(30)
    i = 0
    for grp, n in GROUPS:
        for _ in range(n):
            tags = ((b"__name__", b"pm"), (b"grp", grp.encode()), (b"s", b"%03d" % i))
            sid = encode_tags_id(tags)
            db.write_tagged("ns", tags, T0, float(i))
            vals = rng.standard_normal(47) if i % 2 else rng.integers(0, 9, 47)
            db.write_batch("ns", [
                (sid, T0 + (j + 1) * STEP, float(v)) for j, v in enumerate(vals)])
            i += 1
    db.flush("ns", T0 + 4 * HOUR)
    storage = M3Storage(db, "ns")
    yield storage, Engine(storage)
    db.close()


def _plan_builds_by_cap() -> dict:
    fam = METRICS.collect().get("m3tpu_query_plan_builds_total", {"children": []})
    return {int(c["labels"]["cap"]): c["value"] for c in fam["children"]}


@pytest.mark.parametrize("query,matched,cap", [
    ('pm{grp="n1"}', 1, 8),
    ('pm{grp="n7"}', 7, 8),
    ('pm{grp="n8"}', 8, 8),
    ('pm{grp="n9"}', 9, 16),
    ('pm{grp="n40"}', 40, 64),
    # every doc of the segment: the bitmap's width, today's program
    ("pm", N_GROUPED, 96),
])
def test_decode_capacity_is_the_bucket_of_what_matched(bucket_db, query, matched, cap):
    storage, eng = bucket_db
    built = _plan_builds_by_cap().get(cap, 0)
    st = _assert_bitexact(eng, query, SPAN)
    d = st.to_dict()
    assert d["planSeriesMatched"] == matched and d["planFallbacks"] == 0
    assert d["planLanesDecoded"] == cap * 1  # one block
    entry = next(reversed(storage.planner._cache.values()))  # the newest used
    n_docs_pad = entry.dims[1]
    assert n_docs_pad == 96 and entry.dims[2] == entry.cap == cap
    assert _plan_builds_by_cap()[cap] == built + 1


# four steps: a padded grid no other test of this module compiles for, so
# the programs counted below are built here
SHORT_SPAN = (T0 + 60 * NANOS, T0 + 120 * NANOS, 20 * NANOS)


def test_matchers_in_one_bucket_share_a_program(bucket_db):
    _storage, eng = bucket_db
    programs = lambda: qplan._build_program.cache_info().misses
    before = programs()
    _assert_bitexact(eng, 'pm{grp="n1"}', SHORT_SPAN)
    assert programs() == before + 1
    _assert_bitexact(eng, 'pm{grp="n8"}', SHORT_SPAN)  # 1 and 8 match: cap 8 both
    assert programs() == before + 1
    _assert_bitexact(eng, 'pm{grp="n9"}', SHORT_SPAN)  # 9 match: cap 16
    assert programs() == before + 2


def test_more_matches_than_capacity_falls_back_once_and_rebuilds(bucket_db, monkeypatch):
    import m3_tpu.index.query as iq

    storage, eng = bucket_db
    q = 'pm{grp="n9",s=~".*"}'  # a matcher set no other test has cached
    real = iq.search_segment
    # the build's count and the program's disagree, and go on disagreeing
    # (in every plan build, not in the staged path's own resolve): cap 8
    # for 9 matches
    building, real_build = [], storage.planner._build

    def build(*a, **kw):
        building.append(1)
        try:
            return real_build(*a, **kw)
        finally:
            building.pop()

    monkeypatch.setattr(storage.planner, "_build", build)
    monkeypatch.setattr(
        iq, "search_segment",
        lambda seg, query, *a, **kw: real(seg, query, *a, **kw)[:3 if building else None])
    cached = len(storage.planner._cache)
    vf, mf, st = _run(eng, q, SPAN, explain=True)
    assert st.plan_fallbacks == 1 and st.plan_misses == 1
    reasons = [r["reason"] for r in st.routing if r["path"] == "staged"]
    assert reasons == ["plan:plan-capacity"]
    assert len(storage.planner._cache) == cached  # the entry is gone
    vs, ms, _ = _run(eng, q, SPAN, staged=True)
    assert mf == ms and len(mf) == 9
    assert ((vf == vs) | (np.isnan(vf) & np.isnan(vs))).all()
    # the next request rebuilds at the count the program gave (not the
    # build's own, which is still 3) and is plan-served: one fallback, not
    # one a request
    st = _assert_bitexact(eng, q, SPAN)
    assert st.plan_misses == 1 and st.plan_fallbacks == 0
    assert st.to_dict()["planLanesDecoded"] == 16
    assert len(storage.planner._cache) == cached + 1


def test_plan_invalidates_on_volume_bump(plan_db):
    from m3_tpu.codec.m3tsz import Encoder
    from m3_tpu.storage.fs import FilesetID, write_fileset

    sids = _seed(plan_db, n_series=8)
    storage = M3Storage(plan_db, "ns")
    eng = Engine(storage)
    q = 'pm{job=~"app.*"}'
    v0, _, _ = _run(eng, q, SPAN)
    assert storage.planner.misses == 1
    # supersede one series' block with a NEW VOLUME holding different
    # data (the cold-flush supersession shape)
    ns = plan_db.namespaces["ns"]
    bsz = ns.opts.block_size_nanos
    sid = sids[0]
    shard = ns.shard_for(sid)
    keys, _ = shard.scan_block_keys(sid, SPAN[0], SPAN[1])
    bs = keys[0].block_start
    reader = shard.reader(FilesetID("ns", shard.id, bs, 0))
    series = {s: reader.stream(s) for s in reader.series_ids}
    enc = Encoder(T0)
    enc.encode(T0 + 60 * NANOS, 4242.0)
    series[sid] = enc.stream()
    fid = FilesetID("ns", shard.id, bs, 1)
    with shard.lock:
        write_fileset(plan_db.base, fid, series, bsz)
        shard._invalidate_filesets()
        shard._readers.pop(bs, None)
        payload = shard._collect_admission_locked([fid])
    plan_db.resident_pool.invalidate_block(
        "ns", shard.id, bs, below_volume=1
    )
    shard._admit_payload(payload)
    v1, m1, st = _run(eng, q, SPAN, explain=True)
    # the cached plan must NOT have served stale volume-0 pages
    assert st.plan_hits == 0
    assert storage.planner.misses >= 2 or st.plan_fallbacks >= 1
    vs, ms, _ = _run(eng, q, SPAN, staged=True)
    assert m1 == ms
    assert ((v1 == vs) | (np.isnan(v1) & np.isnan(vs))).all()
    idx = m1.index(
        tuple(sorted(((b"__name__", b"pm"), (b"job", b"app0"), (b"s", b"000"))))
    )
    assert 4242.0 in v1[idx]


def test_plan_invalidates_on_eviction_and_clear(plan_db):
    _seed(plan_db)
    storage = M3Storage(plan_db, "ns")
    eng = Engine(storage)
    q = 'rate(pm{job=~"app.*"}[2m])'
    _assert_bitexact(eng, q, SPAN)
    plan_db.resident_pool.clear()  # operator eviction churn
    vf, mf, st = _run(eng, q, SPAN, explain=True)
    assert st.plan_hits == 0  # stale plan not served
    assert st.plan_fallbacks >= 1
    # the fallback path releases stale entries (their pinned device
    # tables + index arrays must not linger until LRU displacement)
    assert len(storage.planner._cache) == 0
    vs, ms, _ = _run(eng, q, SPAN, staged=True)
    assert mf == ms
    assert ((vf == vs) | (np.isnan(vf) & np.isnan(vs))).all()


def test_plan_invalidates_on_segment_swap(plan_db):
    _seed(plan_db)
    storage = M3Storage(plan_db, "ns")
    eng = Engine(storage)
    q = 'pm{job=~"app.*"}'
    _run(eng, q, SPAN)
    misses0 = storage.planner.misses
    # a new doc in the SAME index block (index-only write: no buffer,
    # no data) then a flush: seal_before + persist_before compact the
    # block's segments into a NEW DiskSegment — a segment IDENTITY swap
    tags = ((b"__name__", b"pm"), (b"job", b"app9"), (b"s", b"zzz"))
    ns_index = plan_db.namespaces["ns"].index
    ns_index.write(encode_tags_id(tags), tags, T0 + 100 * NANOS)
    plan_db.flush("ns", T0 + 4 * HOUR)
    vf, mf, st = _run(eng, q, SPAN)
    assert st.plan_hits == 0  # stale plan must not serve the new segment
    assert storage.planner.misses == misses0 + 1
    vs, ms, _ = _run(eng, q, SPAN, staged=True)
    assert mf == ms
    # the new doc has no data: present in metas, all-NaN row, both paths
    assert tuple(sorted(tags)) in mf
    assert ((vf == vs) | (np.isnan(vf) & np.isnan(vs))).all()


def test_plan_invalidates_on_new_sealed_block(plan_db):
    _seed(plan_db)
    storage = M3Storage(plan_db, "ns")
    eng = Engine(storage)
    wide = (T0 + 60 * NANOS, T0 + HOUR + 600 * NANOS, 60 * NANOS)
    q = 'pm{job=~"app.*"}'
    _run(eng, q, wide)
    # seal a NEW block inside the (cached) plan's range: the shard
    # fileset epoch bumps and the plan must rebuild to include it
    tags = ((b"__name__", b"pm"), (b"job", b"app0"), (b"s", b"000"))
    sid = encode_tags_id(tags)
    plan_db.write_tagged("ns", tags, T0 + HOUR + 100 * NANOS, 777.0)
    plan_db.flush("ns", T0 + 8 * HOUR)
    vf, mf, st = _run(eng, q, wide)
    assert st.plan_hits == 0  # stale block set must not serve
    vs, ms, _ = _run(eng, q, wide, staged=True)
    assert mf == ms
    assert ((vf == vs) | (np.isnan(vf) & np.isnan(vs))).all()
    assert 777.0 in vf[mf.index(tuple(sorted(tags)))]


def test_concurrent_identical_queries_coalesce_to_one_scan(plan_db):
    """Scan coalescing (singleflight in Planner.run): N identical
    eligible queries arriving together execute as FEWER device scans
    than queries — followers share the leader's arrays (copied, so
    callers can't alias each other) and the answers stay bit-identical
    to a solo run."""
    import threading

    _seed(plan_db)
    storage = M3Storage(plan_db, "ns")
    eng = Engine(storage)
    q = 'rate(pm{job=~"app.*"}[2m])'
    baseline, base_metas, _ = _run(eng, q, SPAN)  # compile + build
    n = 8
    barrier = threading.Barrier(n)
    rows = [None] * n
    recs = [None] * n
    errs = []

    def worker(i):
        st = stats.start(q)
        try:
            barrier.wait()
            r = eng.query_range(q, *SPAN)
            rows[i] = (np.asarray(r.values), [m.tags for m in r.metas])
        except Exception as exc:  # pragma: no cover - surfaced below
            errs.append(exc)
        finally:
            stats.finish(st, 0.0)
            recs[i] = st

    threads = [
        threading.Thread(target=worker, args=(i,), daemon=True)
        for i in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    assert not errs, errs
    dispatches = sum(st.device_dispatches for st in recs)
    coalesced = sum(st.plan_coalesced for st in recs)
    assert dispatches < n, [st.device_dispatches for st in recs]
    assert coalesced >= 1 and coalesced == storage.planner.coalesced
    # every follower (no dispatch of its own) still got the exact answer
    for vals, metas in rows:
        assert metas == base_metas
        eq = (vals == baseline) | (np.isnan(vals) & np.isnan(baseline))
        assert eq.all()
    # followers got COPIES of the leader's value grid, never views of
    # the same buffer — one caller's result can't alias another's
    for i in range(1, n):
        assert not np.shares_memory(rows[0][0], rows[i][0])


def test_coalesce_key_distinguishes_spans(plan_db):
    """Different fetch windows must NOT coalesce — the singleflight key
    carries the span and grid, not just the plan identity."""
    _seed(plan_db)
    storage = M3Storage(plan_db, "ns")
    eng = Engine(storage)
    q = 'rate(pm{job=~"app.*"}[2m])'
    _run(eng, q, SPAN)
    before = storage.planner.coalesced
    other = (T0 + 80 * NANOS, T0 + 480 * NANOS, 20 * NANOS)
    _run(eng, q, other)  # sequential AND different span: no coalesce
    _run(eng, q, SPAN)
    assert storage.planner.coalesced == before


# ---------------------------------------------------------------------------
# packed side planes (ops/sideplane.py)
# ---------------------------------------------------------------------------


def test_sideplane_pack_roundtrip_exact():
    from m3_tpu.ops.sideplane import pack_side_rows, unpack_side_rows

    rng = np.random.default_rng(7)
    bs = int(T0 - 1600 * NANOS)
    snaps = []
    for j in range(50):
        pt = 0 if j == 0 else bs + int(rng.integers(0, 1 << 43))
        u64r = lambda: int(rng.integers(0, 1 << 64, dtype=np.uint64))
        snaps.append(
            dict(
                off=int(rng.integers(0, 1 << 21)),
                prev_time=pt,
                prev_delta=int(rng.integers(0, 1 << 44)),
                prev_float_bits=u64r(),
                prev_xor=u64r(),
                int_val=u64r(),
                time_unit=int(rng.integers(0, 8)),
                sig=int(rng.integers(0, 64)),
                mult=int(rng.integers(0, 20)),
                is_float=bool(rng.integers(0, 2)),
                fast=bool(rng.integers(0, 2)),
                fast_float=bool(rng.integers(0, 2)),
            )
        )
    rows = pack_side_rows(snaps, bs)
    assert rows is not None and rows.shape == (50, 10)
    back = unpack_side_rows(rows, bs)
    for orig, rt in zip(snaps, back):
        for k in ("off", "prev_time", "prev_delta", "prev_float_bits",
                  "prev_xor", "int_val", "time_unit", "sig", "mult",
                  "is_float", "fast", "fast_float"):
            assert rt[k] == orig[k], (k, orig, rt)


def test_sideplane_pack_overflow_degrades_streamed(plan_db):
    """A chunk state the packed layout can't hold admits WITHOUT side
    planes (counted), and scans fall back streamed with correct totals."""
    from m3_tpu.ops.sideplane import pack_side_row

    assert pack_side_row(
        dict(off=0, prev_time=0, prev_delta=1 << 50, prev_float_bits=0,
             prev_xor=0, int_val=0, time_unit=1, sig=0, mult=0,
             is_float=False),
        T0,
    ) is None
    # prev_time BEFORE block start is unrepresentable too
    assert pack_side_row(
        dict(off=0, prev_time=5, prev_delta=0, prev_float_bits=0,
             prev_xor=0, int_val=0, time_unit=1, sig=0, mult=0,
             is_float=False),
        T0,
    ) is None
    pool = plan_db.resident_pool
    bad_snap = dict(
        off=0, prev_time=0, prev_delta=1 << 50, prev_float_bits=0,
        prev_xor=0, int_val=0, time_unit=1, sig=0, mult=0, is_float=False,
        span=64, total_bits=64, fast=False, fast_float=False,
    )
    res = pool.admit_block(
        "ns", 0, T0, 0, [(b"ovf", b"\x00" * 8, 8, [bad_snap])]
    )
    assert res.admitted == 1
    assert pool.side_pack_overflows == 1
    from m3_tpu.cache.block_cache import BlockKey

    entry = pool.get(BlockKey("ns", 0, b"ovf", T0, 0))
    assert entry is not None and entry.n_chunks == 0  # no side planes
    assert pool.plan_chunked([BlockKey("ns", 0, b"ovf", T0, 0)]) is None


def test_fileset_side_v3_roundtrip(tmp_path):
    """Filesets persist packed v3 side rows; side_table() round-trips
    them to the exact snapshot dicts a v2 reader would produce."""
    from m3_tpu.codec.m3tsz import Encoder
    from m3_tpu.ops.chunked import snapshot_stream
    from m3_tpu.storage.fs import (
        CHUNK_K,
        FilesetID,
        FilesetReader,
        write_fileset,
    )

    enc = Encoder(T0)
    for j in range(80):
        enc.encode(T0 + (j + 1) * STEP, float(j % 11) + 0.25)
    stream = enc.stream()
    fid = FilesetID("ns", 0, int(T0), 0)
    write_fileset(str(tmp_path), fid, {b"a": stream}, HOUR)
    reader = FilesetReader(str(tmp_path), fid)
    assert reader.info["sideVersion"] == 3
    got = reader.side_table(b"a")
    want = snapshot_stream(stream, CHUNK_K)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for k in w:
            assert g[k] == w[k], (k, g, w)


def test_fileset_side_v2_fallback_still_readable(tmp_path):
    """A fileset whose chunk state overflows the packed layout falls
    back to the v2 struct side file for the WHOLE file — and the reader
    must open and serve it (regression: the v3 reader wiring broke the
    v1/v2 record-size branch with an AttributeError)."""
    from m3_tpu.codec.m3tsz import Encoder
    from m3_tpu.ops.chunked import snapshot_stream
    from m3_tpu.storage.fs import (
        CHUNK_K,
        FilesetID,
        FilesetReader,
        write_fileset,
    )

    enc = Encoder(T0)
    for j in range(CHUNK_K - 1):
        enc.encode(T0 + (j + 1) * NANOS, float(j))
    # an ~11h gap as the LAST record of chunk 0: chunk 1's prev_delta
    # carry then exceeds the packed 45-bit range, forcing the
    # whole-file v2 fallback
    enc.encode(T0 + 11 * 3600 * NANOS, 1.0)
    enc.encode(T0 + 11 * 3600 * NANOS + NANOS, 2.0)
    enc.encode(T0 + 11 * 3600 * NANOS + 2 * NANOS, 3.0)
    stream = enc.stream()
    fid = FilesetID("ns", 0, int(T0), 0)
    write_fileset(str(tmp_path), fid, {b"a": stream}, 12 * HOUR)
    reader = FilesetReader(str(tmp_path), fid)
    assert reader.info["sideVersion"] == 2
    got = reader.side_table(b"a")
    want = snapshot_stream(stream, CHUNK_K)
    assert len(got) == len(want) >= 2
    for g, w in zip(got, want):
        for k in w:
            assert g[k] == w[k], (k, g, w)
    assert reader.stream(b"a") == stream


# ---------------------------------------------------------------------------
# cross-segment batched leaf match (index/device/batch.py)
# ---------------------------------------------------------------------------


def test_batched_leaf_match_across_segments(tmp_path):
    from m3_tpu.index.query import conj, regexp, term
    from m3_tpu.utils.instrument import DEFAULT

    db = Database(
        str(tmp_path / "b"), num_shards=2, commitlog_enabled=False,
        index_device_options=IndexDeviceOptions(max_bytes=64 << 20),
    )
    db.create_namespace("ns", NamespaceOptions(block_size_nanos=HOUR))
    for blk in range(3):
        for i in range(16):
            tags = ((b"__name__", b"m"), (b"s", b"%03d" % i),
                    (b"blk", b"%d" % blk))
            db.write_tagged("ns", tags, T0 + blk * HOUR + i * NANOS, float(i))
    db.flush("ns", T0 + 10 * HOUR)
    q = conj(term(b"__name__", b"m"), regexp(b"s", b"00[0-7]"))
    ctr = DEFAULT.counter("index_batched_match_total")
    before = ctr.value
    dev = sorted(d.id for d in db.query_ids("ns", q, T0, T0 + 3 * HOUR).docs)
    host = sorted(
        d.id
        for d in db.query_ids("ns", q, T0, T0 + 3 * HOUR, force_host=True).docs
    )
    assert ctr.value == before + 1  # ONE launch for three segments
    assert dev == host and len(dev) == 24
    db.close()

@pytest.mark.parametrize("query,cap", [('pm{grp="n40"}', 64), ("pm", 96)])
def test_the_chips_point_kernel_serves_the_same_answers(bucket_db, monkeypatch, query, cap):
    """On the chip stage 4 decodes with ops/fused.decode_points_pallas (one
    device operation) where the CPU runs the lax.scan: here the plan's
    program is built as on the chip, with the kernel interpreted, and the
    reply compared bit for bit with the staged path over float and int
    lanes, at a bucketed capacity and at the whole segment's."""
    import functools

    import m3_tpu.device as device
    import m3_tpu.ops.fused as fused

    storage, eng = bucket_db
    kernel, traced = fused.decode_points_pallas, []

    def interpreted(*a, **kw):
        traced.append(kw["k"])
        return kernel(*a, **kw, interpret=True)

    monkeypatch.setattr(fused, "decode_points_pallas", interpreted)
    real_build = qplan._build_program

    @functools.wraps(real_build)
    def build_as_on_the_chip(ast, dims):
        with monkeypatch.context() as m:
            m.setattr(device, "on_tpu", lambda: True)
            return real_build.__wrapped__(ast, dims)

    monkeypatch.setattr(qplan, "_build_program", build_as_on_the_chip)
    storage.planner._cache.clear()
    try:
        st = _assert_bitexact(eng, query, SPAN)
        assert st.plan_misses == 1 and st.to_dict()["planLanesDecoded"] == cap
        assert traced, "the program was not built with the point kernel"
    finally:
        storage.planner._cache.clear()  # no later test meets this program
