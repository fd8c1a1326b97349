"""Device query plans: a query is ONE XLA program.

The recurring cost is the host round trip per dispatch — and the staged
executor pays it 4–6 times per query because `query/m3_storage.py` stitches the
stages with host-side types: the device index resolves doc ids to the
host, the host walks per-doc block keys, the resident pool plans a
gather, the decode dispatches, and consolidation runs a per-series
Python loop. Every piece already lives on device; this module composes
them inside ONE jit program per query *shape*:

    term binary-search match  (index/device/kernels.match_terms_traced)
      → postings bitmaps + bitwise AST set algebra (same kernels)
      → matched-doc compaction (cumsum over the doc bitmap) into ``cap``
        decode slots: the power-of-two bucket of the matched count, which
        one index resolve at plan build takes (a full match is the
        bitmap's width)
      → per-lane page-table gather  (plan tables uploaded once per
        (segment, block set) and cached)
      → resident chunked decode  (resident/gather assembly, then on the
        chip ops/fused.decode_points_pallas, ONE device operation, and
        off it the same step as ops/chunked.decode_chunked_lanes'
        lax.scan; straight from the pool's pages + packed side planes)
      → step-grid consolidation  (per step the newest valid point at
        or before it: a masked max over the point axis and one-hot
        select-sums, u64-pair compares, no gather — TPU gathers lower
        to per-element loops, see functions/temporal.py "window index
        machinery")

A range that reaches into the OPEN block (the live edge a dashboard
asks for) is served by the same program with an overlay stage: the
shards' ingest planes (ingest/buffer.py) hold every acknowledged row of
the open window as (ts, value) u32 pairs, so the program gathers the
matched series' lanes from them, masks them to the fetch range and
appends them after each series' decoded sealed points before stage 5.
Blocks partition time, so that is a concatenation, not a merge. Matchers
resolve over the sealed segment on the device as above and over the
open index block's mutable docs on the host; a series only the open
block knows takes a slot of its own, served from the planes alone, and a
range wholly inside the open block is served from the planes with no
sealed stage at all. Before the dispatch the plan syncs each matched
shard's staged tail (every acknowledged row is on the device when the
program reads it) and holds the planes under the buffer's lease. The
plan key names the open window's block start, never its extent: the
overlay's slot extent is a power-of-two bucket of the lanes' synced
counts, so a new tick changes no program. What the planes cannot stand
for falls back staged, counted by reason
(``m3tpu_query_plan_overlay_fallbacks_total``, ``OVERLAY_FALLBACKS``).

The program returns the CONSOLIDATED grid as raw (hi, lo) value pairs
plus validity masks; the host then runs the exact same float64
reconstruction the staged path uses (ops/decode.finalize_decode math)
and hands the grid to the unchanged engine pipeline (temporal
functions, aggregations). Bit-identity with the staged path is
therefore structural: both paths reconstruct values with the same f64
arithmetic and pick grid samples with the same upper-bound rule — the
property suite asserts exact equality, not tolerance.

Plan cache: an LRU keyed by (namespace, matchers, block set, grid
shape). Entries carry the uploaded plan-vector tables and revalidate
per execution against pool eviction/invalidation counters, shard
fileset epochs, and index-segment identity — a segment swap, volume
bump, or resident eviction invalidates the plan (regression-tested).
Ineligible queries fall back to the staged executor transparently with
an EXPLAIN routing reason per cause (host-regexp leaf, non-resident
block, a buffer over a sealed block, multi-segment index, ...).

Knobs:

    M3_TPU_QUERY_PLAN          "0" disables planning entirely
    M3_TPU_QUERY_PLAN_CACHE    LRU entries (default 64)
"""

from __future__ import annotations

import functools
import os
import threading
from collections import OrderedDict
from contextlib import contextmanager

import numpy as np

from ..index.device.kernels import pad_pow2
from ..utils.instrument import DEFAULT as METRICS
from ..utils.instrument import KernelProfiler
from ..utils.trace import TRACER

_M_HITS = METRICS.counter(
    "query_plan_hits_total",
    "fetches served by a cached device query plan (one fused dispatch)",
)
_M_MISSES = METRICS.counter(
    "query_plan_misses_total",
    "device query plans built (cache miss: first sighting, or a stamp "
    "mismatch after segment swap / volume bump / eviction)",
)
_M_FALLBACKS = METRICS.counter(
    "query_plan_fallbacks_total",
    "fetches that degraded to the staged executor (EXPLAIN records the "
    "routing reason per cause)",
)
_M_COMPILES = METRICS.counter(
    "query_plan_compiles_total",
    "fused plan programs compiled (one per distinct query/plan shape)",
)
_M_ERRORS = METRICS.counter(
    "query_plan_errors_total",
    "device plan executions that raised and fell back staged (the "
    "staged path is always correct; errors are counted, never surfaced)",
)
_M_COALESCED = METRICS.counter(
    "query_plan_coalesced_total",
    "fetches served by joining another concurrent query's in-flight "
    "device scan (N concurrent identical fetches -> 1 dispatch)",
)


_M_OVERLAY_LANES = METRICS.counter(
    "query_plan_overlay_lanes_total",
    "open-block lanes plan dispatches read from the shards' ingest planes",
)
_M_OVERLAY_SYNC_ROWS = METRICS.counter(
    "query_plan_overlay_sync_rows_total",
    "acknowledged rows a plan synced to the ingest planes before its "
    "dispatch (the staged tail the write path had not synced yet)",
)

# why a range that reaches into the open block went staged: a buffer over
# a sealed block (a merge, not a concatenation), more than one buffered
# block in range, a shard without ingest planes, an out-of-order lane, a
# row the planes refused (no lane, no slot, no window), a lane the device
# decoder bailed on (its host re-read knows no planes), or a lane table
# that moved between the overlay's lookup and its read
OVERLAY_FALLBACKS = ("buffer-overlay", "open-windows", "ingest-off",
                     "dirty-lane", "spilled-row", "err-lane", "raced")
_M_OVERLAY_FALLBACKS = {
    r: METRICS.counter(
        "query_plan_overlay_fallbacks_total",
        "ranges reaching into buffered data that the plan's overlay could "
        "not serve, by reason (the staged path served them)",
        labels={"reason": r},
    )
    for r in OVERLAY_FALLBACKS
}


def _overlay_fallback(reason: str) -> "Ineligible":
    """Count one overlay fallback; the Ineligible to raise for it (a
    buffer over a sealed block keeps the plan's older reason)."""
    _M_OVERLAY_FALLBACKS[reason].inc()
    return Ineligible(
        "buffer-overlay" if reason == "buffer-overlay" else f"overlay:{reason}")


def _plan_builds(cap: int):
    return METRICS.counter(
        "query_plan_builds_total", "device query plans built, by decode "
        "capacity: the power-of-two bucket of the matched-series count (one "
        "compiled program a query shape and bucket)",
        labels={"cap": str(cap)},
    )


# the newest built plan's dimensions (PERF.md section 3): every decoded
# lane pays the window of the segment's widest lane, and the lanes decoded
# are the bucket of what matched (``cap``), not the segment
_G_PLAN_DIMS = {
    "window_words": METRICS.gauge(
        "query_plan_window_words", "cw of the newest built plan: 32-bit "
        "words a chunk's decode window spans, set by the segment's widest lane"),
    "chunks": METRICS.gauge(
        "query_plan_chunks", "chunks per lane of the newest built plan"),
    "decode_slots": METRICS.gauge(
        "query_plan_decode_slots", "lanes the newest built plan decodes a "
        "dispatch (cap x blocks; cap is the matched-series count rounded up "
        "to a power of two)"),
    "gather_words": METRICS.gauge(
        "query_plan_gather_words", "words the newest built plan's window "
        "gather moves a dispatch (decode_slots x chunks x window_words)"),
}

# the fused program's dispatch seam: compile attribution + sampled
# wall-time under the SAME profiler contract as every other kernel, and
# the per-query device_dispatches counter ticks here — exactly once per
# plan-served fetch
PROF = KernelProfiler("query_plan")

_SENTINEL_GRID = 8  # minimum padded grid length
_MIN_CAP = 8  # minimum decode capacity (matched-series slots)
# the overlay reads the first ``width`` slots of each matched lane: the
# power-of-two bucket of the lanes' synced rows, at least this many, so a
# tick (one more row a lane) compiles nothing until the bucket fills
_MIN_OVERLAY_WIDTH = 8
# grid steps per compare-and-reduce pass of stage 5, at least: a power of
# two no larger than _SENTINEL_GRID, so it divides every padded grid
_GRID_TILE = 8
# [cap, steps, t_pts] compare cells a pass of stage 5 walks: what a
# whole-segment plan walks at _GRID_TILE steps a pass (4,064 x 8 x 736 =
# 2^24.5, the size measured best on the v5e: PERF.md, PR 28). A plan whose
# cap follows a small match takes more steps a pass, the whole grid where
# it fits: each pass is ~13 device operations whatever its size
_GRID_PASS_CELLS = 1 << 25


def _bucket_window_words(cw: int) -> int:
    """``cw`` rounded up to four significant bits (a multiple of 1 below
    16 words, of 2 below 32, of 4 below 64, of 8 below 128: at most an
    eighth more). The widest chunk span of a segment follows the VALUES by
    a word or two (TSBS devops at 40 hosts: 73, 74, 75 or 76 words over 60
    seeds), every distinct width is a plan program to compile, and the
    window gather's device time is proportional to it: the same deployment
    runs the same program, and does the same work, whatever the samples
    were. The price is the wider gather: 19-20 % a request where 73-74
    became 80 (PERF.md section 6, PR 29), until the gather stops costing
    by the word (ROADMAP S8)."""
    step = 1 << max(cw.bit_length() - 4, 0)
    return -(-cw // step) * step


def plan_enabled() -> bool:
    return os.environ.get("M3_TPU_QUERY_PLAN", "1") != "0"


def _cache_cap() -> int:
    try:
        return max(int(os.environ.get("M3_TPU_QUERY_PLAN_CACHE", "64")), 1)
    except ValueError:
        return 64


# ---------------------------------------------------------------------------
# force-staged probe (the bit-identity surface CI diffs against)
# ---------------------------------------------------------------------------

_FORCE = threading.local()


@contextmanager
def force_staged():
    """Disable device plans for this thread's queries (the parity probe:
    tools/check_pipeline.py runs every query twice, fused and
    force-staged, and asserts bit-identical results)."""
    prev = getattr(_FORCE, "on", False)
    _FORCE.on = True
    try:
        yield
    finally:
        _FORCE.on = prev


def staged_forced() -> bool:
    return getattr(_FORCE, "on", False)


class Ineligible(Exception):
    """Query/plan state the fused pipeline does not cover — the caller
    records ``reason`` in EXPLAIN routing and runs the staged path."""

    def __init__(self, reason: str) -> None:
        super().__init__(reason)
        self.reason = reason


# ---------------------------------------------------------------------------
# AST shape extraction
# ---------------------------------------------------------------------------


def _ast_shape(q, arrays, leaves: list, ranges: list):
    """Index query AST -> a hashable shape tree whose leaves reference
    slots in ``leaves`` (exact-match values, one row each) and
    ``ranges`` ((lo, hi) global term ranges, host-narrowed). Static
    per-leaf postings-slab bounds ride the tree so the program builder
    can bake them. Raises Ineligible for nodes the device cannot model
    (general regexps keep their automaton on the host)."""
    from ..index.device.segment import classify_regexp
    from ..index.query import (
        AllQuery,
        ConjunctionQuery,
        DisjunctionQuery,
        FieldQuery,
        NegationQuery,
        RegexpQuery,
        TermQuery,
    )

    def field_slab(field: bytes):
        _, _, ds, de = arrays.fields.get(field, (0, 0, 0, 0))
        from ..index.device import kernels

        return ds, kernels.pad_pow2(de - ds)

    def leaf(field: bytes, values: list):
        slot = len(leaves)
        leaves.extend((field, v) for v in values)
        ds, slab = field_slab(field)
        return ("terms", slot, len(values), ds, slab)

    def rng(field: bytes, lo: int, hi: int):
        ridx = len(ranges)
        ranges.append((lo, hi))
        ds, slab = field_slab(field)
        return ("range", ridx, ds, slab)

    def walk(node):
        if isinstance(node, TermQuery):
            return leaf(node.field, [node.value])
        if isinstance(node, RegexpQuery):
            kind, val = classify_regexp(node.pattern)
            if kind == "literal":
                return leaf(node.field, [val])
            if kind == "alternation":
                return leaf(node.field, list(val))
            if kind == "prefix" and arrays.dot_safe:
                start, count = arrays.fields.get(node.field, (0, 0, 0, 0))[:2]
                lo, hi = _prefix_bounds(arrays, val, start, start + count)
                return rng(node.field, lo, hi)
            raise Ineligible("host-regexp-leaf")
        if isinstance(node, FieldQuery):
            start, count = arrays.fields.get(node.field, (0, 0, 0, 0))[:2]
            return rng(node.field, start, start + count)
        if isinstance(node, AllQuery):
            return ("all",)
        if isinstance(node, ConjunctionQuery):
            pos = [walk(s) for s in node.queries
                   if not isinstance(s, NegationQuery)]
            negs = [walk(s.query) for s in node.queries
                    if isinstance(s, NegationQuery)]
            return ("and", tuple(pos), tuple(negs))
        if isinstance(node, DisjunctionQuery):
            return ("or", tuple(walk(s) for s in node.queries))
        if isinstance(node, NegationQuery):
            return ("not", walk(node.query))
        raise Ineligible(f"unsupported-node:{type(node).__name__}")

    return walk(q)


def _prefix_bounds(arrays, prefix: bytes, lo: int, hi: int):
    """Host prefix narrow over the key-matrix mirror — identical to
    DeviceSegment._prefix_range (shared compare in kernels.py)."""
    from ..index.device import kernels
    from ..index.segment import prefix_upper

    width = 4 * arrays.k_words
    if len(prefix) > width:
        return lo, lo
    pk, pl = kernels.build_term_keys([prefix], arrays.k_words)
    lo = kernels.host_lower_bound(
        arrays.host_keys, arrays.host_lens, lo, hi, pk[0], int(pl[0])
    )
    up = prefix_upper(prefix)
    if up is not None and len(up) <= width:
        uk, ul = kernels.build_term_keys([up], arrays.k_words)
        hi = kernels.host_lower_bound(
            arrays.host_keys, arrays.host_lens, lo, hi, uk[0], int(ul[0])
        )
    return lo, hi


# ---------------------------------------------------------------------------
# the fused program (built once per shape, cached)
# ---------------------------------------------------------------------------


def _consolidate_last(ts, planes, valid, g, flo, fhi, lb):
    """Stage 5, traced: decoded rows onto the step grid by the 'last' rule
    of engine.consolidate_row — per (row, step j) the newest valid point
    with ts <= t_j, kept while t_j - ts < lookback.

    ``ts`` (u64 pair) and every plane are [cap, t_pts]; ``g`` is the
    [t_grid] step pair; ``flo``/``fhi``/``lb`` scalar pairs. Returns
    (counts [cap], the planes on [cap, t_grid], ok [cap, t_grid]).

    NO gather: TPU gathers (take_along_axis on [S, T]) lower to
    per-element loops (functions/temporal.py, "window index machinery").
    A row's valid timestamps ascend, so the wanted point is the valid
    one of LARGEST INDEX with ts <= t_j: a masked max over the point
    axis, and each plane rides along as a one-hot select-sum. Integer
    compares, selects and adds only. XLA fuses broadcast, compare and
    reduce, so the [cap, t_grid, t_pts] cube never exists in memory;
    lax.map walks the grid _GRID_PASS_CELLS compare cells at a time
    (_GRID_TILE steps for a whole segment: measured faster on the v5e
    than one reduce over the whole grid, PERF.md, PR 28; the whole grid
    in one pass where cap is small)."""
    import jax
    import jax.numpy as jnp

    from ..ops import u64

    i32 = jnp.int32
    # range mask mirrors the staged fetch window [fetch_lo, fetch_hi)
    valid = valid & ~u64.lt_u(ts, flo) & u64.lt_u(ts, fhi)
    counts = jnp.sum(valid.astype(i32), axis=1)
    rows = lambda x: x[:, None, :]
    tsr, live = (rows(ts[0]), rows(ts[1])), rows(valid)

    def tile(gt):
        gt = (gt[0][None, :], gt[1][None, :])
        gc = (gt[0][..., None], gt[1][..., None])
        le = live & ~u64.lt_u(gc, tsr)  # ts_i <= t_j
        iota = jax.lax.broadcasted_iota(i32, le.shape, 2)
        pick = jnp.max(jnp.where(le, iota, -1), axis=2)
        hot = iota == pick[..., None]
        sel = lambda x: jnp.sum(
            jnp.where(hot, rows(x), 0), axis=2, dtype=x.dtype
        )
        age = u64.sub(gt, (sel(ts[0]), sel(ts[1])))
        ok = (pick >= 0) & u64.lt_u(age, lb)
        return tuple(sel(x) for x in planes), ok

    t_grid = g[0].shape[0]
    # both powers of two, so the pass divides the padded grid
    fit = _GRID_PASS_CELLS // max(valid.size, 1)
    steps = min(t_grid, max(_GRID_TILE, 1 << max(fit.bit_length() - 1, 0)))
    if steps == t_grid:
        out, ok = tile(g)
        return counts, out, ok
    out, ok = jax.lax.map(tile, tuple(x.reshape(-1, steps) for x in g))
    # [passes, cap, steps] -> [cap, t_grid]
    flat = lambda x: jnp.moveaxis(x, 0, 1).reshape(x.shape[1], -1)
    return counts, tuple(flat(x) for x in out), flat(ok)



def _overlay_points(overlay, width: int):
    """The overlay stage, traced: the matched series' open-block rows from
    the shards' ingest planes, one row per decode slot. ``overlay`` is
    (cols, counts, lanes, slot): per source shard its ``[4, lanes, slots]``
    u32 planes (ts hi, ts lo, value hi, value lo) and ``[lanes]`` synced
    counts, the ``[n_src, cap_s]`` lanes each source's matched series sit
    in, and per slot its row of the sources' gathered lanes (the last row,
    all zero, where a slot has no lane). Returns (ts pair, value hi,
    value lo, valid), each ``[cap, width]``: the first ``width`` slots of
    each lane, valid below its synced count.

    Every gather takes whole rows of a 2-D plane, the one gather form the
    chip does at memory speed (PERF.md section 6): a gather of
    ``[4, lanes, slots]`` along its middle axis made the compiler lay the
    whole 16 MiB plane out anew, a shard a request (PERF.md section 5)."""
    import jax
    import jax.numpy as jnp

    cols, counts, lanes, slot = overlay
    i32 = jnp.int32
    planes = []
    for p in range(4):
        rows = [jnp.take(c[p], lanes[i], axis=0)[:, :width]
                for i, c in enumerate(cols)]
        rows.append(jnp.zeros((1, width), jnp.uint32))
        planes.append(jnp.take(jnp.concatenate(rows), slot, axis=0))
    ns = [n[lanes[i]] for i, n in enumerate(counts)] + [jnp.zeros(1, i32)]
    n = jnp.concatenate(ns)[slot]
    valid = jax.lax.broadcasted_iota(i32, (slot.shape[0], width), 1) < n[:, None]
    return (planes[0], planes[1]), planes[2], planes[3], valid


def _unpack_request_traced(request, t_grid: int):
    """What ``_pack_request`` packed: the step grid as (hi, lo) and the
    fetch bounds and lookback as (hi, lo) pairs."""
    g = (request[:t_grid], request[t_grid:2 * t_grid])
    flo, fhi, lb = (
        (request[2 * t_grid + 2 * i], request[2 * t_grid + 2 * i + 1])
        for i in range(3)
    )
    return g, flo, fhi, lb


def _pack_outputs(*outs):
    """The program's outputs as ONE u32 array (``_unpack_reply``)."""
    import jax
    import jax.numpy as jnp

    return jnp.concatenate([
        x.reshape(-1) if x.dtype == jnp.uint32
        else jax.lax.bitcast_convert_type(x.astype(jnp.int32), jnp.uint32).reshape(-1)
        for x in outs
    ])


@functools.lru_cache(maxsize=64)
def _build_program(ast, dims, odims=None):
    """ONE jitted program for a (query shape, plan shapes) class. ``ast``
    is the hashable shape tree (leaf slots + static slab bounds baked
    in); ``dims`` the static dimension tuple. Runtime VALUES (query
    keys, range bounds, pool buffers, plan tables, grid) are inputs, so
    one compilation serves every query of the same shape.

    ``odims`` (cap, sources, cap_s, width, t_grid) adds the overlay
    stage: the program then takes ``overlay=`` (``_overlay_points``) and
    appends its rows after the decoded points. Without ``odims`` the
    program is the sealed-only one, op for op."""
    import jax
    import jax.numpy as jnp

    from ..index.device.kernels import (
        bitmap_from_term_range_traced,
        bitmap_from_terms_traced,
        match_terms_traced,
    )
    from .. import device
    from ..ops.chunked import decode_chunked_lanes
    from ..ops.fused import decode_points_pallas
    from ..resident.gather import assemble_lanes_traced

    (n_words, n_docs_pad, cap, n_blocks, c, k, cw, lp, sl,
     page_words, spc, t_grid) = dims
    t_pts = n_blocks * c * k
    # on the chip the K-record decode is one Pallas operation; the lax.scan
    # form (the same step, the same bits) is ~29 small operations a record
    # whatever the lane count, and since cap follows the match that
    # overhead was the decode's whole time (and 2/3 of a request's device
    # operations: PERF.md section 5, PR 30)
    decode = decode_points_pallas if device.on_tpu() else decode_chunked_lanes

    def program(term_keys, term_lens, post_idx, post_data, all_words,
                q_keys, q_lens, q_lo, q_hi, r_lo, r_hi,
                pool_words, side_words,
                t_pages, t_sides, t_chunks, t_bits, t_bhi, t_blo,
                request, overlay=None):
        i32 = jnp.int32
        # what a request brings, in one array (one transfer to the device,
        # where two arrays and six scalars were eight: _pack_request)
        (g_hi, g_lo), flo, fhi, lb = _unpack_request_traced(request, t_grid)

        # ---- stage 1: batched term match (every exact leaf, one search)
        if q_keys.shape[0]:
            gis = match_terms_traced(
                term_keys, term_lens, q_lo, q_hi, q_keys, q_lens
            )
        else:
            gis = jnp.zeros(0, i32)

        # ---- stage 2: bitmap algebra compiled from the AST shape
        def eval_node(node):
            tag = node[0]
            if tag == "terms":
                _, slot, n, ds, slab = node
                rows = gis[slot : slot + n]
                b_pad = pad_pow2(n)
                if b_pad != n:
                    rows = jnp.concatenate(
                        [rows, jnp.full(b_pad - n, -1, i32)]
                    )
                return bitmap_from_terms_traced(
                    post_idx, post_data, rows, jnp.int32(ds), n_words, slab
                )
            if tag == "range":
                _, ridx, ds, slab = node
                return bitmap_from_term_range_traced(
                    post_idx, post_data, r_lo[ridx], r_hi[ridx],
                    jnp.int32(ds), n_words, slab,
                )
            if tag == "all":
                return all_words
            if tag == "and":
                _, pos, negs = node
                if pos:
                    acc = eval_node(pos[0])
                    for s in pos[1:]:
                        acc = acc & eval_node(s)
                else:
                    acc = all_words
                for s in negs:
                    acc = acc & ~eval_node(s)
                return acc
            if tag == "or":
                acc = jnp.zeros(n_words, jnp.uint32)
                for s in node[1]:
                    acc = acc | eval_node(s)
                return acc
            if tag == "not":
                return all_words & ~eval_node(node[1])
            raise AssertionError(node)

        bitmap = eval_node(ast)

        # ---- stage 3: matched-doc compaction (doc bitmap -> dense slots)
        bits = (
            (bitmap[:, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
        ).reshape(-1)[:n_docs_pad] != 0
        ncum = jnp.cumsum(bits.astype(i32))
        n_matched = ncum[-1] if n_docs_pad else jnp.int32(0)
        slot = ncum - 1
        sent = n_docs_pad  # sentinel row: the all-zero lane block
        sel = (
            jnp.full(cap + 1, sent, i32)
            .at[jnp.where(bits, slot, cap)]
            .set(jnp.arange(n_docs_pad, dtype=i32), mode="drop")[:cap]
        )

        # ---- stage 4: per-lane plan gather + resident assembly + decode
        lane_rows = (
            sel[:, None] * n_blocks + jnp.arange(n_blocks, dtype=i32)[None, :]
        ).reshape(-1)
        kw = assemble_lanes_traced(
            pool_words, side_words,
            t_pages[lane_rows], t_sides[lane_rows], t_chunks[lane_rows],
            t_bits[lane_rows], t_bhi[lane_rows], t_blo[lane_rows],
            c=c, cw=cw, w=page_words, spc=spc,
        )
        res = decode(**kw, k=k)

        rs = lambda x: x.reshape(cap, t_pts)
        ts = (rs(res.ts_hi), rs(res.ts_lo))
        vhi, vlo = rs(res.val_hi), rs(res.val_lo)
        pif, mlt = rs(res.point_is_float), rs(res.mult)
        valid = rs(res.valid)
        err = jnp.any(res.err.reshape(cap, n_blocks * c), axis=1)
        pif = pif.astype(i32)

        if odims is not None:
            # ---- overlay: the open block's rows after the sealed ones
            # (blocks partition time, so every row stays time-ascending);
            # values travel as float64 bit patterns: float mode, no mult
            ots, ovh, ovl, ovalid = _overlay_points(overlay, odims[3])
            cat = lambda a, b: jnp.concatenate([a, b], axis=1)
            ts = (cat(ts[0], ots[0]), cat(ts[1], ots[1]))
            vhi, vlo = cat(vhi, ovh), cat(vlo, ovl)
            pif = cat(pif, jnp.ones(ovh.shape, i32))
            mlt = cat(mlt, jnp.zeros(ovh.shape, mlt.dtype))
            valid = cat(valid, ovalid)

        # ---- stage 5: consolidation onto the step grid
        counts, (g_vh, g_vl, g_pf, g_ml), ok = _consolidate_last(
            ts, (vhi, vlo, pif, mlt), valid,
            (g_hi, g_lo), flo, fhi, lb,
        )
        # ONE array out, as one came in (_unpack_reply): every output read
        # back on its own is a blocking call that hands the interpreter
        # lock away, and beside other handler threads each one ends in a
        # wait to get it back (PERF.md section 6, PR 34)
        return _pack_outputs(n_matched, bitmap, counts, err,
                             g_vh, g_vl, g_pf, g_ml, ok)

    _M_COMPILES.inc()
    return jax.jit(program)


@functools.lru_cache(maxsize=64)
def _build_overlay_program(odims):
    """The plan program of a range wholly inside the open block: no
    sealed stage, the overlay's rows alone onto the step grid (outputs
    as the sealed program's, with an empty bitmap and no error lanes)."""
    import jax
    import jax.numpy as jnp

    cap, _n_src, _cap_s, width, t_grid = odims

    def program(request, overlay):
        i32 = jnp.int32
        g, flo, fhi, lb = _unpack_request_traced(request, t_grid)
        ts, vhi, vlo, valid = _overlay_points(overlay, width)
        counts, (g_vh, g_vl, g_pf, g_ml), ok = _consolidate_last(
            ts, (vhi, vlo, jnp.ones(vhi.shape, i32), jnp.zeros(vhi.shape, i32)),
            valid, g, flo, fhi, lb,
        )
        return _pack_outputs(jnp.int32(0), jnp.zeros(0, jnp.uint32), counts,
                             jnp.zeros(cap, bool), g_vh, g_vl, g_pf, g_ml, ok)

    _M_COMPILES.inc()
    return jax.jit(program)


def _pack_request(grid: np.ndarray, t_grid: int, fetch_lo: int,
                  fetch_hi: int, lookback_nanos: int) -> np.ndarray:
    """A request's own arguments of the plan program as ONE u32 array:
    the padded step grid as hi and lo words, then the fetch bounds and the
    lookback as (hi, lo) pairs. Each host array or scalar handed to the
    program is a transfer of its own, 0.17 ms apiece on the chip (PERF.md
    section 6, PR 34)."""
    g = np.zeros(t_grid, np.int64)
    g[: len(grid)] = grid
    if len(grid):
        g[len(grid):] = grid[-1]  # padded steps are discarded
    g = g.astype(np.uint64)
    out = np.empty(2 * t_grid + 6, np.uint32)
    out[:t_grid] = g >> np.uint64(32)
    out[t_grid:2 * t_grid] = g & np.uint64(0xFFFFFFFF)
    for i, v in enumerate((fetch_lo, fetch_hi, lookback_nanos)):
        v = int(v) & ((1 << 64) - 1)
        out[2 * t_grid + 2 * i] = v >> 32
        out[2 * t_grid + 2 * i + 1] = v & 0xFFFFFFFF
    return out


def _unpack_reply(packed: np.ndarray, n_words: int, cap: int, t_grid: int):
    """The plan program's one output array (the count, the bitmap's
    ``n_words``, counts and error flags a slot, then five [cap, t_grid]
    planes) -> (bitmap, n_matched, counts, err, g_vh, g_vl, g_pf, g_ml,
    ok), as views of it."""
    n_matched, rest = int(packed[0]), packed[1:]
    bitmap, rest = rest[:n_words], rest[n_words:]
    counts, err, rest = rest[:cap].view(np.int32), rest[cap:2 * cap], rest[2 * cap:]
    g_vh, g_vl, g_pf, g_ml, ok = rest.reshape(5, cap, t_grid)
    return (bitmap, n_matched, counts, err != 0, g_vh, g_vl, g_pf,
            g_ml.view(np.int32), ok != 0)


def _finalize_grid(vhi, vlo, pif, mult, ok) -> np.ndarray:
    """Consolidated pair grid -> float64 values, with the EXACT
    reconstruction arithmetic of ops/decode.finalize_decode (f64 bit
    view for float-mode points, int64/10^mult for int-mode) so the fused
    grid matches the staged consolidate output bit for bit."""
    # m3lint: disable=M3L010 -- host-side dtype view: inputs were already finalized to host ndarrays by _execute's single readback; no device sync here
    raw = (np.asarray(vhi, np.uint64) << np.uint64(32)) | np.asarray(
        vlo, np.uint64
    )
    float_vals = raw.view(np.float64)
    int_vals = raw.astype(np.int64).astype(np.float64)
    # m3lint: disable=M3L010 -- host-side dtype view of already-host mult (see raw above)
    scale = np.power(10.0, np.asarray(mult, np.int64))
    # m3lint: disable=M3L010 -- host-side dtype view of already-host pif (see raw above)
    values = np.where(np.asarray(pif, bool) != 0, float_vals, int_vals / scale)
    # m3lint: disable=M3L010 -- host-side dtype view of already-host ok (see raw above)
    return np.where(np.asarray(ok, bool), values, np.nan)


# ---------------------------------------------------------------------------
# plan entries + planner
# ---------------------------------------------------------------------------


class _PlanEntry:
    """One cached plan: the compiled program, the uploaded plan-vector
    tables for its (segment, block set), pre-built query-key inputs, and
    the validity stamp it revalidates against per execution."""

    __slots__ = (
        "ast", "dims", "fn", "seg", "arrays", "inputs", "tables",
        "cap", "stamp", "chunk_k", "matched", "post", "t_grid", "overlay",
    )


class _Overlay:
    """What a plan reads of the open block, for one state of it (``stamp``:
    the open index block's docs and every shard's lane table): the
    matched series in slot order (the sealed segment's, then those only
    the open block knows), and for every shard holding some of them its
    lane table's stamp, their lanes and the series without one. Rebuilt
    when the stamp moves (a new series, a new lane); a tick moves
    nothing."""

    __slots__ = (
        "stamp", "n_sealed", "matched", "cap", "reads", "sources", "cap_s",
        "lanes", "slot", "n_lanes",
    )


class _Flight:
    """One in-flight coalesced device scan: the leader executes, every
    follower that arrives while it runs blocks on ``event`` and shares
    the result (or the exception — an Ineligible leader means every
    follower is ineligible the same way and runs staged itself)."""

    __slots__ = ("event", "result", "error")

    def __init__(self) -> None:
        self.event = threading.Event()
        self.result = None
        self.error: BaseException | None = None


class Planner:
    """Per-storage device query planner with an LRU plan cache."""

    def __init__(self, db, namespace: str) -> None:
        self.db = db
        self.namespace = namespace
        self._cache: "OrderedDict[tuple, _PlanEntry]" = OrderedDict()
        self._lock = threading.Lock()
        # scan coalescing (singleflight): identical concurrent fetches
        # keyed by (plan key, window, grid) share ONE gathered dispatch
        self._flights: dict[tuple, _Flight] = {}
        # plan key -> the matched count a plan's own program reported when
        # it exceeded the capacity its build had counted (_drop): the
        # rebuild's floor, so a persistent disagreement costs one fallback
        self._reported: dict[tuple, int] = {}
        # cache stats for /debug surfaces
        self.hits = 0
        self.misses = 0
        self.fallbacks = 0
        self.coalesced = 0

    def evict_stale(self) -> int:
        """Drop cached plans whose pool/fileset stamp no longer holds —
        called by the fallback path so entries built against evicted or
        invalidated state release their pinned device tables (and the
        index-segment arrays they keep alive) instead of lingering until
        LRU displacement. Segment-identity staleness is covered too: a
        swapped segment's plan was stamped with pool/epoch state that
        moved with the swap's invalidations. O(cache), cache is small."""
        pool = getattr(self.db, "resident_pool", None)
        namespaces = getattr(self.db, "namespaces", None)
        if pool is None or namespaces is None or self.namespace not in namespaces:
            return 0
        ns = namespaces[self.namespace]
        live = (
            pool.evictions, pool.invalidations,
            tuple(sh.fileset_epoch for sh in ns.shards),
        )
        with self._lock:
            stale = [
                k for k, e in self._cache.items() if e.stamp[2:] != live
            ]
            for k in stale:
                del self._cache[k]
            if stale:
                self._reported.clear()  # counts of segments that are gone
        return len(stale)

    def _drop(self, entry, n_matched: int) -> None:
        """Forget a cached plan whose stamp still holds (``_execute``'s
        capacity guard): the next request for its key rebuilds, at no less
        than the ``n_matched`` this plan's program counted."""
        with self._lock:
            for k in [k for k, e in self._cache.items() if e is entry]:
                del self._cache[k]
                self._reported[k] = n_matched

    def run(self, matchers, fetch_lo: int, fetch_hi: int, grid: np.ndarray,
            lookback_nanos: int):
        """Serve one fetch through a device plan. Returns
        (metas, values_f64 [S, T], datapoints) or raises Ineligible with
        the routing reason (the caller records it and runs staged).
        ``grid`` is the engine's consolidation timestamp vector.

        Concurrent identical fetches COALESCE: while one thread's scan is
        in flight, any other thread arriving with the same (plan key,
        window, grid) joins it instead of dispatching its own — N
        concurrent queries over the same resident blocks cost ONE device
        dispatch (the in-flight execution is the batching window; a
        joiner records plan_coalesced and zero deviceDispatches)."""
        from . import stats
        from .m3_storage import matchers_to_index_query

        # stages (utils/trace.py; PERF.md section 3): plan.lookup here and
        # at the cache in _run_leader, then plan.coalesce_wait (a
        # follower) or plan.build (a miss), plan.enqueue,
        # plan.device_wait, plan.finalize
        with TRACER.stage("plan.lookup"):
            if not plan_enabled():
                raise Ineligible("plan-disabled")
            if staged_forced():
                raise Ineligible("force-staged")
            db = self.db
            namespaces = getattr(db, "namespaces", None)
            if namespaces is None or self.namespace not in namespaces:
                raise Ineligible("remote-storage")
            pool = getattr(db, "resident_pool", None)
            if pool is None or not pool.enabled:
                raise Ineligible("resident-pool-disabled")
            ns = namespaces[self.namespace]
            if ns.index is None:
                raise Ineligible("no-index")
            open_bs, buffered = self._open_block(ns, fetch_lo, fetch_hi)
            seg, arrays, mutable = self._index_segments(
                ns.index, fetch_lo, fetch_hi, open_bs)
            blocks = self._block_set(ns, pool, fetch_lo, fetch_hi)
            if open_bs is not None and blocks and blocks[-1][1] >= open_bs:
                # a buffer over a sealed block: a merge the plan cannot do
                raise _overlay_fallback("buffer-overlay")
            if not blocks and (seg is not None or open_bs is None):
                raise Ineligible("no-sealed-blocks")
            if blocks and seg is None:
                raise Ineligible("no-index-segment")
            live = None if open_bs is None else (open_bs, buffered, mutable)

            q = matchers_to_index_query(matchers)
            t_grid = pad_pow2(len(grid), _SENTINEL_GRID)
            # an open block is named by its start, never by its extent: a
            # new tick finds the same plan
            key = (
                self.namespace,
                tuple((m.name, m.op, m.value) for m in matchers),
                tuple(blocks),
                t_grid,
            ) + (() if open_bs is None else (open_bs,))
            if live is not None:
                # no coalescing at the live edge: a write acknowledged
                # after the leader synced would be missing from a
                # follower's answer
                leader, fl = True, None
            else:
                fkey = key + (fetch_lo, fetch_hi, grid.tobytes(), lookback_nanos)
                with self._lock:
                    fl = self._flights.get(fkey)
                    leader = fl is None
                    if leader:
                        fl = self._flights[fkey] = _Flight()
        if fl is None:
            return self._run_leader(
                key, q, seg, arrays, ns, pool, blocks, t_grid,
                fetch_lo, fetch_hi, grid, lookback_nanos, live,
            )
        if not leader:
            # join the in-flight identical scan: this query dispatches
            # nothing (device_dispatches ticks on the leader's thread)
            with TRACER.stage("plan.coalesce_wait"):
                fl.event.wait()
            if fl.error is not None:
                if isinstance(fl.error, Ineligible):
                    # a fresh instance per thread: the reason is shared,
                    # the traceback must not be
                    raise Ineligible(fl.error.reason)
                raise fl.error
            self.coalesced += 1
            _M_COALESCED.inc()
            stats.add(plan_coalesced=1)
            matched, values, datapoints, err_rows = fl.result
            # own values array per follower: the err-lane stitch and
            # downstream transforms may write rows
            return matched, np.array(values, copy=True), datapoints, err_rows
        try:
            result = self._run_leader(
                key, q, seg, arrays, ns, pool, blocks, t_grid,
                fetch_lo, fetch_hi, grid, lookback_nanos,
            )
            fl.result = result
            return result
        except BaseException as exc:
            fl.error = exc
            raise
        finally:
            with self._lock:
                self._flights.pop(fkey, None)
            fl.event.set()

    def _run_leader(self, key, q, seg, arrays, ns, pool, blocks, t_grid,
                    fetch_lo: int, fetch_hi: int, grid: np.ndarray,
                    lookback_nanos: int, live=None):
        from . import stats

        with TRACER.stage("plan.lookup"):
            with self._lock:
                entry = self._cache.get(key)
                if entry is not None:
                    self._cache.move_to_end(key)
            hit = entry is not None and self._valid(entry, seg, arrays, ns, pool)
        if hit:
            self.hits += 1
            _M_HITS.inc()
            stats.add(plan_hits=1)
        else:
            with TRACER.stage("plan.build"):
                if seg is None:
                    entry = self._build_open_only(ns, pool, t_grid)
                else:
                    entry = self._build(q, seg, arrays, ns, pool, blocks,
                                        t_grid, self._reported.get(key, 0))
            with self._lock:
                self._cache[key] = entry
                self._cache.move_to_end(key)
                while len(self._cache) > _cache_cap():
                    self._cache.popitem(last=False)
            self.misses += 1
            _M_MISSES.inc()
            stats.add(plan_misses=1)
        if live is not None:
            return self._execute_overlay(entry, ns, q, live, fetch_lo,
                                         fetch_hi, grid, lookback_nanos)
        return self._execute(entry, ns, fetch_lo, fetch_hi, grid,
                             lookback_nanos)

    # -- eligibility pieces ------------------------------------------------

    @staticmethod
    def _open_block(ns, fetch_lo: int, fetch_hi: int):
        """``(block start, shard ids)`` of the one block in range that
        buffers hold points in, and the shards that hold them; ``(None,
        frozenset())`` where no buffer overlaps the range."""
        opened: set[int] = set()
        shards = []
        for shard in ns.shards:
            got = shard.buffered_blocks(fetch_lo, fetch_hi)
            if got:
                opened.update(got)
                shards.append(shard.id)
        if not opened:
            return None, frozenset()
        if len(opened) > 1:
            raise _overlay_fallback("open-windows")
        return opened.pop(), frozenset(shards)

    @staticmethod
    def _index_segments(index, fetch_lo: int, fetch_hi: int, open_bs):
        """The range's ONE sealed, device-resident index segment (None
        where the range lies wholly in the open block), and the open
        block's mutable segment, which the host resolves (None where it
        holds no docs). Mutable docs of any other block, or more sealed
        segments, degrade staged."""
        with index.lock:
            segs = []
            mutable = None
            stray = 0
            for bs in sorted(index.blocks):
                if bs + index.block_size <= fetch_lo or bs >= fetch_hi:
                    continue
                blk = index.blocks[bs]
                if len(blk.mutable):
                    if bs == open_bs:
                        mutable = blk.mutable
                    else:
                        stray += len(blk.mutable)
                segs.extend(blk.sealed)
        if stray:
            raise Ineligible("mutable-index-block")
        if not segs:
            if open_bs is None:
                raise Ineligible("no-index-segment")
            return None, None, mutable
        if len(segs) > 1:
            raise Ineligible("multi-segment")
        seg = segs[0]
        arrays = getattr(seg, "_arrays", None)
        if arrays is None:
            raise Ineligible("index-not-resident")
        return seg, arrays, mutable

    def _block_set(self, ns, pool, fetch_lo: int, fetch_hi: int):
        """Sorted ((shard, block_start, volume)) of every sealed fileset
        overlapping the range — each must be complete-admitted so a
        page-table miss means 'series absent', never 'not resident'."""
        out = []
        bsz = ns.opts.block_size_nanos
        for shard in ns.shards:
            newest: dict[int, int] = {}
            for fid in shard.filesets():
                if fid.block_start + bsz <= fetch_lo or fid.block_start >= fetch_hi:
                    continue
                cur = newest.get(fid.block_start)
                if cur is None or fid.volume > cur:
                    newest[fid.block_start] = fid.volume
            for bs, vol in newest.items():
                if not pool.is_complete(self.namespace, shard.id, bs, vol):
                    raise Ineligible("non-resident-block")
                out.append((shard.id, bs, vol))
        return sorted(out, key=lambda t: (t[1], t[0]))

    def _stamp(self, seg, arrays, ns, pool):
        return (
            id(seg), id(arrays),
            pool.evictions, pool.invalidations,
            tuple(sh.fileset_epoch for sh in ns.shards),
        )

    def _valid(self, entry, seg, arrays, ns, pool) -> bool:
        return entry.stamp == self._stamp(seg, arrays, ns, pool)

    # -- build -------------------------------------------------------------

    def _build(self, q, seg, arrays, ns, pool, blocks, t_grid,
               n_reported: int = 0) -> _PlanEntry:
        import jax.numpy as jnp

        from ..cache.block_cache import BlockKey
        from ..index.device import kernels
        from ..index.query import search_segment
        from ..ops.chunked import window_words

        # stamp BEFORE the page-table walk: an eviction racing the walk
        # would otherwise free (and let a re-admission reuse) pages this
        # plan just copied into its tables while the stamp still matched
        # current counters — the in-lease re-check in _execute must see
        # a stamp OLDER than any such churn and refuse to serve
        stamp = self._stamp(seg, arrays, ns, pool)
        leaves: list = []
        ranges: list = []
        ast = _ast_shape(q, arrays, leaves, ranges)

        docs = list(seg.docs)
        n_docs = len(docs)
        if n_docs == 0:
            raise Ineligible("empty-segment")
        block_starts = sorted({bs for _, bs, _ in blocks})
        vols = {(sh, bs): vol for sh, bs, vol in blocks}
        n_blocks = len(block_starts)

        # per-(doc, block) lane plan vectors; one trailing all-zero doc
        # row block is the compaction sentinel (padding slots decode
        # nothing). The doc axis pads to the bitmap's natural 32-aligned
        # width so the bit unpack and the compaction agree on capacity.
        n_docs_pad = arrays.n_words * 32
        rows = (n_docs_pad + 1) * n_blocks
        chunk_k = 0
        max_span = 0
        max_pages = 1
        max_side = 1
        lane_entries: list = [None] * rows
        for d, doc in enumerate(docs):
            shard = ns.shard_for(doc.id)
            for b, bs in enumerate(block_starts):
                vol = vols.get((shard.id, bs))
                if vol is None:
                    continue  # this shard has no fileset for the block
                e = pool.get(
                    BlockKey(self.namespace, shard.id, bytes(doc.id), bs, vol)
                )
                if e is None:
                    # complete-admitted fileset without the series: the
                    # series is absent from the block — empty lane
                    continue
                if e.n_chunks <= 0 or not e.side_pages:
                    raise Ineligible("missing-side-planes")
                if chunk_k == 0:
                    chunk_k = e.chunk_k
                elif e.chunk_k != chunk_k:
                    raise Ineligible("mixed-chunk-k")
                lane_entries[d * n_blocks + b] = (e, bs)
                max_span = max(max_span, e.max_span_bits)
                max_pages = max(max_pages, len(e.pages))
                max_side = max(max_side, len(e.side_pages))
        if chunk_k == 0:
            raise Ineligible("no-resident-lanes")

        o = pool.options
        cw = _bucket_window_words(window_words(max_span))
        extra = -(-cw // o.page_words) + 1
        lp = max_pages + extra
        sl = max_side
        c = max(
            (e.n_chunks for e, _ in filter(None, lane_entries)), default=1
        )
        t_pages = np.zeros((rows, lp), np.int32)
        t_sides = np.zeros((rows, sl), np.int32)
        t_chunks = np.zeros(rows, np.int32)
        t_bits = np.zeros(rows, np.int32)
        t_bhi = np.zeros(rows, np.uint32)
        t_blo = np.zeros(rows, np.uint32)
        for i, le in enumerate(lane_entries):
            if le is None:
                continue
            e, bs = le
            pool._check_entry(e)
            t_pages[i, : len(e.pages)] = e.pages
            t_sides[i, : len(e.side_pages)] = e.side_pages
            t_chunks[i] = e.n_chunks
            t_bits[i] = e.num_bits
            t_bhi[i] = (int(bs) >> 32) & 0xFFFFFFFF
            t_blo[i] = int(bs) & 0xFFFFFFFF

        # query-key inputs (values are fixed per entry: matchers carry
        # them, and the entry is keyed by matchers)
        bq = len(leaves)
        bq_pad = kernels.pad_pow2(bq) if bq else 0
        values = [v for _, v in leaves] + [b""] * (bq_pad - bq)
        if bq:
            q_keys, q_lens = kernels.build_query_keys(values, arrays.k_words)
        else:
            q_keys = np.zeros((0, arrays.k_words), np.uint32)
            q_lens = np.zeros(0, np.int32)
        q_lo = np.zeros(bq_pad, np.int32)
        q_hi = np.zeros_like(q_lo)
        for i, (field, _v) in enumerate(leaves):
            start, count = arrays.fields.get(field, (0, 0, 0, 0))[:2]
            q_lo[i], q_hi[i] = start, start + count
        r_lo = np.asarray([lo for lo, _ in ranges] or [0], np.int32)
        r_hi = np.asarray([hi for _, hi in ranges] or [0], np.int32)

        entry = _PlanEntry()
        entry.ast = ast
        entry.seg = seg
        entry.arrays = arrays
        # decode capacity follows what matched, not the segment: the
        # matched set is a pure function of the segment arrays and the
        # matcher values, both frozen while the stamp holds, so one index
        # resolve a build counts it (on the device; on the host where the
        # tier answers None). Stage 3 compacts the matched docs into the
        # first n of cap slots; a power of two, so matchers whose counts
        # share a bucket share a compiled program, and a full match is
        # the whole-segment program. n_reported: what this key's last
        # plan counted in its own program when that exceeded its cap
        entry.post = search_segment(seg, q)
        entry.t_grid = t_grid
        entry.overlay = None
        n_matched = max(len(entry.post), n_reported)
        entry.cap = min(n_docs_pad, pad_pow2(n_matched, _MIN_CAP))
        _plan_builds(entry.cap).inc()
        entry.chunk_k = chunk_k
        entry.stamp = stamp
        entry.dims = (
            arrays.n_words, n_docs_pad, entry.cap, n_blocks, c, chunk_k,
            cw, lp, sl, o.page_words, o.side_page_chunks, t_grid,
        )
        entry.inputs = (
            jnp.asarray(q_keys), jnp.asarray(q_lens),
            jnp.asarray(q_lo), jnp.asarray(q_hi),
            jnp.asarray(r_lo), jnp.asarray(r_hi),
        )
        entry.tables = (
            jnp.asarray(t_pages), jnp.asarray(t_sides),
            jnp.asarray(t_chunks), jnp.asarray(t_bits),
            jnp.asarray(t_bhi), jnp.asarray(t_blo),
        )
        entry.fn = _build_program(ast, entry.dims)
        slots = entry.cap * n_blocks
        for name, value in (("window_words", cw), ("chunks", c),
                            ("decode_slots", slots),
                            ("gather_words", slots * c * cw)):
            _G_PLAN_DIMS[name].set(value)
        # matched-doc cache: the matched set is a pure function of the
        # segment arrays and the matcher values, both frozen while the
        # stamp holds — so the per-doc tag materialization (the cost that
        # dominated large fan-outs host-side) is paid ONCE per plan, not
        # per query
        entry.matched = None
        return entry

    def _build_open_only(self, ns, pool, t_grid) -> _PlanEntry:
        """The plan of a range wholly inside the open block: nothing
        sealed to decode, so nothing but the keying and the stamp; what it
        reads is its overlay's (``_overlay_for``)."""
        entry = _PlanEntry()
        entry.ast = entry.dims = entry.fn = entry.seg = entry.arrays = None
        entry.inputs = entry.tables = entry.matched = entry.overlay = None
        entry.cap = entry.chunk_k = 0
        entry.post = np.zeros(0, np.int32)
        entry.t_grid = t_grid
        entry.stamp = self._stamp(None, None, ns, pool)
        return entry

    def _overlay_for(self, entry, ns, q, live) -> _Overlay:
        """The entry's overlay for the open block as it stands: the cached
        one while its stamp holds, else rebuilt. Matchers resolve over the
        open index block's mutable docs on the host and join the sealed
        segment's matches by series id; each matched series' lane is read
        from its shard's lane table."""
        import jax.numpy as jnp

        from ..block.core import SeriesMeta
        from ..index.query import search_segment

        open_bs, buffered, mutable = live
        tables = tuple(
            None if sh.ingest is None else sh.ingest.frame_stamp(open_bs)
            for sh in ns.shards
        )
        stamp = (open_bs, buffered, id(mutable),
                 0 if mutable is None else len(mutable), tables)
        ov = entry.overlay
        if ov is not None and ov.stamp == stamp:
            return ov

        docs = ([entry.seg.docs[int(i)] for i in entry.post]
                if entry.seg is not None else [])
        n_sealed = len(docs)
        if mutable is not None:
            seen = {d.id for d in docs}
            for i in search_segment(mutable, q):
                d = mutable.docs[int(i)]
                if d.id not in seen:
                    seen.add(d.id)
                    docs.append(d)
        slots_of: dict[int, list[int]] = {}
        for slot, d in enumerate(docs):
            slots_of.setdefault(ns.shard_for(d.id).id, []).append(slot)
        reads, sources = [], []
        for shard_id, slots in sorted(slots_of.items()):
            buf = ns.shards[shard_id].ingest
            got = None if buf is None else buf.lanes_for(
                open_bs, [docs[i].id for i in slots])
            if got is None:
                if shard_id in buffered:
                    # buffered rows the shard has no planes for
                    raise _overlay_fallback(
                        "ingest-off" if buf is None else "spilled-row")
                continue
            table, lanes = got
            have = lanes >= 0
            reads.append((shard_id, table, lanes[have],
                          [docs[i].id for i, h in zip(slots, have) if not h]))
            if have.any():
                sources.append((shard_id, [i for i, h in zip(slots, have) if h],
                                lanes[have]))

        ov = _Overlay()
        ov.stamp = stamp
        ov.n_sealed = n_sealed
        ov.matched = (docs, [SeriesMeta(tags=d.fields) for d in docs])
        n = len(docs)
        # the sealed plan's capacity where the open block adds no series
        ov.cap = (entry.cap if entry.seg is not None and n <= entry.cap
                  else pad_pow2(n, _MIN_CAP))
        ov.reads = reads
        ov.sources = tuple(shard_id for shard_id, _, _ in sources)
        ov.cap_s = pad_pow2(max((len(l) for _, _, l in sources), default=0),
                            _MIN_CAP)
        lane_rows = np.zeros((len(sources), ov.cap_s), np.int32)
        slot_row = np.full(ov.cap, len(sources) * ov.cap_s, np.int32)
        for k, (_, slots, lanes) in enumerate(sources):
            lane_rows[k, : len(lanes)] = lanes
            slot_row[slots] = k * ov.cap_s + np.arange(len(lanes))
        ov.n_lanes = int(sum(len(l) for _, _, l in sources))
        ov.lanes, ov.slot = jnp.asarray(lane_rows), jnp.asarray(slot_row)
        entry.overlay = ov
        return ov

    # -- execute -----------------------------------------------------------

    def _dispatch(self, entry, ns, fn, key, request, **overlay):
        """One dispatch of a plan program with sealed stages, under the
        pool's read lease."""
        pool = self.db.resident_pool
        with pool.read_lease():
            # buffer snapshots under the lease (same discipline as the
            # staged resident scan); the plan tables reference page
            # indices, so the validity stamp re-checks INSIDE the lease:
            # an eviction + re-admission racing between run()'s check and
            # this snapshot could otherwise hand reused pages to stale
            # table rows. Under the lease the snapshot is immutable
            # (admissions take the functional-copy path), so a stamp that
            # holds here holds for the whole dispatch.
            with pool._lock:
                if pool._words is None or pool._side is None:
                    raise Ineligible("resident-pool-empty")
                words, side = pool._words, pool._side
            if entry.stamp != self._stamp(entry.seg, entry.arrays, ns, pool):
                raise Ineligible("raced-invalidation")
            with PROF.dispatch(key) as d:
                return d.done(fn(
                    entry.arrays.term_keys, entry.arrays.term_lens,
                    entry.arrays.post_idx, entry.arrays.post_data,
                    entry.arrays.all_words,
                    *entry.inputs,
                    words, side,
                    *entry.tables,
                    request, **overlay,
                ))

    def _execute(self, entry, ns, fetch_lo: int, fetch_hi: int,
                 grid: np.ndarray, lookback_nanos: int):
        from ..index.device import kernels
        from . import stats

        # plan.enqueue: the lease, the arguments and the dispatch
        # returning; plan.device_wait: the blocked read-back
        with TRACER.stage("plan.enqueue"):
            t_grid = entry.dims[-1]
            request = _pack_request(
                grid, t_grid, fetch_lo, fetch_hi, lookback_nanos)
            outs = self._dispatch(entry, ns, entry.fn, (entry.ast, entry.dims),
                                  request)
        with TRACER.stage("plan.device_wait"):
            (bitmap, n_matched, counts, err, g_vh, g_vl, g_pf, g_ml, ok) = (
                _unpack_reply(
                    # m3lint: disable=M3L010 -- sanctioned end-of-query host finalize: the ONE device->host readback after the fused program dispatch
                    np.asarray(outs), entry.dims[0], entry.cap, t_grid)
            )
        with TRACER.stage("plan.finalize"):
            n = int(n_matched)
            if n > entry.cap:
                # more matches than the capacity the build counted (the two
                # resolves of one frozen segment disagree: not expected):
                # fall back for THIS query and drop the entry, whose stamp
                # still holds, so the next request rebuilds, at the count
                # the program gave
                self._drop(entry, n)
                raise Ineligible("plan-capacity")
            if entry.matched is not None and len(entry.matched[0]) == n:
                matched = entry.matched
            else:
                from ..block.core import SeriesMeta

                doc_ids = kernels.bitmap_to_docids(bitmap)[:n]
                docs = entry.seg.docs
                matched_docs = [docs[int(i)] for i in doc_ids]
                matched = (
                    matched_docs,
                    [SeriesMeta(tags=d.fields) for d in matched_docs],
                )
                entry.matched = matched
            t = len(grid)
            values = _finalize_grid(
                g_vh[:n, :t], g_vl[:n, :t], g_pf[:n, :t], g_ml[:n, :t],
                ok[:n, :t],
            )
            datapoints = int(counts[:n].sum())
            err_rows = np.nonzero(err[:n])[0]
            n_blocks, cw = entry.dims[3], entry.dims[6]
            stats.add_plan(
                lanes_decoded=entry.cap * n_blocks, series_matched=n,
                window_words=cw,
            )
        return matched, values, datapoints, err_rows

    def _execute_overlay(self, entry, ns, q, live, fetch_lo: int,
                         fetch_hi: int, grid: np.ndarray,
                         lookback_nanos: int):
        """``_execute`` for a range that reaches into the open block: the
        overlay's host part (plan.overlay: lane lookup, the staged tail's
        sync, the buffers' leases and their planes), then the one
        dispatch of the sealed stages with the overlay, or of the overlay
        alone."""
        from contextlib import ExitStack

        from . import stats

        open_bs = live[0]
        t_grid = entry.t_grid
        with ExitStack() as held:
            with TRACER.stage("plan.overlay"):
                ov = self._overlay_for(entry, ns, q, live)
                # every row acknowledged before this request onto the
                # device: a sync in flight (another query's, the write
                # path's) is waited for, and one with nothing left to move
                # returns at once
                synced = sum(ns.shards[shard_id].ingest.sync(donate=False)
                             for shard_id, *_ in ov.reads)
                cols, counts, width = {}, {}, 0
                for shard_id, table, lanes, laneless in ov.reads:
                    buf = ns.shards[shard_id].ingest
                    held.enter_context(buf.lease())
                    why, c, n, w = buf.read_lanes(open_bs, table, lanes, laneless)
                    if why is not None:
                        raise _overlay_fallback(why)
                    if c is not None:
                        cols[shard_id], counts[shard_id] = c, n
                        width = max(width, w)
                # no wider than the planes, whose slots need not be a
                # power of two
                width = min(pad_pow2(width, _MIN_OVERLAY_WIDTH),
                            min((c.shape[2] for c in cols.values()),
                                default=_MIN_OVERLAY_WIDTH))
                overlay = (
                    tuple(cols[k] for k in ov.sources),
                    tuple(counts[k] for k in ov.sources),
                    ov.lanes, ov.slot,
                )
                _M_OVERLAY_SYNC_ROWS.inc(synced)
            odims = (ov.cap, len(ov.sources), ov.cap_s, width, t_grid)
            with TRACER.stage("plan.enqueue"):
                request = _pack_request(
                    grid, t_grid, fetch_lo, fetch_hi, lookback_nanos)
                if entry.seg is None:
                    fn = _build_overlay_program(odims)
                    with PROF.dispatch((None, odims)) as d:
                        outs = d.done(fn(request, overlay))
                else:
                    dims = entry.dims[:2] + (ov.cap,) + entry.dims[3:]
                    outs = self._dispatch(
                        entry, ns, _build_program(entry.ast, dims, odims),
                        (entry.ast, dims, odims), request, overlay=overlay)
        n_words = 0 if entry.seg is None else entry.dims[0]
        with TRACER.stage("plan.device_wait"):
            (_bitmap, n_matched, counts, err, g_vh, g_vl, g_pf, g_ml, ok) = (
                _unpack_reply(
                    # m3lint: disable=M3L010 -- sanctioned end-of-query host finalize: the ONE device->host readback after the fused program dispatch
                    np.asarray(outs), n_words, ov.cap, t_grid)
            )
        with TRACER.stage("plan.finalize"):
            if entry.seg is not None and int(n_matched) != ov.n_sealed:
                # the device's match is not the one the slots were laid
                # out for (the two resolves of one frozen segment disagree:
                # not expected): fall back and rebuild, as _execute does
                self._drop(entry, int(n_matched))
                raise Ineligible("plan-capacity")
            n = len(ov.matched[0])
            if err[:n].any():
                # the host re-read of a lane the decoder bailed on reads
                # sealed streams only
                raise _overlay_fallback("err-lane")
            t = len(grid)
            values = _finalize_grid(
                g_vh[:n, :t], g_vl[:n, :t], g_pf[:n, :t], g_ml[:n, :t],
                ok[:n, :t],
            )
            datapoints = int(counts[:n].sum())
            _M_OVERLAY_LANES.inc(ov.n_lanes)
            n_blocks = 0 if entry.seg is None else entry.dims[3]
            cw = 0 if entry.seg is None else entry.dims[6]
            stats.add_plan(
                lanes_decoded=ov.cap * n_blocks, series_matched=n,
                window_words=cw, overlay_lanes=ov.n_lanes,
            )
        return ov.matched, values, datapoints, np.zeros(0, np.int64)
