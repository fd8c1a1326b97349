"""Cross-segment batched leaf match: ONE binary-search launch per query.

The per-segment device executor (segment.py) already batches every
exact-match leaf of a query AST into one ``match_terms`` launch — but a
namespace holding several device-resident segments (multiple index
blocks in range, or mutable/sealed generations) paid one launch PER
SEGMENT, and each launch is a host round trip. Here ALL of a query's exact leaves resolve over ALL
device-resident segments in one launch:

- the segments' fixed-width term-key matrices concatenate into one
  matrix, each padded to the widest segment's key width (trailing zero
  words preserve the (words, length) order within a segment, and every
  search row's [lo, hi) bounds stay inside one segment's field range —
  per-row bounds are exactly what ``match_terms`` was built for);
- query rows are laid out (segment-major) × (leaf), with per-row bounds
  offset by the segment's base; a value wider than ITS segment's key
  width is marked unmatchable for that segment only;
- results map back per segment by subtracting the base.

The concatenated matrix is cached per segment-identity tuple (a tiny
bounded map holding WEAK references to its sources — identity changes
on admission/eviction invalidate entries without pinning evicted
tiers). The concatenated copy itself is device memory OUTSIDE the index
store's byte budget, bounded by the cache cap × the term dictionaries
of one segment set — the deliberate price of the one-launch resolve.
The batcher is best-effort: any failure returns None and segments fall
back to their private single-launch match, so correctness never
depends on it.
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

from ...utils.instrument import DEFAULT as METRICS
from . import kernels
from .segment import collect_leaves

_M_BATCHED = METRICS.counter(
    "index_batched_match_total",
    "cross-segment batched leaf-match launches (one per query touching "
    ">1 device-resident segment; replaces one launch per segment)",
)
_M_ERRORS = METRICS.counter(
    "index_batched_match_errors_total",
    "batched leaf matches that failed and fell back to per-segment "
    "launches (best-effort: never affects results)",
)

_CACHE_CAP = 4
_combined_cache: "OrderedDict[tuple, tuple]" = OrderedDict()


def _combined(arrays_list):
    """Concatenated (keys, lens, bases, k_max) for a segment-arrays
    tuple, cached by identity. Identity is held via WEAK references: a
    plain id()-keyed entry whose sources were garbage-collected could
    alias a recycled address onto different segments and serve a stale
    term matrix, while strong references would pin evicted index tiers
    (device bytes the store's budget thinks are free). A dead or
    mismatched weakref simply rebuilds the bundle."""
    import weakref

    key = tuple(id(a) for a in arrays_list)
    hit = _combined_cache.get(key)
    if hit is not None and all(
        ref() is a for ref, a in zip(hit[4], arrays_list)
    ):
        _combined_cache.move_to_end(key)
        return hit[:4]
    import jax.numpy as jnp

    k_max = max(a.k_words for a in arrays_list)
    mats = []
    lens = []
    bases = [0]
    for a in arrays_list:
        tk = a.term_keys
        if a.k_words < k_max:
            tk = jnp.pad(tk, ((0, 0), (0, k_max - a.k_words)))
        mats.append(tk)
        lens.append(a.term_lens)
        bases.append(bases[-1] + a.n_terms)
    out = (
        jnp.concatenate(mats, axis=0),
        jnp.concatenate(lens, axis=0),
        np.asarray(bases, np.int64),
        k_max,
    )
    _combined_cache[key] = out + (
        tuple(weakref.ref(a) for a in arrays_list),
    )
    while len(_combined_cache) > _CACHE_CAP:
        _combined_cache.popitem(last=False)
    return out


def prematch(device_segs, query) -> dict | None:
    """Resolve every exact-match leaf of ``query`` over every segment in
    ``device_segs`` with ONE ``match_terms`` launch.

    Returns ``{id(seg): (arrays, gis_map, classes)}`` suitable for
    ``DeviceSegment.search_ast(query, prematched=...)`` — each entry
    pinned to the arrays snapshot it was computed against — or None when
    batching is not applicable (a segment's tier mid-eviction, no exact
    leaves) or anything fails (callers fall back to per-segment
    matches)."""
    try:
        snaps = []
        for seg in device_segs:
            arrays = getattr(seg, "_arrays", None)
            if arrays is None:
                return None
            snaps.append((seg, arrays))
        leaves, order, classes = collect_leaves(query)
        if not leaves:
            # nothing to batch; hand every segment its (empty) result so
            # per-segment searches skip their own empty launch too
            return {
                id(seg): (arrays, {}, dict(classes))
                for seg, arrays in snaps
            }
        import jax.numpy as jnp

        keys, lens, bases, k_max = _combined([a for _, a in snaps])
        n_segs = len(snaps)
        b = len(leaves)
        rows = n_segs * b
        rows_pad = kernels.pad_pow2(rows)
        q_rows: list[bytes] = []
        lo = np.zeros(rows_pad, np.int32)
        hi = np.zeros(rows_pad, np.int32)
        over = []  # (row, value wider than its segment's key width)
        for s, (_seg, a) in enumerate(snaps):
            width = 4 * a.k_words
            base = int(bases[s])
            for i, (field, value) in enumerate(leaves):
                row = s * b + i
                q_rows.append(value)
                start, count = a.fields.get(field, (0, 0, 0, 0))[:2]
                lo[row], hi[row] = base + start, base + start + count
                if len(value) > width:
                    over.append(row)
        q_rows += [b""] * (rows_pad - rows)
        q_keys, q_lens = kernels.build_query_keys(q_rows, k_max)
        for row in over:
            # wider than THIS segment's keys: unmatchable there even
            # though the padded width could hold the bytes
            q_lens[row] = -1
        gis = np.asarray(
            kernels.match_terms(
                keys, lens, jnp.asarray(lo), jnp.asarray(hi),
                jnp.asarray(q_keys), jnp.asarray(q_lens),
            )
        )
        _M_BATCHED.inc()
        out: dict = {}
        for s, (seg, a) in enumerate(snaps):
            base = int(bases[s])
            seg_gis = gis[s * b : s * b + b].copy()
            hitmask = seg_gis >= 0
            seg_gis[hitmask] -= base
            gis_map = {}
            for leaf, start, n in order:
                gis_map[id(leaf)] = seg_gis[start : start + n]
            out[id(seg)] = (a, gis_map, dict(classes))
        return out
    except Exception:
        _M_ERRORS.inc()
        return None
