"""Storage adapters: matchers → index query → decoded series; fanout.

Reference: /root/reference/src/query/storage/m3/storage.go:182
(FetchCompressed: resolve namespaces, FetchTagged, wrap into blocks) and
src/query/storage/fanout/storage.go:48-156 (merge across clusters by
retention/resolution attributes).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..index.query import AllQuery, conj, neg, regexp, term
from ..storage.database import Database
from ..utils.instrument import DEFAULT as METRICS
from .promql import Matcher

# read-through re-admission is opportunistic: the streamed result is
# already in hand when it runs, so an admission failure (device OOM near
# the pool budget, fileset torn down underfoot) must never fail the query
_M_READMIT_FAILURES = METRICS.counter(
    "resident_readmission_failures_total",
    "read-through re-admissions that failed (query still served by the "
    "streamed result already computed)",
)


def matchers_to_index_query(matchers: list[Matcher]):
    """models.Matchers → idx.Query (storage/index/convert)."""
    qs = []
    for m in matchers:
        name = m.name.encode()
        value = m.value.encode()
        if m.op == "=":
            qs.append(term(name, value))
        elif m.op == "!=":
            qs.append(neg(term(name, value)))
        elif m.op == "=~":
            qs.append(regexp(name, value))
        elif m.op == "!~":
            qs.append(neg(regexp(name, value)))
        else:
            raise ValueError(f"bad matcher op {m.op}")
    if not qs:
        return AllQuery()
    if len(qs) == 1:
        return qs[0]
    return conj(*qs)


class _EmptyTotals:
    """ScanAggregates stand-in for a scan that matched no lanes."""

    total_sum = 0.0
    total_count = 0
    total_min = float("nan")
    total_max = float("nan")


_EMPTY_TOTALS = _EmptyTotals()


@dataclass
class M3Storage:
    """Engine Storage over one Database namespace."""

    db: Database
    namespace: str

    @property
    def planner(self):
        """Lazy device query planner (query/plan.py) — one per adapter,
        owning the LRU plan cache for this namespace."""
        p = self.__dict__.get("_planner")
        if p is None:
            from .plan import Planner

            p = self.__dict__["_planner"] = Planner(self.db, self.namespace)
        return p

    def fetch_grid(self, matchers, start_nanos, end_nanos, grid, lookback_nanos):
        """One-dispatch fused fetch+consolidate (query/plan.py): matchers
        resolve, decode, and consolidate onto the engine's step grid
        inside ONE device program; the host only reconstructs f64 values
        (the same finalize arithmetic as the staged path — bit-identical
        results) and attaches tags. Returns a consolidated
        ``(metas, values f64[S, T])`` or None to run the staged path —
        every ineligibility cause lands in EXPLAIN routing. A range that
        reaches into the open block is the same one program, reading the
        shards' ingest planes beside the sealed pages (the plan's
        overlay); what the overlay cannot serve comes back None, and the
        staged path's per-series ``buffered-overlay`` route streams it.

        ``grid`` is the engine's consolidation timestamp vector (i64
        nanos); ``[start_nanos, end_nanos)`` the raw fetch window
        (lookback included by the caller)."""
        from . import stats
        from .plan import Ineligible

        try:
            matched, values, datapoints, err_rows = self.planner.run(
                matchers, start_nanos, end_nanos, grid, lookback_nanos
            )
        except Ineligible as e:
            stats.add_routing(b"*", None, "staged", f"plan:{e.reason}")
            if e.reason in ("force-staged", "plan-disabled"):
                # deliberate bypasses (the parity probe, the kill
                # switch) are not degradations: they must not pollute
                # the fallback counters an operator alerts on
                return None
            self.planner.fallbacks += 1
            from .plan import _M_FALLBACKS

            _M_FALLBACKS.inc()
            stats.add(plan_fallbacks=1)
            # release plans stamped against state that has since moved
            # (their pinned device tables + index arrays would otherwise
            # linger until LRU displacement)
            self.planner.evict_stale()
            return None
        except Exception:
            # the staged path is always correct: a device-plan fault
            # degrades, loudly, never fails the query
            from .plan import _M_ERRORS, _M_FALLBACKS

            _M_ERRORS.inc()
            _M_FALLBACKS.inc()
            self.planner.fallbacks += 1
            stats.add(plan_fallbacks=1)
            stats.add_routing(b"*", None, "staged", "plan:device-error")
            return None
        matched, metas = matched
        if len(err_rows):
            # lanes the device decoder bailed on (annotated streams):
            # batched host re-read per block, consolidated with the same
            # rule — EXPLAIN shows the hybrid per series
            values = self._stitch_grid_rows(
                matched, err_rows, values, start_nanos, end_nanos, grid,
                lookback_nanos,
            )
        st = stats.current()
        if st is not None and st.record_routing:
            err_set = set(int(i) for i in err_rows)
            for i, doc in enumerate(matched):
                stats.add_routing(
                    doc.id, None, "fused",
                    "annotated-err-lane (host stitch)" if i in err_set
                    else "device-plan",
                )
        nb = int(values.size) * 16  # times+values equivalent of the staged read
        stats.add(resident_hits=1, bytes_=nb, resident_bytes=nb)
        return metas, values, datapoints

    def _stitch_grid_rows(self, matched, err_rows, values, start_nanos,
                          end_nanos, grid, lookback_nanos):
        """Host-consolidate the err rows from batched codec re-reads —
        through the ONE shared 'last' consolidation rule
        (engine.consolidate_row), so the hybrid rows cannot drift from
        the staged path's."""
        from .engine import consolidate_row

        err_docs = [matched[int(i)] for i in err_rows]
        arrays = self.host_stitch_arrays(err_docs, start_nanos, end_nanos)
        values = np.array(values, copy=True)
        for i, doc in zip(err_rows, err_docs):
            t, v = arrays[doc.id]
            values[int(i)] = consolidate_row(t, v, grid, lookback_nanos)
        return values

    def host_stitch_arrays(self, docs, start_nanos, end_nanos) -> dict:
        """Batched host-codec re-read for lanes the device decoder bailed
        on: ``doc.id -> (times i64, values f64)`` sliced to [start, end).

        Streams are collected with ONE FilesetReader pass per fileset —
        grouped by block, not one series at a time — so a handful of
        annotated lanes can't serialize the fallback into per-series
        reader/lock round trips. Decode then runs the same array path
        Shard.read_arrays uses (native read, iterator fallback); callers
        use this only where no buffer overlays the range (the residency
        gate excludes overlays, and a plan that read the open block falls
        back staged on a lane the decoder bailed on), so fileset streams
        are the whole truth."""
        from ..codec.iterator import MultiReaderIterator
        from ..codec.native_read import read_segments_arrays
        from ..storage.fs import FilesetID

        ns = self.db.namespaces[self.namespace]
        bsz = ns.opts.block_size_nanos
        per_series: dict[bytes, list] = {}
        by_shard: dict[int, list] = {}
        for doc in docs:
            per_series[doc.id] = []
            by_shard.setdefault(ns.shard_for(doc.id).id, []).append(doc.id)
        for shard_id, sids in by_shard.items():
            shard = ns.shards[shard_id]
            # fileset order mirrors Shard._segments_locked (oldest-first
            # listing order) so per-series segment order — and therefore
            # decoded output — is identical to read_arrays
            for fid in shard.filesets():
                if (
                    fid.block_start + bsz <= start_nanos
                    or fid.block_start >= end_nanos
                ):
                    continue
                reader = shard.reader_or_none(FilesetID(
                    self.namespace, shard_id, fid.block_start, fid.volume
                ))
                if reader is None:
                    continue  # retention race or quarantined mid-query
                for sid in sids:
                    stream = reader.stream(sid)
                    if stream:
                        per_series[sid].append(stream)
        out = {}
        for doc in docs:
            segs = per_series[doc.id]
            arrs = read_segments_arrays(segs, start_nanos, end_nanos)
            if arrs is not None:
                out[doc.id] = (
                    np.asarray(arrs[0], np.int64),
                    np.asarray(arrs[1], np.float64),
                )
                continue
            dps = [
                dp
                for dp in MultiReaderIterator(segs)
                if start_nanos <= dp.timestamp < end_nanos
            ]
            out[doc.id] = (
                np.asarray([dp.timestamp for dp in dps], np.int64),
                np.asarray([dp.value for dp in dps], np.float64),
            )
        return out

    def fetch(self, matchers, start_nanos, end_nanos):
        from . import stats

        q = matchers_to_index_query(matchers)
        # decode-from-HBM fast path (m3_tpu/resident/): when every matched
        # block is resident and no live buffer overlays the range, series
        # selection is a device gather of page rows + ONE batched decode —
        # replacing the per-series host select/decode loop below (the
        # host-bound select/pack gap). The index resolves
        # ONCE: the resident plan and any fallback share `docs`. Cache
        # before-stats are captured up front so the pooled fallback's
        # decode work is accounted like the plain path's.
        cache = getattr(self.db, "block_cache", None)
        before = cache.stats() if cache is not None else None
        pool = getattr(self.db, "resident_pool", None)
        rows = None
        if pool is None or not pool.enabled:
            stats.add_routing(b"*", None, "streamed", "resident pool disabled")
        elif len(pool) == 0:
            stats.add_routing(b"*", None, "streamed", "resident pool empty")
        if pool is not None and pool.enabled:
            # an EMPTY pool still takes this branch: the streamed fallback
            # below re-admits sealed complete blocks (read-through), which
            # is exactly how a fully-evicted pool refills under demand
            docs = self.db.query_ids(
                self.namespace, q, start_nanos, end_nanos
            ).docs
            resident = self._fetch_resident(docs, start_nanos, end_nanos)
            if resident is not None:
                nb = sum(t.nbytes + v.nbytes for _, t, v in resident)
                # resident_bytes feeds the tenant ledger's streamed-vs-
                # resident split (bytes_scanned - resident_bytes = streamed)
                stats.add(resident_hits=1, bytes_=nb, resident_bytes=nb)
                return resident
            # fall back through the normal array surface, reusing the
            # plan's index resolution (fetch_tagged_arrays also restores
            # the storage.fetch_tagged span this path must keep emitting)
            rows = self.db.fetch_tagged_arrays(
                self.namespace, q, start_nanos, end_nanos, docs=docs
            )
            # read-through re-admission: a streamed hit on sealed,
            # complete blocks pulls them back into the pool so the hot
            # set stays resident under eviction churn
            self._maybe_readmit(docs, start_nanos, end_nanos)
        if pool is not None:
            stats.add(resident_misses=1)
        out = []
        total_bytes = 0
        # per-query cache accounting from the node-wide cache counter delta —
        # approximate under concurrent queries (deltas interleave), exact in
        # the common single-query case; the alternative (threading a stats
        # handle through every Shard read) isn't worth the hot-path cost.
        # Array surface: decoded arrays come straight from the decoded-block
        # cache (m3_tpu/cache/) on repeat queries — no per-point Datapoint
        # materialization on the scan-and-aggregate hot path.
        if rows is None:
            rows = self.db.fetch_tagged_arrays(
                self.namespace, q, start_nanos, end_nanos
            )
        for sid, tags, (times, vals) in rows:
            times = np.asarray(times, np.int64)
            vals = np.asarray(vals, np.float64)
            total_bytes += times.nbytes + vals.nbytes
            out.append((tags, times, vals))
        if before is not None:
            after = cache.stats()
            stats.add(
                bytes_=total_bytes,
                cache_hits=after["hits"] - before["hits"],
                cache_misses=after["misses"] - before["misses"],
            )
        else:
            stats.add(bytes_=total_bytes)
        return out

    # ---------- residency routing ----------

    def _resident_plan(self, docs, start_nanos, end_nanos):
        """(doc, resident BlockKeys) per matched doc when the query is
        fully servable from the pool, else None. A series is servable when
        every overlapping fileset block is either resident or
        complete-admitted with the series absent, and no buffered data
        overlaps the range. ``docs`` come from the caller's single
        query_ids resolution (shared with the fallback path)."""
        from . import stats

        pool = getattr(self.db, "resident_pool", None)
        if pool is None or not pool.enabled:
            return None
        ns = self.db.namespaces[self.namespace]
        plan = []
        for doc in docs:
            shard = ns.shard_for(doc.id)
            keys, buffered = shard.scan_block_keys(doc.id, start_nanos, end_nanos)
            if buffered:
                # EXPLAIN routing: record the decision that forced the
                # whole query onto the streamed path (entries recorded so
                # far would be misleading half-truths — only the cause and
                # the final outcome are reported)
                stats.add_routing(doc.id, None, "streamed", "buffered-overlay")
                pool.heat.charge(shard.id, misses=1)
                return None
            doc_keys = []
            for key in keys:
                if key in pool:
                    doc_keys.append(key)
                elif pool.is_complete(
                    key.namespace, key.shard_id, key.block_start, key.volume
                ):
                    continue  # fileset fully admitted: series absent from it
                else:
                    stats.add_routing(
                        doc.id, key.block_start, "streamed",
                        "not-resident (evicted or never admitted)",
                    )
                    pool.heat.charge(key.shard_id, misses=1)
                    return None  # evicted / never admitted: stream instead
            plan.append((doc, doc_keys))
        # routing + hit heat are recorded by _record_resident_routing
        # AFTER the resident scan succeeds — the chunked plan can still
        # fail (raced eviction, side-plane mismatch), and EXPLAIN must
        # never claim "resident-chunked" for a query the streamed
        # fallback actually served
        return plan

    def _record_resident_routing(self, plan) -> None:
        """EXPLAIN + per-shard heat for a resident scan that SUCCEEDED:
        the resident decoder is the chunk-parallel kernel reading side
        planes straight from the pool — EXPLAIN shows which decode path
        served every (series, block), aggregated per shard so the hot
        path charges heat once per shard, not once per lane."""
        from . import stats

        pool = self.db.resident_pool
        lanes_per_shard: dict[int, int] = {}
        for doc, doc_keys in plan:
            for key in doc_keys:
                stats.add_routing(doc.id, key.block_start, "resident",
                                  "resident-chunked")
                lanes_per_shard[key.shard_id] = (
                    lanes_per_shard.get(key.shard_id, 0) + 1
                )
        for shard_id, lanes in lanes_per_shard.items():
            pool.heat.charge(shard_id, hits=lanes)

    def _maybe_readmit(self, docs, start_nanos, end_nanos) -> int:
        """Read-through re-admission (carried from PR 3): when a scan
        fell back to the streamed path because sealed, complete blocks
        were NOT resident (evicted, or sealed by a previous process past
        the bootstrap budget), pull exactly those filesets back into the
        pool so the hot set tracks demand under eviction churn.
        "Budget permitting" is literal: re-admissions fill FREE space
        only and never evict published entries — a working set larger
        than the budget would otherwise LRU-ping-pong, each scan's
        re-admissions evicting the previous scan's. Buffered series are
        skipped: their blocks would stream again regardless
        (buffer-overlay rule). Counted in
        m3tpu_resident_readmissions_total."""
        pool = getattr(self.db, "resident_pool", None)
        if pool is None or not pool.enabled:
            return 0
        if not pool.has_free_capacity():
            # re-admissions never evict published entries, so a full
            # pool can't take anything — skip the block walk AND the
            # fileset disk re-reads (a working set larger than the
            # budget would otherwise pay both on every streamed query)
            return 0
        from ..storage.fs import FilesetID

        ns = self.db.namespaces[self.namespace]
        todo: dict[tuple, object] = {}
        for doc in docs:
            shard = ns.shard_for(doc.id)
            keys, buffered = shard.scan_block_keys(doc.id, start_nanos, end_nanos)
            if buffered:
                continue
            for key in keys:
                if key in pool or pool.is_complete(
                    key.namespace, key.shard_id, key.block_start, key.volume
                ):
                    continue
                if pool.never_completable(
                    key.namespace, key.shard_id, key.block_start, key.volume
                ):
                    # a lane over the pool's page-span limit makes this
                    # fileset permanently un-completable: re-admitting it
                    # on every streamed query would re-upload the whole
                    # fileset for nothing
                    continue
                if pool.budget_deferred(
                    key.namespace, key.shard_id, key.block_start, key.volume
                ):
                    # a past re-admission of this fileset was rejected
                    # for budget and no pages have freed since — the
                    # retry is a guaranteed rejection, skip the disk
                    # re-read until eviction/invalidation makes room
                    continue
                todo[(key.shard_id, key.block_start, key.volume)] = shard
        admitted = 0
        for (shard_id, block_start, volume), shard in todo.items():
            try:
                admitted += shard.readmit_fileset(
                    FilesetID(self.namespace, shard_id, block_start, volume)
                )
            except Exception:
                # the streamed result this query will serve is already
                # computed — a failed opportunistic re-admission (device
                # OOM near the pool budget is the likely case, and on the
                # donated-scatter path admit_block resets the pool) must
                # not turn it into a query error; remaining filesets are
                # skipped rather than hammering a struggling device
                _M_READMIT_FAILURES.inc()
                break
        return admitted

    def _fetch_resident(self, docs, start_nanos, end_nanos):
        """Batched decode-from-HBM fetch: [(tags, times, values)] exact
        (finalize_decode reconstructs bit-exact f64), or None to fall back.
        Lanes the device decoder bails on (annotated streams) re-read
        through the host array path per series."""
        from ..resident.scan import resident_fetch_arrays
        from . import stats as query_stats

        from ..utils.trace import TRACER

        plan = self._resident_plan(docs, start_nanos, end_nanos)
        if plan is None:
            return None
        flat_keys = [key for _, doc_keys in plan for key in doc_keys]
        decoded = ([], np.zeros(0, bool))
        # this path replaces db.fetch_tagged_arrays, so it is the same
        # storage.fetch_tagged stage — trace shape in /debug/traces must
        # not vary with residency state
        with TRACER.stage(
            "storage.fetch_tagged", namespace=self.namespace
        ) as span:
            if flat_keys:
                decoded = resident_fetch_arrays(self.db.resident_pool, flat_keys)
                if decoded is None:
                    # raced an eviction (or side-plane/chunk-shape
                    # mismatch): streamed fallback serves the query, and
                    # EXPLAIN says so
                    query_stats.add_routing(
                        b"*", None, "streamed",
                        "resident-plan-failed (raced eviction)",
                    )
                    return None
            self._record_resident_routing(plan)
            arrays, err = decoded
            out = []
            pos = 0
            err_docs = []
            err_slots: list[int] = []
            with query_stats.stage("decode"):
                for doc, doc_keys in plan:
                    lanes = arrays[pos : pos + len(doc_keys)]
                    lane_err = err[pos : pos + len(doc_keys)]
                    pos += len(doc_keys)
                    if lane_err.any():
                        # host re-read keeps Datapoint fidelity for lanes
                        # the device can't decode; blocks are disjoint so
                        # a full per-series host read replaces all its
                        # lanes — collected here, read BATCHED per block
                        # below so one bad lane doesn't serialize the
                        # fallback into per-series reader round trips
                        err_docs.append(doc)
                        err_slots.append(len(out))
                        out.append(None)
                        continue
                    if lanes:
                        times = np.concatenate([t for t, _ in lanes])
                        vals = np.concatenate([v for _, v in lanes])
                    else:
                        times = np.zeros(0, np.int64)
                        vals = np.zeros(0, np.float64)
                    lo = int(np.searchsorted(times, start_nanos, side="left"))
                    hi = int(np.searchsorted(times, end_nanos, side="left"))
                    out.append((doc.fields, times[lo:hi], vals[lo:hi]))
                if err_docs:
                    stitched = self.host_stitch_arrays(
                        err_docs, start_nanos, end_nanos
                    )
                    for slot, doc in zip(err_slots, err_docs):
                        t, v = stitched[doc.id]
                        out[slot] = (doc.fields, t, v)
            span.set_tag("series", len(out))
        return out

    def scan_totals(self, matchers, start_nanos, end_nanos) -> dict:
        """Direct scan-and-aggregate over raw samples (the paper's
        flagship path as a query surface): index-resolve the matchers,
        then either decode-from-HBM (all matched blocks resident) or
        upload-and-decode (streamed fallback) — both through the same
        kernel and reduction shapes, so the two paths agree bit for bit.

        Granularity is BLOCK-aligned: totals cover every datapoint of
        blocks overlapping [start, end) — the compressed streams decode
        whole (that is what makes the scan one kernel launch); callers
        needing exact range edges use fetch(). Returns {"sum", "count",
        "min", "max", "series", "path"} with path "resident"|"streamed".
        """
        from ..resident.scan import resident_scan_totals, streamed_scan_totals
        from ..storage.fs import CHUNK_K
        from . import stats

        q = matchers_to_index_query(matchers)
        ns = self.db.namespaces[self.namespace]
        # ONE index resolution, shared by the resident plan and fallback
        docs = self.db.query_ids(self.namespace, q, start_nanos, end_nanos).docs
        n_series = len(docs)
        plan = self._resident_plan(docs, start_nanos, end_nanos)
        aggs = None
        path = "streamed"
        stream_for = None  # lane idx -> stream bytes (err-lane stitching)
        if plan is not None:
            flat_keys = [key for _, doc_keys in plan for key in doc_keys]
            aggs = (
                resident_scan_totals(self.db.resident_pool, flat_keys)
                if flat_keys
                else _EMPTY_TOTALS
            )
            if aggs is None:
                stats.add_routing(
                    b"*", None, "streamed",
                    "resident-plan-failed (raced eviction)",
                )
            else:
                path = "resident"
                stats.add(resident_hits=1)
                self._record_resident_routing(plan)

                def stream_for(i, _keys=flat_keys):
                    from ..storage.fs import FilesetID

                    key = _keys[i]
                    shard = ns.shards[key.shard_id]
                    reader = shard.reader_or_none(
                        FilesetID(
                            key.namespace, key.shard_id, key.block_start, key.volume
                        )
                    )
                    return (reader.stream(key.series_id) or b"") if reader else b""

        if aggs is None:
            pool = getattr(self.db, "resident_pool", None)
            if pool is not None:
                stats.add(resident_misses=1)
            segments: list[bytes] = []
            chunk_ks: set[int] = set()
            streamed_per_shard: dict[int, int] = {}
            for doc in docs:
                shard = ns.shard_for(doc.id)
                for stream, _bound, chunk_k in shard.scan_segments(
                    doc.id, start_nanos, end_nanos
                ):
                    segments.append(stream)
                    chunk_ks.add(chunk_k)
                    streamed_per_shard[shard.id] = (
                        streamed_per_shard.get(shard.id, 0) + len(stream)
                    )
            if pool is not None:
                # per-shard streamed-fallback bytes: the transfer cost
                # residency would have removed, attributed to the shard
                # whose blocks weren't resident (resident/heat.py)
                for shard_id, nbytes in streamed_per_shard.items():
                    pool.heat.charge(shard_id, streamed_bytes=nbytes)
            # decode with the filesets' chunk size so the streamed twin's
            # chunk decomposition (and hence f32 reduction order) matches
            # the resident path bit for bit; mixed chunk sizes can't have
            # a resident counterpart anyway (plan_chunked refuses them),
            # so any k decodes them correctly — use the default
            k = chunk_ks.pop() if len(chunk_ks) == 1 else CHUNK_K
            aggs = (
                streamed_scan_totals(segments, k=k)
                if segments
                else _EMPTY_TOTALS
            )
            stream_for = lambda i, _segs=segments: _segs[i]
            self._maybe_readmit(docs, start_nanos, end_nanos)
        err = getattr(aggs, "series_err", None)
        if err is not None and np.asarray(err).any():
            # lanes the device decoder bailed on (annotated streams):
            # recompute them through the host codec and rebuild the
            # totals — both paths stitch identically, so silently
            # truncated counts never leave this function
            from ..parallel.scan import stitch_host_errors

            aggs = stitch_host_errors(aggs, stream_for)
        count = int(aggs.total_count)
        stats.add(series=n_series, datapoints=count)
        return {
            "sum": float(aggs.total_sum),
            "count": count,
            "min": float(aggs.total_min),
            "max": float(aggs.total_max),
            "series": n_series,
            "path": path,
            # both paths now decode through the chunk-parallel kernels
            # (side planes paged into the pool; streamed twin prescans) —
            # tools/check_resident.py asserts the resident scan reports it
            "decoder": "chunked",
        }


@dataclass
class ClusterNamespace:
    """One queryable namespace + its retention/resolution attributes
    (storage/m3/types.go ClusterNamespace + Attributes)."""

    storage: object  # Engine Storage (e.g. M3Storage)
    retention_nanos: int
    resolution_nanos: int = 0  # 0 = raw samples
    aggregated: bool = False  # False = the unaggregated namespace


def resolve_cluster_namespaces(
    namespaces: list[ClusterNamespace], now_nanos: int, start_nanos: int
) -> list[ClusterNamespace]:
    """storage/m3/cluster_resolver.go resolveClusterNamespacesForQuery:

    1. the unaggregated namespace wins if its retention covers the query
       start;
    2. otherwise the FINEST-resolution aggregated namespace that covers it;
    3. otherwise nothing covers — fall back to the longest-retention
       namespace (partial data beats none).
    """
    if not namespaces:
        return []
    covers = lambda ns: now_nanos - ns.retention_nanos <= start_nanos
    unagg = [ns for ns in namespaces if not ns.aggregated]
    if unagg and covers(unagg[0]):
        return [unagg[0]]
    covering = sorted(
        (ns for ns in namespaces if ns.aggregated and covers(ns)),
        key=lambda ns: ns.resolution_nanos,
    )
    if covering:
        return [covering[0]]
    return [max(namespaces, key=lambda ns: ns.retention_nanos)]


@dataclass
class FanoutStorage:
    """Retention/resolution-aware fanout (fanout/storage.go:48 +
    cluster_resolver): pick the namespace(s) whose attributes fit the query
    range, fetch, and dedupe exact-id overlaps preferring the
    finer-resolution source.

    The fan-in is concurrent and HEDGED ("The Tail at Scale", the same
    discipline as the client session's replica fan-outs): each resolved
    namespace fetches on its own daemon worker, and when a source has
    been in flight longer than its own per-(source, op) p95 a single
    budget-gated backup twin is issued — first leg per source wins, the
    loser is abandoned, and a loser's late error never surfaces. Local
    single-namespace queries stay inline (there is no independent
    replica behind an in-process storage worth paying a thread for).
    Counters ride the existing ``m3tpu_session_hedges_*`` family under
    ``op="fanout_fetch"``."""

    namespaces: list  # list[ClusterNamespace]
    clock: object = None  # () -> nanos; injectable for tests
    hedge_enabled: bool = True
    # floor under the p95 straggler trigger (seconds): ordinary jitter
    # must not burn hedge budget on sources answering in microseconds
    hedge_min_delay: float = 0.010
    _OP = "fanout_fetch"

    def __post_init__(self) -> None:
        from ..net.resilience import HedgeBudget, LatencyEstimator

        self.latency = LatencyEstimator()
        self.hedge_budget = HedgeBudget()
        self._pool = None

    def _now(self) -> int:
        if self.clock is not None:
            return self.clock()
        import time

        return time.time_ns()

    def resolve(self, start_nanos: int) -> list[ClusterNamespace]:
        return resolve_cluster_namespaces(self.namespaces, self._now(), start_nanos)

    def _ns_key(self, ns: ClusterNamespace) -> str:
        """Stable latency-estimator identity for one source: remote
        coordinators by URL, local storages by position + resolution."""
        url = getattr(ns.storage, "base_url", None)
        if url:
            return str(url)
        try:
            pos = self.namespaces.index(ns)
        except ValueError:
            pos = -1
        return f"local/{pos}/{ns.resolution_nanos}"

    def fetch(self, matchers, start_nanos, end_nanos):
        resolved = self.resolve(start_nanos)
        if len(resolved) == 1 and getattr(
            resolved[0].storage, "base_url", None
        ) is None:
            results = {
                0: resolved[0].storage.fetch(matchers, start_nanos, end_nanos)
            }
        else:
            results = self._hedged_fetch(
                resolved, matchers, start_nanos, end_nanos
            )
        seen: dict = {}
        order = []
        for i in range(len(resolved)):
            for tags, times, vals in results[i]:
                if tags in seen:
                    continue
                seen[tags] = (tags, times, vals)
                order.append(tags)
        return [seen[t] for t in order]

    def _hedged_fetch(self, resolved, matchers, start_nanos, end_nanos):
        import time
        from concurrent.futures import FIRST_COMPLETED
        from concurrent.futures import wait as futures_wait

        from ..client.session import _DaemonPool, _session_hedges

        if self._pool is None:
            self._pool = _DaemonPool(max_workers=8)
        pool = self._pool
        n = len(resolved)
        keys = [self._ns_key(ns) for ns in resolved]
        futs: dict = {}  # Future -> source index
        hedge_futs: set = set()  # backup legs
        legs = [1] * n
        attempted = [False] * n
        unresolved: set[int] = set()  # issued hedges with no outcome yet
        results: dict[int, list] = {}
        errors: dict[int, BaseException] = {}
        now = time.monotonic()
        started = [now] * n
        for i, ns in enumerate(resolved):
            futs[pool.submit(ns.storage.fetch, matchers, start_nanos, end_nanos)] = i
        pending = set(futs)
        while pending and (len(results) + len(errors)) < n:
            # wake exactly when the earliest unhedged source crosses its
            # straggler threshold (or on the first completion)
            now = time.monotonic()
            fire = None
            if self.hedge_enabled:
                for i in range(n):
                    if attempted[i] or i in results or i in errors:
                        continue
                    p95 = self.latency.p95(keys[i], self._OP)
                    if p95 is None:
                        continue
                    at = started[i] + max(p95, self.hedge_min_delay)
                    if fire is None or at < fire:
                        fire = at
            timeout = None if fire is None else max(fire - now, 0.0)
            done, pending = futures_wait(
                pending, timeout=timeout, return_when=FIRST_COMPLETED
            )
            for fut in done:
                i = futs[fut]
                is_hedge = fut in hedge_futs
                exc = fut.exception()
                if exc is None:
                    if i in results:
                        continue  # loser twin: never double-merged
                    results[i] = fut.result()
                    self.latency.record(
                        keys[i], self._OP, time.monotonic() - started[i]
                    )
                    self.hedge_budget.on_success()
                    if i in unresolved:
                        unresolved.discard(i)
                        _session_hedges(
                            "won" if is_hedge else "wasted", self._OP
                        ).inc()
                else:
                    legs[i] -= 1
                    if is_hedge and i in unresolved:
                        unresolved.discard(i)
                        _session_hedges("wasted", self._OP).inc()
                    # a leg's error surfaces only when the source has no
                    # other live leg and no delivered result
                    if i not in results and legs[i] <= 0:
                        errors[i] = exc
            if not pending or (len(results) + len(errors)) >= n:
                break
            if not self.hedge_enabled:
                continue
            # at most ONE budget-gated backup per wake, to the straggler
            now = time.monotonic()
            for i in range(n):
                if attempted[i] or i in results or i in errors:
                    continue
                p95 = self.latency.p95(keys[i], self._OP)
                if p95 is None:
                    continue
                if now - started[i] <= max(p95, self.hedge_min_delay):
                    continue
                attempted[i] = True
                if not self.hedge_budget.try_spend():
                    break
                fut = pool.submit(
                    resolved[i].storage.fetch, matchers, start_nanos, end_nanos
                )
                futs[fut] = i
                hedge_futs.add(fut)
                pending.add(fut)
                legs[i] += 1
                unresolved.add(i)
                _session_hedges("issued", self._OP).inc()
                break
        # fan-in over: hedges with no outcome (both legs abandoned or
        # still in flight) were pure extra load
        for _ in range(len(unresolved)):
            _session_hedges("wasted", self._OP).inc()
        if errors:
            raise errors[min(errors)]
        return results
