"""HBM-resident compressed series store: a per-device paged M3TSZ pool.

The memory-manager analogue of a paged KV cache in an inference stack,
applied to the scan-and-aggregate hot path: instead of streaming sealed
blocks' compressed bytes host->device on every scan, the m3tsz bytes stay
RESIDENT in device memory — at compressed density (~1–2.4B/datapoint) a v5e-8 holds
the whole 50M-series working set — and scans decode straight from HBM.

Layout:

- ONE flat device buffer ``uint32[num_pages, page_words]`` under a byte
  budget (``ResidentOptions.max_bytes``). Page 0 is RESERVED and always
  zero: gather plans pad short lanes with it, so a gathered lane's word
  row is bit-identical to BatchedSegments' zero padding.
- fixed-size pages handed out by a free-list allocator; a sealed block's
  stream occupies ``ceil(bits / page_bits)`` consecutive page-table slots
  (the pages themselves need not be contiguous — the device gather
  reassembles them).
- SIDE PLANES: a second paged device buffer
  ``uint32[num_side_pages * side_page_chunks, N_SIDE_PLANES]`` (one row
  per chunk slot, side page p owning rows [p*spc, (p+1)*spc)) holding the
  per-CHUNK decoder-state side table (ops/chunked.py snapshot_stream:
  byte offset, prev_time/prev_delta/prev_float_bits/prev_xor/int_val
  carries, time unit, sig/mult, is_float, and the v2 fast-chunk
  classification flags) for every resident lane. Side pages live and die
  with their data pages, so the CHUNK-parallel kernels
  (ops/chunked.decode_chunked_lanes) read both stream bytes and chunk
  metadata straight from residency — no host rebuild of chunk tables, no
  T-step whole-stream scan.
- a HOST-side page table: ``BlockKey(namespace, shard, series_id,
  block_start, volume) -> ResidentEntry(pages, side_pages, num_bits,
  n_chunks, chunk_k, max_span_bits, ...)`` — everything plan assembly
  needs as small int vectors; the ~40B/chunk metadata itself never
  leaves the device after admission.

Admission is batched at flush/seal time (storage/database.py): all of a
fileset's streams stage into one host array and land in one device scatter
(``pool.at[idx].set(staged)``), not a device_put per series. Side tables
ride the fileset's persisted ``side`` file when the caller has one, and
are prescanned AT ADMISSION (native/m3tsz.cc batch prescan when built)
otherwise. Eviction is LRU under the byte budget plus explicit
invalidation through the same hooks as the decoded-block cache
(cache/invalidation.py) — a written-to, superseded, or retention-expired
block is never resident.

Updates are in-place WHEN SAFE, functional otherwise: scans take a read
LEASE (``read_lease()``) around plan+decode; an admission that finds no
active lease donates the page buffers into the scatter (XLA aliases
input to output — true in-place, no transient copy), briefly fencing new
leases; an admission racing an active scan falls back to the functional
``.at[].set`` copy so the scan's snapshot stays bit-stable. Either way a
scan sees the old epoch or the fully-published one, never a
half-scattered page (``inplace_admissions`` / ``copy_admissions`` count
which path ran).

Concurrency: the page table, free lists, and counters are guarded by one
lock; ``plan_chunked`` snapshots the device buffer
references under it (callers hold a read lease across use).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

from ..cache.block_cache import BlockKey
from ..storage.fs import CHUNK_K
from ..utils.instrument import DEFAULT as METRICS
from .heat import ShardHeat


class ResidentPoolError(ValueError):
    """Corrupt page-table state detected (satellite contract: corrupt
    metadata must raise, never read out-of-bounds or silently wrap)."""


# Packed per-chunk side-plane layout (ops/sideplane.py): 10 uint32 words
# per chunk instead of the original 16 one-field-per-word planes — the
# ROADMAP item 1 residual, -37.5% side-plane HBM at constant information.
# The resident chunked scan's device assembly unpacks these columns
# (parallel/scan.py via sideplane.unpack_side_planes).
from ..ops.sideplane import SIDE_WORDS as N_SIDE_PLANES
from ..ops.sideplane import pack_side_rows

_M64 = (1 << 64) - 1


def side_rows_from_snaps(snaps: list, block_start: int) -> np.ndarray | None:
    """Per-chunk snapshot dicts (ops/chunked.snapshot_stream or
    storage/fs.FilesetReader.side_table) -> packed uint32[n_chunks,
    N_SIDE_PLANES] device side-plane rows, or None when a chunk's state
    overflows the packed ranges (the lane then decodes streamed)."""
    return pack_side_rows(snaps, block_start)


@dataclass
class ResidentOptions:
    """Knobs for the paged resident store (x/config-style dataclass).

    ``max_bytes`` is the device byte budget for the page buffer (0
    disables the pool). ``page_words`` is the page size in uint32 words
    (default 512 words = 2KiB — one typical 720-point m3tsz block fits in
    1–2 pages). ``max_lane_pages`` caps one (series, block) lane's page
    span: the device gather width is ``max over lanes`` of the page
    count, so one pathological stream must not widen every lane's row.
    ``side_bytes`` budgets the per-chunk side planes (0 = same as
    ``max_bytes``: an m3tsz chunk of K=32 records is ~48B of stream vs
    64B of snapshot, so metadata-for-chunk-parallelism is roughly 1:1);
    ``side_page_chunks`` is the side-page granularity in chunks."""

    enabled: bool = True
    max_bytes: int = 0
    page_words: int = 512
    max_lane_pages: int = 64
    side_bytes: int = 0  # 0 = derive from max_bytes
    side_page_chunks: int = 16
    namespaces: list = field(default_factory=list)

    def validate(self) -> None:
        from ..utils.config import ConfigError

        if self.max_bytes < 0:
            raise ConfigError("resident.max_bytes must be >= 0")
        if self.page_words <= 0:
            raise ConfigError("resident.page_words must be > 0")
        if self.max_lane_pages <= 0:
            raise ConfigError("resident.max_lane_pages must be > 0")
        if self.side_bytes < 0:
            raise ConfigError("resident.side_bytes must be >= 0")
        if self.side_page_chunks <= 0:
            raise ConfigError("resident.side_page_chunks must be > 0")
        # ``enabled`` needs >1 page in BOTH planes (page 0 is reserved):
        # a small positive budget would otherwise pass validation and
        # silently disable the whole pool — reject it loudly instead
        if 0 < self.max_bytes < 2 * self.page_bytes:
            raise ConfigError(
                f"resident.max_bytes {self.max_bytes} is under two pages "
                f"({2 * self.page_bytes}B) — 0 disables the pool explicitly"
            )
        if 0 < self.side_bytes < 2 * self.side_page_bytes:
            raise ConfigError(
                f"resident.side_bytes {self.side_bytes} is under two side "
                f"pages ({2 * self.side_page_bytes}B) — 0 derives from "
                "max_bytes"
            )

    @property
    def page_bytes(self) -> int:
        return self.page_words * 4

    @property
    def num_pages(self) -> int:
        # page 0 is the reserved zero page; it still costs budget
        return self.max_bytes // self.page_bytes

    @property
    def side_page_bytes(self) -> int:
        return self.side_page_chunks * N_SIDE_PLANES * 4

    @property
    def num_side_pages(self) -> int:
        # side page 0 is the reserved zero page (padding lanes' chunk
        # slots resolve to it, yielding all-zero side rows = done lanes)
        budget = self.side_bytes or self.max_bytes
        return budget // self.side_page_bytes


class ResidentEntry(NamedTuple):
    """Page-table row for one resident (series, block, volume) lane."""

    pages: tuple  # page indices, stream order
    num_bits: int  # valid bits of the m3tsz stream
    nbytes: int  # stream length in bytes (occupancy accounting)
    side_pages: tuple = ()  # side-plane page indices, chunk order
    n_chunks: int = 0  # chunks in the side table (0 = no side planes)
    chunk_k: int = 0  # records per chunk the side table was built with
    max_span_bits: int = 0  # widest chunk span (window sizing)


# decode bodies by a chunk's fast-chunk flags (ops/sideplane.py word 8,
# bits 1:0): index = flags & 3; both bits never meet on one chunk
CHUNK_BODIES = ("general", "int_fast", "float_fast")


class AdmitResult(NamedTuple):
    admitted: int
    rejected_span: int  # lanes over the max_lane_pages span limit
    rejected_budget: int  # lanes that could not fit even after eviction
    complete: bool  # every non-empty stream of the group is now resident


class ResidentPool:
    """Paged device pool of sealed blocks' compressed streams + chunk
    side planes."""

    def __init__(self, options: ResidentOptions | None = None, registry=None) -> None:
        self.options = options or ResidentOptions()
        self._lock = threading.Lock()
        # serializes admissions (the device-words chain, functional OR
        # donated); held across staging + upload so the TABLE lock above
        # never is — writes and scans keep flowing while a flush's pages
        # upload
        self._upload_lock = threading.Lock()
        self._od: "OrderedDict[BlockKey, ResidentEntry]" = OrderedDict()
        # admitted-but-not-yet-uploaded entries: invisible to readers
        # (plan_chunked would otherwise serve pages the scatter hasn't
        # written); published into _od after the upload completes, unless
        # an invalidation dropped them mid-upload
        self._pending: dict[BlockKey, ResidentEntry] = {}
        self._by_series: dict[tuple, set] = {}
        self._by_block: dict[tuple, set] = {}
        # (namespace, shard, block_start, volume) groups whose every
        # non-empty stream is resident: lets the query router treat a
        # page-table miss as "series absent from that fileset" instead of
        # "not resident" — dropped conservatively on any eviction or
        # invalidation touching the group
        self._complete: set[tuple] = set()
        # filesets whose admission rejected a lane for page span: they
        # can NEVER become complete at this max_lane_pages, so
        # read-through re-admission skips them instead of re-uploading
        # the fileset on every streamed query (a volume bump is a new
        # tuple and gets retried)
        self._span_incomplete: set[tuple] = set()
        # filesets a READ-THROUGH re-admission rejected for budget,
        # mapped to (data, side) free-list sizes at that failure:
        # retrying is a guaranteed rejection (re-admissions never evict)
        # until pages free up past a watermark in whichever plane was
        # binding, so _maybe_readmit skips the disk re-read until then —
        # self-healing, no invalidation hook
        self._budget_deferred: dict[tuple, tuple[int, int]] = {}
        # bumps on _reset_locked so an in-flight admission knows its
        # pages were already reclaimed by the reset
        self._generation = 0
        # free lists: every page except the reserved zero pages
        self._free: list[int] = list(range(self.options.num_pages - 1, 0, -1))
        self._free_side: list[int] = list(
            range(self.options.num_side_pages - 1, 0, -1)
        )
        self._words = None  # device uint32[num_pages, page_words], lazy
        self._side = None  # device uint32[side_pages * spc, N_SIDE_PLANES], lazy
        self._resident_bytes = 0  # sum of entries' stream bytes
        # scan/admit epoch fence: scans hold a read lease across
        # plan+decode; an admission donates the buffers (true in-place)
        # only when no lease is active, fencing new leases for the
        # duration of the scatter
        self._leases = 0
        self._donating = False
        self._fence = threading.Condition(self._lock)
        self.epoch = 0  # bumps on every buffer publish
        self.admissions = 0
        self.rejections = 0
        self.evictions = 0
        self.invalidations = 0
        self.upload_bytes = 0
        self.readmissions = 0
        self.inplace_admissions = 0
        self.copy_admissions = 0
        self.side_pack_overflows = 0
        self.rebalance_evictions = 0
        self.device_admissions = 0
        self.ingest_side_stage_bytes = 0
        reg = registry or METRICS
        self._m_admissions = reg.counter(
            "resident_admissions_total", "blocks admitted to the resident pool"
        )
        self._m_rejections = reg.counter(
            "resident_rejections_total", "blocks rejected at admission"
        )
        self._m_evictions = reg.counter(
            "resident_evictions_total", "LRU/budget evictions from the pool"
        )
        self._m_invalidations = reg.counter(
            "resident_invalidations_total", "entries dropped by invalidation hooks"
        )
        self._m_upload = reg.counter(
            "resident_upload_bytes_total",
            "host->device block bytes uploaded at admission (warm resident "
            "scans move ZERO such bytes — tests assert on this counter)",
        )
        self._m_readmissions = reg.counter(
            "resident_readmissions_total",
            "read-through re-admissions: streamed-fallback hits on sealed "
            "complete blocks pulled back into the pool",
        )
        self._m_inplace = reg.counter(
            "resident_inplace_admissions_total",
            "admissions whose scatter donated the page buffers (true "
            "in-place, no transient copy)",
        )
        self._m_copy = reg.counter(
            "resident_copy_admissions_total",
            "admissions that fell back to the functional copy because a "
            "scan lease was active",
        )
        self._m_rebalance_evictions = reg.counter(
            "resident_rebalance_evictions_total",
            "entries evicted by the heat-driven budget rebalance after a "
            "topology change (over-share shards shed LRU-oldest first)",
        )
        self._m_side_overflow = reg.counter(
            "resident_side_pack_overflows_total",
            "lanes admitted WITHOUT side planes because a chunk snapshot "
            "overflowed the packed 10-word layout (the lane decodes "
            "streamed; pathological block span or sample gap)",
        )
        self._m_device_admissions = reg.counter(
            "ingest_device_admissions_total",
            "born-resident admissions: lanes whose pages were encoded on "
            "device and scattered device->device (zero stream-byte upload "
            "— resident_upload_bytes_total does not move for these)",
        )
        self._m_side_stage = reg.counter(
            "ingest_side_stage_bytes_total",
            "packed side-plane row bytes staged host->device at "
            "born-resident admission (O(40B/chunk) metadata; the DATA "
            "pages never cross PCIe)",
        )
        # chunks admitted with side planes, by the decode body each lane's
        # own flags ask for (packed side word 8, bits 1:0). The kernel picks
        # a body per TILE: one general lane makes its whole tile general
        self._m_chunks = {
            body: reg.counter(
                "resident_chunks_total",
                "chunks admitted with side planes, by the decode body their "
                "own fast-chunk flags ask for",
                labels={"body": body},
            )
            for body in CHUNK_BODIES
        }
        self._g_bytes = reg.gauge("resident_pool_bytes", "compressed bytes resident")
        self._g_pages = reg.gauge("resident_pool_pages", "pages in use (excl. zero page)")
        self._g_free = reg.gauge("resident_pool_free_pages", "pages on the free list")
        self._g_entries = reg.gauge("resident_pool_entries", "page-table entries")
        self._g_side_pages = reg.gauge(
            "resident_side_pages", "side-plane pages in use (excl. zero page)"
        )
        self._g_occupancy = reg.gauge(
            "resident_pool_occupancy_ratio",
            "pages in use / pages total — with the gauges above, the "
            "self-scrape pipeline stores these as series, so occupancy/"
            "admission/eviction timelines are one PromQL query",
        )
        # per-shard residency heat (heat.py): charged by the query
        # router's resident-vs-streamed decisions, exposed in stats()
        # and as m3tpu_resident_shard_* counters — the measured signal
        # ROADMAP item 5's shard rebalance keys off
        self.heat = ShardHeat(registry=reg)

    # ---------- device buffers ----------

    @property
    def enabled(self) -> bool:
        o = self.options
        return o.enabled and o.num_pages > 1 and o.num_side_pages > 1

    def _ensure_words(self):
        """Allocate the device page buffer on first admission (a node with
        the mode on but nothing sealed yet pays no device memory)."""
        if self._words is None:
            import jax.numpy as jnp

            self._words = jnp.zeros(
                (self.options.num_pages, self.options.page_words), jnp.uint32
            )
        return self._words

    def _ensure_side(self):
        if self._side is None:
            import jax.numpy as jnp

            # one ROW per chunk slot (side page p owns rows [p*spc,
            # (p+1)*spc)): the lane gather takes rows, and on the TPU a
            # [pages, spc, 10] buffer had to be re-laid-out whole — a
            # temp ~9x the side budget inside every scan program — before
            # rows could be taken from it
            o = self.options
            self._side = jnp.zeros(
                (o.num_side_pages * o.side_page_chunks, N_SIDE_PLANES),
                jnp.uint32,
            )
        return self._side

    def device_bytes(self) -> int:
        """Bytes the page + side buffers actually hold on device RIGHT
        NOW — 0 until first admission (never forces the lazy allocation:
        memory accounting must observe, not cause). Buffer snapshots for
        scans go through plan_chunked under a read_lease() — an in-place
        admission donates (deletes) un-leased buffers."""
        with self._lock:
            n = int(self._words.nbytes) if self._words is not None else 0
            n += int(self._side.nbytes) if self._side is not None else 0
            return n

    # ---------- scan/admit epoch fencing ----------

    @contextmanager
    def read_lease(self):
        """Scan-side fence: while any lease is held, admissions take the
        functional-copy path so the lease holder's buffer snapshots stay
        valid; while a donated scatter is in flight, new leases wait (the
        scatter is brief) so they observe either the old epoch or the
        fully-published one — never a half-scattered page."""
        with self._lock:
            while self._donating:
                self._fence.wait()
            self._leases += 1
        try:
            yield self
        finally:
            with self._lock:
                self._leases -= 1
                if self._leases == 0:
                    self._fence.notify_all()

    # ---------- admission ----------

    def admit_block(
        self,
        namespace: str,
        shard_id: int,
        block_start: int,
        volume: int,
        items: list,
        chunk_k: int = CHUNK_K,
        readmission: bool = False,
    ) -> AdmitResult:
        """Admit one sealed fileset block's streams in ONE batched upload.

        ``items``: ``[(series_id, stream_bytes, num_points_bound)]`` or
        ``[(series_id, stream_bytes, num_points_bound, side_snaps)]`` —
        empty streams are skipped (series absent from the block). When
        ``side_snaps`` (the per-chunk snapshot dicts of
        ops/chunked.snapshot_stream / storage/fs side tables) is absent,
        the chunk prescan + fast-chunk classification runs HERE, at
        admission time, so every resident lane carries device side planes
        and scans dispatch the chunk-parallel kernels. All staged pages
        land with a single host->device transfer + scatter per buffer.

        Three phases so the TABLE lock is held only for bookkeeping —
        never across staging, the upload, or an XLA scatter compile
        (writers invalidating and queries planning keep flowing while a
        flush's pages upload):

        1. under the table lock: allocate data + side pages (LRU-evicting
           published entries as needed) and park the new entries in
           ``_pending`` — invisible to readers, whose plan would
           otherwise gather pages the scatter hasn't written;
        2. no table lock: build the staging arrays and run the device
           scatters (serialized by the upload lock; donated in-place when
           no scan lease is active, functional copy otherwise);
        3. under the table lock: swap in the new buffers and publish
           surviving pending entries (an invalidation that raced the
           upload drops its entry instead of publishing stale bytes).
        """
        if not self.enabled:
            return AdmitResult(0, 0, 0, False)
        o = self.options
        if o.namespaces and namespace not in o.namespaces:
            return AdmitResult(0, 0, 0, False)
        page_bytes = o.page_bytes
        spc = o.side_page_chunks
        norm = [
            (it[0], it[1], it[2], it[3] if len(it) > 3 else None) for it in items
        ]
        # chunk prescan for items that arrived without a side table — the
        # pure host walk runs BEFORE any lock (native batch prescan when
        # built, ~50x the Python walk)
        missing = [i for i, it in enumerate(norm) if it[3] is None and it[1]]
        if missing:
            snaps_all = self._prescan([norm[i][1] for i in missing], chunk_k)
            for i, snaps in zip(missing, snaps_all):
                sid, stream, bound, _ = norm[i]
                norm[i] = (sid, stream, bound, snaps)
        # key, stream, pages, side_pages, packed side rows, chunk/span meta
        plan: list[tuple] = []
        rejected_span = 0
        side_overflows = 0
        for sid, stream, num_points, snaps in norm:
            if not stream:
                continue
            n_pages = -(-len(stream) // page_bytes)
            if n_pages > o.max_lane_pages:
                rejected_span += 1
                continue
            snaps = snaps or []
            rows = side_rows_from_snaps(snaps, block_start) if snaps else None
            if snaps and rows is None:
                # a chunk's decoder state overflows the packed 10-word
                # layout (pathological block span / sample gap): the lane
                # admits WITHOUT side planes and scans fall back streamed
                # for it — counted, never silent
                side_overflows += 1
                snaps = []
            n_chunks = len(snaps)
            max_span = max((p["span"] for p in snaps), default=0)
            n_side = -(-n_chunks // spc) if n_chunks else 0
            key = BlockKey(namespace, shard_id, bytes(sid), block_start, volume)
            plan.append((key, bytes(stream), n_pages, n_side, rows, n_chunks, max_span))
        if side_overflows:
            self.side_pack_overflows += side_overflows
            self._m_side_overflow.inc(side_overflows)
        rejected_budget = 0
        admitted = 0
        already_resident = 0
        batch_entries: list[tuple[BlockKey, ResidentEntry, bytes, list]] = []
        with self._upload_lock:
            with self._lock:
                for key, stream, n_pages, n_side, rows, n_chunks, max_span in plan:
                    if readmission:
                        cur = self._od.get(key)
                        if cur is not None:
                            # lane already resident at this exact key —
                            # one evicted shard-mate must not re-stage
                            # and re-upload the whole fileset's bytes;
                            # the lane was just streamed, so touch its
                            # LRU slot and count it toward completeness
                            self._od.move_to_end(key)
                            already_resident += 1
                            continue
                    # re-admissions fill FREE space only ("budget
                    # permitting"): evicting published entries for them
                    # would ping-pong a working set larger than the pool
                    alloc = self._alloc_locked(
                        n_pages, n_side, evict_ok=not readmission
                    )
                    if alloc is None:
                        rejected_budget += 1
                        continue
                    pages, side_pages = alloc
                    old = self._od.pop(key, None)
                    if old is not None:
                        self._unindex_locked(key, old)
                        self._free.extend(old.pages)
                        self._free_side.extend(old.side_pages)
                        self._resident_bytes -= old.nbytes
                    entry = ResidentEntry(
                        pages=tuple(pages),
                        num_bits=len(stream) * 8,
                        nbytes=len(stream),
                        side_pages=tuple(side_pages),
                        n_chunks=n_chunks,
                        chunk_k=chunk_k if n_chunks else 0,
                        max_span_bits=max_span,
                    )
                    self._pending[key] = entry
                    admitted += 1
                    batch_entries.append((key, entry, stream, rows))
            # ---- no table lock: stage + upload ----
            # Pending pages are off the free lists (never LRU-evicted), so
            # intra-batch cannibalization is impossible: each staged page
            # has exactly one owner and the scatter's indices are unique.
            # A racing invalidation can still DROP a pending entry; only
            # entries still pending at staging time get rows.
            staged_rows: list[np.ndarray] = []
            staged_idx: list[int] = []
            side_rows: list[np.ndarray] = []
            side_idx: list[int] = []
            staged_keys: set = set()
            with self._lock:
                generation = self._generation
            try:
                if batch_entries:
                    with self._lock:
                        survivors_snapshot = [
                            tup
                            for tup in batch_entries
                            if self._pending.get(tup[0]) is tup[1]
                        ]
                    for key, entry, stream, packed in survivors_snapshot:
                        staged_keys.add(key)
                        for j, p in enumerate(entry.pages):
                            row = np.zeros(o.page_words, np.uint32)
                            chunk = stream[j * page_bytes : (j + 1) * page_bytes]
                            padded = chunk + b"\x00" * (-len(chunk) % 4)
                            row[: len(padded) // 4] = np.frombuffer(
                                padded, ">u4"
                            ).astype(np.uint32)
                            staged_rows.append(row)
                            staged_idx.append(p)
                        if packed is not None and len(packed):
                            for j, sp in enumerate(entry.side_pages):
                                page = np.zeros((spc, N_SIDE_PLANES), np.uint32)
                                seg = packed[j * spc : (j + 1) * spc]
                                page[: len(seg)] = seg
                                side_rows.append(page)
                                side_idx.append(sp)
                    if staged_rows or side_rows:
                        # publishes the new buffers itself (under the same
                        # lock acquisition that lifts the donation fence)
                        self._upload(staged_rows, staged_idx, side_rows, side_idx)
            except BaseException:
                # staging/upload failed: this batch's pages are off the
                # free lists with nothing published — reclaim them here
                # (unless a donated-scatter failure already reset the
                # whole pool, rebuilding the free lists)
                with self._lock:
                    if self._generation == generation:
                        for key, entry, _stream, _snaps in batch_entries:
                            if self._pending.get(key) is entry:
                                del self._pending[key]
                            self._free.extend(entry.pages)
                            self._free_side.extend(entry.side_pages)
                        self._publish_locked()
                raise
            # ---- publish ----
            published_rows: list = []
            with self._lock:
                survivors = 0
                for key, entry, stream, rows in batch_entries:
                    present = self._pending.get(key) is entry
                    if present:
                        del self._pending[key]
                    if present and key in staged_keys:
                        survivors += 1
                        published_rows.append(rows)
                        self._od[key] = entry
                        self._index_locked(key)
                        self._resident_bytes += entry.nbytes
                    else:
                        # invalidated mid-upload (or dropped before
                        # staging): never publish; the pages belong to
                        # this batch, so reclamation happens HERE, not in
                        # the invalidation hook
                        self._free.extend(entry.pages)
                        self._free_side.extend(entry.side_pages)
                complete = (
                    admitted + already_resident > 0
                    and rejected_span == 0
                    and rejected_budget == 0
                    and survivors + already_resident == len(plan)
                )
                group = (namespace, shard_id, block_start, volume)
                if complete:
                    self._complete.add(group)
                if rejected_span:
                    self._span_incomplete.add(group)
                if readmission:
                    if rejected_budget:
                        # cooldown watermark: retrying this fileset is a
                        # guaranteed rejection until EITHER free list
                        # grows past its size at THIS failure (whichever
                        # plane was binding; self-healing — no
                        # invalidation hook required)
                        self._budget_deferred[group] = (
                            len(self._free), len(self._free_side)
                        )
                    else:
                        self._budget_deferred.pop(group, None)
                self.admissions += admitted
                self.rejections += rejected_span + rejected_budget
                self._m_admissions.inc(admitted)
                if readmission and admitted:
                    self.readmissions += admitted
                    self._m_readmissions.inc(admitted)
                if rejected_span + rejected_budget:
                    self._m_rejections.inc(rejected_span + rejected_budget)
                self._publish_locked()
        self._count_chunk_bodies(published_rows)
        return AdmitResult(admitted, rejected_span, rejected_budget, complete)

    def admit_block_device(
        self,
        namespace: str,
        shard_id: int,
        block_start: int,
        volume: int,
        words,
        items: list,
        chunk_k: int = CHUNK_K,
        host_items: list | None = None,
    ) -> AdmitResult:
        """Born-resident admission: seal pages that are ALREADY on device.

        ``words`` is the encode kernel's ``uint32[M, W]`` output
        (ops/encode.py) with W a multiple of ``page_words``; ``items`` is
        ``[(series_id, lane_row, nbytes, n_chunks, max_span_bits,
        packed_side_rows | None)]``. The data pages move device->device
        (a gather out of the encode buffer into the pool scatter) — the
        hot path uploads ZERO stream bytes, which is the whole point:
        ``resident_upload_bytes_total`` does not move. The packed side
        rows are O(40B/chunk) host metadata and stage under
        ``ingest_side_stage_bytes_total`` instead, so the zero-upload
        contract stays assertable while side staging stays visible.

        ``host_items`` carries the block's HOST-FALLBACK lanes
        (annotated/mixed/overflow — ``(sid, stream, num_points)`` like
        :meth:`admit_block`'s items): they ride the SAME three-phase
        batch so the group's completeness marker is computed over the
        union, never set by a partial subset. Their bytes stage
        host->device and count under ``resident_upload_bytes_total`` as
        usual — only device-encoded lanes are free.

        Same three phases and the same donation/epoch fence discipline
        as :meth:`admit_block`."""
        if not self.enabled:
            return AdmitResult(0, 0, 0, False)
        o = self.options
        if o.namespaces and namespace not in o.namespaces:
            return AdmitResult(0, 0, 0, False)
        page_bytes = o.page_bytes
        pw = o.page_words
        spc = o.side_page_chunks
        W = int(words.shape[1]) if items else pw
        if W % pw != 0:
            raise ResidentPoolError(
                f"device encode width {W} not a multiple of page_words {pw} "
                "(encode with round_words_to=pool.options.page_words)"
            )
        lane_pages = W // pw
        # plan rows: (key, src, nbytes, n_pages, n_side, rows, n_chunks,
        # max_span) — src is an int lane row (device) or bytes (host)
        plan: list[tuple] = []
        rejected_span = 0
        side_overflows = 0
        for sid, lane_row, nbytes, n_chunks, max_span, rows in items:
            if not nbytes:
                continue
            n_pages = -(-int(nbytes) // page_bytes)
            if n_pages > o.max_lane_pages or n_pages > lane_pages:
                rejected_span += 1
                continue
            if rows is None and n_chunks:
                # a chunk overflowed the packed layout: lane admits
                # without side planes and decodes streamed (counted)
                side_overflows += 1
                n_chunks = 0
            key = BlockKey(namespace, shard_id, bytes(sid), block_start, volume)
            plan.append(
                (key, int(lane_row), int(nbytes), n_pages,
                 -(-int(n_chunks) // spc) if n_chunks else 0,
                 rows if n_chunks else None, int(n_chunks), int(max_span))
            )
        for sid, stream, _num_points in host_items or []:
            if not stream:
                continue
            n_pages = -(-len(stream) // page_bytes)
            if n_pages > o.max_lane_pages:
                rejected_span += 1
                continue
            snaps = self._prescan([stream], chunk_k)[0]
            rows = side_rows_from_snaps(snaps, block_start) if snaps else None
            if snaps and rows is None:
                side_overflows += 1
                snaps = []
            n_chunks = len(snaps)
            max_span = max((p["span"] for p in snaps), default=0)
            key = BlockKey(namespace, shard_id, bytes(sid), block_start, volume)
            plan.append(
                (key, bytes(stream), len(stream), n_pages,
                 -(-n_chunks // spc) if n_chunks else 0,
                 rows, n_chunks, max_span)
            )
        if side_overflows:
            self.side_pack_overflows += side_overflows
            self._m_side_overflow.inc(side_overflows)
        rejected_budget = 0
        admitted = 0
        batch_entries: list[tuple] = []
        with self._upload_lock:
            with self._lock:
                for key, src, nbytes, n_pages, n_side, rows, n_chunks, max_span in plan:
                    alloc = self._alloc_locked(n_pages, n_side)
                    if alloc is None:
                        rejected_budget += 1
                        continue
                    pages, side_pages = alloc
                    old = self._od.pop(key, None)
                    if old is not None:
                        self._unindex_locked(key, old)
                        self._free.extend(old.pages)
                        self._free_side.extend(old.side_pages)
                        self._resident_bytes -= old.nbytes
                    entry = ResidentEntry(
                        pages=tuple(pages),
                        num_bits=nbytes * 8,
                        nbytes=nbytes,
                        side_pages=tuple(side_pages),
                        n_chunks=n_chunks,
                        chunk_k=chunk_k if n_chunks else 0,
                        max_span_bits=max_span,
                    )
                    self._pending[key] = entry
                    admitted += 1
                    batch_entries.append((key, entry, src, rows))
            src_rows: list[int] = []
            dst_pages: list[int] = []
            host_rows: list[np.ndarray] = []
            host_idx: list[int] = []
            side_rows_staged: list[np.ndarray] = []
            side_idx: list[int] = []
            staged_keys: set = set()
            with self._lock:
                generation = self._generation
            try:
                if batch_entries:
                    with self._lock:
                        survivors_snapshot = [
                            tup
                            for tup in batch_entries
                            if self._pending.get(tup[0]) is tup[1]
                        ]
                    for key, entry, src, rows in survivors_snapshot:
                        staged_keys.add(key)
                        if isinstance(src, int):
                            for j, p in enumerate(entry.pages):
                                src_rows.append(src * lane_pages + j)
                                dst_pages.append(p)
                        else:
                            for j, p in enumerate(entry.pages):
                                row = np.zeros(pw, np.uint32)
                                chunk = src[j * page_bytes : (j + 1) * page_bytes]
                                padded = chunk + b"\x00" * (-len(chunk) % 4)
                                row[: len(padded) // 4] = np.frombuffer(
                                    padded, ">u4"
                                ).astype(np.uint32)
                                host_rows.append(row)
                                host_idx.append(p)
                        if rows is not None and len(rows):
                            for j, sp in enumerate(entry.side_pages):
                                page = np.zeros((spc, N_SIDE_PLANES), np.uint32)
                                seg = rows[j * spc : (j + 1) * spc]
                                page[: len(seg)] = seg
                                side_rows_staged.append(page)
                                side_idx.append(sp)
                    if src_rows or host_rows or side_rows_staged:
                        self._upload_device(
                            words, src_rows, dst_pages, host_rows, host_idx,
                            side_rows_staged, side_idx,
                        )
            except BaseException:
                with self._lock:
                    if self._generation == generation:
                        for key, entry, _row, _rows in batch_entries:
                            if self._pending.get(key) is entry:
                                del self._pending[key]
                            self._free.extend(entry.pages)
                            self._free_side.extend(entry.side_pages)
                        self._publish_locked()
                raise
            published_rows: list = []
            with self._lock:
                survivors = 0
                dev_survivors = 0
                for key, entry, src, rows in batch_entries:
                    present = self._pending.get(key) is entry
                    if present:
                        del self._pending[key]
                    if present and key in staged_keys:
                        survivors += 1
                        published_rows.append(rows)
                        if isinstance(src, int):
                            dev_survivors += 1
                        self._od[key] = entry
                        self._index_locked(key)
                        self._resident_bytes += entry.nbytes
                    else:
                        self._free.extend(entry.pages)
                        self._free_side.extend(entry.side_pages)
                complete = (
                    admitted > 0
                    and rejected_span == 0
                    and rejected_budget == 0
                    and survivors == len(plan)
                )
                if complete:
                    self._complete.add((namespace, shard_id, block_start, volume))
                if rejected_span:
                    self._span_incomplete.add(
                        (namespace, shard_id, block_start, volume)
                    )
                self.admissions += admitted
                self.device_admissions += dev_survivors
                self.rejections += rejected_span + rejected_budget
                self._m_admissions.inc(admitted)
                self._m_device_admissions.inc(dev_survivors)
                if rejected_span + rejected_budget:
                    self._m_rejections.inc(rejected_span + rejected_budget)
                self._publish_locked()
        self._count_chunk_bodies(published_rows)
        return AdmitResult(admitted, rejected_span, rejected_budget, complete)

    def _count_chunk_bodies(self, packed_rows: list) -> None:
        """``resident_chunks_total{body}`` for one published batch: each
        element is a lane's packed side rows (or None: no side planes)."""
        rows = [r for r in packed_rows if r is not None and len(r)]
        if not rows:
            return
        flags = np.concatenate([np.asarray(r)[:, 8] for r in rows]) & 3
        n = np.bincount(flags, minlength=4)
        for body, count in zip(CHUNK_BODIES, (n[0], n[1] + n[3], n[2])):
            if count:
                self._m_chunks[body].inc(int(count))

    def _upload_device(
        self, words_src, src_rows: list, dst_pages: list, host_rows: list,
        host_idx: list, side_rows: list, side_idx: list
    ):
        """Device->device data publication + (tiny) side-plane staging —
        the born-resident half of :meth:`_upload`, same donation fence
        and epoch discipline, but the device-encoded pages never cross
        PCIe and ``upload_bytes`` does not move for them. Host-fallback
        rows of the same batch (``host_rows``) concatenate into the same
        scatter and DO count under ``upload_bytes``."""
        import jax
        import jax.numpy as jnp

        with self._lock:
            words = self._ensure_words()
            side = self._ensure_side()
            donate = self._leases == 0
            if donate:
                self._donating = True
        try:
            new_words = new_side = None
            if src_rows or host_rows:
                pw = self.options.page_words
                parts = []
                if src_rows:
                    parts.append(
                        words_src.reshape(-1, pw)[np.asarray(src_rows, np.int32)]
                    )
                if host_rows:
                    staged_host = np.stack(host_rows)
                    self.upload_bytes += staged_host.nbytes
                    self._m_upload.inc(staged_host.nbytes)
                    parts.append(jax.device_put(staged_host))
                n = len(src_rows) + len(host_rows)
                n_pad = 1 << max(n - 1, 0).bit_length()
                if n_pad > n:
                    # padding rows re-write zeros into the reserved zero
                    # page, exactly like the host staging path
                    parts.append(jnp.zeros((n_pad - n, pw), jnp.uint32))
                gathered = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
                indices = np.zeros(n_pad, np.int32)
                indices[: len(src_rows)] = np.asarray(dst_pages, np.int32)
                indices[len(src_rows) : n] = np.asarray(host_idx, np.int32)
                new_words = _scatter(
                    words, jax.device_put(indices), gathered, donate
                )
            if side_rows:
                staged, indices = self._stage_side(side_rows, side_idx)
                self.ingest_side_stage_bytes += staged.nbytes
                self._m_side_stage.inc(staged.nbytes)
                new_side = _scatter(side, jax.device_put(indices),
                                    jax.device_put(staged), donate)
        except BaseException:
            with self._lock:
                if donate:
                    self._reset_locked()
                    self._donating = False
                    self._fence.notify_all()
            raise
        with self._lock:
            if new_words is not None:
                self._words = new_words
            if new_side is not None:
                self._side = new_side
            if new_words is not None or new_side is not None:
                self.epoch += 1
            if donate:
                self._donating = False
                self._fence.notify_all()
        if donate:
            self.inplace_admissions += 1
            self._m_inplace.inc()
        else:
            self.copy_admissions += 1
            self._m_copy.inc()

    @staticmethod
    def _prescan(streams: list, chunk_k: int) -> list:
        from .. import native

        if native.available():
            return native.prescan_batch(streams, k=chunk_k)
        from ..ops.chunked import snapshot_stream

        return [snapshot_stream(s, chunk_k) for s in streams]

    def _upload(self, rows: list, idx: list, side_rows: list, side_idx: list):
        """One host->device transfer + scatter per buffer for the batch —
        runs WITHOUT the table lock (serialized by the upload lock) and
        PUBLISHES the new buffers itself, under the SAME lock acquisition
        that lifts the donation fence: a lease waking on the fence must
        already see the published buffers, never the donated (deleted)
        old ones.

        When no scan lease is active the current buffers are DONATED to
        the scatter: XLA aliases input to output and writes the pages in
        place — the PR-3 transient copy is gone. While the donated
        scatter is in flight new leases wait on the fence (the old buffer
        no longer exists); an active lease instead downgrades this
        admission to the functional copy.

        If a scatter fails AFTER a donation consumed a buffer, every
        entry (published and pending) points into a deleted array — the
        pool resets (table dropped, buffers lazily re-zeroed) rather
        than bricking; read-through re-admission repopulates the hot
        set. The functional path keeps the old buffers on failure.

        The page count is padded to a power of two (extra rows re-write
        zeros into the reserved zero page) so the jitted scatter compiles
        once per bucket, not once per fileset size."""
        import jax

        with self._lock:
            words = self._ensure_words()
            side = self._ensure_side()
            donate = self._leases == 0
            if donate:
                self._donating = True
        try:
            new_words = new_side = None
            if rows:
                staged, indices = self._stage(rows, idx, (self.options.page_words,))
                self.upload_bytes += staged.nbytes
                self._m_upload.inc(staged.nbytes)
                new_words = _scatter(words, jax.device_put(indices),
                                     jax.device_put(staged), donate)
            if side_rows:
                staged, indices = self._stage_side(side_rows, side_idx)
                # side-plane staging is host->device transfer like the
                # data pages (~1:1 with stream bytes) — count it, or the
                # upload accounting under-reports admission cost ~2x and
                # the zero-transfer contract can't see side re-uploads
                self.upload_bytes += staged.nbytes
                self._m_upload.inc(staged.nbytes)
                new_side = _scatter(side, jax.device_put(indices),
                                    jax.device_put(staged), donate)
        except BaseException:
            with self._lock:
                if donate:
                    self._reset_locked()
                    self._donating = False
                    self._fence.notify_all()
            raise
        with self._lock:
            if new_words is not None:
                self._words = new_words
            if new_side is not None:
                self._side = new_side
            if new_words is not None or new_side is not None:
                self.epoch += 1
            if donate:
                self._donating = False
                self._fence.notify_all()
        if donate:
            self.inplace_admissions += 1
            self._m_inplace.inc()
        else:
            self.copy_admissions += 1
            self._m_copy.inc()

    def _stage_side(self, side_rows: list, side_idx: list):
        """Side pages -> (rows uint32[n_pad * spc, N_SIDE_PLANES], their
        row indices) for the scatter into the row-major side buffer."""
        spc = self.options.side_page_chunks
        staged, pages = self._stage(side_rows, side_idx, (spc, N_SIDE_PLANES))
        rows = pages[:, None] * spc + np.arange(spc, dtype=np.int32)[None, :]
        return staged.reshape(-1, N_SIDE_PLANES), rows.reshape(-1)

    @staticmethod
    def _stage(rows: list, idx: list, row_shape: tuple):
        n = len(rows)
        n_pad = 1 << max(n - 1, 0).bit_length() if n else 1
        staged = np.zeros((n_pad,) + row_shape, np.uint32)
        staged[:n] = np.stack(rows)
        indices = np.zeros(n_pad, np.int32)
        indices[:n] = np.asarray(idx, np.int32)
        return staged, indices

    def _alloc_locked(self, n_pages: int, n_side: int, evict_ok: bool = True):
        """Pop pages from both free lists, LRU-evicting until they fit
        (never evicting the reserved zero pages, which are not on the
        free lists). ``evict_ok=False`` admits only into free space —
        read-through re-admissions use it so a working set larger than
        the budget can't LRU-ping-pong (each scan evicting the previous
        scan's re-admissions). Returns (pages, side_pages) or None."""
        while len(self._free) < n_pages or len(self._free_side) < n_side:
            if not evict_ok or not self._evict_one_locked():
                return None
        return (
            [self._free.pop() for _ in range(n_pages)],
            [self._free_side.pop() for _ in range(n_side)],
        )

    def _evict_one_locked(self) -> bool:
        if not self._od:
            return False
        key, entry = self._od.popitem(last=False)
        self._unindex_locked(key, entry)
        self._free.extend(entry.pages)
        self._free_side.extend(entry.side_pages)
        self._resident_bytes -= entry.nbytes
        self.evictions += 1
        self._m_evictions.inc()
        return True

    # ---------- lookup / scan planning ----------

    def get(self, key: BlockKey) -> ResidentEntry | None:
        with self._lock:
            entry = self._od.get(key)
            if entry is not None:
                self._od.move_to_end(key)
            return entry

    def is_complete(self, namespace: str, shard_id: int, block_start: int, volume: int) -> bool:
        with self._lock:
            return (namespace, shard_id, block_start, volume) in self._complete

    def has_free_capacity(self) -> bool:
        """Cheap gate for read-through re-admission: free pages exist in
        BOTH planes. Re-admissions never evict (see _alloc_locked), so a
        full pool makes any attempt pointless — callers skip the fileset
        re-read entirely instead of paying disk I/O for a guaranteed
        budget rejection."""
        with self._lock:
            return bool(self._free) and bool(self._free_side)

    def never_completable(
        self, namespace: str, shard_id: int, block_start: int, volume: int
    ) -> bool:
        """True when a past admission of this fileset rejected a lane for
        page span — it can never reach the complete marker, so
        read-through re-admission would re-upload it on every streamed
        query for nothing."""
        with self._lock:
            return (namespace, shard_id, block_start, volume) in self._span_incomplete

    def budget_deferred(
        self, namespace: str, shard_id: int, block_start: int, volume: int
    ) -> bool:
        """True when a past read-through re-admission of this fileset was
        rejected for budget and NEITHER free list (data or side plane —
        either can be the binding constraint) has grown since: retrying
        would pay the whole-fileset disk re-read for another guaranteed
        rejection (re-admissions never evict). Any eviction or
        invalidation that frees pages in either plane past its recorded
        watermark lets the next streamed query retry (which refreshes
        the marker if it fails again)."""
        with self._lock:
            rec = self._budget_deferred.get(
                (namespace, shard_id, block_start, volume)
            )
            return (
                rec is not None
                and len(self._free) <= rec[0]
                and len(self._free_side) <= rec[1]
            )

    def __contains__(self, key: BlockKey) -> bool:
        with self._lock:
            return key in self._od

    def __len__(self) -> int:
        return len(self._od)

    def _entries_locked(self, keys: list):
        entries = []
        for key in keys:
            e = self._od.get(key)
            if e is None:
                return None
            self._od.move_to_end(key)
            entries.append(e)
        return entries

    def _check_entry(self, e: ResidentEntry) -> None:
        # entries are immutable NamedTuples and options never change, so
        # validation needs no lock — plan_chunked runs this O(lanes ×
        # pages) walk AFTER releasing the table lock (a 64k-lane bench
        # scan must not block writers/invalidations for its duration)
        o = self.options
        n = len(e.pages)
        if n > o.max_lane_pages:
            raise ResidentPoolError(
                f"page table entry spans {n} pages > limit {o.max_lane_pages}"
            )
        if n * o.page_words * 32 < e.num_bits:
            raise ResidentPoolError(
                f"page table entry holds {e.num_bits} bits in {n} pages "
                f"of {o.page_words * 32} bits"
            )
        for p in e.pages:
            if not 0 < p < o.num_pages:
                raise ResidentPoolError(
                    f"corrupt page index {p} (pool has {o.num_pages} pages)"
                )
        for p in e.side_pages:
            if not 0 < p < o.num_side_pages:
                raise ResidentPoolError(
                    f"corrupt side page index {p} "
                    f"(pool has {o.num_side_pages} side pages)"
                )
        if e.n_chunks > len(e.side_pages) * o.side_page_chunks:
            raise ResidentPoolError(
                f"side table holds {e.n_chunks} chunks in "
                f"{len(e.side_pages)} side pages"
            )

    def plan_chunked(self, keys: list) -> "ResidentChunkedPlan | None":
        """Assemble the CHUNK-parallel device gather inputs for ``keys``:
        page rows + side-page rows + per-series chunk counts, everything
        the device-side lane assembly (parallel/scan.py
        assemble_resident_lanes) needs to build a ChunkedBatch-shaped
        view by gather — O(series) host ints, no chunk table rebuild.

        Returns None when any key is not resident, lacks side planes, or
        the entries mix chunk sizes (the caller falls back to the
        streamed path). Callers hold read_lease() across plan + use."""
        from ..ops.chunked import window_words

        o = self.options
        with self._lock:
            if not self.enabled or self._words is None or self._side is None:
                return None
            entries = self._entries_locked(keys)
            if entries is None:
                return None
            words = self._words
            side = self._side
        for e in entries:
            self._check_entry(e)
        k = 0
        for e in entries:
            if e.n_chunks <= 0 or not e.side_pages:
                return None  # admitted without side planes
            if k == 0:
                k = e.chunk_k
            elif e.chunk_k != k:
                return None  # mixed chunk sizes: shapes would disagree
        if k <= 0:
            return None
        s = len(entries)
        c = max(e.n_chunks for e in entries)
        cw = window_words(max(e.max_span_bits for e in entries))
        # trailing zero-page columns so a window starting in the last
        # stream word can read its full cw span + alignment from zeros
        extra = -(-cw // o.page_words) + 1
        lp = max(len(e.pages) for e in entries) + extra
        sl = max(len(e.side_pages) for e in entries)
        page_rows = np.zeros((s, lp), np.int32)
        side_rows = np.zeros((s, sl), np.int32)
        n_chunks = np.zeros(s, np.int32)
        total_bits = np.zeros(s, np.int32)
        # per-series block_start as a u32 pair: the packed side planes
        # store prev_time block-relative, so the device unpack re-bases
        block_hi = np.zeros(s, np.uint32)
        block_lo = np.zeros(s, np.uint32)
        for i, (key, e) in enumerate(zip(keys, entries)):
            page_rows[i, : len(e.pages)] = e.pages
            side_rows[i, : len(e.side_pages)] = e.side_pages
            n_chunks[i] = e.n_chunks
            total_bits[i] = e.num_bits
            bs = int(key.block_start) & ((1 << 64) - 1)
            block_hi[i] = bs >> 32
            block_lo[i] = bs & 0xFFFFFFFF
        return ResidentChunkedPlan(
            words=words,
            side=side,
            page_rows=page_rows,
            side_rows=side_rows,
            n_chunks=n_chunks,
            total_bits=total_bits,
            block_hi=block_hi,
            block_lo=block_lo,
            chunk_k=k,
            num_chunks=c,
            window_words=cw,
            page_words=o.page_words,
            side_page_chunks=o.side_page_chunks,
        )

    # ---------- invalidation surface (cache/invalidation.py drives this) ----------

    def invalidate_series_block(
        self, namespace: str, shard_id: int, series_id: bytes, block_start: int
    ) -> int:
        """Drop every volume of one (series, block) — the write hook."""
        with self._lock:
            self._drop_pending_locked(
                lambda k: k.series_key
                == (namespace, shard_id, series_id, block_start)
            )
            keys = self._by_series.pop(
                (namespace, shard_id, series_id, block_start), None
            )
            return self._drop_locked(keys)

    def invalidate_block(
        self, namespace: str, shard_id: int, block_start: int, below_volume=None
    ) -> int:
        """Drop a block's entries across series; ``below_volume`` restricts
        to superseded volumes (cold-flush supersession)."""
        with self._lock:
            self._drop_pending_locked(
                lambda k: k.block_key == (namespace, shard_id, block_start)
                and (below_volume is None or k.volume < below_volume)
            )
            keys = self._by_block.get((namespace, shard_id, block_start))
            if keys is None:
                # entries may be gone while the complete marker lingers
                # (e.g. all evicted): still clear markers for the block
                self._drop_complete_locked(namespace, shard_id, block_start, below_volume)
                return 0
            if below_volume is not None:
                keys = {k for k in keys if k.volume < below_volume}
            else:
                keys = set(keys)
            self._drop_complete_locked(namespace, shard_id, block_start, below_volume)
            return self._drop_locked(keys)

    def drop_shard(self, namespace: str | None, shard_id: int) -> int:
        """Drop every entry of one shard — the SOURCE side of a shard
        handoff: once the placement stops assigning the shard here its
        residency is dead weight starving the shards this node still
        owns. ``namespace=None`` matches all namespaces."""
        with self._lock:
            self._drop_pending_locked(
                lambda k: k.shard_id == shard_id
                and (namespace is None or k.namespace == namespace)
            )
            keys = {
                k
                for k in self._od
                if k.shard_id == shard_id
                and (namespace is None or k.namespace == namespace)
            }
            for k in keys:
                self._drop_complete_locked(
                    k.namespace, k.shard_id, k.block_start, None
                )
            return self._drop_locked(keys)

    def clear(self) -> int:
        with self._lock:
            self._drop_pending_locked(lambda k: True)
            n = len(self._od)
            for entry in self._od.values():
                self._free.extend(entry.pages)
                self._free_side.extend(entry.side_pages)
            self._resident_bytes = 0
            self._od.clear()
            self._by_series.clear()
            self._by_block.clear()
            self._complete.clear()
            self._span_incomplete.clear()
            self._budget_deferred.clear()
            self.invalidations += n
            self._m_invalidations.inc(n)
            self._publish_locked()
            return n

    def shard_usage(self) -> dict[tuple[str, int], int]:
        """Resident bytes per (namespace, shard) across published entries
        — the heat-driven rebalancer's occupancy input."""
        with self._lock:
            usage: dict[tuple[str, int], int] = {}
            for key, entry in self._od.items():
                k = (key.namespace, key.shard_id)
                usage[k] = usage.get(k, 0) + entry.nbytes
            return usage

    def rebalance(self, heat: dict, slack: float = 0.10) -> int:
        """Heat-driven budget redistribution after a topology change:
        shards holding MORE than their heat-weighted share of the byte
        budget shed LRU-oldest entries first, freeing pages for gained
        hot shards' warm streaming and read-through re-admission.

        ``heat`` is ShardHeat.dump() shape ({shard_id_str: {"hits", ...}});
        a shard's weight is hits+misses (demand observed at the router),
        floored at 1 so an unmeasured shard keeps a sliver instead of
        being wiped. ``slack`` avoids churn at the boundary. Nothing is
        admitted here — admission stays flush/demand-driven; this only
        makes room where the heat says it is owed. Returns entries
        evicted (counted in ``resident_rebalance_evictions_total``)."""
        with self._lock:
            usage: dict[tuple[str, int], int] = {}
            for key, entry in self._od.items():
                k = (key.namespace, key.shard_id)
                usage[k] = usage.get(k, 0) + entry.nbytes
            if len(usage) <= 1:
                return 0  # one shard resident: nothing to redistribute
            weights = {}
            for k in usage:
                h = heat.get(str(k[1])) or {}
                weights[k] = max(
                    float(h.get("hits", 0)) + float(h.get("misses", 0)), 1.0
                )
            total_w = sum(weights.values())
            budget = float(self.options.max_bytes)
            victims: list = []
            for k, used in usage.items():
                target = budget * (weights[k] / total_w) * (1.0 + slack)
                over = float(used) - target
                if over <= 0:
                    continue
                for key, entry in self._od.items():  # LRU order: oldest first
                    if (key.namespace, key.shard_id) != k:
                        continue
                    victims.append(key)
                    over -= entry.nbytes
                    if over <= 0:
                        break
            for key in victims:
                entry = self._od.pop(key, None)
                if entry is None:
                    continue
                self._unindex_locked(key, entry)
                self._free.extend(entry.pages)
                self._free_side.extend(entry.side_pages)
                self._resident_bytes -= entry.nbytes
                self.evictions += 1
                self._m_evictions.inc()
                self.rebalance_evictions += 1
                self._m_rebalance_evictions.inc()
            if victims:
                self._publish_locked()
            return len(victims)

    def _reset_locked(self) -> None:
        """Last-resort recovery for a failed DONATED scatter: the old
        buffer may already be deleted, so every entry — published and
        pending — points into an unusable array. Drop the whole table,
        rebuild the free lists, and null the buffers (lazily re-zeroed
        on next use); read-through re-admission repopulates the hot set.
        Counted as invalidations, never silent."""
        n = len(self._od)
        self._od.clear()
        self._pending.clear()
        self._by_series.clear()
        self._by_block.clear()
        self._complete.clear()
        self._span_incomplete.clear()
        self._budget_deferred.clear()
        self._free = list(range(self.options.num_pages - 1, 0, -1))
        self._free_side = list(range(self.options.num_side_pages - 1, 0, -1))
        self._resident_bytes = 0
        self._words = None
        self._side = None
        self.epoch += 1
        self._generation += 1
        self.invalidations += n
        self._m_invalidations.inc(n)
        self._publish_locked()

    def _drop_pending_locked(self, match) -> None:
        """Drop matching in-flight admissions so stale data never
        publishes. Their pages stay OFF the free lists — the admitting
        thread owns them and reclaims at publish time (the scatter may
        still be writing them)."""
        for key in [k for k in self._pending if match(k)]:
            del self._pending[key]

    def _drop_complete_locked(self, namespace, shard_id, block_start, below_volume) -> None:
        for markers in (self._complete, self._span_incomplete):
            for g in [
                g
                for g in markers
                if g[0] == namespace
                and g[1] == shard_id
                and g[2] == block_start
                and (below_volume is None or g[3] < below_volume)
            ]:
                markers.discard(g)
        for g in [
            g
            for g in self._budget_deferred
            if g[0] == namespace
            and g[1] == shard_id
            and g[2] == block_start
            and (below_volume is None or g[3] < below_volume)
        ]:
            del self._budget_deferred[g]

    def _drop_locked(self, keys) -> int:
        if not keys:
            return 0
        dropped = 0
        for key in list(keys):
            entry = self._od.pop(key, None)
            if entry is None:
                continue
            self._unindex_locked(key, entry)
            self._free.extend(entry.pages)
            self._free_side.extend(entry.side_pages)
            self._resident_bytes -= entry.nbytes
            dropped += 1
        self.invalidations += dropped
        self._m_invalidations.inc(dropped)
        self._publish_locked()
        return dropped

    # ---------- bookkeeping ----------

    def _index_locked(self, key: BlockKey) -> None:
        self._by_series.setdefault(key.series_key, set()).add(key)
        self._by_block.setdefault(key.block_key, set()).add(key)

    def _unindex_locked(self, key: BlockKey, entry: ResidentEntry) -> None:
        for index, sub in (
            (self._by_series, key.series_key),
            (self._by_block, key.block_key),
        ):
            keys = index.get(sub)
            if keys is not None:
                keys.discard(key)
                if not keys:
                    del index[sub]
        # any entry leaving the pool makes its fileset group incomplete
        self._complete.discard(
            (key.namespace, key.shard_id, key.block_start, key.volume)
        )

    def _publish_locked(self) -> None:
        used = self.options.num_pages - 1 - len(self._free)
        side_used = self.options.num_side_pages - 1 - len(self._free_side)
        self._g_bytes.set(float(self._resident_bytes))
        self._g_pages.set(float(used))
        self._g_free.set(float(len(self._free)))
        self._g_entries.set(float(len(self._od)))
        self._g_side_pages.set(float(side_used))
        self._g_occupancy.set(used / max(self.options.num_pages - 1, 1))

    def stats(self) -> dict:
        with self._lock:
            o = self.options
            used_pages = o.num_pages - 1 - len(self._free)
            side_used = o.num_side_pages - 1 - len(self._free_side)
            resident_bytes = self._resident_bytes
            return {
                "enabled": self.enabled,
                "entries": len(self._od),
                "bytes": resident_bytes,
                "max_bytes": o.max_bytes,
                "page_bytes": o.page_bytes,
                "pages_used": used_pages,
                "pages_total": max(o.num_pages - 1, 0),
                "occupancy": used_pages / max(o.num_pages - 1, 1),
                "side_pages_used": side_used,
                "side_pages_total": max(o.num_side_pages - 1, 0),
                "side_page_bytes": o.side_page_bytes,
                "complete_blocks": len(self._complete),
                "admissions": self.admissions,
                "rejections": self.rejections,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "upload_bytes": self.upload_bytes,
                "readmissions": self.readmissions,
                "inplace_admissions": self.inplace_admissions,
                "copy_admissions": self.copy_admissions,
                "side_pack_overflows": self.side_pack_overflows,
                "rebalance_evictions": self.rebalance_evictions,
                "device_admissions": self.device_admissions,
                "ingest_side_stage_bytes": self.ingest_side_stage_bytes,
                "epoch": self.epoch,
                "shard_heat": self.heat.dump(),
            }


class ResidentChunkedPlan(NamedTuple):
    """Chunk-parallel device gather inputs (pool.plan_chunked): the
    host-side part is O(series) int vectors; windows and per-chunk lane
    metadata assemble ON DEVICE from ``words`` + ``side``."""

    words: object  # device uint32[num_pages, page_words]
    side: object  # device uint32[num_side_pages * spc, N_SIDE_PLANES]
    page_rows: np.ndarray  # int32[S, LP] incl. trailing zero-page columns
    side_rows: np.ndarray  # int32[S, SL] side-page index per slot
    n_chunks: np.ndarray  # int32[S]
    total_bits: np.ndarray  # int32[S]
    block_hi: np.ndarray  # uint32[S] block_start >> 32 (side-plane re-base)
    block_lo: np.ndarray  # uint32[S] block_start & 0xFFFFFFFF
    chunk_k: int  # records per chunk (uniform across the plan)
    num_chunks: int  # C = max chunks per series
    window_words: int  # cw (ops/chunked.window_words over max spans)
    page_words: int
    side_page_chunks: int


def _scatter(buf, indices, staged, donate: bool):
    """Page scatter (jitted lazily; module import stays light). The
    donated variant aliases input to output — true in-place on backends
    that support donation; jax silently falls back to a copy elsewhere."""
    import jax

    global _SCATTER_JIT, _SCATTER_DONATE_JIT
    if donate:
        if _SCATTER_DONATE_JIT is None:
            _SCATTER_DONATE_JIT = jax.jit(
                lambda w, i, s: w.at[i].set(s), donate_argnums=(0,)
            )
        return _SCATTER_DONATE_JIT(buf, indices, staged)
    if _SCATTER_JIT is None:
        _SCATTER_JIT = jax.jit(lambda w, i, s: w.at[i].set(s))
    return _SCATTER_JIT(buf, indices, staged)


_SCATTER_JIT = None
_SCATTER_DONATE_JIT = None
