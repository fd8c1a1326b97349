"""Per-shard device column write buffer: the ingest half of residency.

Write batches append into per-block-window FRAMES of ``(series_lane,
slot)`` columns — timestamps, values, and a per-lane cleanliness flag —
host-staged as numpy and mirrored to device planes in batched syncs (one
scatter per sync, donation/epoch discipline borrowed from the resident
pool: a sync donates the plane buffers to the scatter when no reader
lease is active, else falls back to the functional copy).

The frames ring over block windows: at most ``IngestOptions.windows``
windows are open at once; a write landing outside every open window (too
old after its window sealed, or too new while the ring is full of
unsealed windows) SPILLS to the host path — counted by reason, never
silent. Likewise a full lane table ("lanes") or a full lane ("slots").
Spilled rows still live in the shard's ``SeriesBuffer`` (the read-path
truth, which every write also lands in); a spill just means that lane
seals through the host codec instead of the device encode kernel.

A lane is CLEAN while its appends arrive strictly time-ascending (no
duplicates, no out-of-order rows). Clean lanes ARE the merged point set
— sorted, unique — so seal feeds them to ops/encode.py without the
sort/dedup merge pass; one out-of-order append marks the lane dirty for
the window and seal falls back to the SeriesBuffer merge for that
series (counted).

Metric family: ``m3tpu_ingest_*`` (label policy M3L005 — the spill
counter's only label is ``reason``, a closed enum; series ids never
label metrics).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass

import numpy as np

from ..utils.instrument import DEFAULT as METRICS
from ..utils.trace import TRACER

SPILL_REASONS = ("window", "lanes", "slots")
# slots a tile of a sync: a tail of one tick's rows and one of a few
# dozen ticks run the same scatter program, one tile or a few
_SYNC_TILE_SLOTS = 32


@dataclass(frozen=True)
class IngestOptions:
    """Sizing for one shard's column write buffer."""

    enabled: bool = True
    lanes: int = 1024  # series lanes per block-window frame
    slots: int = 1024  # samples per lane per window
    windows: int = 2  # block windows open at once (ring depth)
    # staged appends that trigger a device-plane sync from the write path;
    # a query plan that reads the planes syncs the staged tail itself
    # before it dispatches (query/plan.py), so this bounds only how much
    # one write batch may have to move
    sync_batch: int = 8192

    def __post_init__(self):
        if self.lanes < 1 or self.slots < 1 or self.windows < 1:
            raise ValueError("lanes, slots and windows must be positive")


class SealLane(dict):
    """One sealed clean lane: ``sid``, ``times``, ``values``, ``units``
    column views (dict for tooling-friendly dumps)."""

    __getattr__ = dict.__getitem__


class _Frame:
    """Host staging for one open block window."""

    __slots__ = (
        "block_start", "lane_of", "sids", "times", "values", "units",
        "counts", "clean", "last_time", "synced", "spilled", "unlaned",
    )

    def __init__(self, block_start: int, lanes: int, slots: int) -> None:
        self.block_start = block_start
        self.lane_of: dict[bytes, int] = {}
        self.sids: list[bytes] = []
        self.times = np.zeros((lanes, slots), np.int64)
        self.values = np.zeros((lanes, slots), np.float64)
        self.units = np.zeros((lanes, slots), np.int8)
        self.counts = np.zeros(lanes, np.int32)
        self.clean = np.ones(lanes, bool)
        self.last_time = np.full(lanes, np.iinfo(np.int64).min, np.int64)
        # per-lane slot count already mirrored to the device planes
        self.synced = np.zeros(lanes, np.int32)
        # lanes that refused a row for want of slots, and series refused
        # a lane for want of lanes: the planes lack rows the SeriesBuffer
        # holds, so a plan must not read them (read_lanes)
        self.spilled = np.zeros(lanes, bool)
        self.unlaned: set[bytes] = set()


class ColumnWriteBuffer:
    """Device column write buffer for ONE shard (ring of `_Frame`s)."""

    def __init__(
        self, options: IngestOptions, block_size_nanos: int, registry=None
    ) -> None:
        self.options = options
        self.block_size_nanos = int(block_size_nanos)
        self._lock = threading.Lock()
        self._frames: dict[int, _Frame] = {}  # block_start -> frame
        # device planes per open window, built lazily at first sync:
        # block_start -> dict of uint32[lanes, slots] planes + counts
        self._planes: dict[int, dict] = {}
        self._staged_since_sync = 0
        # block starts that refused rows because no ring window was free:
        # their frame, if one opens later, lacks those rows
        self._window_spilled: set[int] = set()
        # one sync at a time: a query's sync and the write path's may race,
        # and each reads the planes it scatters into
        self._sync_lock = threading.Lock()
        # donation/epoch discipline (resident/pool.py): a query plan
        # leases the planes across its dispatch; a sync donates the plane
        # buffers to its scatter only when no lease is active, and new
        # leases fence on the in-flight donation
        self._leases = 0
        self._donating = False
        self._fence = threading.Condition(self._lock)
        self.epoch = 0
        self.appends = 0
        self.spills = dict.fromkeys(SPILL_REASONS, 0)
        self.device_syncs = 0
        self.device_sync_bytes = 0
        self.sealed_clean_lanes = 0
        self.dirty_lane_fallbacks = 0
        reg = registry or METRICS
        self._m_appends = reg.counter(
            "ingest_appends_total", "rows accepted into the column write buffer"
        )
        self._m_spilled = {
            r: reg.counter(
                "ingest_spilled_total",
                "rows the column buffer could not take, by reason — the "
                "row still lives in the host SeriesBuffer and its lane "
                "seals through the host codec (window: outside every "
                "open ring window; lanes: lane table full; slots: lane "
                "at capacity)",
                labels={"reason": r},
            )
            for r in SPILL_REASONS
        }
        self._m_syncs = reg.counter(
            "ingest_device_syncs_total",
            "batched column-plane scatters (host staged tail -> device)",
        )
        self._m_sync_bytes = reg.counter(
            "ingest_device_sync_bytes_total",
            "bytes moved by column-plane syncs — the write path's ONLY "
            "host->device traffic; admission of the encoded pages moves "
            "zero (resident_upload_bytes_total stays flat on device seals)",
        )
        self._m_sealed = reg.counter(
            "ingest_sealed_clean_lanes_total",
            "lanes sealed clean: sorted/unique columns handed straight "
            "to the device encode kernel, no merge pass",
        )
        self._m_dirty = reg.counter(
            "ingest_dirty_lane_fallbacks_total",
            "lanes that went out-of-order or duplicated in-window: seal "
            "falls back to the SeriesBuffer merge for them",
        )

    # ---------- writes ----------

    def append_batch(self, sids: list, times, values, units) -> np.ndarray:
        """Append a write batch; returns a bool mask of ACCEPTED rows
        (rejected rows are spilled-by-reason; callers need no action —
        the SeriesBuffer already holds every row).

        Rows are grouped per (window, lane) so the host staging cost is
        one numpy slice assignment per group, not per row."""
        times = np.asarray(times, np.int64)
        values = np.asarray(values, np.float64)
        units = np.asarray(units, np.int8)
        n = len(times)
        accepted = np.zeros(n, bool)
        if not self.options.enabled or n == 0:
            return accepted
        bsz = self.block_size_nanos
        o = self.options
        with self._lock:
            lo_bs = (int(times.min()) // bsz) * bsz
            hi_bs = (int(times.max()) // bsz) * bsz
            if lo_bs == hi_bs:  # whole batch in one window: no grouping
                frame = self._frame_locked(lo_bs, n)
                if frame is not None:
                    self._append_frame_locked(
                        frame, None, sids, times, values, units, accepted
                    )
            else:
                starts = (times // bsz) * bsz
                for bs in dict.fromkeys(starts.tolist()):  # arrival order
                    rows = np.nonzero(starts == bs)[0]
                    frame = self._frame_locked(bs, len(rows))
                    if frame is None:
                        continue
                    self._append_frame_locked(
                        frame,
                        rows,
                        [sids[i] for i in rows.tolist()],
                        times[rows],
                        values[rows],
                        units[rows],
                        accepted,
                    )
            got = int(accepted.sum())
            self.appends += got
            self._staged_since_sync += got
            self._m_appends.inc(got)
            want_sync = self._staged_since_sync >= o.sync_batch
        if want_sync:
            # one stage a sync (the device_puts and the scatter dispatch),
            # a child of the caller's write.ingest_append
            with TRACER.stage("ingest.sync"):
                self.sync()
        return accepted

    def _frame_locked(self, bs: int, n_rows: int):
        frame = self._frames.get(bs)
        if frame is None:
            if len(self._frames) >= self.options.windows:
                self._spill_locked("window", n_rows)
                self._window_spilled.add(bs)
                return None
            frame = _Frame(bs, self.options.lanes, self.options.slots)
            self._frames[bs] = frame
        return frame

    def _append_frame_locked(
        self, frame, rows, sids, times, values, units, accepted
    ) -> None:
        """Stage one window's slice of a batch (``rows is None`` = the
        whole batch): lane lookup is the only per-row Python work (a
        C-level ``map`` over the sid list); slot assignment, the column
        scatters, and the cleanliness bookkeeping are grouped numpy
        ops."""
        o = self.options
        lane_of = frame.lane_of
        raw = list(map(lane_of.get, sids))
        if None in raw:  # new sids: assign lanes in arrival order
            for j, lane in enumerate(raw):
                if lane is None:
                    sid = sids[j]
                    lane = lane_of.get(sid)
                    if lane is None:
                        if len(frame.sids) >= o.lanes:
                            raw[j] = -1
                            frame.unlaned.add(sid)
                            continue
                        lane = len(frame.sids)
                        lane_of[sid] = lane
                        frame.sids.append(sid)
                    raw[j] = lane
            lanes_idx = np.asarray(raw, np.int64)
            full = lanes_idx < 0
            if full.any():
                self._spill_locked("lanes", int(full.sum()))
                keep = ~full
                lanes_idx = lanes_idx[keep]
                rows = np.nonzero(keep)[0] if rows is None else rows[keep]
                times, values, units = times[keep], values[keep], units[keep]
                if not len(lanes_idx):
                    return
        else:
            lanes_idx = np.asarray(raw, np.int64)
        # stable sort by lane keeps arrival order within each lane, so
        # slot positions and the dirty check see the original sequence
        order = np.argsort(lanes_idx, kind="stable")
        ls = lanes_idx[order]
        t, v, u = times[order], values[order], units[order]
        first = np.nonzero(np.r_[True, ls[1:] != ls[:-1]])[0]
        cnt = np.diff(np.append(first, len(ls)))
        cum = np.arange(len(ls)) - np.repeat(first, cnt)
        slot = frame.counts[ls].astype(np.int64) + cum
        fit = slot < o.slots
        if not fit.all():
            self._spill_locked("slots", int((~fit).sum()))
            frame.spilled[ls[~fit]] = True
            # overflow is always a per-lane TAIL (slots ascend within a
            # lane), so groups stay contiguous after the filter
            order, ls, t, v, u, slot = (
                order[fit], ls[fit], t[fit], v[fit], u[fit], slot[fit]
            )
            if not len(ls):
                return
            first = np.nonzero(np.r_[True, ls[1:] != ls[:-1]])[0]
            cnt = np.diff(np.append(first, len(ls)))
        uniq = ls[first]
        frame.times[ls, slot] = t
        frame.values[ls, slot] = v
        frame.units[ls, slot] = u
        frame.counts[uniq] += cnt.astype(np.int32)
        prev = np.empty_like(t)
        prev[1:] = t[:-1]
        prev[first] = frame.last_time[uniq]
        viol = t <= prev
        if viol.any():
            frame.clean[np.unique(ls[viol])] = False
        frame.last_time[uniq] = np.maximum(
            frame.last_time[uniq], np.maximum.reduceat(t, first)
        )
        accepted[order if rows is None else rows[order]] = True

    def append(self, sid: bytes, t_nanos: int, value: float, unit: int) -> bool:
        return bool(self.append_batch([sid], [t_nanos], [value], [unit])[0])

    def _spill_locked(self, reason: str, count: int = 1) -> None:
        self.spills[reason] += count
        self._m_spilled[reason].inc(count)

    # ---------- device planes ----------

    def sync(self, donate: bool = True) -> int:
        """Mirror the staged column tail to the device planes, donating the
        plane buffers to the scatter when ``donate`` and no lease is
        active. The write path calls it every ``sync_batch`` appends and a
        query plan before it reads the planes (``donate=False``: another
        query's lease decides whether a donation may happen, and a donated
        scatter is another program, which a query must not compile
        inside a window). Syncs run one at a time, so a caller returns only
        once every row acknowledged before its call is on the device,
        whichever sync moved it. The tiles and the counts they mark synced
        are taken under one lock: a row appended meanwhile waits for the
        next sync. Returns rows moved."""
        import jax
        import jax.numpy as jnp

        o = self.options
        m32 = np.uint64(0xFFFFFFFF)
        w = min(_SYNC_TILE_SLOTS, o.slots)
        with self._sync_lock:
            moved = 0
            with self._lock:
                work = []
                for bs, frame in self._frames.items():
                    dirty = np.nonzero(frame.synced < frame.counts)[0]
                    if not len(dirty):
                        continue
                    # stage only the dirty slot TAIL — rectangular tiles of
                    # ``w`` slots covering [lo, hi) across the dirty lanes,
                    # the lane count padded to a power of two, so a tick's
                    # rows and set-up's leftovers run one scatter program
                    # (O(log) variants by lane count, not one per shape).
                    # Padding restages rows/slots already on device with
                    # identical values, which keeps the duplicate-index
                    # scatter exact.
                    lo = int(frame.synced[dirty].min())
                    hi = int(frame.counts[dirty].max())
                    nd = 1 << max(len(dirty) - 1, 0).bit_length()
                    pad = np.concatenate(
                        [dirty, np.repeat(dirty[-1], nd - len(dirty))]
                    )
                    tiles = []
                    for start in range(lo, hi, w):
                        start = min(start, o.slots - w)
                        ts = frame.times[pad, start:start + w].view(np.uint64)
                        vb = frame.values[pad, start:start + w].view(np.uint64)
                        tiles.append((start, np.stack(
                            [
                                (ts >> np.uint64(32)).astype(np.uint32),
                                (ts & m32).astype(np.uint32),
                                (vb >> np.uint64(32)).astype(np.uint32),
                                (vb & m32).astype(np.uint32),
                            ]
                        )))
                    now = frame.counts[dirty].copy()
                    moved += int((now - frame.synced[dirty]).sum())
                    work.append((bs, frame, dirty, now, pad, tiles,
                                 frame.counts[pad].copy()))
                self._staged_since_sync = 0
                if not work:
                    return 0
                donate = donate and self._leases == 0
                if donate:
                    self._donating = True
            try:
                for bs, frame, dirty, now, pad, tiles, counts_host in work:
                    planes = self._planes.get(bs)
                    if planes is None:
                        planes = {
                            # ts_hi / ts_lo / val_hi / val_lo as one stacked
                            # tensor: a tile moves ONE host->device staging
                            # buffer and runs ONE scatter for all four
                            "cols": jnp.zeros(
                                (4, o.lanes, o.slots), jnp.uint32
                            ),
                            "counts": jnp.zeros(o.lanes, jnp.int32),
                        }
                    cols, counts = planes["cols"], planes["counts"]
                    # m3lint: disable=M3L001,M3L010 -- sanctioned host->device staging: dirty host tiles must cross PCIe once per sync; under _sync_lock, which only syncs take (a query's and the write path's scatter into the planes the other reads), never the shard's or the buffer's lock
                    idx = jax.device_put(pad.astype(np.int32))
                    # m3lint: disable=M3L001,M3L010 -- sanctioned host->device staging (same boundary as idx above)
                    staged_c = jax.device_put(counts_host)
                    scatter = _scatter_tile4_donate if donate else _scatter_tile4
                    nbytes = counts_host.nbytes
                    for start, host in tiles:
                        # m3lint: disable=M3L001,M3L010 -- sanctioned host->device staging (same boundary as idx above)
                        lo_dev = jax.device_put(np.int32(start))
                        # m3lint: disable=M3L001,M3L010 -- sanctioned host->device staging (same boundary as idx above)
                        staged = jax.device_put(host)
                        nbytes += host.nbytes
                        cols, counts = scatter(cols, counts, idx, lo_dev,
                                               staged, staged_c)
                    with self._lock:
                        # a window sealed or dropped meanwhile keeps no planes
                        if self._frames.get(bs) is frame:
                            self._planes[bs] = {"cols": cols, "counts": counts}
                            frame.synced[dirty] = now
                        self.epoch += 1
                        self.device_syncs += 1
                        self.device_sync_bytes += nbytes
                    self._m_syncs.inc()
                    self._m_sync_bytes.inc(nbytes)
            finally:
                if donate:
                    with self._lock:
                        self._donating = False
                        self._fence.notify_all()
        return moved

    def lease(self):
        """Context manager: hold the device planes stable across a
        reader's dispatch (syncs downgrade to functional copies)."""
        return _Lease(self)

    # ---------- the query plan's read face (query/plan.py) ----------

    def _stamp_locked(self, block_start: int):
        frame = self._frames.get(block_start)
        spilled = block_start in self._window_spilled
        if frame is None:
            return ("spilled",) if spilled else None
        return id(frame), len(frame.sids), spilled

    def frame_stamp(self, block_start: int):
        """What moves when the window's lane table does: its frame, the
        lanes handed out, and whether rows for it were refused for want
        of a ring window; None where the window has no frame and refused
        nothing."""
        with self._lock:
            return self._stamp_locked(block_start)

    def lanes_for(self, block_start: int, sids: list):
        """``(stamp, lanes)`` of one open window: the lane of every sid
        (-1: none yet) and the window's :meth:`frame_stamp` they were read
        under; None where the window has no frame."""
        with self._lock:
            frame = self._frames.get(block_start)
            if frame is None:
                return None
            get = frame.lane_of.get
            lanes = np.fromiter((get(s, -1) for s in sids), np.int32, len(sids))
            return self._stamp_locked(block_start), lanes

    def read_lanes(self, block_start: int, stamp, lanes: np.ndarray,
                   laneless: list):
        """What a plan reads of one open window, to be called under
        :meth:`lease` after :meth:`sync`: ``(None, cols, counts, width)``
        (the planes as the last sync left them, and the most synced rows
        of any of ``lanes``), or ``(reason, ...)`` where the planes cannot
        stand for what the shard holds: ``raced`` (the lane table moved
        since ``stamp``, its :meth:`frame_stamp`), ``dirty-lane`` (rows
        out of order: only the SeriesBuffer merge has them right),
        ``spilled-row`` (the window, a lane, or a series of ``laneless``
        refused rows that the SeriesBuffer holds).
        The checks and the planes are taken under one lock, so a lane that
        turns dirty after the planes were read is not in them."""
        with self._lock:
            frame = self._frames.get(block_start)
            if frame is None or self._stamp_locked(block_start) != stamp:
                return "raced", None, None, 0
            if stamp[2] or frame.spilled[lanes].any() or (
                    frame.unlaned and not frame.unlaned.isdisjoint(laneless)):
                return "spilled-row", None, None, 0
            if not frame.clean[lanes].all():
                return "dirty-lane", None, None, 0
            if not len(lanes):
                return None, None, None, 0
            planes = self._planes.get(block_start)
            if planes is None:
                return "raced", None, None, 0
            return (None, planes["cols"], planes["counts"],
                    int(frame.synced[lanes].max()))

    # ---------- seal ----------

    def seal_window(self, block_start: int):
        """Close one window and hand back its lanes: ``(clean, dirty)``
        where ``clean`` is a list of :class:`SealLane` (sorted, unique —
        encode-kernel ready) and ``dirty`` the sids that must seal
        through the SeriesBuffer merge. The frame and its device planes
        are released."""
        with self._lock:
            frame = self._frames.pop(block_start, None)
            self._planes.pop(block_start, None)
            self._window_spilled.discard(block_start)
            if frame is None:
                return [], []
            clean: list[SealLane] = []
            dirty: list[bytes] = []
            for lane, sid in enumerate(frame.sids):
                c = int(frame.counts[lane])
                if frame.clean[lane]:
                    clean.append(
                        SealLane(
                            sid=sid,
                            times=frame.times[lane, :c].copy(),
                            values=frame.values[lane, :c].copy(),
                            units=frame.units[lane, :c].astype(np.int32),
                        )
                    )
                else:
                    dirty.append(sid)
            self.sealed_clean_lanes += len(clean)
            self.dirty_lane_fallbacks += len(dirty)
            self._m_sealed.inc(len(clean))
            self._m_dirty.inc(len(dirty))
            self.epoch += 1
            return clean, dirty

    def drop_window(self, block_start: int) -> None:
        """Release a window without sealing (retention expiry)."""
        with self._lock:
            self._frames.pop(block_start, None)
            self._planes.pop(block_start, None)
            self._window_spilled.discard(block_start)

    def open_windows(self) -> list[int]:
        with self._lock:
            return sorted(self._frames)

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": self.options.enabled,
                "open_windows": sorted(self._frames),
                "appends": self.appends,
                "spills": dict(self.spills),
                "device_syncs": self.device_syncs,
                "device_sync_bytes": self.device_sync_bytes,
                "sealed_clean_lanes": self.sealed_clean_lanes,
                "dirty_lane_fallbacks": self.dirty_lane_fallbacks,
                "epoch": self.epoch,
            }


class _Lease:
    def __init__(self, buf: ColumnWriteBuffer) -> None:
        self._buf = buf

    def __enter__(self):
        buf = self._buf
        with buf._lock:
            while buf._donating:
                buf._fence.wait()
            buf._leases += 1
        return self

    def __exit__(self, *exc):
        buf = self._buf
        with buf._lock:
            buf._leases -= 1
        return False


def _tile4_set(b, c, i, lo, s, sc):
    """One dispatch for a sync: scatter the stacked column tile AND the
    per-lane counts."""
    import jax.numpy as jnp

    cols = lo + jnp.arange(s.shape[2], dtype=jnp.int32)
    return b.at[:, i[:, None], cols[None, :]].set(s), c.at[i].set(sc)


def _scatter_tile4(b, c, idx, lo, staged, staged_c):
    global _TILE_JIT
    import jax

    if _TILE_JIT is None:
        _TILE_JIT = jax.jit(_tile4_set)
    return _TILE_JIT(b, c, idx, lo, staged, staged_c)


def _scatter_tile4_donate(b, c, idx, lo, staged, staged_c):
    global _TILE_DONATE_JIT
    import jax

    if _TILE_DONATE_JIT is None:
        _TILE_DONATE_JIT = jax.jit(_tile4_set, donate_argnums=(0, 1))
    return _TILE_DONATE_JIT(b, c, idx, lo, staged, staged_c)


_TILE_JIT = None
_TILE_DONATE_JIT = None
