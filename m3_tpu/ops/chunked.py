"""Chunked M3TSZ decode: side-table-indexed, gather-free device scan.

The TPU redesign of the reference's sequential iterator
(/root/reference/src/dbnode/encoding/m3tsz/iterator.go): streams are split
into chunks of K records, each chunk carrying a ~40-byte snapshot of the
decoder state at its start (SURVEY.md §7 hard part #1 — "host prescan index
of record offsets stored alongside segments at encode time"). Decode then
runs as a K-step `lax.scan` over S×C chunk-lanes:

  - sequential dependence is confined WITHIN a chunk (K steps instead of T);
  - every chunk reads bits from its own small word window, so the per-step
    bit fetch is a narrow [N, CW] take instead of a strided HBM gather over
    the full [S, W] stream matrix;
  - lane parallelism multiplies by C = ceil(T/K), which keeps the VPU busy
    even for few-series batches.

Side tables come from the encoder (it walks the stream anyway) or from a
one-time host prescan for foreign streams; on-device results are bit-identical
to the CPU iterator either way.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..codec.m3tsz import DEFAULT_INT_OPTIMIZATION, ReaderIterator, initial_time_unit
from ..utils.instrument import KernelProfiler
from ..utils.xtime import Unit
from . import u64
from .decode import DecodeResult, DecodeState, _decode_timestamp, _decode_value, _int_val_to_f32

I32 = jnp.int32
U32 = jnp.uint32

# device-tier observability for the chunked decode kernel: first-call
# compile attribution + sampled block_until_ready-bounded dispatch wall
# time (M3_TPU_PROFILE_SAMPLE_RATE) in m3tpu_kernel_dispatch_seconds
# {kernel="chunked_decode"}; eager callers (parallel/scan.py) dispatch
# through this — inside an outer jit trace they must not (wall time there
# measures tracing, and blocking on tracers is impossible)
PROFILER = KernelProfiler("chunked_decode")

# Decoder-state fields stored as (hi, lo) uint32 pairs.
STATE_PAIR_FIELDS = ("prev_time", "prev_delta", "prev_float_bits", "prev_xor", "int_val")
# Every per-lane field of ChunkedBatch, in decode_chunked_lanes order.
LANE_FIELDS = (
    "windows",
    "rel_pos",
    "num_bits",
    "first",
    *STATE_PAIR_FIELDS,
    "time_unit",
    "sig",
    "mult",
    "is_float",
)


def lane_kwargs(batch: "ChunkedBatch", transform=None) -> dict:
    """ChunkedBatch → decode_chunked_lanes kwargs; ``transform`` maps each
    array (applied to both halves of pair fields)."""
    t = transform or (lambda x: x)
    out = {}
    for f in LANE_FIELDS:
        v = getattr(batch, f)
        out[f] = (t(v[0]), t(v[1])) if f in STATE_PAIR_FIELDS else t(v)
    return out


def _split64(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    x = x.astype(np.uint64)
    return (x >> np.uint64(32)).astype(np.uint32), (x & np.uint64(0xFFFFFFFF)).astype(np.uint32)


@dataclass
class ChunkedBatch:
    """Flattened [S*C] chunk lanes + per-chunk decoder-state side table."""

    windows: np.ndarray  # uint32[N, CW]
    rel_pos: np.ndarray  # int32[N] bit offset of chunk start within window
    num_bits: np.ndarray  # int32[N] window-relative valid bit bound
    first: np.ndarray  # bool[N] first chunk of its series
    prev_time: tuple  # (hi, lo) uint32[N]
    prev_delta: tuple
    prev_float_bits: tuple
    prev_xor: tuple
    int_val: tuple
    time_unit: np.ndarray  # int32[N]
    sig: np.ndarray
    mult: np.ndarray
    is_float: np.ndarray  # bool[N]
    k: int
    num_series: int
    num_chunks: int  # C per series (uniform, zero-padded)
    # host-classified fast chunks (all-int, marker-free, constant {s,ms}
    # unit, exactly k records — see snapshot_stream); empty padding lanes
    # are fast=True so they never force a mixed tile slow
    fast: np.ndarray = None  # bool[N]
    # float-mode analogue: marker-free XOR/repeat records, float at chunk
    # start and after every record (the float-specialized kernel body)
    fast_float: np.ndarray = None  # bool[N]

    @property
    def num_lanes(self) -> int:
        return self.windows.shape[0]


def snapshot_stream(
    data: bytes,
    k: int,
    int_optimized: bool = DEFAULT_INT_OPTIMIZATION,
    default_unit: Unit = Unit.SECOND,
) -> list[dict]:
    """Host prescan of one stream: decoder-state snapshot every ``k`` records.

    This is the side table our fileset format persists next to each stream
    (persisted by storage/fs.py); the encoder path can also emit it directly
    at flush time since it walks the stream anyway."""
    it = ReaderIterator(data, int_optimized=int_optimized, default_unit=default_unit)
    per: list[dict] = []
    nrec = 0
    total_bits = len(data) * 8
    # fast-chunk classification (device kernel specialization, ops/fused.py):
    # a chunk is fast iff all k records are marker-free int-mode records with
    # a constant {s, ms} time unit; tracked record by record below.
    # fast_float: the float-mode analogue — every record marker-free and
    # float-mode with the chunk ALREADY in float mode at its start, so the
    # device sees only "1"+XOR (OPCODE_NO_UPDATE=1) or "01" repeat
    # (OPCODE_UPDATE=0 + OPCODE_REPEAT=1) records; an int→float transition
    # record carries a full float the float body can't parse — requiring
    # is_float at start AND after every record excludes it.
    chunk_fast = True
    chunk_fast_float = True
    chunk_start_float = False
    chunk_recs = 0

    def snap():
        st = it.stream
        ts = it.ts_iterator
        unit = ts.time_unit
        if nrec == 0 and len(data) >= 8:
            nt = int.from_bytes(data[:8], "big")
            unit = initial_time_unit(nt, default_unit)
        return dict(
            off=st.byte_pos * 8 + st.bit_pos,
            prev_time=ts.prev_time & 0xFFFFFFFFFFFFFFFF,
            prev_delta=ts.prev_time_delta & 0xFFFFFFFFFFFFFFFF,
            time_unit=int(unit),
            prev_float_bits=it.float_iter.prev_float_bits,
            prev_xor=it.float_iter.prev_xor,
            int_val=int(it.int_val) & 0xFFFFFFFFFFFFFFFF,
            sig=it.sig,
            mult=it.mult,
            is_float=it.is_float,
        )

    while True:
        pending = snap() if nrec % k == 0 else None
        if pending is not None and per:
            # the previous chunk just completed all k records: seal its flag
            per[-1]["fast"] = chunk_fast and chunk_recs == k
            per[-1]["fast_float"] = (
                chunk_fast_float and chunk_start_float and chunk_recs == k
            )
        if pending is not None:
            chunk_fast, chunk_recs = True, 0
            chunk_fast_float = True
            chunk_start_float = bool(it.is_float) and int_optimized
        markers_before = it.ts_iterator.num_markers
        if not it.next():
            # no record followed: don't emit an empty trailing chunk
            break
        if pending is not None:
            per.append(pending)
        nrec += 1
        chunk_recs += 1
        marker_seen = it.ts_iterator.num_markers != markers_before
        unit_ok = int(it.ts_iterator.time_unit) in (
            int(Unit.SECOND), int(Unit.MILLISECOND)
        )
        if (
            marker_seen
            or it.is_float
            or not unit_ok
            or not int_optimized
            # int32-safety: the specialized body runs the whole int path in
            # 32-bit (sig <= 31, value in i32 range after every record; the
            # chunk's starting value is the previous record's, also checked)
            or it.sig > 31
            or abs(it.int_val) > 2147483647
        ):
            chunk_fast = False
        if marker_seen or not it.is_float or not unit_ok or not int_optimized:
            chunk_fast_float = False
        if it.ts_iterator.done or it.err is not None:
            break
    if per and chunk_recs > 0:
        # seal the trailing chunk; a break exactly on a boundary (chunk_recs
        # == 0 after reset) means the last chunk was already sealed above
        per[-1]["fast"] = chunk_fast and chunk_recs == k
        per[-1]["fast_float"] = (
            chunk_fast_float and chunk_start_float and chunk_recs == k
        )
    offs = [p["off"] for p in per] + [total_bits]
    for i, p in enumerate(per):
        p["span"] = offs[i + 1] - offs[i]
        p["total_bits"] = total_bits
        p.setdefault("fast", False)
        p.setdefault("fast_float", False)
    return per


def window_words(max_span_bits: int, min_window_words: int = 0) -> int:
    """Window width (uint32 words) covering the widest chunk span plus 4
    lookahead words and up to 31 bits of alignment slack. ONE shared
    definition: the streamed assembler below and the resident pool's
    device-side assembly (m3_tpu/resident/) must agree on cw or their
    window arrays — and therefore their f32 reduction trees — diverge."""
    cw = (31 + max_span_bits + 31) // 32 + 4
    return max(cw, min_window_words, 6)


def assemble_chunked(
    streams: list[bytes], snaps: list[list[dict]], k: int, min_window_words: int = 0
) -> ChunkedBatch:
    """Pack streams + per-chunk snapshots into the dense lane arrays."""
    s = len(streams)
    c = max((len(p) for p in snaps), default=1)
    c = max(c, 1)
    n = s * c
    max_span = max((p["span"] for per in snaps for p in per), default=0)
    cw = window_words(max_span, min_window_words)

    windows = np.zeros((n, cw), np.uint32)
    rel = np.zeros(n, np.int32)
    nbits = np.zeros(n, np.int32)
    first = np.zeros(n, bool)
    pt = np.zeros(n, np.uint64)
    pd = np.zeros(n, np.uint64)
    pfb = np.zeros(n, np.uint64)
    pxr = np.zeros(n, np.uint64)
    iv = np.zeros(n, np.uint64)
    tu = np.zeros(n, np.int32)
    sig = np.zeros(n, np.int32)
    mult = np.zeros(n, np.int32)
    isf = np.zeros(n, bool)
    fast = np.ones(n, bool)  # empty padding lanes stay fast
    fast_float = np.ones(n, bool)  # likewise

    for si, (data, per) in enumerate(zip(streams, snaps)):
        padded = (
            np.frombuffer(data + b"\x00" * (-len(data) % 4), dtype=">u4").astype(np.uint32)
            if data
            else np.zeros(0, np.uint32)
        )
        for ci, p in enumerate(per):
            i = si * c + ci
            w0 = p["off"] >> 5
            rel[i] = p["off"] & 31
            seg = padded[w0 : w0 + cw]
            windows[i, : len(seg)] = seg
            nbits[i] = max(0, min(p["total_bits"] - (w0 << 5), cw * 32))
            first[i] = ci == 0
            pt[i] = p["prev_time"]
            pd[i] = p["prev_delta"]
            pfb[i] = p["prev_float_bits"]
            pxr[i] = p["prev_xor"]
            iv[i] = p["int_val"]
            tu[i] = p["time_unit"]
            sig[i] = p["sig"]
            mult[i] = p["mult"]
            isf[i] = p["is_float"]
            # the first chunk decodes the 64-bit head + first-value format
            # the fast bodies don't implement
            fast[i] = bool(p.get("fast", False)) and ci != 0
            fast_float[i] = bool(p.get("fast_float", False)) and ci != 0

    return ChunkedBatch(
        windows=windows,
        rel_pos=rel,
        num_bits=nbits,
        first=first,
        prev_time=_split64(pt),
        prev_delta=_split64(pd),
        prev_float_bits=_split64(pfb),
        prev_xor=_split64(pxr),
        int_val=_split64(iv),
        time_unit=tu,
        sig=sig,
        mult=mult,
        is_float=isf,
        k=k,
        num_series=s,
        num_chunks=c,
        fast=fast,
        fast_float=fast_float,
    )


def build_chunked(
    streams: list[bytes],
    k: int = 32,
    int_optimized: bool = DEFAULT_INT_OPTIMIZATION,
    default_unit: Unit = Unit.SECOND,
    min_window_words: int = 0,
) -> ChunkedBatch:
    """Prescan + assemble (see snapshot_stream / assemble_chunked). Uses the
    native C++ prescanner (native/m3tsz.cc, ~50x the Python walk) when built."""
    from .. import native

    if native.available():
        snaps = native.prescan_batch(
            streams, k=k, default_unit=int(default_unit), int_optimized=int_optimized
        )
    else:
        snaps = [
            snapshot_stream(d, k, int_optimized=int_optimized, default_unit=default_unit)
            for d in streams
        ]
    return assemble_chunked(streams, snaps, k, min_window_words=min_window_words)


def tile_chunked(batch: ChunkedBatch, n_series: int) -> ChunkedBatch:
    """Tile a small unique batch up to n_series (bench helper)."""
    reps = -(-n_series // batch.num_series)
    cut = n_series * batch.num_chunks

    def t(x):
        return np.tile(np.asarray(x), (reps,) + (1,) * (np.asarray(x).ndim - 1))[:cut]

    return ChunkedBatch(
        **lane_kwargs(batch, transform=t),
        k=batch.k,
        num_series=n_series,
        num_chunks=batch.num_chunks,
        fast=t(batch.fast) if batch.fast is not None else None,
        fast_float=t(batch.fast_float) if batch.fast_float is not None else None,
    )


def pad_series(batch: ChunkedBatch, multiple: int) -> ChunkedBatch:
    """Pad with EMPTY series (zero-bit lanes decode zero records) so the
    series count divides a mesh size — the query fan-out's matched count is
    arbitrary, the mesh's shard axis is not. Empty lanes match
    build_chunked's padding exactly (all-zero state, fast=True) so they
    route through the fast kernel body and contribute nothing."""
    pad = (-batch.num_series) % multiple
    if pad == 0:
        return batch
    n_new = pad * batch.num_chunks

    def t(x):
        x = np.asarray(x)
        z = np.zeros((n_new,) + x.shape[1:], x.dtype)
        return np.concatenate([x, z])

    kw = lane_kwargs(batch, transform=t)
    return ChunkedBatch(
        **kw,
        k=batch.k,
        num_series=batch.num_series + pad,
        num_chunks=batch.num_chunks,
        fast=(
            np.concatenate([np.asarray(batch.fast), np.ones(n_new, bool)])
            if batch.fast is not None
            else None
        ),
        fast_float=(
            np.concatenate([np.asarray(batch.fast_float), np.ones(n_new, bool)])
            if batch.fast_float is not None
            else None
        ),
    )


def select_series(batch: ChunkedBatch, series_idx) -> ChunkedBatch:
    """Query-fanout gather: a new ChunkedBatch holding only the selected
    series (index query postings → decode, the config-5 fan-out shape).
    Host-side numpy fancy indexing over the series-major lane layout."""
    sel = np.asarray(series_idx, np.int64)
    c = batch.num_chunks
    lanes = (sel[:, None] * c + np.arange(c)[None, :]).ravel()

    def g(x):
        # np.take is ~20% faster than fancy indexing for these row gathers
        # (contiguous output, no intermediate index normalization)
        return np.take(np.asarray(x), lanes, axis=0)

    return ChunkedBatch(
        **lane_kwargs(batch, transform=g),
        k=batch.k,
        num_series=int(sel.size),
        num_chunks=c,
        fast=g(batch.fast) if batch.fast is not None else None,
        fast_float=g(batch.fast_float) if batch.fast_float is not None else None,
    )


def _window_columns(windows):
    """Pre-split the [N, CW] window into CW+3 column vectors (zero-padded).

    Device gathers are catastrophically slow on TPU (XLA lowers them to
    scalar dynamic-slices), so the per-step fetch is a pure vector select
    chain over these columns instead."""
    n, cw = windows.shape
    zero = jnp.zeros((n,), U32)
    cols = [windows[:, j] for j in range(cw)] + [zero, zero, zero]
    return cols


def _fetch4_select(cols, cw, base_rel, pos, max_widx: int | None = None):
    """Aligned 4-word fetch via a barrel shift over the lane-private window
    columns — O(CW + 4 log CW) VPU selects, no gather.

    One shared barrel shifter (high bit first, narrowing the live candidate
    list to 4 + remaining-shift entries each stage) replaces four independent
    select trees: ~46 selects vs ~124 at CW=24.

    ``max_widx`` (static) bounds the word index the caller can reach — for
    unrolled record loops the cursor after j records is statically bounded,
    so early records need far fewer barrel stages."""
    p = base_rel + pos
    widx = p >> 5
    zero = jnp.zeros_like(cols[0])
    bound = cw - 1 if max_widx is None else min(max_widx, cw - 1)
    cand = list(cols[: min(bound + 4, cw + 3)])
    while len(cand) < 4:
        cand.append(zero)
    if bound <= 0:
        s = 0  # cursor provably in word 0: no barrel stages at all
    else:
        s = 1
        while s * 2 <= bound:
            s *= 2
    while s >= 1:
        flag = (widx & s) != 0
        width = min(4 + s - 1, len(cand))
        cand = [
            jnp.where(flag, cand[i + s] if i + s < len(cand) else zero, cand[i])
            for i in range(width)
        ]
        s //= 2
    ws = (cand[0], cand[1], cand[2], cand[3])
    r = (p & 31).astype(U32)
    nz = r != 0
    inv = U32(32) - r

    def sh(a, b):
        return (a << r) | jnp.where(nz, b >> inv, U32(0))

    return (sh(ws[0], ws[1]), sh(ws[1], ws[2]), sh(ws[2], ws[3]), ws[3] << r)


def _point_step(fetch4, nb, nt0, first_vec, int_optimized, state):
    """Decode ONE record for every lane: the next DecodeState and the
    record's seven output planes (ts pair, value pair, point_is_float,
    mult, valid). Shared by the lax.scan below and the Pallas point kernel
    (ops/fused.decode_points_pallas), so both emit the same bits."""
    was_active = ~state.done & ~state.err
    state, _ = _decode_timestamp(fetch4, nb, state, first_vec, nt=nt0)
    ts_active = ~state.done & ~state.err
    state = _decode_value(fetch4, state, first_vec, int_optimized)
    now_active = ~state.done & ~state.err
    valid = was_active & ts_active & now_active
    point_is_float = jnp.logical_or(not int_optimized, state.is_float)
    val = u64.select(point_is_float, state.prev_float_bits, state.int_val)
    out = (
        state.prev_time[0],
        state.prev_time[1],
        val[0],
        val[1],
        point_is_float,
        state.mult,
        valid,
    )
    return state, out


@functools.partial(jax.jit, static_argnames=("k", "int_optimized"))
def decode_chunked_lanes(
    windows,
    rel_pos,
    num_bits,
    first,
    prev_time,
    prev_delta,
    prev_float_bits,
    prev_xor,
    int_val,
    time_unit,
    sig,
    mult,
    is_float,
    k: int,
    int_optimized: bool = True,
) -> DecodeResult:
    """K-step scan over chunk lanes. Same record semantics as
    decode.decode_batched; only the fetch and initial state differ."""
    windows = jnp.asarray(windows, U32)
    rel_pos = jnp.asarray(rel_pos, I32)
    n = windows.shape[0]
    cols = _window_columns(windows)
    fetch4 = functools.partial(_fetch4_select, cols, windows.shape[1], rel_pos)
    as_pair = lambda p: (jnp.asarray(p[0], U32), jnp.asarray(p[1], U32))

    state = DecodeState(
        pos=jnp.zeros((n,), I32),
        done=jnp.asarray(num_bits, I32) <= jnp.asarray(rel_pos, I32),
        err=jnp.zeros((n,), bool),
        prev_time=as_pair(prev_time),
        prev_delta=as_pair(prev_delta),
        time_unit=jnp.asarray(time_unit, I32),
        prev_float_bits=as_pair(prev_float_bits),
        prev_xor=as_pair(prev_xor),
        int_val=as_pair(int_val),
        mult=jnp.asarray(mult, I32),
        sig=jnp.asarray(sig, I32),
        is_float=jnp.asarray(is_float, bool),
    )
    first_chunk = jnp.asarray(first, bool)
    nb = jnp.asarray(num_bits, I32) - rel_pos  # bits available from chunk start
    from .decode import _extract

    zero_pos = jnp.zeros((n,), I32)
    nt0 = _extract(fetch4(zero_pos), 0, 64)

    def step(state, idx):
        return _point_step(
            fetch4, nb, nt0, first_chunk & (idx == 0), int_optimized, state
        )

    final_state, outs = jax.lax.scan(step, state, jnp.arange(k))
    ts_hi, ts_lo, val_hi, val_lo, pif, mlt, valid = outs
    tr = lambda x: jnp.swapaxes(x, 0, 1)
    val_pair = (tr(val_hi), tr(val_lo))
    values_f32 = jnp.where(
        tr(pif),
        u64.f64_bits_to_f32(val_pair),
        _int_val_to_f32(val_pair, tr(mlt)),
    )
    return DecodeResult(
        ts_hi=tr(ts_hi),
        ts_lo=tr(ts_lo),
        val_hi=val_pair[0],
        val_lo=val_pair[1],
        point_is_float=tr(pif),
        mult=tr(mlt),
        valid=tr(valid),
        err=final_state.err,
        values_f32=jnp.where(tr(valid), values_f32, jnp.float32(jnp.nan)),
    )


def decode_chunked(batch: ChunkedBatch, int_optimized: bool = True) -> DecodeResult:
    """Decode a ChunkedBatch; outputs reshaped to [S, C*K] per-series rows."""
    res = decode_chunked_lanes(
        **lane_kwargs(batch), k=batch.k, int_optimized=int_optimized
    )
    s, c, k = batch.num_series, batch.num_chunks, batch.k

    def rs(x):
        return x.reshape(s, c * k)

    return DecodeResult(
        ts_hi=rs(res.ts_hi),
        ts_lo=rs(res.ts_lo),
        val_hi=rs(res.val_hi),
        val_lo=rs(res.val_lo),
        point_is_float=rs(res.point_is_float),
        mult=rs(res.mult),
        valid=rs(res.valid),
        err=jnp.any(res.err.reshape(s, c), axis=1),
        values_f32=rs(res.values_f32),
    )
