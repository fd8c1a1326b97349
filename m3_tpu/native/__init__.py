"""ctypes bindings for the native C++ codec (native/m3tsz.cc).

Builds lazily with g++ if the shared library is missing; every entry point
has a pure-Python fallback so the framework degrades gracefully on hosts
without a toolchain.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_LIB_PATH = os.path.abspath(os.path.join(_DIR, "libm3tsz.so"))
_SRC_PATH = os.path.abspath(os.path.join(_DIR, "m3tsz.cc"))

_lib = None


class _SnapRec(ctypes.Structure):
    _pack_ = 1
    _fields_ = [
        ("off", ctypes.c_uint32),
        ("prev_time", ctypes.c_uint64),
        ("prev_delta", ctypes.c_uint64),
        ("prev_float_bits", ctypes.c_uint64),
        ("prev_xor", ctypes.c_uint64),
        ("int_val", ctypes.c_uint64),
        ("time_unit", ctypes.c_uint8),
        ("sig", ctypes.c_uint8),
        ("mult", ctypes.c_uint8),
        ("is_float", ctypes.c_uint8),
        ("flags", ctypes.c_uint8),  # bit 0: int-fast chunk; bit 1: float-fast
    ]


def _cpu_signature() -> str:
    """Identity of this host's ISA (for -march=native cache safety): a
    library built on a wider-ISA host would SIGILL here, so the cached .so
    is only trusted when the CPU flags that produced it match."""
    import hashlib
    import platform

    sig = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    sig += hashlib.sha256(line.encode()).hexdigest()[:16]
                    break
    except OSError:
        pass
    return sig


def _build() -> bool:
    """Compile native/m3tsz.cc and move the result into place atomically:
    g++ writes a private temporary name and ``os.replace`` publishes it
    (then the .buildinfo), so a concurrent loader — xdist workers, or a
    dbnode and a coordinator starting on a fresh checkout — sees the old
    file, no file, or a whole one, never a half-written one."""
    if not os.path.exists(_SRC_PATH):
        return False
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            [
                "g++",
                "-O3",
                "-march=native",  # cached per-CPU-signature (see load())
                "-shared",
                "-fPIC",
                "-std=c++17",
                "-o",
                tmp,
                _SRC_PATH,
                "-lpthread",
            ],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, _LIB_PATH)
        with open(tmp, "w") as f:
            f.write(_cpu_signature())
        os.replace(tmp, _LIB_PATH + ".buildinfo")
        return True
    except (subprocess.CalledProcessError, FileNotFoundError, OSError):
        return False
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _needs_build() -> bool:
    if not os.path.exists(_LIB_PATH):
        return True
    if (
        os.path.exists(_SRC_PATH)
        and os.path.getmtime(_SRC_PATH) > os.path.getmtime(_LIB_PATH)
    ):
        return True
    # a -march=native .so copied from a wider-ISA host would SIGILL
    # (uncatchably) on first call: rebuild unless the recorded CPU
    # signature matches this host
    try:
        with open(_LIB_PATH + ".buildinfo") as f:
            return f.read() != _cpu_signature()
    except OSError:
        return True


def _open():
    try:
        return ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None


def load():
    """Load (building if needed) the native library; None if unavailable."""
    global _lib
    if _lib is not None:
        return _lib
    lib = None if _needs_build() else _open()
    if lib is None:
        # missing, stale, or an existing file that would not load (left
        # half-written by a build that was not atomic): build, then retry
        if not _build():
            return None
        lib = _open()
        if lib is None:
            return None
    lib.m3tsz_encode_batch.restype = ctypes.c_int64
    lib.m3tsz_encode_series.restype = ctypes.c_int64
    lib.m3tsz_prescan.restype = ctypes.c_int32
    lib.m3tsz_prescan_batch.restype = ctypes.c_int32
    lib.m3agg_window_keys.restype = None
    lib.m3agg_count.restype = ctypes.c_int32
    lib.m3agg_pack.restype = None
    lib.m3tsz_decode_batch.restype = ctypes.c_int32
    lib.m3hash_shards.restype = None
    _lib = lib
    return lib


def available() -> bool:
    return load() is not None


def _encode_batch_native(lib, times, values, lengths, default_unit, int_optimized, n_threads, cap):
    out_buf = np.zeros(cap, np.uint8)
    offsets = np.zeros(len(lengths) + 1, np.int64)
    total = lib.m3tsz_encode_batch(
        times.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_int32(len(lengths)),
        ctypes.c_int(default_unit),
        ctypes.c_int(1 if int_optimized else 0),
        out_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_int64(cap),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int32(n_threads),
    )
    return total, out_buf, offsets


def encode_batch(
    times: np.ndarray,
    values: np.ndarray,
    lengths: np.ndarray,
    default_unit: int = 1,
    int_optimized: bool = True,
    n_threads: int = 0,
) -> list[bytes]:
    """Encode N series (concatenated columns) → list of finalized streams.

    Falls back to the Python encoder when the native lib is unavailable."""
    lib = load()
    times = np.ascontiguousarray(times, np.int64)
    values = np.ascontiguousarray(values, np.float64)
    lengths = np.ascontiguousarray(lengths, np.int32)
    n = len(lengths)
    if lib is None:
        from ..codec.m3tsz import encode_series
        from ..utils.xtime import Unit

        out = []
        pos = 0
        for ln in lengths:
            out.append(
                encode_series(
                    times[pos : pos + ln].tolist(),
                    values[pos : pos + ln].tolist(),
                    int_optimized=int_optimized,
                    unit=Unit(default_unit),
                )
            )
            pos += ln
        return out
    if n_threads <= 0:
        n_threads = min(os.cpu_count() or 1, 16)
    cap = max(int(times.size * 16 + n * 16 + 1024), 4096)
    total, out_buf, offsets = _encode_batch_native(
        lib, times, values, lengths, default_unit, int_optimized, n_threads, cap
    )
    if total < 0:  # grow to the exact required size and retry once
        total, out_buf, offsets = _encode_batch_native(
            lib, times, values, lengths, default_unit, int_optimized, n_threads, -total
        )
    raw = out_buf.tobytes()
    return [raw[offsets[i] : offsets[i + 1]] for i in range(n)]


def prescan_batch(
    streams: list[bytes],
    k: int = 32,
    default_unit: int = 1,
    int_optimized: bool = True,
    n_threads: int = 0,
) -> list[list[dict]]:
    """Side-table prescan for N streams → per-series snapshot dict lists
    (same shape as ops.chunked.snapshot_stream)."""
    lib = load()
    if lib is None:
        from ..ops.chunked import snapshot_stream
        from ..utils.xtime import Unit

        return [
            snapshot_stream(s, k, int_optimized=int_optimized, default_unit=Unit(default_unit))
            for s in streams
        ]
    n = len(streams)
    if n == 0:
        return []
    data = b"".join(streams)
    offsets = np.zeros(n + 1, np.int64)
    for i, s in enumerate(streams):
        offsets[i + 1] = offsets[i] + len(s)
    max_len = max((len(s) for s in streams), default=0)
    # record lower bound ~3 bits, so snapshots per stream are bounded by this
    max_snaps = max((max_len * 8) // max(3 * k, 1) + 2, 2)
    buf = (_SnapRec * (n * max_snaps))()
    counts = np.zeros(n, np.int32)
    arr = np.frombuffer(data, np.uint8) if data else np.zeros(1, np.uint8)
    if n_threads <= 0:
        n_threads = min(os.cpu_count() or 1, 16)
    lib.m3tsz_prescan_batch(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int32(n),
        ctypes.c_int32(k),
        ctypes.c_int(default_unit),
        ctypes.c_int(1 if int_optimized else 0),
        buf,
        ctypes.c_int32(max_snaps),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_int32(n_threads),
    )
    out: list[list[dict]] = []
    for i in range(n):
        total_bits = len(streams[i]) * 8
        per = []
        c = max(int(counts[i]), 0)
        for j in range(c):
            r = buf[i * max_snaps + j]
            per.append(
                dict(
                    off=r.off,
                    prev_time=r.prev_time,
                    prev_delta=r.prev_delta,
                    prev_float_bits=r.prev_float_bits,
                    prev_xor=r.prev_xor,
                    int_val=r.int_val,
                    time_unit=r.time_unit,
                    sig=r.sig,
                    mult=r.mult,
                    is_float=bool(r.is_float),
                    fast=bool(r.flags & 1),
                    fast_float=bool(r.flags & 2),
                    total_bits=total_bits,
                )
            )
        offs = [p["off"] for p in per] + [total_bits]
        for j, p in enumerate(per):
            p["span"] = offs[j + 1] - p["off"]
        out.append(per)
    return out


def pack_windowed_dense(
    ids: np.ndarray,
    times_nanos: np.ndarray,
    values: np.ndarray,
    window0_nanos: int,
    resolution_nanos: int,
    n_windows: int,
    n_series: int,
    n_threads: int = 0,
):
    """Fused window bucketing + dense [G, P] pack for the device rollup
    kernels (aggregator/kernels.py aggregate_dense): keys/torder, counts and
    the arrival-order-exact dense scatter in three memory-bound C++ passes.
    Returns (vals[G, P] f32, torder[G, P] i32, valid[G, P] bool).

    Falls back to the numpy path (kernels.window_keys + pack_dense_groups)
    when the native lib is unavailable. Reference hot loop:
    /root/reference/src/aggregator/aggregation/{counter,timer,gauge}.go."""
    lib = load()
    n = len(ids)
    n_groups = n_series * n_windows
    # the native kernel computes int32 group keys (m3tsz.cc m3agg_window_keys)
    # and m3agg_count indexes with them: past INT32_MAX the cast wraps
    # negative and the atomic fetch_add writes out of bounds — route
    # oversized grids through the int64-keyed numpy path instead
    if lib is None or n_groups > np.iinfo(np.int32).max:
        from ..aggregator.kernels import pack_dense_groups, window_keys

        keys, _, order = window_keys(
            np.asarray(ids), np.asarray(times_nanos), window0_nanos,
            resolution_nanos, n_windows,
        )
        return pack_dense_groups(keys, values, order, n_groups)
    if n_threads <= 0:
        n_threads = min(os.cpu_count() or 1, 16)
    ids = np.ascontiguousarray(ids, np.int64)
    times_nanos = np.ascontiguousarray(times_nanos, np.int64)
    values = np.ascontiguousarray(values, np.float32)
    keys = np.empty(n, np.int32)
    torder = np.empty(n, np.int32)
    lib.m3agg_window_keys(
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        times_nanos.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int64(n),
        ctypes.c_int64(window0_nanos),
        ctypes.c_int64(resolution_nanos),
        ctypes.c_int32(n_windows),
        keys.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        torder.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_int32(n_threads),
    )
    counts = np.zeros(n_groups, np.int32)
    p = int(
        lib.m3agg_count(
            keys.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_int64(n),
            ctypes.c_int64(n_groups),
            counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            ctypes.c_int32(n_threads),
        )
    )
    p = max(p, 1)
    vals = np.empty((n_groups, p), np.float32)
    tor = np.empty((n_groups, p), np.int32)
    lib.m3agg_pack(
        keys.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        torder.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_int64(n),
        ctypes.c_int64(n_groups),
        ctypes.c_int32(p),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        tor.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.c_int32(n_threads),
    )
    # match the numpy fallback exactly: a NaN input value occupies a slot
    # but must be INVALID (stale markers etc. are dropped, not folded into
    # sum/min/max as NaN)
    valid = (np.arange(p, dtype=np.int32)[None, :] < counts[:, None]) & ~np.isnan(
        vals
    )
    return vals, tor, valid


def decode_batch(
    streams: list[bytes],
    default_unit: int = 1,
    int_optimized: bool = True,
    n_threads: int = 0,
    max_points: int | None = None,
    with_flags: bool = False,
):
    """Batch-decode N m3tsz streams → list of (times i64[n], values f64[n],
    units u8[n]) numpy triples. ~100x the pure-Python decoder; serves host
    paths that need plain points — shard reads, repair digests, the
    comparator, CPU benches. Annotations do not alter (t, v, u) decoding;
    with ``with_flags`` the return is (triples, flags u8[n]) where flag
    bit0 marks streams that DO carry annotations, so callers that must
    surface them (Datapoint.annotation) can re-decode those few through the
    Python iterator.

    Reference: the Go iterator's batch decode role
    (/root/reference/src/dbnode/encoding/m3tsz/iterator.go:64). Falls back
    to the Python decoder when the native lib is unavailable."""

    def _python_one(s):
        from ..codec.m3tsz import decode
        from ..utils.xtime import Unit

        dps = decode(s, int_optimized=int_optimized, default_unit=Unit(default_unit))
        return (
            np.asarray([d.timestamp for d in dps], np.int64),
            np.asarray([d.value for d in dps], np.float64),
            np.asarray([int(d.unit) for d in dps], np.uint8),
        )

    def _python_flags(s):
        from ..codec.m3tsz import decode
        from ..utils.xtime import Unit

        dps = decode(s, int_optimized=int_optimized, default_unit=Unit(default_unit))
        return 1 if any(d.annotation for d in dps) else 0

    lib = load()
    n = len(streams)
    if n == 0:
        return ([], np.zeros(0, np.uint8)) if with_flags else []
    if lib is None:
        triples = [_python_one(s) for s in streams]
        if with_flags:
            return triples, np.asarray([_python_flags(s) for s in streams], np.uint8)
        return triples
    data = b"".join(streams)
    offsets = np.zeros(n + 1, np.int64)
    for i, s in enumerate(streams):
        offsets[i + 1] = offsets[i] + len(s)
    arr = np.frombuffer(data, np.uint8) if data else np.zeros(1, np.uint8)
    # capacity: one datapoint per 2 encoded bits is unreachable by the
    # format (min ~3 bits/record), so bits//2 + 2 never overflows; callers
    # that know their block shape pass max_points to avoid page-fault cost
    # on oversized outputs
    cap = max_points or max(int(max(len(s) for s in streams)) * 4 + 2, 4)
    if n_threads <= 0:
        n_threads = min(os.cpu_count() or 1, 16)
    times = np.empty((n, cap), np.int64)
    values = np.empty((n, cap), np.float64)
    units = np.empty((n, cap), np.uint8)
    counts = np.zeros(n, np.int64)
    flags = np.zeros(n, np.uint8)
    failed = lib.m3tsz_decode_batch(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int32(n),
        ctypes.c_int(default_unit),
        ctypes.c_int(1 if int_optimized else 0),
        ctypes.c_int64(cap),
        times.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        units.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        flags.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_int32(n_threads),
    )
    if failed:
        if max_points is not None and any(c == -2 for c in counts):
            # caller's cap was too small somewhere: retry with the safe bound
            return decode_batch(
                streams, default_unit=default_unit, int_optimized=int_optimized,
                n_threads=n_threads, max_points=None, with_flags=with_flags,
            )
        bad = [i for i, c in enumerate(counts) if c < 0]
        raise ValueError(f"m3tsz decode failed for {len(bad)} streams (first: {bad[:3]})")
    triples = [
        (
            times[i, : counts[i]].copy(),
            values[i, : counts[i]].copy(),
            units[i, : counts[i]].copy(),
        )
        for i in range(n)
    ]
    return (triples, flags) if with_flags else triples


def encode_one(
    times: np.ndarray,
    values: np.ndarray,
    units: np.ndarray | None = None,
    default_unit: int = 1,
    int_optimized: bool = True,
) -> bytes | None:
    """Encode ONE series with optional per-point units via the native
    encoder (m3tsz_encode_series); None when the lib is unavailable (the
    caller uses the Python reference encoder). The buffer-bucket merge
    path (storage/series.py) is the hot consumer."""
    lib = load()
    if lib is None:
        return None
    times = np.ascontiguousarray(times, np.int64)
    values = np.ascontiguousarray(values, np.float64)
    n = len(times)
    if n == 0:
        return b""
    u_ptr = None
    if units is not None:
        units = np.ascontiguousarray(units, np.int32)
        u_ptr = units.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    cap = n * 16 + 1024
    for _ in range(2):
        out = np.zeros(cap, np.uint8)
        r = int(
            lib.m3tsz_encode_series(
                times.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                ctypes.c_int32(n),
                ctypes.c_int(default_unit),
                u_ptr,
                ctypes.c_int(1 if int_optimized else 0),
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                ctypes.c_int64(cap),
            )
        )
        if r >= 0:
            return out[:r].tobytes()
        if r == -1:
            return None  # encode error: let the python path raise properly
        cap = -r
    return None


def shard_batch(ids: list[bytes], num_shards: int) -> "np.ndarray | None":
    """murmur3-32 shard routing for a batch of series ids in one native
    call (sharding/shardset.go DefaultHashFn; parity with utils/hash.py).
    None when the lib is unavailable (callers hash per-id in Python)."""
    lib = load()
    if lib is None:
        return None
    n = len(ids)
    blob = b"".join(ids)
    offsets = np.zeros(n + 1, np.int64)
    for i, s in enumerate(ids):
        offsets[i + 1] = offsets[i] + len(s)
    arr = np.frombuffer(blob, np.uint8) if blob else np.zeros(1, np.uint8)
    out = np.empty(n, np.int32)
    lib.m3hash_shards(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        ctypes.c_int32(n),
        ctypes.c_int32(num_shards),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return out
