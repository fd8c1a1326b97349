"""Device-memory accounting: the live-buffer half of the device tier.

Answers "what is holding device memory RIGHT NOW" with the same split
the storage layers think in:

- ``resident_pool`` — the paged HBM pool's flat page buffer
  (m3_tpu/resident/: the compressed working set);
- ``decoded_cache`` — the decoded-block cache's arrays
  (m3_tpu/cache/: the byte-budget LRU of decoded lanes);
- ``index`` — the device-resident inverted index tier
  (m3_tpu/index/device/: term-key matrices + postings arrays);
- ``other`` — every other live jax buffer (staging arrays, kernel
  outputs still referenced, query intermediates).

Published as ``m3tpu_device_memory_bytes{kind}`` gauges so the selfmon
pipeline stores the split as series (an OOM-adjacent incident becomes
one PromQL query over ``_m3tpu``), refreshed on the stack sampler's
schedule and on demand by the ``/debug/dump`` ``device_memory.json``
snapshot.

``jax.live_arrays()`` walks the client's live-buffer list — cheap at
the fleet's array counts (the pool and cache keep FEW large arrays by
design), but not free, which is why refresh rides the sampler's slow
``memory_interval`` rather than every sample tick.
"""

from __future__ import annotations

import sys
import threading

from ..utils.instrument import DEFAULT as METRICS
from ..utils.trace import TRACER

KINDS = ("resident_pool", "decoded_cache", "index", "other")

_HELP = (
    "live device/process memory by holder: resident_pool = the paged "
    "compressed HBM pool, decoded_cache = decoded-block cache arrays, "
    "index = device-resident inverted index segments, "
    "other = remaining live jax buffers"
)


def _gauge(kind: str):
    return METRICS.gauge("device_memory_bytes", _HELP, labels={"kind": kind})


def collect_device_memory(db=None) -> dict:
    """Snapshot the split, set the gauges, return the dict (the
    ``device_memory.json`` shape). ``db`` is any Database-surface object;
    None (or a cluster SessionDatabase with no local pool/cache) still
    accounts ``other``. Never raises — a jax-less or mid-teardown
    process reports what it can."""
    resident = 0
    cache = 0
    index_bytes = 0
    pool = getattr(db, "resident_pool", None) if db is not None else None
    if pool is not None:
        resident = pool.device_bytes()
    index_store = getattr(db, "index_device_store", None) if db is not None else None
    if index_store is not None:
        index_bytes = index_store.device_bytes()
    block_cache = getattr(db, "block_cache", None) if db is not None else None
    if block_cache is not None:
        try:
            cache = int(block_cache.stats().get("bytes", 0))
        except Exception:
            cache = 0
    total_live = 0
    try:
        # NEVER initiate the jax import from here: this runs on the
        # sampler's daemon thread, and racing the main thread's first
        # `import jax` leaves jax.numpy partially initialized for the
        # request path (observed as AttributeError in RPC handlers). A
        # process that hasn't imported jax has no live buffers to count.
        import sys as _sys

        jax = _sys.modules.get("jax")
        if jax is not None:
            total_live = sum(int(a.nbytes) for a in jax.live_arrays())
        else:
            total_live = resident + index_bytes
    except Exception:
        # partially initialized / backend torn down: report what we can
        total_live = resident + index_bytes
    # the decoded cache may hold HOST arrays (numpy) on some paths — it
    # is accounted from its own byte budget, not subtracted from the
    # live-buffer total (which only sees device arrays)
    other = max(total_live - resident - index_bytes, 0)
    out = {
        "resident_pool": resident,
        "decoded_cache": cache,
        "index": index_bytes,
        "other": other,
        "total_live_jax_bytes": total_live,
    }
    for kind in KINDS:
        _gauge(kind).set(float(out[kind]))
    device_stat()
    return out


def device_stat() -> dict:
    """``peak_bytes_in_use`` and ``bytes_in_use`` of the fullest local
    device (None where the backend reports none, as the CPU's does not),
    published as gauges too. Like the accounting above it never starts
    the jax import."""
    peak = in_use = None
    jax = sys.modules.get("jax")
    try:
        for d in jax.local_devices() if jax is not None else ():
            ms = d.memory_stats() or {}
            if "peak_bytes_in_use" in ms:
                peak = max(peak or 0, int(ms["peak_bytes_in_use"]))
                in_use = max(in_use or 0, int(ms.get("bytes_in_use", 0)))
    except Exception:
        # partially initialized / backend torn down: report nothing, like
        # the accounting above (this runs on the sampler's schedule)
        peak = in_use = None
    if peak is not None:
        METRICS.gauge(
            "device_peak_bytes_in_use",
            "peak bytes in use on the fullest local device since the process started",
        ).set(float(peak))
        METRICS.gauge(
            "device_bytes_in_use", "bytes in use on the fullest local device"
        ).set(float(in_use))
    return {"peak_bytes_in_use": peak, "bytes_in_use": in_use}


# one jax.profiler session per process; the lock orders start against stop
_capture_lock = threading.Lock()
_capture_dir: str | None = None


def start_capture(directory: str) -> dict:
    """Start a jax.profiler capture of this process into ``directory``
    (the ``device_profile`` op): the Python tracer off, the host tracer at
    level 1, so the trace holds the device's operations and the program's
    stage annotations on one clock and little else. While it runs every
    request is sampled (``TRACER.capturing``)."""
    global _capture_dir
    jax = sys.modules.get("jax")
    if jax is None:
        raise RuntimeError("this process has not imported jax: nothing to profile")
    with _capture_lock:
        if _capture_dir == directory:
            # a retried start: the op is duplicate-safe (wire.IDEMPOTENT_OPS)
            return {"capturing": True, "dir": directory}
        if _capture_dir is not None:
            raise RuntimeError(f"a capture into {_capture_dir} is already running")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(directory, profiler_options=opts)
        _capture_dir = directory
        TRACER.capturing = True
    return {"capturing": True, "dir": directory}


def stop_capture() -> dict:
    """Stop the running capture and write its ``.xplane.pb``; a stop with
    none running changes nothing."""
    global _capture_dir
    with _capture_lock:
        directory, _capture_dir = _capture_dir, None
        TRACER.capturing = False
        if directory is not None:
            sys.modules["jax"].profiler.stop_trace()
    return {"capturing": False, "dir": directory}


def capture_dir() -> str | None:
    return _capture_dir
