"""In-process + cross-process request tracing: sampled spans in a bounded
ring buffer, with Dapper-style context propagation over the RPC layer.

Reference: the reference threads opentracing through its contexts
(/root/reference/src/x/context/context.go StartSampledTraceSpan,
src/dbnode/server wiring of jaeger/lightstep tracers) and exposes debug
dumps (x/debug). This framework keeps the same shape without external
backends: a process-wide sampled tracer whose finished spans land in a ring
buffer served by the coordinator's /debug/traces route and bundled into the
/debug/dump archive.

Usage::

    from m3_tpu.utils.trace import TRACER
    with TRACER.span("db.write", namespace=ns):
        ...

Spans nest through a thread-local stack: a span started while another is
open on the same thread becomes its child. Across threads or processes the
stack does NOT follow — extract the active context with
``TRACER.current_context()`` on the parent side and adopt it with
``TRACER.span_from_context(name, ctx)`` on the other side (the net/ RPC
layer does exactly this, so a query fanning out coordinator → dbnode
replicas produces ONE stitched trace).

Stages are the one timing mechanism INSIDE a served request
(``TRACER.stage(name)``; ``TRACER.request(op, ctx)`` opens the root,
``rpc.server.<op>``). Every stage, sampled or not, adds wall seconds and
one call to the process-wide stage table (``m3tpu_stage_seconds_total`` /
``m3tpu_stage_calls_total`` ``{op,stage}``), adds the same seconds to the
request record bound to the thread (``bind_record``; for queries
``QueryStats.stages``), and is entered as a ``jax.profiler.TraceAnnotation``
when jax is already imported, so a profiler capture stamps host stages and
device operations with one clock (``m3_tpu/profiling/gaps.py`` reads it).
Where the request is sampled — its frame carried a sampled context, or a
``device_profile`` capture is running (``TRACER.capturing``) — a stage also
records a child ``Span`` and adds its thread-CPU seconds to
``m3tpu_stage_cpu_seconds_total`` (wall less CPU is time waited: GIL, locks,
disk, device). Only then, because the thread-CPU clock is a system call:
15 us a reading on the chip's sandboxed host, which at some thirty stages
a write batch took 8 % off ingest when every stage read it (PERF.md, PR
27). So compare wall and CPU over a capture's window, where every call is
measured. Stages are per request and per phase — never per entry, per
series or per point.

Configuration (read once at import for the process-wide ``TRACER``):

    M3_TPU_TRACE_SAMPLE_RATE   root-span sample rate in [0, 1] (default 1.0)
    M3_TPU_TRACE_CAPACITY      finished-span ring capacity (default 4096)
"""

from __future__ import annotations

import itertools
import os
import random
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field

from .instrument import DEFAULT as METRICS

# what a stage may be called: ``<family>.<phase>``, or one of the query
# record's four older stage names. profiling/gaps.py tells stage
# annotations from jax's own host events by this rule, so it lives here,
# beside the helper that emits them.
STAGE_FAMILIES = frozenset(
    {"rpc", "wire", "write", "ingest", "commitlog", "plan", "query", "reply",
     "seal", "storage"}
)
QUERY_STAGES = frozenset({"parse", "index_resolve", "fetch", "decode"})

# ops come off the wire: past this many (op, stage) rows new ops share one
_MAX_STAGE_ROWS = 512


def is_stage_name(name: str) -> bool:
    return name in QUERY_STAGES or name.split(".", 1)[0] in STAGE_FAMILIES


_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax is there


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for ``name``, or None while jax
    is not (fully) imported. Never imports jax: utils/ sits below the
    device layer, and a first ``import jax`` on a handler thread racing
    the main thread's leaves jax half made (profiling/device.py)."""
    global _TRACE_ANNOTATION
    cls = _TRACE_ANNOTATION
    if cls is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        cls = _TRACE_ANNOTATION = getattr(profiler, "TraceAnnotation", None)
        if cls is None:
            return None
    return cls(name)


@dataclass
class Span:
    trace_id: int
    span_id: int
    parent_id: int | None
    name: str
    start_nanos: int
    end_nanos: int | None = None
    tags: dict = field(default_factory=dict)
    error: str | None = None

    @property
    def duration_nanos(self) -> int | None:
        if self.end_nanos is None:
            return None
        return self.end_nanos - self.start_nanos

    def to_dict(self) -> dict:
        return {
            "traceId": f"{self.trace_id:016x}",
            "spanId": f"{self.span_id:016x}",
            "parentId": f"{self.parent_id:016x}" if self.parent_id else None,
            "name": self.name,
            "startNanos": self.start_nanos,
            "durationNanos": self.duration_nanos,
            "tags": {k: str(v) for k, v in self.tags.items()},
            "error": self.error,
        }


class _ActiveSpan:
    """Context manager binding a span to the thread-local stack."""

    def __init__(self, tracer: "Tracer", span: Span | None) -> None:
        self.tracer = tracer
        self.span = span  # None = unsampled (no-op)

    def set_tag(self, key: str, value) -> "_ActiveSpan":
        if self.span is not None:
            self.span.tags[key] = value
        return self

    def __enter__(self) -> "_ActiveSpan":
        if self.span is not None:
            self.tracer._local.stack.append(self.span)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.span is None:
            return
        stack = self.tracer._local.stack
        if stack and stack[-1] is self.span:
            stack.pop()
        self.span.end_nanos = time.time_ns()
        if exc is not None:
            self.span.error = f"{exc_type.__name__}: {exc}"
        self.tracer._record(self.span)


class _Stage:
    """One timed phase of a request (``Tracer.stage`` / ``Tracer.request``).

    After exit ``wall_ns`` holds its wall nanoseconds. ``op`` may be set
    inside the block where the op is only known then (``wire.decode``
    learns it from the frame it decodes)."""

    __slots__ = ("tracer", "name", "op", "tags", "root", "ctx", "span",
                 "wall_ns", "_t0", "_c0", "_ann", "_rec", "_prev",
                 "_prev_op")

    def __init__(self, tracer: "Tracer", name: str, tags: dict,
                 op: str | None = None, root: bool = False,
                 ctx: dict | None = None) -> None:
        self.tracer = tracer
        self.name = name
        self.tags = tags
        self.op = op
        self.root = root
        self.ctx = ctx
        self.span: Span | None = None
        self.wall_ns = 0

    @property
    def seconds(self) -> float:
        return self.wall_ns / 1e9

    def set_tag(self, key: str, value) -> "_Stage":
        if self.span is not None:
            self.span.tags[key] = value
        return self

    def __enter__(self) -> "_Stage":
        tracer = self.tracer
        loc = tracer._local
        if self.root:
            self._prev_op = loc.op
            loc.op = self.op
            self.span = tracer._root_span(self.name, self.ctx, self.tags)
        elif loc.stack:
            self.span = tracer._new_span(loc.stack[-1], self.name, self.tags)
        if self.span is not None:
            loc.stack.append(self.span)
        rec = self._rec = loc.record
        if rec is not None:
            self._prev = rec.current_stage
            rec.current_stage = self.name
        ann = self._ann = _annotation(self.name)
        if ann is not None:
            ann.__enter__()
        # the thread-CPU clock is a system call: read only where someone
        # is looking (module docstring)
        measured = self.span is not None or tracer.capturing
        self._c0 = time.thread_time_ns() if measured else -1
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        wall = self.wall_ns = time.perf_counter_ns() - self._t0
        span = self.span
        if span is not None:
            # a span ends on the clock that started it, read in program
            # order (after its children's ends, as its start was read
            # before their starts), so it covers them whatever the load;
            # start_nanos + wall would not, a thread can be held between
            # the two clocks' readings in __enter__
            span.end_nanos = time.time_ns()
        cpu = time.thread_time_ns() - self._c0 if self._c0 >= 0 else 0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        tracer = self.tracer
        loc = tracer._local
        tracer._add_stage(self.op or loc.op, self.name, wall, cpu)
        rec = self._rec
        if rec is not None:
            rec.add_stage(self.name, wall / 1e9)
            rec.current_stage = self._prev
        if self.root:
            loc.op = self._prev_op
        if span is not None:
            stack = loc.stack
            if stack and stack[-1] is span:
                stack.pop()
            if exc is not None:
                span.error = f"{exc_type.__name__}: {exc}"
            tracer._record(span)


_NO_SPANS = {"sampled": False}  # request(spans=False): identity-compared


class _Local(threading.local):
    """Per-thread tracer state (class-level defaults: a plain attribute
    read, no ``getattr`` fallback on the hot path)."""

    op = "-"  # the request op whose root stage is open on this thread
    record = None  # the request record stages add their seconds to

    def __init__(self) -> None:
        self.stack: list = []


class Tracer:
    """Process tracer: sample_rate in [0, 1], ring buffer of finished spans.

    ``started``/``sampled`` counters and the span-id sequence are guarded by
    one lock — spans start on many threads concurrently (RPC handler
    threads, host-queue flushers), so the read-modify-writes must not race.
    Span ids count up from a random 62-bit base so ids minted by different
    PROCESSES joining one trace don't collide.
    """

    def __init__(self, sample_rate: float = 1.0, capacity: int = 4096,
                 registry=None) -> None:
        self.sample_rate = sample_rate
        self.finished: deque[Span] = deque(maxlen=capacity)
        self._ids = itertools.count(random.getrandbits(62) | 1)
        self._local = _Local()
        self._lock = threading.Lock()
        self.started = 0
        self.sampled = 0
        # set while a device_profile capture runs (profiling/device.py):
        # every request is sampled then
        self.capturing = False
        # the stage table: (op, stage) -> [wall_ns, cpu_ns, calls]
        self._registry = registry or METRICS
        self._stages: dict[tuple, list] = {}
        self._stage_lock = threading.Lock()

    @classmethod
    def from_env(cls) -> "Tracer":
        """Build a tracer from M3_TPU_TRACE_SAMPLE_RATE / M3_TPU_TRACE_CAPACITY
        (malformed values fall back to the defaults rather than killing the
        process at import)."""
        try:
            rate = float(os.environ.get("M3_TPU_TRACE_SAMPLE_RATE", "1.0"))
        except ValueError:
            rate = 1.0
        try:
            capacity = int(os.environ.get("M3_TPU_TRACE_CAPACITY", "4096"))
        except ValueError:
            capacity = 4096
        return cls(sample_rate=min(max(rate, 0.0), 1.0), capacity=max(capacity, 1))

    def active(self) -> bool:
        """Whether a sampled span is open on THIS thread."""
        return bool(self._local.stack)

    def current_context(self) -> dict | None:
        """Wire-propagatable context of the innermost active span, or None.

        The dict shape is what net/wire's inject/extract helpers carry:
        {"trace_id": int, "span_id": int, "sampled": bool}.
        """
        stack = self._local.stack
        if not stack:
            return None
        top = stack[-1]
        return {"trace_id": top.trace_id, "span_id": top.span_id, "sampled": True}

    # -- stages ------------------------------------------------------------

    def stage(self, name: str, op: str | None = None, **tags) -> _Stage:
        """One phase of the request open on this thread (module docstring).
        ``op`` labels a stage that runs outside any request (the commit
        log's writer thread)."""
        return _Stage(self, name, tags, op=op)

    def request(self, op: str, ctx: dict | None = None, spans: bool = True,
                **tags) -> _Stage:
        """The root stage of a served request, ``rpc.server.<op>``: every
        stage under it is labelled with ``op``. It roots a span tree where
        ``ctx`` (the caller's wire context) is sampled, or while a capture
        runs; ``spans=False`` never does (the ops pollers hammer)."""
        return _Stage(self, f"rpc.server.{op}", tags, op=op, root=True,
                      ctx=ctx if spans else _NO_SPANS)

    def bind_record(self, record) -> None:
        """Bind (or with None unbind) this thread's request record: an
        object with ``add_stage(name, seconds)`` and a ``current_stage``
        attribute. query/stats.py binds its QueryStats."""
        self._local.record = record

    def record(self):
        return self._local.record

    def _add_stage(self, op: str, name: str, wall_ns: int, cpu_ns: int) -> None:
        key = (op, name)
        with self._stage_lock:
            row = self._stages.get(key)
            if row is None:
                row = self._stage_row(key)
            row[0] += wall_ns
            row[1] += cpu_ns
            row[2] += 1

    def _stage_row(self, key: tuple) -> list:
        """A new row and its three exposition views (under _stage_lock)."""
        if len(self._stages) >= _MAX_STAGE_ROWS:
            key = ("_overflow", key[1])
            row = self._stages.get(key)
            if row is not None:
                return row
        row = self._stages[key] = [0, 0, 0]
        labels = {"op": key[0], "stage": key[1]}
        reg = self._registry
        reg.counter_view(
            "stage_seconds_total",
            "wall seconds inside each stage of the served paths",
            labels, lambda: row[0] / 1e9)
        reg.counter_view(
            "stage_cpu_seconds_total",
            "thread-CPU seconds inside each stage, of sampled requests and "
            "of every request while a device_profile capture runs (over "
            "such a window wall less CPU is time waited: GIL, locks, disk, "
            "device)",
            labels, lambda: row[1] / 1e9)
        reg.counter_view(
            "stage_calls_total", "times each stage ran", labels,
            lambda: float(row[2]))
        return row

    def stage_table(self) -> dict:
        """{(op, stage): (wall_s, cpu_s, calls)} snapshot."""
        with self._stage_lock:
            return {k: (r[0] / 1e9, r[1] / 1e9, r[2])
                    for k, r in self._stages.items()}

    # -- spans -------------------------------------------------------------

    def _mint(self, sampled: bool = True) -> int:
        with self._lock:
            self.started += 1
            if not sampled:
                return 0
            self.sampled += 1
            return next(self._ids)

    def _new_span(self, parent: Span, name: str, tags: dict) -> Span:
        return Span(
            trace_id=parent.trace_id,
            span_id=self._mint(),
            parent_id=parent.span_id,
            name=name,
            start_nanos=time.time_ns(),
            tags=tags,
        )

    def _root_span(self, name: str, ctx: dict | None, tags: dict) -> Span | None:
        """The span a request's root stage opens: joined to the caller's
        trace where its context is sampled, a fresh trace while a capture
        runs, None otherwise (no Span object is built)."""
        if ctx is _NO_SPANS:
            return None
        if ctx is not None:
            return self.span_from_context(name, ctx, **tags).span
        if self.capturing:
            return self.span(name, **tags).span
        return None

    def span(self, name: str, **tags) -> _ActiveSpan:
        stack = self._local.stack
        parent = stack[-1] if stack else None
        if parent is not None:
            return _ActiveSpan(self, self._new_span(parent, name, tags))
        sampled = self.sample_rate >= 1.0 or random.random() < self.sample_rate
        span_id = self._mint(sampled)
        if not sampled:
            return _ActiveSpan(self, None)
        sp = Span(
            trace_id=span_id,
            span_id=span_id,
            parent_id=None,
            name=name,
            start_nanos=time.time_ns(),
            tags=tags,
        )
        return _ActiveSpan(self, sp)

    def span_from_context(self, name: str, ctx: dict | None, **tags) -> _ActiveSpan:
        """Start a span whose parent is a REMOTE (or cross-thread) span.

        ``ctx`` is a dict from :meth:`current_context` carried over the wire;
        the new span joins that trace instead of rooting a new one, so the
        server side of an RPC stitches into the client's tree. ``ctx`` of
        None falls back to the normal local-parent path; an EXPLICITLY
        unsampled context (sampled=False) is a no-op — the upstream decided
        not to trace this request, and rooting a fresh local trace here
        would litter every downstream ring with orphan spans.
        """
        if ctx is None:
            return self.span(name, **tags)
        if not ctx.get("sampled", True):
            self._mint(sampled=False)
            return _ActiveSpan(self, None)
        sp = Span(
            trace_id=int(ctx["trace_id"]),
            span_id=self._mint(),
            parent_id=int(ctx["span_id"]),
            name=name,
            start_nanos=time.time_ns(),
            tags=tags,
        )
        return _ActiveSpan(self, sp)

    def _record(self, span: Span) -> None:
        with self._lock:
            self.finished.append(span)

    def dump(self, limit: int | None = None) -> list[dict]:
        with self._lock:
            spans = list(self.finished)
        if limit is not None:
            spans = spans[-limit:] if limit > 0 else []
        return [s.to_dict() for s in spans]


# process-wide default (the reference hangs its tracer off instrument opts);
# sample rate / capacity configurable via M3_TPU_TRACE_* env vars
TRACER = Tracer.from_env()

# shared no-op span (what span() returns when unsampled): for callers that
# decide themselves not to trace something
NOOP_SPAN = _ActiveSpan(None, None)
