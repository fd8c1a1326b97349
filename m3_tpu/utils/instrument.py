"""Instrumentation: process metrics registry with Prometheus exposition.

Reference: /root/reference/src/x/instrument/ — every service carries an
instrument.Options scope emitting counters/gauges/timers about itself
(tally → Prometheus). Here: a Registry of Counter/Gauge/Histogram handles
with label sets, rendered in the Prometheus text format by services'
/metrics endpoints (coordinator HTTP route, dbnode RPC op).
"""

from __future__ import annotations

import bisect
import math
import os
import threading
import time
from dataclasses import dataclass, field


def _escape_label_value(v) -> str:
    """Prometheus text exposition label-value escaping: backslash, double
    quote, and line feed must be escaped (exposition_formats.md) — regex
    matchers used as label values otherwise corrupt the whole scrape."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(labels: tuple) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape_label_value(v)}"' for k, v in labels)
    return "{" + inner + "}"


def _fmt_exemplar(ex: tuple) -> str:
    """OpenMetrics exemplar suffix for a bucket sample:
    `` # {trace_id="...",tenant="..."} value timestamp`` — the trace-ID
    link the 0.0.4 format can only serve out-of-band via
    /debug/exemplars. ``ex`` is Histogram.exemplars' tuple form
    (value, trace_id, unix_nanos, tenant)."""
    v, trace_id, unix_nanos, tenant = ex
    labels = [("trace_id", trace_id)]
    if tenant is not None:
        labels.append(("tenant", tenant))
    return f" # {_fmt_labels(tuple(labels))} {v} {unix_nanos / 1e9:.9f}"


class Counter:
    def __init__(self) -> None:
        self._v = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._v += n

    @property
    def value(self) -> float:
        return self._v


class Gauge:
    def __init__(self) -> None:
        self._v = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        self._v = v

    def add(self, n: float) -> None:
        """Relative adjust (in-flight style gauges): must not lose updates
        under concurrent RPC handler threads."""
        with self._lock:
            self._v += n

    @property
    def value(self) -> float:
        return self._v


class CounterView:
    """A counter child whose value lives elsewhere and is read at
    exposition (the tracer's stage table keeps wall, CPU and calls of a
    stage in one row under one lock, not in three counters)."""

    def __init__(self, read) -> None:
        self._read = read

    @property
    def value(self) -> float:
        return self._read()


DEFAULT_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10
)


class Histogram:
    def __init__(self, buckets=DEFAULT_BUCKETS) -> None:
        self.buckets = tuple(sorted(buckets))
        self.counts = [0] * (len(self.buckets) + 1)
        self.sum = 0.0
        self.total = 0
        # bucket index -> (value, trace_id, unix_nanos, tenant): the LAST
        # traced observation per bucket (OpenMetrics-exemplar role) — a
        # slow bucket links straight to its stitched trace in
        # /debug/traces and its /debug/slow_queries record, and carries
        # the tenant the observation was attributed to. Kept out of the
        # text exposition (the 0.0.4 format has no exemplar grammar;
        # tools/check_metrics validates every line) — served by collect()
        # and /debug/exemplars.
        self.exemplars: dict[int, tuple[float, str, int, str | None]] = {}
        self._lock = threading.Lock()

    def observe(self, v: float, trace_id: str | None = None,
                tenant: str | None = None) -> None:
        with self._lock:
            i = bisect.bisect_left(self.buckets, v)
            self.counts[i] += 1
            self.sum += v
            self.total += 1
            if trace_id is not None:
                self.exemplars[i] = (v, trace_id, time.time_ns(), tenant)

    def snapshot(self) -> tuple[list[int], float, int]:
        """(counts, sum, total) read atomically vs concurrent observe() —
        exposition must not report a count/sum pair from different instants."""
        with self._lock:
            return list(self.counts), self.sum, self.total

    def exemplar_rows(self) -> list[dict]:
        """Exemplars as rows keyed by the bucket's ``le`` bound."""
        with self._lock:
            items = sorted(self.exemplars.items())
        out = []
        for i, (v, tid, ts, tenant) in items:
            le = self.buckets[i] if i < len(self.buckets) else float("inf")
            row = {"le": le, "value": v, "traceId": tid, "timeUnixNanos": ts}
            if tenant is not None:
                row["tenant"] = tenant
            out.append(row)
        return out


@dataclass
class _Family:
    kind: str  # counter | gauge | histogram
    help: str
    children: dict = field(default_factory=dict)  # labels tuple -> metric


class Registry:
    """tally.Scope-equivalent: named metric families with label children."""

    def __init__(self, prefix: str = "") -> None:
        self.prefix = prefix
        self._fams: dict[str, _Family] = {}
        self._lock = threading.Lock()

    def _family(self, name: str, kind: str, help_: str) -> _Family:
        with self._lock:
            fam = self._fams.get(name)
            if fam is None:
                fam = _Family(kind, help_)
                self._fams[name] = fam
            elif fam.kind != kind:
                raise ValueError(f"metric {name} already registered as {fam.kind}")
            return fam

    def _child(self, name: str, kind: str, help_: str, labels: dict | None, ctor):
        fam = self._family(name, kind, help_)
        key = tuple(sorted((labels or {}).items()))
        with self._lock:
            child = fam.children.get(key)
            if child is None:
                child = ctor()
                fam.children[key] = child
            return child

    def counter(self, name: str, help: str = "", labels: dict | None = None) -> Counter:
        return self._child(name, "counter", help, labels, Counter)

    def counter_view(self, name: str, help: str, labels: dict | None, read) -> CounterView:
        return self._child(name, "counter", help, labels, lambda: CounterView(read))

    def gauge(self, name: str, help: str = "", labels: dict | None = None) -> Gauge:
        return self._child(name, "gauge", help, labels, Gauge)

    def histogram(
        self, name: str, help: str = "", labels: dict | None = None, buckets=DEFAULT_BUCKETS
    ) -> Histogram:
        return self._child(
            name, "histogram", help, labels, lambda: Histogram(buckets)
        )

    def collect(self) -> dict:
        """Structured snapshot of every family — the machine-readable
        sibling of :meth:`expose` (tools/check_metrics.py consumes this
        instead of re-parsing text).

        Returns {name: {"kind", "help", "children": [{"labels", ...}]}}
        where counter/gauge children carry {"value"} and histogram children
        {"sum", "count", "buckets": [[le, cumulative_count], ...]}.
        """
        with self._lock:
            fams = {
                n: (f.kind, f.help, dict(f.children))
                for n, f in sorted(self._fams.items())
            }
        out: dict = {}
        for name, (kind, help_, children) in fams.items():
            rows = []
            for labels, m in sorted(children.items()):
                row: dict = {"labels": dict(labels)}
                if kind in ("counter", "gauge"):
                    row["value"] = m.value
                else:
                    counts, h_sum, h_total = m.snapshot()
                    acc, buckets = 0, []
                    for b, c in zip(m.buckets, counts):
                        acc += c
                        buckets.append([float(b), acc])
                    buckets.append([float("inf"), h_total])
                    row.update(sum=h_sum, count=h_total, buckets=buckets)
                    exemplars = m.exemplar_rows()
                    if exemplars:
                        row["exemplars"] = exemplars
                rows.append(row)
            out[f"{self.prefix}{name}"] = {
                "kind": kind, "help": help_, "children": rows
            }
        return out

    def expose_openmetrics(self) -> str:
        """OpenMetrics 1.0 text exposition (``/metrics`` content
        negotiation: ``Accept: application/openmetrics-text``).

        Differences from :meth:`expose` the spec mandates:

        - a counter FAMILY is named without the ``_total`` suffix in its
          HELP/TYPE lines while its sample keeps it (``# TYPE x counter``
          + ``x_total 1``) — our counter families are all registered with
          the suffix, so it is stripped for the metadata lines;
        - histogram bucket samples carry their exemplars inline
          (``... # {trace_id="..."} value timestamp``) — the trace-ID
          exemplars the 0.0.4 format can only serve via /debug/exemplars;
        - the exposition ends with the mandatory ``# EOF`` terminator
          (its absence is how a consumer detects a truncated scrape).
        """
        lines = []
        with self._lock:
            fams = {
                n: (f.kind, f.help, dict(f.children))
                for n, f in sorted(self._fams.items())
            }
        for name, (kind, help_, children) in fams.items():
            full = f"{self.prefix}{name}"
            fam = full
            if kind == "counter" and fam.endswith("_total"):
                fam = fam[: -len("_total")]
            if help_:
                lines.append(f"# HELP {fam} {help_}")
            lines.append(f"# TYPE {fam} {kind}")
            for labels, m in sorted(children.items()):
                ls = _fmt_labels(labels)
                if kind == "counter":
                    lines.append(f"{fam}_total{ls} {m.value}")
                elif kind == "gauge":
                    lines.append(f"{fam}{ls} {m.value}")
                else:
                    counts, h_sum, h_total = m.snapshot()
                    with m._lock:
                        exemplars = dict(m.exemplars)
                    acc = 0
                    for i, (b, c) in enumerate(zip(m.buckets, counts)):
                        acc += c
                        lb = tuple(list(labels) + [("le", repr(float(b)))])
                        line = f"{fam}_bucket{_fmt_labels(lb)} {acc}"
                        ex = exemplars.get(i)
                        if ex is not None:
                            line += _fmt_exemplar(ex)
                        lines.append(line)
                    lb = tuple(list(labels) + [("le", "+Inf")])
                    line = f"{fam}_bucket{_fmt_labels(lb)} {h_total}"
                    ex = exemplars.get(len(m.buckets))
                    if ex is not None:
                        line += _fmt_exemplar(ex)
                    lines.append(line)
                    lines.append(f"{fam}_sum{ls} {h_sum}")
                    lines.append(f"{fam}_count{ls} {h_total}")
        lines.append("# EOF")
        return "\n".join(lines) + "\n"

    def expose(self) -> str:
        """Prometheus text exposition format."""
        lines = []
        with self._lock:
            fams = {
                n: (f.kind, f.help, dict(f.children))
                for n, f in sorted(self._fams.items())
            }
        for name, (kind, help_, children) in fams.items():
            full = f"{self.prefix}{name}"
            if help_:
                lines.append(f"# HELP {full} {help_}")
            lines.append(f"# TYPE {full} {kind}")
            for labels, m in sorted(children.items()):
                ls = _fmt_labels(labels)
                if kind in ("counter", "gauge"):
                    lines.append(f"{full}{ls} {m.value}")
                else:
                    counts, h_sum, h_total = m.snapshot()
                    acc = 0
                    for b, c in zip(m.buckets, counts):
                        acc += c
                        lb = tuple(list(labels) + [("le", repr(float(b)))])
                        lines.append(f"{full}_bucket{_fmt_labels(lb)} {acc}")
                    lb = tuple(list(labels) + [("le", "+Inf")])
                    lines.append(f"{full}_bucket{_fmt_labels(lb)} {h_total}")
                    lines.append(f"{full}_sum{ls} {h_sum}")
                    lines.append(f"{full}_count{ls} {h_total}")
        return "\n".join(lines) + "\n"


# the process-default registry (instrument.NewOptions default scope)
DEFAULT = Registry(prefix="m3tpu_")


# device-seconds attribution hook: query/tenants.py installs a callable
# ``(kernel, seconds)`` invoked for every SAMPLED, non-compile profiled
# dispatch, charging device time to the tenant context active on the
# dispatching thread. A settable seam (not an import) because this module
# sits below the query layer — utils must not import m3_tpu.query.
_KERNEL_ATTRIBUTION = None


def set_kernel_attribution(fn) -> None:
    global _KERNEL_ATTRIBUTION
    _KERNEL_ATTRIBUTION = fn


# per-query device-dispatch counter hook: query/stats.py installs a
# callable ``(kernel)`` invoked for EVERY profiled kernel dispatch
# (sampled or not), charging it to the query record active on the
# dispatching thread — the seam the one-dispatch fused query pipeline's
# acceptance check counts through. Same settable-seam shape as
# _KERNEL_ATTRIBUTION: utils must not import m3_tpu.query.
_DISPATCH_COUNTER = None


def set_dispatch_counter(fn) -> None:
    global _DISPATCH_COUNTER
    _DISPATCH_COUNTER = fn


# kernel dispatch latencies span ~10µs (a warm tiny batch on CPU) to whole
# seconds (a cold 50M-series scan): finer low end than the RPC buckets
KERNEL_BUCKETS = (
    1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 0.001, 0.0025, 0.005,
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)


def _env_sample_rate() -> float:
    """M3_TPU_PROFILE_SAMPLE_RATE in [0, 1]; default 0 (profiling off —
    a sampled dispatch pays a block_until_ready, so the fleet default is
    zero-overhead and the knob is explicit)."""
    try:
        rate = float(os.environ.get("M3_TPU_PROFILE_SAMPLE_RATE", "0"))
    except ValueError:
        return 0.0
    return min(max(rate, 0.0), 1.0)


def _env_cost_flag() -> bool | None:
    """M3_TPU_PROFILE_COST: force HLO cost capture on ("1") or off ("0")
    regardless of the sampling rate; unset (None) defers to 'capture iff
    the profiler samples' (cost capture pays one extra AOT lower+compile
    per signature, so it follows the same explicit-opt-in as sampling)."""
    raw = os.environ.get("M3_TPU_PROFILE_COST", "")
    if raw == "1":
        return True
    if raw == "0":
        return False
    return None


class KernelProfiler:
    """Device-tier dispatch observability: SAMPLED wall-time profiles of
    every kernel dispatch, and HLO cost capture once per signature.

    JAX dispatch is async — wall time around the call measures Python
    dispatch, not device work — so a profiled sample bounds the dispatch
    with ``jax.block_until_ready`` on the result and records the whole
    span in ``m3tpu_kernel_dispatch_seconds{kernel=...}``. Sampling is
    DETERMINISTIC (dispatch ``n`` is sampled iff ``floor(n·rate)`` advances
    over ``floor((n−1)·rate)``), so profiles are reproducible run to run
    and exactly ``rate`` of dispatches pay the sync. The first dispatch of
    a ``key`` is taken to be its compile: it stays out of the dispatch
    histogram and is where the cost is captured. The process's compile
    COUNTERS (``m3tpu_jit_*``) are not fed from here: they count jax's own
    backend-compile events (``device.install_compile_counters``), which
    see every program, profiled or not.

    Usage::

        _PROF = KernelProfiler("packed_lane_agg")
        with _PROF.dispatch((windows4.shape, n, k)) as d:
            d.done(lane_aggregates_packed(...))
    """

    def __init__(self, kernel: str, registry: Registry | None = None,
                 sample_rate: float | None = None,
                 capture_costs: bool | None = None) -> None:
        reg = registry or DEFAULT
        self.kernel = kernel
        self._seen: set = set()  # dispatch keys already compiled
        self._lock = threading.Lock()
        self.sample_rate = (
            _env_sample_rate() if sample_rate is None
            else min(max(float(sample_rate), 0.0), 1.0)
        )
        # HLO cost capture (continuous profiling's device tier): on when
        # the profiler samples, force-on/off via M3_TPU_PROFILE_COST=1/0
        # — decided ONCE at construction so tests that poke sample_rate
        # at runtime don't surprise-pay the extra AOT compile
        if capture_costs is None:
            env_flag = _env_cost_flag()
            capture_costs = (
                env_flag if env_flag is not None else self.sample_rate > 0.0
            )
        self.capture_costs = bool(capture_costs)
        labels = {"kernel": kernel}
        self._dispatches = reg.counter(
            "kernel_dispatches_total", "kernel dispatches", labels
        )
        self._hist = reg.histogram(
            "kernel_dispatch_seconds",
            "block_until_ready-bounded wall time of SAMPLED kernel "
            "dispatches (M3_TPU_PROFILE_SAMPLE_RATE; compiles excluded)",
            labels,
            buckets=KERNEL_BUCKETS,
        )
        self._g_flops = reg.gauge(
            "kernel_flops",
            "XLA cost-analysis FLOPs of this kernel's most recent "
            "compilation (Compiled.cost_analysis; with dispatch-seconds "
            "and bytes this turns device time into work done)",
            labels,
        )
        self._g_bytes_accessed = reg.gauge(
            "kernel_bytes_accessed",
            "XLA cost-analysis bytes accessed of this kernel's most "
            "recent compilation",
            labels,
        )
        self._m_cost_captures = reg.counter(
            "kernel_cost_captures_total",
            "HLO cost analyses captured (once per compilation signature)",
            labels,
        )
        self._m_cost_errors = reg.counter(
            "kernel_cost_errors_total",
            "cost-analysis captures that failed (backend without cost "
            "analysis, AOT path unavailable) — capture is best-effort "
            "and never breaks a dispatch",
            labels,
        )
        self._n = 0  # dispatch sequence (guarded by _lock)
        self._costs: dict = {}  # compilation key -> {"flops", "bytes_accessed"}
        self._cost_seen: set = set()

    def _first_sight(self, key) -> bool:
        """Whether this is the first dispatch of ``key`` (its compile)."""
        with self._lock:
            if key in self._seen:
                return False
            self._seen.add(key)
            return True

    def _next_sampled(self) -> bool:
        rate = self.sample_rate
        with self._lock:
            self._n += 1
            n = self._n
        if rate <= 0.0:
            return False
        if rate >= 1.0:
            return True
        return math.floor(n * rate) > math.floor((n - 1) * rate)

    def dispatch(self, key=None, cost=None) -> "_Dispatch":
        """``cost``: optional ``(jitted_fn, args, kwargs)`` — when this
        dispatch turns out to be the first-call compile of ``key`` and
        cost capture is on, the compiled executable's HLO cost analysis
        is recorded via :meth:`capture_cost`."""
        return _Dispatch(self, key, cost)

    def capture_cost(self, key, fn, *args, **kwargs):
        """Record ``fn``'s compiled HLO cost analysis ONCE per
        compilation ``key``: ``fn.lower(*args).compile().cost_analysis()``
        (the jax AOT path — one extra trace+compile per signature, which
        is why capture follows the profiling opt-in). Tolerant of
        backends without cost analysis (errors counted, never raised).
        Returns the ``{"flops", "bytes_accessed"}`` dict or None."""
        if not self.capture_costs:
            return None
        with self._lock:
            if key in self._cost_seen:
                return self._costs.get(key)
            self._cost_seen.add(key)
        # the AOT lower/compile runs OUTSIDE the lock (M3L001 discipline:
        # an XLA compile under a lock stalls every concurrent dispatch)
        try:
            analysis = fn.lower(*args, **kwargs).compile().cost_analysis()
            if isinstance(analysis, (list, tuple)):
                analysis = analysis[0] if analysis else {}
            if analysis is None:
                analysis = {}
            cost = {
                "flops": float(analysis.get("flops", 0.0)),
                "bytes_accessed": float(analysis.get("bytes accessed", 0.0)),
            }
        except Exception:
            self._m_cost_errors.inc()
            return None
        with self._lock:
            self._costs[key] = cost
        self._g_flops.set(cost["flops"])
        self._g_bytes_accessed.set(cost["bytes_accessed"])
        self._m_cost_captures.inc()
        return cost

    def cost_analysis(self) -> dict:
        """Captured per-compilation costs, keyed by the dispatch key's
        string form (the debug-surface shape)."""
        with self._lock:
            return {str(k): dict(v) for k, v in self._costs.items()}


class _Dispatch:
    """One profiled kernel dispatch; call ``done(result)`` with the device
    output so a sampled dispatch can block on it."""

    __slots__ = ("profiler", "key", "cost", "sampled", "result", "_t0")

    def __init__(self, profiler: KernelProfiler, key, cost=None) -> None:
        self.profiler = profiler
        self.key = key
        self.cost = cost  # (jitted_fn, args, kwargs) for HLO cost capture
        self.sampled = profiler._next_sampled()
        self.result = None

    def done(self, result):
        self.result = result
        return result

    def __enter__(self) -> "_Dispatch":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            return
        prof = self.profiler
        prof._dispatches.inc()
        counter = _DISPATCH_COUNTER
        if counter is not None:
            counter(prof.kernel)
        compiled = False
        if self.key is not None:
            compiled = prof._first_sight(self.key)
        if compiled and self.cost is not None:
            # first sighting of this signature = the compile just
            # happened: capture its HLO cost analysis once (no-op when
            # cost capture is off)
            fn, args, kwargs = self.cost
            prof.capture_cost(self.key, fn, *args, **(kwargs or {}))
        if self.sampled and not compiled:
            if self.result is not None:
                try:
                    import jax

                    jax.block_until_ready(self.result)
                except ImportError:  # host-only result: nothing to sync
                    pass
            elapsed = time.perf_counter() - self._t0
            prof._hist.observe(elapsed)
            hook = _KERNEL_ATTRIBUTION
            if hook is not None:
                hook(prof.kernel, elapsed)
