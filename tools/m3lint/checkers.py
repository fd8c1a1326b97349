"""The project-invariant checkers. Codes:

- M3L001 device-op-under-lock — no jax device/compile ops inside a
  ``with <lock>:`` body (PR 3's admission rule: uploads stage OUTSIDE
  the shard/table lock so the hot path never stalls behind PCIe).
- M3L002 jit-mutable-capture — a ``@jax.jit`` function must not close
  over ``self`` state or module globals that are reassigned at runtime
  (the trace captures the value once; later mutation is silently stale).
- M3L003 wire-registry-consistency — wire.IDEMPOTENT_OPS/UNTRACED_OPS
  entries must be dispatched ops, no mutating op may be registered
  idempotent, every dispatched op must be classified, RETRYABLE_ETYPES
  must name defined exception classes, and client literal `_call` ops
  must exist server-side.
- M3L004 deadline-clock-discipline — `time.time()` must not feed a
  wait/backoff deadline computation (use `time.monotonic()`; the wire
  `_deadline` wall-clock sites carry explicit suppressions).
- M3L005 metric-name-discipline — registry metric names are static
  snake_case literals (the registry adds the single `m3tpu_` prefix)
  and label KEYS come from a fixed allowlist, so exposition cardinality
  is bounded by code review, not by runtime input.
- M3L006 thread-daemon-discipline — `threading.Thread` in net//client//
  cluster//services/ must set daemon=True (abandoned stragglers must
  never wedge interpreter exit — the PR 4 fan-out rule).
- M3L007 swallowed-exception — no bare `except:`; an
  `except Exception:` body that is only `pass` must count or log.
- M3L008 durable-write-discipline — storage/ code never opens a file
  for writing with bare ``open()`` (all durable bytes go through the
  storage.faults DiskIO seam: write-temp → fsync → rename, and fault
  injection reaches them), and within a function the checkpoint file is
  written LAST (the checkpoint commits the volume; anything written
  after it is outside the atomic-commit protocol).
"""

from __future__ import annotations

import ast
import re

from . import Checker, FileContext, register
from .model import is_mutating_op

# ---------------------------------------------------------------- helpers


def _terminal_name(node: ast.expr) -> str:
    """The rightmost identifier of a Name/Attribute/Subscript chain."""
    while isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _receiver_name(node: ast.expr) -> str:
    """The leftmost identifier (``jax`` in ``jax.device_put``)."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value if isinstance(node, ast.Attribute) else node.value
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _walk_skip_defs(nodes):
    """Walk statements, skipping nested function/class bodies: code in a
    nested def does not RUN where it is written."""
    stack = list(nodes)
    while stack:
        node = stack.pop()
        if isinstance(
            node,
            (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Lambda),
        ):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


_LOCK_NAME = re.compile(r"(lock|mutex)s?$|(^|_)(mu|cv|cond)$", re.IGNORECASE)


def _is_lock_like(expr: ast.expr) -> bool:
    return bool(_LOCK_NAME.search(_terminal_name(expr)))


# ---------------------------------------------------------------- M3L001


@register
class DeviceOpUnderLock(Checker):
    code = "M3L001"
    name = "device-op-under-lock"

    DEVICE_ATTRS = {"device_put", "block_until_ready", "pallas_call"}
    # socket-blocking boundary: a frame send can stall for the peer's TCP
    # window (or a fault-injected delay); holding any lock across it turns
    # one slow peer into a process-wide pile-up. The runtime twin is the
    # lockcheck harness's wrap_blocking(wire.send_frame) boundary under
    # tools/check_chaos.py.
    SOCKET_ATTRS = {"send_frame"}

    def check_file(self, ctx: FileContext):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.With):
                continue
            if not any(_is_lock_like(item.context_expr) for item in node.items):
                continue
            lock = next(
                _terminal_name(item.context_expr)
                for item in node.items
                if _is_lock_like(item.context_expr)
            )
            for inner in _walk_skip_defs(node.body):
                if not isinstance(inner, ast.Call):
                    continue
                attr = _terminal_name(inner.func)
                is_device = attr in self.DEVICE_ATTRS or (
                    attr == "jit" and _receiver_name(inner.func) == "jax"
                )
                if is_device:
                    yield self.finding(
                        ctx,
                        inner.lineno,
                        f"jax {attr}() inside `with {lock}:` — device "
                        "uploads/compiles must stage OUTSIDE the lock "
                        "(PR 3 admission rule: the hot path must never "
                        "stall behind PCIe or XLA under a shard/table lock)",
                    )
                elif attr in self.SOCKET_ATTRS:
                    yield self.finding(
                        ctx,
                        inner.lineno,
                        f"{attr}() inside `with {lock}:` — a socket send "
                        "can block on the peer's TCP window; frames must "
                        "be sent OUTSIDE locks (the collector's scrape/"
                        "write loop and every RPC path snapshot under the "
                        "lock, then send lock-free)",
                    )


# ---------------------------------------------------------------- M3L002


def _is_jit_expr(node: ast.expr) -> bool:
    return _terminal_name(node) == "jit"


def _is_jit_decorator(dec: ast.expr) -> bool:
    # @jax.jit / @jit
    if _is_jit_expr(dec):
        return True
    # @functools.partial(jax.jit, ...) / @partial(jit, ...)
    if (
        isinstance(dec, ast.Call)
        and _terminal_name(dec.func) == "partial"
        and dec.args
        and _is_jit_expr(dec.args[0])
    ):
        return True
    return False


@register
class JitMutableCapture(Checker):
    code = "M3L002"
    name = "jit-mutable-capture"

    def check_file(self, ctx: FileContext):
        mutated = self._mutated_globals(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            if not any(_is_jit_decorator(d) for d in node.decorator_list):
                continue
            local = self._local_names(node)
            for inner in ast.walk(node):
                if not isinstance(inner, ast.Name):
                    continue
                if inner.id == "self":
                    yield self.finding(
                        ctx,
                        inner.lineno,
                        f"@jit function {node.name}() references `self` — "
                        "the trace captures instance state once and never "
                        "sees later mutation; pass arrays as arguments",
                    )
                elif (
                    isinstance(inner.ctx, ast.Load)
                    and inner.id in mutated
                    and inner.id not in local
                ):
                    yield self.finding(
                        ctx,
                        inner.lineno,
                        f"@jit function {node.name}() reads module global "
                        f"`{inner.id}` which is reassigned at runtime — "
                        "the traced value goes stale; pass it as an "
                        "argument or mark it static",
                    )

    @staticmethod
    def _mutated_globals(tree: ast.Module) -> set:
        """Module globals assigned MORE than once at module level, or
        declared ``global`` and assigned inside a function."""
        counts: dict = {}
        for stmt in tree.body:
            for target in _assign_targets(stmt):
                counts[target] = counts.get(target, 0) + 1
        mutated = {n for n, c in counts.items() if c > 1}
        for node in ast.walk(tree):
            if isinstance(node, ast.Global):
                mutated.update(node.names)
        return mutated

    @staticmethod
    def _local_names(fn: ast.FunctionDef) -> set:
        names = {a.arg for a in fn.args.args + fn.args.kwonlyargs}
        if fn.args.vararg:
            names.add(fn.args.vararg.arg)
        if fn.args.kwarg:
            names.add(fn.args.kwarg.arg)
        for node in ast.walk(fn):
            for target in _assign_targets(node):
                names.add(target)
        return names


def _assign_targets(node):
    targets = []
    if isinstance(node, ast.Assign):
        targets = node.targets
    elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
        targets = [node.target]
    elif isinstance(node, ast.For):
        targets = [node.target]
    for t in targets:
        if isinstance(t, ast.Name):
            yield t.id
        elif isinstance(t, (ast.Tuple, ast.List)):
            for elt in t.elts:
                if isinstance(elt, ast.Name):
                    yield elt.id


# ---------------------------------------------------------------- M3L003


@register
class WireRegistryConsistency(Checker):
    code = "M3L003"
    name = "wire-registry-consistency"

    def check_project(self, model):
        if not model.has_wire:
            return  # nothing to check against (synthetic single-file runs)
        wire = model.wire_rel
        idem = model.registry("IDEMPOTENT_OPS")
        untraced = model.registry("UNTRACED_OPS")
        retryable = model.registry("RETRYABLE_ETYPES")

        for op in sorted(idem.ops):
            if op not in model.dispatched:
                yield self.finding(
                    wire,
                    idem.entry_lines.get(op, idem.line),
                    f"IDEMPOTENT_OPS entry {op!r} is not dispatched by any "
                    "service — stale registry entry or typo",
                )
            if is_mutating_op(op):
                yield self.finding(
                    wire,
                    idem.entry_lines.get(op, idem.line),
                    f"IDEMPOTENT_OPS contains mutating op {op!r} — the "
                    "client would transparently re-apply state changes on "
                    "transport failure (PR 4 at-most-once rule)",
                )
        for op in sorted(untraced.ops):
            if op not in model.dispatched:
                yield self.finding(
                    wire,
                    untraced.entry_lines.get(op, untraced.line),
                    f"UNTRACED_OPS entry {op!r} is not dispatched by any "
                    "service — stale registry entry or typo",
                )
        for etype in sorted(retryable.ops):
            if etype not in model.classes:
                yield self.finding(
                    wire,
                    retryable.entry_lines.get(etype, retryable.line),
                    f"RETRYABLE_ETYPES names {etype!r} but no such "
                    "exception class is defined anywhere in the tree",
                )
        for op, sites in sorted(model.dispatched.items()):
            if op not in idem.ops and not is_mutating_op(op):
                rel, line = sites[0]
                yield self.finding(
                    rel,
                    line,
                    f"dispatched op {op!r} is unclassified: add it to "
                    "wire.IDEMPOTENT_OPS (read/probe, duplicate-safe) or "
                    "to the mutating-op model in tools/m3lint/model.py",
                )
        for op, sites in sorted(model.client_calls.items()):
            if op not in model.dispatched:
                rel, line = sites[0]
                yield self.finding(
                    rel,
                    line,
                    f"client calls op {op!r} which no service dispatches — "
                    "typo or missing op_ handler",
                )


# ---------------------------------------------------------------- M3L004


@register
class DeadlineClockDiscipline(Checker):
    code = "M3L004"
    name = "deadline-clock-discipline"

    TIME_MODULES = {"time", "_time", "_t"}

    def check_file(self, ctx: FileContext):
        for node in ast.walk(ctx.tree):
            if not self._is_wall_clock_call(node):
                continue
            reason = self._deadline_context(node, ctx.parents)
            if reason is not None:
                yield self.finding(
                    ctx,
                    node.lineno,
                    f"time.time() used in {reason} — wall clock jumps "
                    "under NTP steps; use time.monotonic() for "
                    "waits/backoff/deadlines (wire `_deadline` frames are "
                    "the one wall-clock exception and carry suppressions)",
                )

    def _is_wall_clock_call(self, node) -> bool:
        return (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "time"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id in self.TIME_MODULES
        )

    @staticmethod
    def _deadline_context(node, parents):
        """A time.time() call feeds a deadline/duration when it is an
        operand of +/- arithmetic or of a comparison, or sits in a
        `while` loop condition."""
        child, cur = node, parents.get(node)
        while cur is not None:
            if isinstance(cur, ast.BinOp) and isinstance(
                cur.op, (ast.Add, ast.Sub)
            ):
                return "deadline/duration arithmetic"
            if isinstance(cur, ast.Compare):
                return "a deadline comparison"
            if isinstance(cur, ast.While) and child is cur.test:
                return "a while-loop wait condition"
            if isinstance(cur, ast.stmt) and not isinstance(cur, ast.While):
                break
            child, cur = cur, parents.get(cur)
        return None


# ---------------------------------------------------------------- M3L005


@register
class MetricNameDiscipline(Checker):
    code = "M3L005"
    name = "metric-name-discipline"

    METRIC_METHODS = {"counter", "gauge", "histogram"}
    RECEIVER = re.compile(r"^(METRICS|DEFAULT|reg|registry|_?metrics)$")
    NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
    # Prometheus recording-rule convention (level:metric:operation) —
    # colon-form names are legal ONLY in the ruler writer context
    # (m3_tpu/ruler/), which derives them from configured rules; anywhere
    # else a colon name would masquerade as a recorded series
    # (selfmon/convert.py skips them from scraped snapshots for the same
    # reason). Kept in sync with convert.RECORDED_NAME_RE.
    RECORDED_NAME_RE = re.compile(r"^[a-z_][a-z0-9_]*(:[a-z_][a-z0-9_]*)+$")
    RULER_PATH_PREFIX = "m3_tpu/ruler/"
    # the fixed label-key allowlist: every key must be grep-able and the
    # exposition cardinality per key must be argued when it is added here.
    # "ns": bounded by the operator-configured namespace count; labeling
    # write-path counters per namespace is what lets the self-scrape skip
    # its own reserved-namespace activity (selfmon/convert.py).
    # "group": bounded by the operator-configured ruleset (rule groups in
    # the ruler's KV-mirrored rules file) — per-group eval health is the
    # signal that makes the ruler itself alertable.
    # "tenant": values come off unauthenticated HTTP headers and wire
    # frames, but the TenantLedger caps distinct ids (M3_TPU_TENANT_CAP,
    # default 64; the rest collapse into __overflow__, counted loudly) —
    # per-tenant spend is exactly what open item 3's scheduler keys off.
    # "scope": the fixed cost-enforcer chain links (query|tenant|global).
    # "shard": configured shard ids (bounded by --num-shards), hard-capped
    # by resident/heat.ShardHeat (M3_TPU_SHARD_HEAT_CAP, overflow
    # collapsed loudly) — the per-shard heat signal rebalancing keys off.
    # "reason": the shed/rejection cause vocabulary — a hand-enumerated
    # constant set per emitting module (query/scheduler.py's SHED_*
    # trio), never derived from request data; paired with "tenant" it is
    # what lets dashboards split "who got shed" from "why".
    # "peer": placement instance ids — bounded by the operator-built
    # placement (node count), never derived from request data. The
    # migration family (storage/cluster_db.py
    # migration_streamed_bytes_total{peer}) keys on it so a handoff's
    # byte flow is attributable to the source that served it.
    # "objective": SLO objective names — bounded by the operator's
    # --slo-config spec (spec.py rejects duplicates and non-slug names),
    # never derived from request data; the m3tpu_slo_* family and the
    # probe counters key on it so budget/burn series join 1:1 to the
    # compiled slo:<name>:ratio_rate<w> recordings.
    # "window": the spec's burn/budget window tokens ("5m", "1h",
    # "5m/1h") — a handful of values fixed at config load; paired with
    # "objective" it is what lets a dashboard overlay fast vs slow burn.
    # Deliberately ABSENT: "frame"/"stack" — profile stacks are
    # unbounded runtime data and live in the profiling table
    # (m3_tpu/profiling/), never in metric labels.
    # "file": fileset file roles ("data", "digest", "checkpoint", ...) —
    # bounded by fs.SUFFIXES; the m3tpu_storage_corruption_total family
    # keys on it so a scrub alert names WHICH file of a volume rotted.
    # "encoder": who sealed a lane, "device" or "host" — two constants of
    # storage/database.py (m3tpu_seal_lanes_total{encoder,kind}).
    # "body": the decode body a chunk's own flags ask for — the three
    # constants of resident/pool.py CHUNK_BODIES
    # (m3tpu_resident_chunks_total{body}).
    # "cap": a plan's decode capacity, the power-of-two bucket of its
    # matched-series count between 8 and the segment's bitmap width — at
    # most log2(docs) values a segment, ten at 4,064 docs
    # (m3tpu_query_plan_builds_total{cap}).
    LABEL_KEYS = {"component", "op", "peer", "to", "kernel", "kind", "stage",
                  "ns", "group", "tenant", "scope", "shard", "reason",
                  "objective", "window", "file", "encoder", "body", "cap"}

    def check_file(self, ctx: FileContext):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if not isinstance(node.func, ast.Attribute):
                continue
            if node.func.attr not in self.METRIC_METHODS:
                continue
            if not self.RECEIVER.match(_terminal_name(node.func.value)):
                continue
            yield from self._check_call(ctx, node)

    def _check_call(self, ctx, node: ast.Call):
        name_arg = node.args[0] if node.args else None
        if not (
            isinstance(name_arg, ast.Constant)
            and isinstance(name_arg.value, str)
        ):
            yield self.finding(
                ctx,
                node.lineno,
                f"metric name passed to .{node.func.attr}() is not a "
                "static string literal — dynamic names are unbounded "
                "exposition cardinality",
            )
        else:
            name = name_arg.value
            if not self.NAME_RE.match(name):
                if self.RECORDED_NAME_RE.match(name) and ctx.rel.startswith(
                    self.RULER_PATH_PREFIX
                ):
                    pass  # colon-form recorded names, ruler context only
                elif self.RECORDED_NAME_RE.match(name):
                    yield self.finding(
                        ctx,
                        node.lineno,
                        f"colon-form recorded name {name!r} outside the "
                        f"ruler writer context ({self.RULER_PATH_PREFIX}) "
                        "— only recording rules may mint "
                        "level:metric:operation names",
                    )
                else:
                    yield self.finding(
                        ctx,
                        node.lineno,
                        f"metric name {name!r} is not snake_case "
                        "([a-z][a-z0-9_]*)",
                    )
            if name.startswith("m3tpu_"):
                yield self.finding(
                    ctx,
                    node.lineno,
                    f"metric name {name!r} hardcodes the m3tpu_ prefix — "
                    "the process registry adds it once; this would expose "
                    "m3tpu_m3tpu_*",
                )
        labels = next(
            (kw.value for kw in node.keywords if kw.arg == "labels"),
            node.args[2] if len(node.args) > 2 else None,
        )
        if isinstance(labels, ast.Dict):
            for key in labels.keys:
                if not (
                    isinstance(key, ast.Constant)
                    and isinstance(key.value, str)
                ):
                    yield self.finding(
                        ctx,
                        node.lineno,
                        "metric label KEY is not a string literal — "
                        "dynamic label keys are unbounded cardinality",
                    )
                elif key.value not in self.LABEL_KEYS:
                    yield self.finding(
                        ctx,
                        node.lineno,
                        f"metric label key {key.value!r} is not in the "
                        f"allowlist {sorted(self.LABEL_KEYS)} — add it to "
                        "MetricNameDiscipline.LABEL_KEYS with a "
                        "cardinality argument",
                    )


# ---------------------------------------------------------------- M3L006


@register
class ThreadDaemonDiscipline(Checker):
    code = "M3L006"
    name = "thread-daemon-discipline"

    SCOPED_DIRS = (
        "m3_tpu/net/",
        "m3_tpu/client/",
        "m3_tpu/cluster/",
        "m3_tpu/services/",
    )

    def check_file(self, ctx: FileContext):
        if not ctx.rel.startswith(self.SCOPED_DIRS):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if _terminal_name(node.func) != "Thread":
                continue
            daemon = next(
                (kw.value for kw in node.keywords if kw.arg == "daemon"), None
            )
            if not (
                isinstance(daemon, ast.Constant) and daemon.value is True
            ):
                yield self.finding(
                    ctx,
                    node.lineno,
                    "threading.Thread without daemon=True in the RPC "
                    "plane — an abandoned straggler (hung peer, "
                    "fan-out timeout) must never wedge interpreter exit "
                    "(PR 4 fan-out rule)",
                )


# ---------------------------------------------------------------- M3L007


@register
class SwallowedException(Checker):
    code = "M3L007"
    name = "swallowed-exception"

    BROAD = {"Exception", "BaseException"}

    def check_file(self, ctx: FileContext):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                yield self.finding(
                    ctx,
                    node.lineno,
                    "bare `except:` — catches SystemExit/KeyboardInterrupt; "
                    "catch Exception (or narrower) instead",
                )
                continue
            if not self._is_broad(node.type):
                continue
            if all(isinstance(stmt, ast.Pass) for stmt in node.body):
                yield self.finding(
                    ctx,
                    node.lineno,
                    "`except Exception: pass` silently swallows failures — "
                    "count (METRICS counter) or log it, or suppress with a "
                    "rationale if best-effort is genuinely intended",
                )

    def _is_broad(self, type_node) -> bool:
        if isinstance(type_node, ast.Tuple):
            return any(self._is_broad(e) for e in type_node.elts)
        return _terminal_name(type_node) in self.BROAD


# ---------------------------------------------------------------- M3L008


@register
class DurableWriteDiscipline(Checker):
    code = "M3L008"
    name = "durable-write-discipline"

    SCOPED_DIRS = ("m3_tpu/storage/",)
    # the seam itself is the one place allowed to touch files directly
    EXCLUDED = ("m3_tpu/storage/faults.py",)
    # the shared write-temp → fsync → rename primitives (storage/faults
    # DiskIO.write_durable; utils/blob wraps it with framing)
    DURABLE_CALLS = {"write_durable", "write_atomic_checked_blob"}
    WRITE_MODES = frozenset("wax+")

    def check_file(self, ctx: FileContext):
        if not ctx.rel.startswith(self.SCOPED_DIRS):
            return
        if ctx.rel in self.EXCLUDED:
            return
        yield from self._check_bare_open(ctx)
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_checkpoint_order(ctx, node)

    def _check_bare_open(self, ctx):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            # builtin open() only — os.open(devnull) and DISK.open are
            # Attribute calls and stay out of scope
            if not (isinstance(node.func, ast.Name) and node.func.id == "open"):
                continue
            mode = next(
                (kw.value for kw in node.keywords if kw.arg == "mode"),
                node.args[1] if len(node.args) > 1 else None,
            )
            if mode is None:
                continue  # default "r"
            if (
                isinstance(mode, ast.Constant)
                and isinstance(mode.value, str)
                and not (set(mode.value) & self.WRITE_MODES)
            ):
                continue  # read-only literal mode
            yield self.finding(
                ctx,
                node.lineno,
                "bare open() for writing in storage/ — durable bytes go "
                "through the storage.faults DiskIO seam (DISK.open / "
                "DISK.write_durable: write-temp → fsync → rename, fault "
                "injection included)",
            )

    def _check_checkpoint_order(self, ctx, fn):
        writes = []  # (lineno, is_checkpoint)
        for node in _walk_skip_defs(fn.body):
            if not isinstance(node, ast.Call):
                continue
            if _terminal_name(node.func) not in self.DURABLE_CALLS:
                continue
            is_ckpt = any(
                isinstance(sub, ast.Constant)
                and isinstance(sub.value, str)
                and "checkpoint" in sub.value
                for arg in node.args + [kw.value for kw in node.keywords]
                for sub in ast.walk(arg)
            )
            writes.append((node.lineno, is_ckpt))
        writes.sort()
        ckpt_line = next((ln for ln, c in writes if c), None)
        if ckpt_line is None:
            return
        for ln, is_ckpt in writes:
            if ln > ckpt_line and not is_ckpt:
                yield self.finding(
                    ctx,
                    ln,
                    "durable write after the checkpoint write in the same "
                    "function — the checkpoint commits the volume and must "
                    "be written LAST (fs.py atomic-commit protocol; a crash "
                    "between checkpoint and this write leaves a 'complete' "
                    "volume missing data)",
                )
