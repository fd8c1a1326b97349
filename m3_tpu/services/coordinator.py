"""Coordinator service: HTTP API front end over storage + engine + downsampler.

Reference: /root/reference/src/query/server/query.go:177 (Run: storage,
downsampler, engine, HTTP router) and src/query/api/v1/handler/ — Prometheus
remote write (prometheus/remote/write.go:257, snappy+protobuf), remote read,
PromQL native range/instant (native/read.go:120), label endpoints
(native/complete_tags.go), admin namespace/placement/topic handlers, health.

Served with the stdlib threading HTTP server — the process seam where the
reference uses its router; handlers match the reference's routes.
"""

from __future__ import annotations

import json
import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

import numpy as np

from ..aggregator.downsampler import Downsampler
from ..block.core import make_tags
from ..cluster.kv import KVStore
from ..cluster.placement import PlacementService
from ..gen import prompb_pb2 as prompb
from ..metrics.types import MetricType
from ..msg.bus import ConsumerService, Topic, TopicService
from ..query.engine import Engine, Result
from ..query.m3_storage import M3Storage
from ..query.promql import Matcher
from ..storage.database import Database, NamespaceOptions
from ..utils.snappy import compress, decompress


NANOS = 1_000_000_000
MS = 1_000_000


class Coordinator:
    """The single-process coordinator: DB + engine + optional downsampler."""

    def __init__(
        self,
        db: Database | None = None,
        namespace: str = "default",
        downsampler: Downsampler | None = None,
        kv: KVStore | None = None,
        base_dir: str | None = None,
        query_limits=None,
        tenant_limits=None,
        scheduler=None,
    ) -> None:
        import tempfile

        if db is None:
            db = Database(base_dir or tempfile.mkdtemp(prefix="m3tpu-"), num_shards=4)
            db.create_namespace(namespace, NamespaceOptions())
        self.db = db
        self.namespace = namespace
        global_enforcer = None
        if query_limits is not None:
            from ..query.cost import GlobalEnforcer, QueryLimits

            # global ceiling defaults to 10x the per-query scope (x/cost)
            global_enforcer = GlobalEnforcer(
                QueryLimits(
                    max_series=query_limits.max_series * 10,
                    max_datapoints=query_limits.max_datapoints * 10,
                )
            )
        tenant_enforcers = None
        if tenant_limits is not None:
            # the per-tenant middle scope of the enforcer chain
            # (query → tenant → global): tenant_limits is a
            # tenants.TenantLimitSet (load_tenant_limits file format)
            from ..query.tenants import TenantEnforcers

            tenant_enforcers = TenantEnforcers.from_limit_set(
                tenant_limits, global_enforcer=global_enforcer
            )
        self.engine = Engine(
            M3Storage(db, namespace),
            limits=query_limits,
            global_enforcer=global_enforcer,
            tenant_enforcers=tenant_enforcers,
            scheduler=scheduler,
        )
        self.downsampler = downsampler
        self.kv = kv or KVStore()
        self.placement_svc = PlacementService(self.kv)
        self.topic_svc = TopicService(self.kv)
        # per-namespace engine cache (the `namespace` query param routes
        # PromQL to other namespaces — notably the reserved `_m3tpu`
        # self-monitoring namespace); engines share the cost limiters
        self._engines: dict[str, Engine] = {namespace: self.engine}
        self._engines_lock = threading.Lock()
        self.selfmon = None  # SelfMonCollector when start_selfmon() ran
        self.ruler = None  # ruler.Ruler when start_ruler() ran
        self.slo = None  # slo.SLOEngine when start_slo() ran
        self._ruler_groups = []  # file-sourced groups (start_ruler keeps
        # them so start_slo can re-publish file + generated together)
        self._selfmon_ns_ready = False
        # fleet-profile peer source (m3_tpu/profiling/): a zero-arg
        # callable yielding {instance_id: node} of `profile`-op-capable
        # stubs — main() wires the placement + static peers in; None
        # means /debug/pprof/fleet serves only this process
        self.peer_source = None
        self.instance_id = "coordinator0"

    def engine_for(self, namespace: str | None) -> Engine:
        if not namespace or namespace == self.namespace:
            return self.engine
        with self._engines_lock:
            eng = self._engines.get(namespace)
            if eng is not None:
                return eng
            eng = Engine(
                M3Storage(self.db, namespace),
                limits=self.engine.limits,
                global_enforcer=self.engine.global_enforcer,
                tenant_enforcers=self.engine.tenant_enforcers,
                # ONE admission scheduler across namespaces: the slots
                # bound the process, not each namespace separately
                scheduler=self.engine.scheduler,
            )
            # cache only namespaces the store actually knows: the param
            # comes off an unauthenticated HTTP query string, and caching
            # arbitrary strings would grow this dict without bound (an
            # unknown namespace still gets a transient engine — its query
            # fails with the store's own error, uncached)
            if namespace in self.db.namespaces:
                self._engines[namespace] = eng
            return eng

    # --- self-monitoring (m3_tpu/selfmon/) ---

    def start_selfmon(
        self, interval: float, peers=None, instance: str = "coordinator0"
    ):
        """Start the self-scrape collector: this process's registry (plus
        ``peers``: a zero-arg callable yielding {id: RemoteNode}) stored
        as series under the reserved namespace through the normal ingest
        path — queryable right back through this coordinator's PromQL
        surface with ``namespace=_m3tpu``."""
        from ..selfmon import RESERVED_NS, DatabaseSink, SelfMonCollector

        self._ensure_selfmon_namespace()
        self.selfmon = SelfMonCollector(
            DatabaseSink(self.db, RESERVED_NS),
            interval=interval,
            instance=instance,
            component="coordinator",
            peers=peers,
        )
        self.selfmon.start()
        return self.selfmon

    # --- ruler (m3_tpu/ruler/): recording + alerting over stored series ---

    def start_ruler(
        self,
        rules_path: str | None = None,
        webhooks=(),
        instance: str = "coordinator0",
        jitter: bool = True,
        default_rules: bool = True,
    ):
        """Start the rule engine: groups from ``rules_path`` (YAML/JSON)
        are validated, mirrored into the shared KV ruleset (all
        coordinators converge on one version; alert state checkpoints
        survive failover), and evaluated per group through the same
        per-namespace engine cache the HTTP query surface uses — so
        ``namespace: _m3tpu`` rules watch the fleet's own stored
        telemetry. ``webhooks``: notifier URLs (each gets the resilience
        plane's retry policy); a log notifier is always attached.

        ``default_rules`` merges in the built-in groups
        (ruler/defaults.py — the storage durability burn-rate group over
        ``m3tpu_storage_corruption_total``); a file group reusing a
        default group's name wins, so a deployment can override the
        defaults rule-for-rule or drop them with ``--no-default-rules``."""
        from ..ruler import Ruler, WebhookNotifier, groups_to_spec

        self.ruler = Ruler(
            engine_for=self.engine_for,
            db=self.db,
            kv=self.kv,
            notifiers=[WebhookNotifier(u) for u in webhooks],
            instance=instance,
            default_namespace=self.namespace,
            ensure_namespace=lambda ns: self._ensure_selfmon_namespace(),
            jitter=jitter,
        )
        groups = []
        if rules_path:
            from ..ruler import load_rules_file

            groups = load_rules_file(rules_path, self.namespace)
        if default_rules:
            from ..ruler.defaults import default_groups

            named = {g.name for g in groups}
            groups = groups + [
                g for g in default_groups() if g.name not in named
            ]
        if groups:
            self._ruler_groups = groups
            self.ruler.publish(groups_to_spec(self._ruler_groups))
        self.ruler.start()
        return self.ruler

    # --- SLO engine (m3_tpu/slo/): error budgets over the ruler's output ---

    def start_slo(
        self,
        slo_path: str,
        webhooks=(),
        instance: str = "coordinator0",
        jitter: bool = True,
    ):
        """Start the fleet SLO engine from an ``--slo-config`` spec file:
        the objectives compile into one generated ``slo`` rule group
        (ratio recordings + multi-window burn-rate alerts) published
        through the ruler alongside any file-sourced groups, and the
        engine's status/probe loops feed ``m3tpu_slo_*`` metrics plus the
        ``/api/v1/slo`` + ``/debug/slo`` surfaces.

        Requires a running self-scrape (the compiled rules read the
        fleet's own stored telemetry in ``_m3tpu``); starts the ruler if
        none is running yet."""
        from ..ruler import groups_to_spec
        from ..slo import SLO_GROUP, SLOEngine, load_slo_file

        if self.selfmon is None:
            raise RuntimeError(
                "the SLO engine consumes the fleet's own stored telemetry: "
                "start the self-scrape (--selfmon-interval) before "
                "--slo-config, or the compiled SLI rules evaluate over an "
                "empty _m3tpu namespace forever"
            )
        spec = load_slo_file(slo_path)
        if self.ruler is None:
            self.start_ruler(webhooks=webhooks, instance=instance, jitter=jitter)
        if any(g.name == SLO_GROUP for g in self._ruler_groups):
            raise ValueError(
                f"rule group name {SLO_GROUP!r} is reserved for the "
                "generated SLO group (--slo-config); rename the file group"
            )
        self.slo = SLOEngine(
            spec,
            engine_for=self.engine_for,
            db=self.db,
            ruler=self.ruler,
            namespace=self.namespace,
            instance=instance,
        )
        self.ruler.publish(
            groups_to_spec(list(self._ruler_groups) + self.slo.rule_groups())
        )
        self.slo.start()
        return self.slo

    # --- continuous profiling (m3_tpu/profiling/) ---

    def fleet_profile(self, seconds: float = 30.0) -> dict:
        """One whole-fleet folded-stack profile: this coordinator's own
        sampler plus every peer's ``profile`` wire op, merged by stack
        with per-instance counts (/debug/pprof/fleet). Dead peers are
        reported in ``errors``, never fatal."""
        from ..profiling import collect_fleet_profile, process_profile

        peers = {}
        source_error = None
        if self.peer_source is not None:
            try:
                peers = dict(self.peer_source())
            except Exception as exc:
                # a broken topology source must not make a local-only
                # profile look like a healthy single-node fleet
                source_error = f"{type(exc).__name__}: {exc}"
        out = collect_fleet_profile(
            self.instance_id, process_profile(seconds=seconds), peers, seconds
        )
        if source_error is not None:
            out["errors"]["peer_source"] = source_error
        return out

    def _ensure_selfmon_namespace(self) -> None:
        from ..selfmon import RESERVED_NS

        # memoized: this runs per ingested selfmon metric, and in cluster
        # mode the check below would otherwise cost a control-plane KV
        # round trip every time (SessionDatabase.namespaces is the static
        # constructor tuple, never containing the reserved ns)
        if self._selfmon_ns_ready:
            return
        if RESERVED_NS in self.db.namespaces:
            self._selfmon_ns_ready = True
            return
        if hasattr(self.db, "create_namespace"):
            # short retention: self telemetry is operational, not archival
            self.db.create_namespace(
                RESERVED_NS,
                NamespaceOptions(
                    retention_nanos=24 * 3600 * NANOS,
                    block_size_nanos=3600 * NANOS,
                ),
            )
            self._selfmon_ns_ready = True
            return
        # cluster mode (SessionDatabase): register in the control-plane
        # namespace registry — every watching dbnode creates it live
        from ..cluster.namespaces import NamespaceExistsError, NamespaceRegistry

        try:
            NamespaceRegistry(self.kv).add(
                RESERVED_NS, 24 * 3600 * NANOS, 3600 * NANOS
            )
        except NamespaceExistsError:
            pass  # another coordinator (or operator) won the race: same goal
        self._selfmon_ns_ready = True

    # --- ingest (downsamplerAndWriter ingest/write.go:138) ---

    def ingest_aggregated(self, msgs) -> int:
        """m3msg ingest (ingest/m3msg/ingest.go): aggregated metrics from
        the aggregator tier land in storage. Tag-wire metric IDs are
        decoded back to tags and written tagged (indexed) with the
        aggregation type as an extra label (the reference's suffix scheme,
        label-form so PromQL metric names stay valid); opaque IDs write
        untagged."""
        from ..selfmon import RESERVED_NS, SELFMON_MARKER, selfmon_writer
        from ..utils.serialize import decode_tags, is_tag_id

        n = 0
        for m in msgs:
            if is_tag_id(m.id):
                try:
                    tags = tuple(sorted(decode_tags(m.id)))
                except ValueError:
                    tags = None
                if tags is not None and SELFMON_MARKER in tags:
                    # bus-ingested self telemetry (an aggregator's MsgSink):
                    # strip the marker and route into the reserved
                    # namespace, unsuffixed — these are registry snapshots,
                    # not aggregated rollups
                    tags = tuple(t for t in tags if t != SELFMON_MARKER)
                    self._ensure_selfmon_namespace()
                    with selfmon_writer():
                        self.db.write_tagged(
                            RESERVED_NS, tags, m.time_nanos, m.value
                        )
                    n += 1
                    continue
                if tags is not None:
                    tags = tuple(tags) + ((b"agg", m.agg_type.type_string.encode()),)
                    self.db.write_tagged(self.namespace, tags, m.time_nanos, m.value)
                    n += 1
                    continue
            # opaque IDs: the aggregation type must still split series —
            # same suffix scheme as the direct-forward path (suffixed_id)
            sid = m.id + b"." + m.agg_type.type_string.encode()
            self.db.write(self.namespace, sid, m.time_nanos, m.value)
            n += 1
        return n

    def serve_msg_ingest(self, host: str = "127.0.0.1", port: int = 0):
        """Start the m3msg consumer endpoint (coordinator m3msg ingester,
        src/cmd/services/m3coordinator/ingest/m3msg/) — returns the
        ConsumerServer (its .port is the listen port)."""
        from ..metrics.encoding import decode_aggregated_batch
        from ..msg.transport import ConsumerServer

        def handler(message) -> bool:
            try:
                self.ingest_aggregated(decode_aggregated_batch(message.payload))
                return True
            except Exception:
                return False  # nack: the producer's retry sweep redelivers

        server = ConsumerServer(handler, host=host, port=port)
        server.start()
        return server

    def write_prom(self, req: prompb.WriteRequest) -> int:
        """Remote-write ingest; storage writes ride the BATCHED path
        end-to-end (client host queues → one write_tagged_batch RPC per
        host) when the backing db supports it."""
        count = 0
        rows = []
        for ts in req.timeseries:
            tags = make_tags([(l.name, l.value) for l in ts.labels])
            for s in ts.samples:
                rows.append((tags, s.timestamp * MS, s.value, MetricType.GAUGE))
                count += 1
        # mapping/rollup rules evaluate over the whole batch (cached
        # matcher, one aggregator lock) instead of per sample
        if self.downsampler is not None and rows:
            keeps = self.downsampler.write_batch(rows)
        else:
            keeps = [True] * len(rows)
        batch = [
            (tags, t_nanos, v, 1)
            for (tags, t_nanos, v, _), keep in zip(rows, keeps)
            if keep
        ]
        if batch:
            if hasattr(self.db, "write_tagged_batch"):
                errs = self.db.write_tagged_batch(self.namespace, batch)
                failed = [e for e in errs if e]
                if failed:
                    # entries that reached quorum stay written; the client
                    # retry re-upserts them idempotently
                    raise RuntimeError(
                        f"remote write partial failure: {len(failed)}/{len(errs)} "
                        f"samples (first: {failed[0]})"
                    )
            else:
                for tags, t_nanos, v, unit in batch:
                    self.db.write_tagged(self.namespace, tags, t_nanos, v)
        from ..query.tenants import charge_writes

        charge_writes(count)
        return count

    def read_prom(self, req: prompb.ReadRequest) -> prompb.ReadResponse:
        resp = prompb.ReadResponse()
        for q in req.queries:
            matchers = []
            for m in q.matchers:
                op = {0: "=", 1: "!=", 2: "=~", 3: "!~"}[m.type]
                matchers.append(Matcher(m.name, op, m.value))
            result = resp.results.add()
            raw = self.engine.storage.fetch(
                matchers, q.start_timestamp_ms * MS, (q.end_timestamp_ms + 1) * MS
            )
            for tags, times, vals in raw:
                ts = result.timeseries.add()
                for k, v in tags:
                    ts.labels.add(name=k.decode(), value=v.decode())
                for t, v in zip(times, vals):
                    ts.samples.add(value=float(v), timestamp=int(t) // MS)
        return resp

    def query_range(self, query: str, start_s: float, end_s: float, step_s: float,
                    namespace: str | None = None,
                    force_staged: bool = False) -> dict:
        # force_staged: the fused-pipeline parity probe (query/plan.py) —
        # device query plans are disabled for this evaluation so callers
        # can diff fused vs staged results bit for bit
        from ..query import plan as query_plan

        eng = self.engine_for(namespace)
        args = (query, int(start_s * NANOS), int(end_s * NANOS),
                int(step_s * NANOS))
        if force_staged:
            with query_plan.force_staged():
                r = eng.query_range(*args)
        else:
            r = eng.query_range(*args)
        return _prom_matrix(r, int(start_s * NANOS), int(step_s * NANOS))

    def query_instant(self, query: str, time_s: float,
                      namespace: str | None = None) -> dict:
        r = self.engine_for(namespace).query_instant(query, int(time_s * NANOS))
        return _prom_vector(r, time_s)

    def explain(self, query: str, start_s: float, end_s: float, step_s: float,
                namespace: str | None = None) -> dict:
        """Query EXPLAIN (Engine.explain): per-stage timings, scan
        counters, and the per-block resident-vs-streamed routing record."""
        return self.engine_for(namespace).explain(
            query, int(start_s * NANOS), int(end_s * NANOS), int(step_s * NANOS)
        )

    def _cost_parent(self):
        """The parent scope a fresh per-query Enforcer chains to: the
        active tenant's middle scope when tenant limits are configured,
        else the global ceiling (None when neither is)."""
        if self.engine.tenant_enforcers is not None:
            from ..query.tenants import current as current_tenant

            return self.engine.tenant_enforcers.scope_for(current_tenant())
        return self.engine.global_enforcer

    # --- graphite (src/query/api/v1/handler/graphite/render.go + find.go) ---

    def _graphite_engine(self, enforcer=None):
        from ..graphite.engine import GraphiteEngine

        ns = "graphite" if "graphite" in self.db.namespaces else self.namespace
        return GraphiteEngine(self.db, namespace=ns, enforcer=enforcer)

    def graphite_render(self, q: dict) -> list[dict]:
        import time as _time

        now_s = _time.time()
        start_s = _graphite_time(q.get("from", ["-1h"])[0], now_s)
        end_s = _graphite_time(q.get("until", ["now"])[0], now_s)
        step_s = _parse_step(q.get("step", ["10"])[0])
        if step_s <= 0:
            raise ValueError("step must be positive")
        steps = max(int((end_s - start_s) // step_s), 1)
        # the graphite path honors the same cost limits as PromQL: bound the
        # step grid up front, charge fetched output per target — through
        # the same query → tenant → global chain. The graphite engine has
        # no QueryStats record (stats.finish is the PromQL path's ledger
        # seam), so this surface charges the tenant ledger itself — every
        # query surface must attribute, or /debug/tenants lies for it.
        from ..query import tenants as _tenants
        from ..query.cost import QueryLimitError

        limits = self.engine.limits
        parent = self._cost_parent()
        enforcer = None
        rejected = errored = False
        try:
            if limits is not None or parent is not None:
                from ..query.cost import Enforcer, QueryLimits, limit_error

                if limits is not None and 0 < limits.max_datapoints < steps:
                    raise limit_error(
                        "query", "datapoints", steps, limits.max_datapoints
                    )
                enforcer = Enforcer(
                    limits if limits is not None else QueryLimits(), parent
                )
            # the enforcer rides inside the engine's fetch, so oversized
            # globs abort at fetch depth (like the PromQL path), not after
            # rendering
            engine = self._graphite_engine(enforcer=enforcer)
            out = []
            for target in q.get("target", []):
                series = engine.render(
                    target, int(start_s * NANOS), int(end_s * NANOS), int(step_s * NANOS)
                )
                for s in series:
                    pts = [
                        [None if np.isnan(v) else float(v), int(start_s + i * step_s)]
                        for i, v in enumerate(s.values)
                    ]
                    out.append({"target": s.name, "datapoints": pts})
            return out
        except Exception as exc:
            errored = True
            rejected = isinstance(exc, QueryLimitError)
            raise
        finally:
            if enforcer is not None:
                enforcer.release()
            _tenants.LEDGER.charge(
                _tenants.current() or _tenants.DEFAULT_TENANT,
                queries=1,
                series=enforcer.series if enforcer is not None else 0,
                datapoints=enforcer.datapoints if enforcer is not None else 0,
                limit_rejections=1 if rejected else 0,
                errors=1 if errored else 0,
            )

    def graphite_find(self, pattern: str) -> list[dict]:
        return self._graphite_engine().find(pattern)

    @staticmethod
    def _parse_prom_matchers(expr: str) -> list[Matcher]:
        """A match[] selector string → matchers (reuses the PromQL parser)."""
        from ..query.promql import VectorSelector, parse

        ast = parse(expr)
        if not isinstance(ast, VectorSelector):
            raise ValueError(f"match[] must be a series selector: {expr!r}")
        matchers = list(ast.matchers)
        if ast.name:
            matchers.append(Matcher("__name__", "=", ast.name))
        return matchers

    def _index_query(self, match_exprs: list[str]):
        from ..query.m3_storage import matchers_to_index_query

        if not match_exprs:
            return None
        from ..index.query import disj

        qs = [
            matchers_to_index_query(self._parse_prom_matchers(e))
            for e in match_exprs
        ]
        return qs[0] if len(qs) == 1 else disj(*qs)

    def series(self, match_exprs: list[str], start_nanos: int, end_nanos: int):
        """/api/v1/series (api/v1/handler/prometheus/native + remote in the
        reference): label sets of series matching any selector."""
        if not match_exprs:
            # prometheus requires at least one selector; an unbounded full
            # index dump would bypass the cost limits
            raise ValueError("series endpoint requires at least one match[]")
        q = self._index_query(match_exprs)
        limit = None
        if self.engine.limits is not None and self.engine.limits.max_series:
            limit = self.engine.limits.max_series
        result = self.db.query_ids(self.namespace, q, start_nanos, end_nanos, limit=limit)
        return [
            {k.decode(): v.decode() for k, v in doc.fields}
            for doc in result.docs
        ]

    def search(self, match_exprs: list[str], start_nanos: int, end_nanos: int,
               limit: int | None = None):
        """/api/v1/search (api/v1/handler/search.go): series IDs + tags
        matching the given selectors."""
        if not match_exprs:
            raise ValueError("search requires at least one match[]")
        q = self._index_query(match_exprs)
        result = self.db.query_ids(self.namespace, q, start_nanos, end_nanos, limit=limit)
        return [
            {
                "id": doc.id.decode("utf-8", "replace"),
                "tags": {k.decode(): v.decode() for k, v in doc.fields},
            }
            for doc in result.docs
        ]

    def write_influx(self, body: str, precision: str = "ns") -> int:
        """InfluxDB line-protocol ingest (handler/influxdb/write.go)."""
        from .influx import parse_body

        points = parse_body(body, precision=precision)
        rows = []
        for name, tags, t_nanos, value in points:
            # __name__ must win over any same-named line tag
            tag_pairs = make_tags({**tags, "__name__": name})
            rows.append((tag_pairs, t_nanos, value, MetricType.GAUGE))
        if self.downsampler is not None and rows:
            keeps = self.downsampler.write_batch(rows)
        else:
            keeps = [True] * len(rows)
        for (tag_pairs, t_nanos, value, _), keep in zip(rows, keeps):
            if keep:
                self.db.write_tagged(self.namespace, tag_pairs, t_nanos, value)
        from ..query.tenants import charge_writes

        charge_writes(len(points))
        return len(points)

    def labels(self, match_exprs: list[str] | None = None,
               start_nanos: int = 0, end_nanos: int = 2**62) -> list[str]:
        q = self._index_query(match_exprs or [])
        agg = self.db.aggregate_query(self.namespace, q, start_nanos, end_nanos)
        return sorted(k.decode() for k in agg)

    def label_values(self, name: str, match_exprs: list[str] | None = None,
                     start_nanos: int = 0, end_nanos: int = 2**62) -> list[str]:
        q = self._index_query(match_exprs or [])
        agg = self.db.aggregate_query(
            self.namespace, q, start_nanos, end_nanos, field_filter=[name.encode()]
        )
        return sorted(v.decode() for v in agg.get(name.encode(), ()))


def _prom_matrix(r: Result, start_nanos: int, step_nanos: int) -> dict:
    out = []
    vals = np.asarray(r.values)
    for i, meta in enumerate(r.metas):
        metric = {k.decode(): v.decode() for k, v in meta.tags}
        values = []
        for t in range(vals.shape[1]):
            v = vals[i, t]
            if np.isnan(v):
                continue
            values.append([(start_nanos + t * step_nanos) / NANOS, _fmt(v)])
        if values:
            out.append({"metric": metric, "values": values})
    return {"status": "success", "data": {"resultType": "matrix", "result": out}}


def _prom_vector(r: Result, time_s: float) -> dict:
    out = []
    vals = np.asarray(r.values)
    for i, meta in enumerate(r.metas):
        v = vals[i, -1]
        if np.isnan(v):
            continue
        metric = {k.decode(): v2.decode() for k, v2 in meta.tags}
        out.append({"metric": metric, "value": [time_s, _fmt(v)]})
    return {"status": "success", "data": {"resultType": "vector", "result": out}}


def _fmt(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    return repr(float(v))


class _Handler(BaseHTTPRequestHandler):
    coordinator: Coordinator = None  # injected by serve()

    def log_message(self, *args) -> None:  # quiet
        pass

    def _send(self, code: int, body: bytes, ctype: str = "application/json") -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _json(self, obj, code: int = 200) -> None:
        self._send(code, json.dumps(obj).encode())

    def _body(self) -> bytes:
        n = int(self.headers.get("Content-Length", 0))
        return self.rfile.read(n)

    def _tenant(self, q: dict) -> str:
        """The caller's tenant identity: ``M3-Tenant`` header first, then
        the ``tenant=`` query param, default anonymous — normalized so
        junk ids collapse into the capped overflow tenant."""
        from ..query.tenants import normalize

        return normalize(
            self.headers.get("M3-Tenant") or q.get("tenant", [None])[0]
        )

    def _deadline_scope(self, q: dict):
        """Client deadline propagation: the ``timeout=`` query param (or
        ``M3-Timeout`` header) in duration syntax (``500``, ``2.5``,
        ``30s``, ``1m``) becomes the request thread's ambient MONOTONIC
        deadline — QueryScheduler.admit bounds its queue wait by it
        (shed reason ``deadline``) and outbound RPC calls tighten their
        wall-clock budget and ``_deadline`` frame to it, so nobody works
        for a caller that already gave up. Unparseable or absent →
        no-op scope (only ``--sched-max-wait`` bounds the wait)."""
        from ..net.resilience import deadline_scope

        raw = self.headers.get("M3-Timeout") or q.get("timeout", [None])[0]
        if not raw:
            return deadline_scope(None)
        try:
            timeout_s = _parse_step(raw)
        except ValueError:
            return deadline_scope(None)
        import time as _time

        return deadline_scope(_time.monotonic() + timeout_s)

    def _debug_dump(self) -> bytes:
        """x/debug/debug.go zip dump: thread stacks, metrics, namespaces,
        placement, recent traces."""
        import io
        import sys
        import traceback
        import zipfile

        from ..utils.instrument import DEFAULT as METRICS
        from ..utils.trace import TRACER

        c = self.coordinator
        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
            stacks = []
            for tid, frame in sys._current_frames().items():
                stacks.append(f"--- thread {tid} ---")
                stacks.extend(traceback.format_stack(frame))
            z.writestr("stacks.txt", "\n".join(stacks))
            z.writestr("metrics.txt", METRICS.expose())
            z.writestr("traces.json", json.dumps(TRACER.dump(limit=512), indent=1))
            from ..query.stats import ACTIVE, RING

            z.writestr(
                "slow_queries.json", json.dumps(RING.dump(limit=128), indent=1)
            )
            z.writestr(
                "active_queries.json", json.dumps(ACTIVE.dump(), indent=1)
            )
            from ..query.tenants import LEDGER

            z.writestr("tenants.json", json.dumps(LEDGER.dump(), indent=1))
            # incident snapshot: the current folded-stack profile and the
            # device-memory split ride along, so one dump answers "where
            # was the time and the memory" next to slow_queries/tenants
            from ..profiling import collect_device_memory, process_profile

            z.writestr(
                "profile.json", json.dumps(process_profile(), indent=1)
            )
            z.writestr(
                "device_memory.json",
                json.dumps(collect_device_memory(c.db), indent=1),
            )
            if getattr(c.db, "resident_pool", None) is not None:
                # per-shard residency heat (resident/heat.py) + pool
                # stats: the rebalance signal next to the incident data
                z.writestr(
                    "resident.json",
                    json.dumps(c.db.resident_stats(), indent=1),
                )
            if hasattr(c.db, "index_stats"):
                # device index tier + postings cache: segment counts,
                # device bytes vs budget, eviction/routing counters
                # (m3_tpu/index/device/)
                z.writestr(
                    "index.json",
                    json.dumps(c.db.index_stats(), indent=1),
                )
            if c.ruler is not None:
                z.writestr(
                    "ruler.json",
                    json.dumps(
                        {"rules": c.ruler.rules_dict(),
                         "alerts": c.ruler.alerts_dict()},
                        indent=1,
                    ),
                )
            if c.slo is not None:
                z.writestr(
                    "slo.json", json.dumps(c.slo.debug_dict(), indent=1)
                )
            ns_info = {}
            if hasattr(c.db, "lock"):
                with c.db.lock:
                    namespaces = list(c.db.namespaces.items())
                for name, ns in namespaces:
                    counts = []
                    for s in ns.shards:
                        with s.lock:
                            counts.append(len(s.series))
                    ns_info[name] = {
                        "blockSizeNanos": ns.opts.block_size_nanos,
                        "retentionNanos": ns.opts.retention_nanos,
                        "numShards": len(ns.shards),
                        "numSeries": sum(counts),
                    }
            else:
                # cluster mode (SessionDatabase): the shards live on the
                # dbnodes — dump the known namespace names only
                ns_info = {name: {} for name in sorted(c.db.namespaces)}
            z.writestr("namespaces.json", json.dumps(ns_info, indent=1))
            p = c.placement_svc.get()
            z.writestr("placement.json", json.dumps(p.to_dict() if p else {}, indent=1))
        return buf.getvalue()

    def do_GET(self) -> None:
        from ..utils.trace import TRACER

        c = self.coordinator
        url = urlparse(self.path)
        q = parse_qs(url.query)
        try:
            # poller endpoints (health checks, metric scrapes, the trace
            # endpoints themselves) would evict useful spans from the ring
            from ..utils.trace import NOOP_SPAN

            span = (
                NOOP_SPAN
                if url.path in (
                    "/health", "/metrics", "/debug/traces",
                    "/debug/slow_queries", "/debug/dump",
                    "/debug/exemplars", "/debug/active_queries",
                    "/debug/tenants", "/debug/pprof/profile",
                    "/debug/pprof/fleet", "/api/v1/slo", "/debug/slo",
                )
                else TRACER.span("http.get", path=url.path)
            )
            # tenant identity (M3-Tenant header / tenant= param) rides a
            # thread-local for the whole request: QueryStats, the cost
            # chain's tenant scope, the ledger, and outbound RPC frames
            # all read it from here
            from ..query.tenants import tenant_context

            tenant = self._tenant(q)
            span.set_tag("tenant", tenant)
            with tenant_context(tenant), self._deadline_scope(q), span:
                if url.path == "/health":
                    self._json({"ok": True})
                elif url.path == "/metrics":
                    from ..utils.instrument import DEFAULT as METRICS

                    # content negotiation (openmetrics_spec): a scraper
                    # advertising openmetrics-text gets the 1.0 exposition
                    # (counter _total naming, exemplars on bucket lines,
                    # # EOF); everyone else keeps the 0.0.4 text format
                    accept = self.headers.get("Accept", "")
                    if "application/openmetrics-text" in accept:
                        self._send(
                            200,
                            METRICS.expose_openmetrics().encode(),
                            ctype="application/openmetrics-text; "
                            "version=1.0.0; charset=utf-8",
                        )
                    else:
                        self._send(
                            200, METRICS.expose().encode(),
                            ctype="text/plain; version=0.0.4",
                        )
                elif url.path == "/api/v1/query_range":
                    self._json(
                        c.query_range(
                            q["query"][0],
                            float(q["start"][0]),
                            float(q["end"][0]),
                            _parse_step(q.get("step", ["15"])[0]),
                            namespace=q.get("namespace", [None])[0],
                            force_staged=q.get("force_staged", ["0"])[0]
                            in ("1", "true"),
                        )
                    )
                elif url.path == "/api/v1/query":
                    self._json(
                        c.query_instant(
                            q["query"][0],
                            float(q["time"][0]),
                            namespace=q.get("namespace", [None])[0],
                        )
                    )
                elif url.path == "/api/v1/explain":
                    self._json(
                        c.explain(
                            q["query"][0],
                            float(q["start"][0]),
                            float(q.get("end", q["start"])[0]),
                            _parse_step(q.get("step", ["15"])[0]),
                            namespace=q.get("namespace", [None])[0],
                        )
                    )
                elif url.path == "/api/v1/labels":
                    self._json(
                        {"status": "success",
                         "data": c.labels(q.get("match[]", []), *_prom_range(q))}
                    )
                elif url.path == "/api/v1/series":
                    self._json(
                        {"status": "success",
                         "data": c.series(q.get("match[]", []), *_prom_range(q))}
                    )
                elif (m := re.match(r"^/api/v1/label/([^/]+)/values$", url.path)) is not None:
                    self._json(
                        {"status": "success",
                         "data": c.label_values(
                             m.group(1), q.get("match[]", []), *_prom_range(q)
                         )}
                    )
                elif url.path == "/api/v1/search":
                    self._json(
                        {"status": "success",
                         "data": c.search(
                             q.get("match[]", []) or q.get("query", []),
                             *_prom_range(q),
                             limit=int(q["limit"][0]) if "limit" in q else None,
                         )}
                    )
                elif url.path == "/api/v1/services/m3db/placement":
                    p = c.placement_svc.get()
                    self._json(p.to_dict() if p else {}, 200 if p else 404)
                elif url.path == "/api/v1/rules":
                    # one route, two rule planes: the r2 aggregation
                    # rulesets (namespaces/rulesets keys, unchanged) plus
                    # the Prometheus rules-API shape (status/data.groups)
                    # for the ruler's recording/alerting groups
                    from ..rules.r2 import RuleStore, listing_dict

                    out = listing_dict(RuleStore(c.kv))
                    out["status"] = "success"
                    out["data"] = (
                        c.ruler.rules_dict() if c.ruler is not None
                        else {"groups": []}
                    )
                    self._json(out)
                elif url.path == "/api/v1/alerts":
                    self._json(
                        {
                            "status": "success",
                            "data": (
                                c.ruler.alerts_dict() if c.ruler is not None
                                else {"alerts": []}
                            ),
                        }
                    )
                elif (m := re.match(r"^/api/v1/rules/([^/]+)$", url.path)) is not None:
                    from ..rules.r2 import RuleStore, ruleset_to_dict

                    rs = RuleStore(c.kv).get(m.group(1))
                    if rs is None:
                        self._json({"error": "not found"}, 404)
                    else:
                        self._json(ruleset_to_dict(rs))
                elif url.path == "/api/v1/slo":
                    # live SLO status: per-objective budget remaining +
                    # burn rates joined to the firing burn alerts
                    self._json(
                        {
                            "status": "success",
                            "data": (
                                c.slo.status_dict() if c.slo is not None
                                else {"objectives": []}
                            ),
                        }
                    )
                elif url.path == "/debug/slo":
                    # status + the spec + the generated rule plane: the
                    # operator's alert → objective → rules walk
                    self._json(
                        c.slo.debug_dict() if c.slo is not None
                        else {"objectives": [], "spec": None}
                    )
                elif url.path == "/debug/traces":
                    limit = int(q.get("limit", ["256"])[0])
                    self._json({"spans": TRACER.dump(limit=limit)})
                elif url.path == "/debug/slow_queries":
                    from ..query.stats import RING

                    limit = int(q.get("limit", ["64"])[0])
                    self._json({"queries": RING.dump(limit=limit)})
                elif url.path == "/debug/active_queries":
                    # what is running RIGHT NOW: trace id, namespace,
                    # elapsed, current stage — joined by traceId to
                    # /debug/slow_queries and /debug/traces
                    from ..query.stats import ACTIVE

                    self._json(ACTIVE.dump())
                elif url.path == "/debug/tenants":
                    # who is spending what: per-tenant rolling-window +
                    # cumulative ledger columns (query/tenants.py), the
                    # live sibling of the stored m3tpu_tenant_* series
                    from ..query.tenants import LEDGER

                    self._json(LEDGER.dump())
                elif url.path == "/debug/exemplars":
                    # trace-ID exemplars per histogram bucket: join a slow
                    # bucket to its stitched trace (/debug/traces) and its
                    # /debug/slow_queries record by traceId. (Exemplars
                    # live here, not in the 0.0.4 text exposition, which
                    # has no grammar for them.)
                    from ..utils.instrument import DEFAULT as METRICS

                    out = {}
                    for name, fam in METRICS.collect().items():
                        rows = [
                            {"labels": ch["labels"],
                             "exemplars": ch["exemplars"]}
                            for ch in fam["children"]
                            if ch.get("exemplars")
                        ]
                        if rows:
                            out[name] = rows
                    self._json({"exemplars": out})
                elif url.path == "/debug/pprof/profile":
                    # this process's wall-clock folded-stack profile
                    # (m3_tpu/profiling/): flamegraph-ready folded text
                    # by default, the structured table with format=json
                    from ..profiling import folded_text, process_profile

                    prof = process_profile(
                        seconds=float(q.get("seconds", ["30"])[0])
                    )
                    if q.get("format", ["text"])[0] == "json":
                        self._json(prof)
                    else:
                        self._send(
                            200,
                            folded_text(prof["folded"]).encode(),
                            ctype="text/plain",
                        )
                elif url.path == "/debug/pprof/fleet":
                    # whole-fleet profile: own sampler + every peer's
                    # `profile` op over the placement, merged by stack
                    # with per-instance counts
                    from ..profiling import folded_text

                    prof = c.fleet_profile(
                        seconds=float(q.get("seconds", ["30"])[0])
                    )
                    if q.get("format", ["json"])[0] == "text":
                        self._send(
                            200,
                            folded_text(prof["folded"]).encode(),
                            ctype="text/plain",
                        )
                    else:
                        self._json(prof)
                elif url.path == "/debug/dump":
                    self._send(
                        200, self._debug_dump(), ctype="application/zip"
                    )
                elif url.path in ("/api/v1/graphite/render", "/render"):
                    self._json(c.graphite_render(q))
                elif url.path in ("/api/v1/graphite/metrics/find", "/metrics/find"):
                    self._json(c.graphite_find(q.get("query", ["*"])[0]))
                else:
                    self._json({"error": "not found"}, 404)
        except Exception as exc:  # surface handler errors as 4xx/5xx
            self._handler_error(exc)

    def _handler_error(self, exc: Exception) -> None:
        """Typed error mapping shared by GET/POST: a scheduler shed is
        503 (retry later, with errorType=shed + Retry-After), a cost
        limit is 422 (your query is too expensive), anything else 400."""
        from ..query.cost import QueryLimitError
        from ..query.scheduler import QueryShedError

        if isinstance(exc, QueryShedError):
            body = json.dumps(
                {
                    "status": "error",
                    "errorType": "shed",
                    "reason": exc.reason,
                    "error": str(exc),
                }
            ).encode()
            self.send_response(503)
            self.send_header("Retry-After", "1")
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        code = 422 if isinstance(exc, QueryLimitError) else 400
        self._json({"status": "error", "error": str(exc)}, code)

    def do_POST(self) -> None:
        from ..utils.trace import TRACER

        c = self.coordinator
        url = urlparse(self.path)
        try:
            from ..query.tenants import tenant_context

            q = parse_qs(url.query)
            tenant = self._tenant(q)
            span = TRACER.span("http.post", path=url.path)
            span.set_tag("tenant", tenant)
            with tenant_context(tenant), self._deadline_scope(q), span:
                if url.path in (
                    "/api/v1/graphite/render",
                    "/render",
                    "/api/v1/graphite/metrics/find",
                    "/metrics/find",
                ):
                    # Grafana's graphite datasource POSTs form-encoded bodies
                    form = parse_qs(self._body().decode())
                    form.update(parse_qs(url.query))
                    # header/query-param identity wins; a tenant supplied
                    # only in the form body (the Grafana POST shape) must
                    # still attribute — nested context, restored on exit
                    from ..query.tenants import DEFAULT_TENANT, normalize

                    form_tenant = form.get("tenant", [None])[0]
                    inner = (
                        tenant_context(normalize(form_tenant))
                        if tenant == DEFAULT_TENANT and form_tenant
                        else tenant_context(None)
                    )
                    with inner:
                        if url.path.endswith("find"):
                            self._json(
                                c.graphite_find(form.get("query", ["*"])[0])
                            )
                        else:
                            self._json(c.graphite_render(form))
                elif url.path == "/api/v1/prom/remote/write":
                    raw = decompress(self._body())
                    req = prompb.WriteRequest()
                    req.ParseFromString(raw)
                    n = c.write_prom(req)
                    self._send(200, b"")
                elif url.path == "/api/v1/prom/remote/read":
                    raw = decompress(self._body())
                    req = prompb.ReadRequest()
                    req.ParseFromString(raw)
                    resp = c.read_prom(req)
                    self._send(
                        200,
                        compress(resp.SerializeToString()),
                        ctype="application/x-protobuf",
                    )
                elif url.path == "/api/v1/influxdb/write":
                    q = parse_qs(url.query)
                    n = c.write_influx(
                        self._body().decode(),
                        precision=q.get("precision", ["ns"])[0],
                    )
                    self._send(204, b"")
                elif url.path == "/api/v1/json/write":
                    body = json.loads(self._body())
                    tags = make_tags(body["tags"])
                    c.db.write_tagged(
                        c.namespace, tags, int(body["timestamp"] * NANOS), float(body["value"])
                    )
                    from ..query.tenants import charge_writes

                    charge_writes(1)
                    self._json({"ok": True})
                elif url.path == "/api/v1/services/m3db/database/create":
                    body = json.loads(self._body())
                    name = body["namespaceName"]
                    retention = int(
                        _parse_step(body.get("retentionTime", "48h")) * NANOS
                    )
                    block_size = int(
                        _parse_step(body.get("blockSize", "2h")) * NANOS
                    )
                    # dynamic registry (namespace/dynamic.go): every dbnode
                    # watching the control plane creates the namespace live
                    from ..cluster.namespaces import NamespaceRegistry

                    from ..cluster.namespaces import NamespaceExistsError

                    try:
                        # conflict detection lives INSIDE add()'s CAS loop
                        # (a pre-check here would race concurrent creates)
                        NamespaceRegistry(c.kv).add(name, retention, block_size)
                    except NamespaceExistsError as exc:
                        # running nodes never re-shape a live namespace —
                        # accepting different options would diverge new/
                        # restarted replicas from live ones
                        self._json({"error": str(exc)}, 409)
                        return
                    if hasattr(c.db, "create_namespace") and name not in c.db.namespaces:
                        c.db.create_namespace(
                            name,
                            NamespaceOptions(
                                retention_nanos=retention,
                                block_size_nanos=block_size,
                            ),
                        )
                    self._json({"namespace": name}, 201)
                elif (m := re.match(r"^/api/v1/rules/([^/]+)$", url.path)) is not None:
                    from ..rules.r2 import RuleStore, ruleset_from_dict

                    rs = ruleset_from_dict(json.loads(self._body()))
                    RuleStore(c.kv).set(m.group(1), rs)
                    self._json({"namespace": m.group(1), "version": rs.version}, 200)
                elif url.path == "/api/v1/topic":
                    body = json.loads(self._body())
                    c.topic_svc.add(
                        Topic(
                            body["name"],
                            body.get("numberOfShards", 64),
                            [
                                ConsumerService(s["serviceName"], s.get("consumptionType", "shared"))
                                for s in body.get("consumerServices", [])
                            ],
                        )
                    )
                    self._json({"ok": True}, 201)
                else:
                    self._json({"error": "not found"}, 404)
        except Exception as exc:
            self._handler_error(exc)


def _prom_range(q: dict) -> tuple[int, int]:
    """start/end query params (epoch seconds) → nanos, unbounded defaults."""
    start = q.get("start", [None])[0]
    end = q.get("end", [None])[0]
    s = int(float(start) * NANOS) if start is not None else 0
    e = int(float(end) * NANOS) if end is not None else 2**62
    return s, e


def _graphite_time(s: str, now_s: float) -> float:
    """Graphite time spec: epoch seconds, 'now', or relative '-1h'/'-30min'
    (render.go / graphite-web from/until parsing)."""
    s = str(s).strip()
    if s in ("now", ""):
        return now_s
    if s.startswith("-") or s.startswith("+"):
        from ..graphite.functions import parse_interval

        return now_s + parse_interval(s.lstrip("+")) / NANOS
    return float(s)


def _parse_step(s: str) -> float:
    m = re.match(r"^(\d+(?:\.\d+)?)([smhd]?)$", s)
    if not m:
        raise ValueError(f"bad duration {s!r}")
    mult = {"": 1, "s": 1, "m": 60, "h": 3600, "d": 86400}[m.group(2)]
    return float(m.group(1)) * mult


# --- service binary (cmd/services/m3coordinator/main) ---

from dataclasses import dataclass as _dataclass, field as _dc_field


@_dataclass
class LimitsConfig:
    max_series: int = 0
    max_datapoints: int = 0


@_dataclass
class CoordinatorConfig:
    """YAML schema for the coordinator binary (utils/config.py loader)."""

    host: str = "127.0.0.1"
    port: int = 0
    namespace: str = "default"
    base_dir: str = ""
    num_shards: int = 4
    limits: LimitsConfig = _dc_field(default_factory=LimitsConfig)
    # path to a per-tenant limits file (query/tenants.load_tenant_limits
    # format): enables the tenant middle scope of the cost chain
    tenant_limits: str = ""


def main(argv=None) -> int:
    """Runnable coordinator process:

        python -m m3_tpu.services.coordinator --port 7201 --base-dir /data

    or with a YAML config (utils/config.py schema = CoordinatorConfig):

        python -m m3_tpu.services.coordinator --config coordinator.yml

    Prints ``LISTENING <host> <port>`` once serving.
    """
    import argparse
    import signal

    from ..query.cost import QueryLimits
    from ..utils.config import load_config

    p = argparse.ArgumentParser(prog="m3tpu-coordinator")
    p.add_argument("--config", default="")
    p.add_argument("--host", default=None)
    p.add_argument("--port", type=int, default=None)
    p.add_argument("--base-dir", default=None)
    p.add_argument("--namespace", default=None)
    p.add_argument(
        "--tenant-limits",
        default=None,
        help="path to a per-tenant limits YAML/JSON file "
        "(query/tenants.load_tenant_limits format): adds the per-tenant "
        "middle scope to the cost-enforcer chain so one tenant's "
        "runaway scan 422s without starving the fleet",
    )
    p.add_argument(
        "--kv-endpoint",
        default="",
        help="host:port of the control-plane KV server: admin APIs "
        "(placement/topic/rules) operate on the shared control plane",
    )
    p.add_argument(
        "--cluster",
        action="store_true",
        help="route the data plane through the placement to dbnode "
        "processes (requires --kv-endpoint) instead of embedding storage",
    )
    p.add_argument(
        "--failure-detector",
        action="store_true",
        help="run the liveness→auto-replace loop in this coordinator "
        "(requires --kv-endpoint); spares via --spare",
    )
    p.add_argument("--spare", action="append", default=[])
    p.add_argument("--heartbeat-timeout", type=float, default=10.0)
    p.add_argument(
        "--msg-listen",
        action="store_true",
        help="serve an m3msg consumer endpoint for aggregated-metric "
        "ingest (prints MSG_LISTENING <host> <port>)",
    )
    p.add_argument(
        "--selfmon-interval",
        type=float,
        default=0.0,
        help="self-scrape interval in seconds (0 disables): this "
        "coordinator's registry — plus every placement dbnode in "
        "--cluster mode and every --selfmon-peer — is stored as series "
        "under the reserved _m3tpu namespace and queryable via "
        "/api/v1/query*?namespace=_m3tpu",
    )
    p.add_argument(
        "--selfmon-peer",
        action="append",
        default=[],
        help="host:port of an extra RPC-scrapable process (dbnode port, "
        "aggregator --debug-port) to pull into the self-scrape",
    )
    p.add_argument(
        "--sched-max-inflight",
        type=int,
        default=0,
        help="cost-aware query admission (query/scheduler.py): at most "
        "this many PromQL queries evaluate concurrently; excess queries "
        "queue by shed-priority (tenant pressure + estimated cost − age) "
        "and the worst are shed with typed 503s "
        "(m3tpu_query_shed_total{tenant,reason}). 0 disables admission",
    )
    p.add_argument(
        "--sched-max-queue",
        type=int,
        default=64,
        help="admission queue capacity (with --sched-max-inflight): past "
        "it the worst-priority entry is shed with reason=queue_full",
    )
    p.add_argument(
        "--sched-max-wait",
        type=float,
        default=5.0,
        help="max seconds a query may wait queued before a "
        "reason=deadline shed (with --sched-max-inflight)",
    )
    p.add_argument("--instance-id", default="coordinator0")
    p.add_argument(
        "--profile-hz",
        type=float,
        default=None,
        help="wall-clock stack-sampler rate (m3_tpu/profiling/): serves "
        "/debug/pprof/profile and the whole-fleet /debug/pprof/fleet "
        "merge; default M3_TPU_PROFILE_HZ (19), 0 disables",
    )
    p.add_argument(
        "--ruler-rules",
        default="",
        help="path to a YAML/JSON rule file (recording + alerting "
        "groups): starts the ruler, mirrors the ruleset into the KV "
        "control plane when one is configured, and serves "
        "/api/v1/rules + /api/v1/alerts",
    )
    p.add_argument(
        "--ruler-webhook",
        action="append",
        default=[],
        help="alert webhook receiver URL (repeatable); firing/resolved "
        "transitions POST the Alertmanager webhook payload with "
        "retries under the resilience plane's budget",
    )
    p.add_argument(
        "--no-default-rules",
        action="store_true",
        help="skip the built-in default rule groups (ruler/defaults.py: "
        "the storage durability burn-rate group over "
        "m3tpu_storage_corruption_total); a rules file reusing a default "
        "group's name also overrides it without this flag",
    )
    p.add_argument(
        "--slo-config",
        default="",
        help="path to a YAML/JSON SLO spec (m3_tpu/slo/spec.py schema): "
        "compiles the objectives into recording + multi-window burn-rate "
        "alerting rules over _m3tpu, runs freshness/durability probes, "
        "and serves /api/v1/slo + /debug/slo; requires "
        "--selfmon-interval, starts the ruler if --ruler-rules is absent",
    )
    args = p.parse_args(argv)

    from .. import device

    device.configure_compile_cache()
    device.install_compile_counters()
    cfg = load_config(CoordinatorConfig, args.config) if args.config else CoordinatorConfig()
    host = args.host if args.host is not None else cfg.host
    port = args.port if args.port is not None else cfg.port
    base_dir = args.base_dir if args.base_dir is not None else (cfg.base_dir or None)
    namespace = args.namespace if args.namespace is not None else cfg.namespace

    kv = None
    if args.kv_endpoint:
        from ..cluster.kv_service import RemoteKVStore

        kv = RemoteKVStore.connect(args.kv_endpoint)

    db = None
    if args.cluster:
        if kv is None:
            p.error("--cluster requires --kv-endpoint")
        from ..client.session_db import SessionDatabase

        db = SessionDatabase(kv, namespaces=(namespace,))
    elif base_dir:
        db = Database(base_dir, num_shards=cfg.num_shards)
        db.create_namespace(namespace, NamespaceOptions())
        db.bootstrap()
    limits = None
    if cfg.limits.max_series or cfg.limits.max_datapoints:
        limits = QueryLimits(
            max_series=cfg.limits.max_series,
            max_datapoints=cfg.limits.max_datapoints,
        )
    tenant_limits = None
    tenant_limits_path = (
        args.tenant_limits if args.tenant_limits is not None
        else cfg.tenant_limits
    )
    if tenant_limits_path:
        from ..query.tenants import load_tenant_limits

        tenant_limits = load_tenant_limits(tenant_limits_path)
    scheduler = None
    if args.sched_max_inflight > 0:
        from ..query.scheduler import QueryScheduler

        scheduler = QueryScheduler(
            max_inflight=args.sched_max_inflight,
            max_queue=args.sched_max_queue,
            max_queue_wait=args.sched_max_wait,
        )
    coord = Coordinator(
        db=db, namespace=namespace, query_limits=limits, kv=kv,
        tenant_limits=tenant_limits, scheduler=scheduler,
    )
    coord.instance_id = args.instance_id
    server, bound = serve(coord, port, host=host)

    # ONE peer source shared by the self-scrape pull and the fleet
    # profile merge: static --selfmon-peer endpoints plus (in --cluster
    # mode) every placement dbnode, re-evaluated per use so topology
    # changes are picked up live
    static_peers = {}
    if args.selfmon_peer:
        from ..net.client import RemoteNode

        for ep in args.selfmon_peer:
            static_peers[ep] = RemoteNode.connect(ep)

    def fleet_peers() -> dict:
        peers = dict(static_peers)
        if args.cluster and hasattr(coord.db, "remote_nodes"):
            peers.update(coord.db.remote_nodes())
        return peers

    coord.peer_source = fleet_peers
    if args.selfmon_interval > 0:
        coord.start_selfmon(
            args.selfmon_interval, peers=fleet_peers,
            instance=args.instance_id,
        )

    from ..profiling import start_sampler

    profiler = start_sampler(
        hz=args.profile_hz, instance=args.instance_id, db=coord.db
    )

    if args.ruler_rules:
        coord.start_ruler(
            rules_path=args.ruler_rules,
            webhooks=list(args.ruler_webhook),
            instance=args.instance_id,
            default_rules=not args.no_default_rules,
        )

    if args.slo_config:
        if args.selfmon_interval <= 0:
            p.error(
                "--slo-config requires --selfmon-interval: the compiled "
                "SLI rules evaluate over the fleet's own stored telemetry "
                "in _m3tpu, which only the self-scrape populates"
            )
        coord.start_slo(
            args.slo_config,
            webhooks=list(args.ruler_webhook),
            instance=args.instance_id,
        )

    detector = None
    if args.failure_detector:
        if kv is None:
            p.error("--failure-detector requires --kv-endpoint")
        from ..cluster.failure import FailureDetector
        from ..cluster.services import Services

        detector = FailureDetector(
            Services(kv, heartbeat_timeout=args.heartbeat_timeout),
            coord.placement_svc,
            grace=args.heartbeat_timeout / 2.0,
            spares=list(args.spare),
        )
        detector.start(interval=max(args.heartbeat_timeout / 4.0, 0.1))

    def shutdown(signum, frame):
        raise SystemExit(0)

    msg_server = None
    if args.msg_listen:
        msg_server = coord.serve_msg_ingest(host=host)

    signal.signal(signal.SIGTERM, shutdown)
    signal.signal(signal.SIGINT, shutdown)
    print(f"LISTENING {host} {bound}", flush=True)
    if msg_server is not None:
        print(f"MSG_LISTENING {host} {msg_server.port}", flush=True)
    try:
        # serve() already runs the accept loop on a daemon thread; a second
        # serve_forever() here would race it on the same socket. Park until
        # a signal raises SystemExit.
        threading.Event().wait()
    finally:
        if detector is not None:
            detector.stop()
        if msg_server is not None:
            msg_server.stop()
        if profiler is not None:
            profiler.stop()
        if coord.slo is not None:
            coord.slo.stop()
        if coord.selfmon is not None:
            coord.selfmon.stop()
        if coord.ruler is not None:
            coord.ruler.stop()
        for node in static_peers.values():
            try:
                node.close()
            except Exception:
                # m3lint: disable=M3L007 -- best-effort socket teardown on shutdown; the process is exiting
                pass
        server.shutdown()
        coord.db.close()
        if kv is not None:
            kv.close()
    return 0



def serve(
    coordinator: Coordinator, port: int = 0, host: str = "127.0.0.1"
) -> tuple[ThreadingHTTPServer, int]:
    """Start the HTTP server on a background thread; returns (server, port)."""
    handler = type("BoundHandler", (_Handler,), {"coordinator": coordinator})
    srv = ThreadingHTTPServer((host, port), handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv, srv.server_address[1]
if __name__ == "__main__":
    import sys

    sys.exit(main())
