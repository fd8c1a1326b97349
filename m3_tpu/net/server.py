"""Node RPC server: the network data plane of a storage node.

Reference: /root/reference/src/dbnode/network/server/tchannelthrift/node/
service.go — write (:449), writeTagged, fetch, fetchTagged (:626), query,
aggregate, plus the peer-streaming endpoints the bootstrapper/repair use.
Here: a threaded TCP server speaking the net.wire framing; each connection
is a sequential request/response loop (clients pool connections for
concurrency); per-request errors return {"ok": False} without killing the
connection.
"""

from __future__ import annotations

import os
import socket
import socketserver
import threading
import time
from typing import NamedTuple

from ..utils.instrument import DEFAULT as METRICS
from ..utils.trace import TRACER
from ..utils.xtime import Unit
from . import wire
from .faults import plan_from_env
from .resilience import UnavailableError


class OpHandles(NamedTuple):
    """One op's metric handles. ``label`` is the op, or ``_overflow`` past
    the cardinality cap: what stages of the op are labelled with."""

    requests: object
    errors: object
    inflight: object
    duration: object
    label: str
    request_bytes: object
    response_bytes: object


class RpcMiddleware:
    """Observability + admission middleware over any ``handle(req) ->
    result`` service (x/instrument's tally-scope-per-server role +
    opentracing adoption + the server half of the resilience plane):

    - per-op request/error counters, latency histograms, and an in-flight
      gauge, all labeled {component, op} so one /metrics scrape separates
      dbnode data-plane ops from control-plane KV traffic;
    - the request's root stage, ``rpc.server.<op>`` (utils/trace.py): its
      wall and CPU seconds in the stage table, every stage under it
      labelled with the op, and trace adoption — an incoming request
      carrying a wire trace context gets a server-side span that JOINS the
      client's trace (the other half of net/client's injection), so a
      query fanning out coordinator → dbnode replicas renders as one
      stitched tree in /debug/traces;
    - deadline enforcement: a request whose propagated ``_deadline``
      already expired is refused with a typed retryable UnavailableError
      BEFORE dispatch — the caller stopped waiting, so doing the work only
      adds load exactly when the server is slow ("Tail at Scale");
    - load shedding: past ``max_inflight`` concurrent requests the server
      fast-fails new work with the same typed UnavailableError instead of
      queueing into collapse ('metrics' is exempt so overload stays
      observable);
    - a universal ``metrics`` op: services without their own op_metrics
      (raft KV, loadgen agents) still answer a Prometheus scrape, so every
      node in the fleet is scrapable over its existing RPC port.
    """

    def __init__(self, service, component: str = "rpc",
                 max_inflight: int | None = None) -> None:
        self.service = service
        self.component = component
        if max_inflight is None:
            try:
                max_inflight = int(os.environ.get("M3_TPU_RPC_MAX_INFLIGHT", "0"))
            except ValueError:
                max_inflight = 0
        self.max_inflight = max(0, max_inflight)  # 0 = uncapped
        self._inflight_total = 0
        self._load_lock = threading.Lock()
        labels = {"component": component}
        self._deadline_exceeded = METRICS.counter(
            "rpc_deadline_exceeded_total",
            "requests refused because their propagated deadline expired",
            labels=labels,
        )
        self._shed = METRICS.counter(
            "rpc_shed_total",
            "requests fast-failed past the in-flight cap",
            labels=labels,
        )
        # per-op metric handles, resolved once: registry child resolution
        # costs registry-lock round trips — the op set is small and fixed,
        # so every request after the first is one dict lookup
        self._per_op: dict = {}
        self._per_op_lock = threading.Lock()

    # op-label cardinality cap: op names come off the WIRE, and unknown ops
    # are only rejected at dispatch — without a cap, a fuzzer sending unique
    # bogus op strings would grow the process registry (and /metrics output)
    # without bound. Real services have far fewer ops than this.
    _MAX_OPS = 64

    def handles_for(self, op: str) -> OpHandles:
        handles = self._per_op.get(op)
        if handles is not None:
            return handles
        with self._per_op_lock:
            handles = self._per_op.get(op)
            if handles is not None:
                return handles
            if len(self._per_op) >= self._MAX_OPS:
                op = "_overflow"
                handles = self._per_op.get(op)
                if handles is not None:
                    return handles
            labels = {"component": self.component, "op": op}
            handles = self._per_op[op] = OpHandles(
                METRICS.counter("rpc_requests_total", labels=labels),
                METRICS.counter("rpc_errors_total", labels=labels),
                METRICS.gauge("rpc_inflight", labels=labels),
                METRICS.histogram(
                    "rpc_request_duration_seconds", labels=labels
                ),
                op,
                METRICS.counter(
                    "rpc_request_bytes_total",
                    "request frame bytes received, length prefix included",
                    labels=labels,
                ),
                METRICS.counter(
                    "rpc_response_bytes_total",
                    "reply frame bytes sent, length prefix included",
                    labels=labels,
                ),
            )
            return handles

    def handle(self, req: dict):
        op = str(req.get("op"))
        ctx = wire.extract_trace(req)
        deadline = wire.extract_deadline(req)
        tenant = wire.extract_tenant(req)
        if tenant is not None:
            # normalize BEFORE any accounting: tenant ids come off the
            # wire, and junk/flood ids must collapse into the capped
            # overflow tenant, not mint ledger accounts or label values
            from ..query.tenants import normalize

            tenant = normalize(tenant)
        if op == "metrics" and not hasattr(self.service, "op_metrics"):
            # fmt="json" serves the structured Registry.collect() snapshot
            # (what the self-scrape collector pulls); default stays the
            # Prometheus text exposition for scrapers
            if req.get("fmt") == "json":
                return METRICS.collect()
            return METRICS.expose()
        requests, errors, inflight, hist, op_label = self.handles_for(op)[:5]
        requests.inc()
        # admission: shed past the in-flight cap before spending anything
        # else on the request ('metrics' stays admitted so the scrape that
        # would show the overload is never itself shed). The shared counter
        # (and its lock) is only maintained when a cap is configured — the
        # per-op gauges already cover observability in the default config.
        tracked = bool(self.max_inflight) and op != "metrics"
        if tracked:
            with self._load_lock:
                shed = self._inflight_total >= self.max_inflight
                if not shed:
                    self._inflight_total += 1
            if shed:
                self._shed.inc()
                errors.inc()
                if tenant is not None:
                    # the shed is attributed: per-tenant shed counters are
                    # what admission-control rules (tenant:shed:rate5m)
                    # key off
                    from ..query.tenants import LEDGER

                    LEDGER.charge(tenant, sheds=1)
                raise UnavailableError(
                    f"overloaded: {self.max_inflight} requests in flight, "
                    f"shedding {op!r}"
                )
        trace_hex = None
        span = TRACER.request(
            op_label, ctx, spans=op not in wire.UNTRACED_OPS,
            component=self.component,
        )
        inflight.add(1)
        t0 = time.perf_counter()
        try:
            # m3lint: disable=M3L004 -- the propagated _deadline is wall-clock by protocol; peers are assumed clock-synced
            if deadline is not None and time.time() >= deadline:
                self._deadline_exceeded.inc()
                raise UnavailableError(
                    # m3lint: disable=M3L004 -- lateness report against the wall-clock wire deadline
                    f"deadline expired {time.time() - deadline:.3f}s before "
                    f"dispatch of {op!r}"
                )
            with span:
                if tenant is None:
                    return self.service.handle(req)
                # re-establish the caller's tenant context around dispatch
                # (a thread-local cannot cross the socket — the same seam
                # shape as the selfmon wire marker): storage/decode work
                # under this handler, including the KernelProfiler's
                # sampled device-seconds, is attributed to the tenant
                from ..query.tenants import LEDGER, tenant_context

                LEDGER.charge(tenant, rpcs=1)
                span.set_tag("tenant", tenant)
                with tenant_context(tenant):
                    return self.service.handle(req)
        except Exception:
            errors.inc()
            raise
        finally:
            if span.span is not None:
                # exemplar for the latency histogram: a slow bucket links
                # to the stitched trace this request belongs to
                trace_hex = f"{span.span.trace_id:016x}"
            hist.observe(time.perf_counter() - t0, trace_id=trace_hex)
            inflight.add(-1)
            if tracked:
                with self._load_lock:
                    self._inflight_total -= 1


class DebugService:
    """Minimal RPC surface for processes with no data-plane service of
    their own (the aggregator's rawtcp ingest is one-way): behind the
    middleware it answers `health` and the universal `metrics` scrape, so
    every daemon in the fleet exposes the same observability ops."""

    def __init__(self, info: dict | None = None) -> None:
        self.info = info or {}

    def handle(self, req: dict):
        op = req.get("op")
        if op == "health":
            return {"ok": True, **self.info}
        if op == "traces":
            return TRACER.dump(limit=req.get("limit") or 256)
        if op == "profile":
            # wall-clock folded-stack profile of this process (the
            # continuous profiler's wire face; m3_tpu/profiling/) — the
            # aggregator's --debug-port surface answers it too, so the
            # coordinator's fleet merge covers every role
            from ..profiling import process_profile

            return process_profile(seconds=req.get("seconds"))
        raise ValueError(f"unknown op {op!r}")


class NodeService:
    """Dispatch table over a storage Database + shard assignment state."""

    def __init__(self, db, node_id: str = "", assigned_shards=None) -> None:
        self.db = db
        self.node_id = node_id
        self.assigned_shards: set[int] = set(assigned_shards or ())

    def handle(self, req: dict):
        op = req.get("op")
        fn = getattr(self, f"op_{op}", None)
        if fn is None:
            raise ValueError(f"unknown op {op!r}")
        return fn(req)

    # -- rpc.thrift surface --

    def op_health(self, req):
        return {"id": self.node_id, "bootstrapped": self.db.bootstrapped}

    # write ops honor the wire `selfmon` marker: the coordinator's
    # self-scrape collector writes the reserved `_m3tpu` namespace through
    # the normal cluster write plane, and its thread-local writer context
    # cannot cross the socket — the marker re-establishes it around
    # dispatch (selfmon/guard.py invariant 1); unmarked reserved-namespace
    # writes still raise inside storage.Database

    # write ops also attribute ingested datapoint counts to the caller's
    # wire-carried tenant context (query/tenants.charge_writes — a no-op
    # for unattributed intra-fleet traffic)

    # stages: write.route is the frame's lists turned into what the
    # database takes; write_batch's further stages are the database's
    # own (storage/database.py), the tagged ops' is one, write.tagged,
    # around a database call that is a per-entry loop

    def op_write(self, req):
        from ..query.tenants import charge_writes
        from ..selfmon.guard import wire_writer

        with wire_writer(req.get("selfmon")):
            self.db.write(
                req["ns"], req["sid"], req["t"], req["v"], Unit(req.get("unit", 1))
            )
        charge_writes(1)
        return True

    def op_write_batch(self, req):
        from ..query.tenants import charge_writes
        from ..selfmon.guard import wire_writer

        with TRACER.stage("write.route"):
            entries = [tuple(e) for e in req["entries"]]
        with wire_writer(req.get("selfmon")):
            self.db.write_batch(req["ns"], entries)
        charge_writes(len(entries))
        return True

    def op_write_tagged(self, req):
        from ..query.tenants import charge_writes
        from ..selfmon.guard import wire_writer

        with TRACER.stage("write.route"):
            tags = tuple((n, v) for n, v in req["tags"])
        with wire_writer(req.get("selfmon")), TRACER.stage("write.tagged"):
            result = self.db.write_tagged(
                req["ns"], tags, req["t"], req["v"], Unit(req.get("unit", 1))
            )
        charge_writes(1)
        return result

    def op_write_tagged_batch(self, req):
        """One RPC per host-queue flush (host_queue.go role); per-entry
        errors ride back so the session counts quorum per datapoint."""
        from ..query.tenants import charge_writes
        from ..selfmon.guard import wire_writer

        with TRACER.stage("write.route"):
            entries = [
                (tuple((n, v) for n, v in tags), t, val, unit)
                for tags, t, val, unit in req["entries"]
            ]
        with wire_writer(req.get("selfmon")), TRACER.stage("write.tagged"):
            errs = self.db.write_tagged_batch(req["ns"], entries)
        charge_writes(sum(1 for e in errs if not e) if errs else len(entries))
        return errs

    def op_fetch(self, req):
        dps = self.db.read(req["ns"], req["sid"], req["start"], req["end"])
        return wire.dps_to_wire(dps)

    def op_fetch_blocks(self, req):
        # compressed read: raw encoded segments (rpc.thrift fetchBlocksRaw)
        return self.db.fetch_blocks(req["ns"], req["sid"], req["start"], req["end"])

    def op_fetch_tagged(self, req):
        q = wire.query_from_wire(req["query"])
        res = self.db.fetch_tagged(
            req["ns"], q, req["start"], req["end"], limit=req.get("limit")
        )
        return wire.series_to_wire(res)

    def op_query_ids(self, req):
        # force_host bypasses the device index tier (read-only knob):
        # the doc-id parity half of tools/check_index.py diffs a normal
        # resolve against a host-forced one on the same node
        q = wire.query_from_wire(req["query"])
        result = self.db.query_ids(
            req["ns"], q, req["start"], req["end"], limit=req.get("limit"),
            force_host=bool(req.get("force_host")),
        )
        return {
            "docs": [[d.id, [[k, v] for k, v in d.fields]] for d in result.docs],
            "exhaustive": result.exhaustive,
        }

    def op_aggregate_query(self, req):
        q = wire.query_from_wire(req["query"])
        ff = req.get("field_filter")
        agg = self.db.aggregate_query(
            req["ns"], q, req["start"], req["end"],
            field_filter=[bytes(f) for f in ff] if ff else None,
        )
        return [[k, sorted(vs)] for k, vs in agg.items()]

    def op_stream_shard(self, req):
        return wire.series_to_wire(
            self.db.stream_shard(
                req["ns"], req["shard"],
                exclude_blocks=req.get("exclude") or (),
            )
        )

    # -- shard-handoff migration source (warm residency streaming) --

    def op_migrate_manifest(self, req):
        """Streamable sealed-fileset inventory for one shard: per complete
        fileset, byte sizes of every file role (compressed data pages,
        packed side planes, index/bloom/summaries, digest) a receiver
        fetches before cutover."""
        from ..storage.fs import migration_manifest

        return migration_manifest(self.db.base, req["ns"], req["shard"])

    def op_migrate_fetch(self, req):
        """One resumable byte-range read of one fileset file role
        ({"data": bytes, "eof": bool}). Immutable source files make
        re-reads duplicate-safe; a fileset retention raced away surfaces
        as the error the receiver's fallback handles."""
        from ..storage.fs import FilesetID, read_fileset_chunk

        fid = FilesetID(
            req["ns"], req["shard"], req["block_start"], req["volume"]
        )
        data, eof = read_fileset_chunk(
            self.db.base, fid, req["suffix"], req["offset"], req["max_bytes"]
        )
        return {"data": data, "eof": eof}

    # -- repair endpoints (storage/repair.go metadata + block fetch) --

    def op_block_metadata(self, req):
        from ..storage.repair import block_metadata

        return block_metadata(self.db, req["ns"], req["shard"])

    def op_stream_series_blocks(self, req):
        from ..storage.repair import stream_series_blocks

        items = [(sid, bs) for sid, bs in req["items"]]
        out = stream_series_blocks(self.db, req["ns"], items, shard_id=req["shard"])
        return [[sid, bs, wire.dps_to_wire(dps)] for sid, bs, dps in out]

    def op_metrics(self, req):
        """Self-observability exposition (x/instrument): Prometheus text,
        or the structured Registry.collect() snapshot with fmt="json" (the
        form the self-scrape collector ingests)."""
        if req.get("fmt") == "json":
            return METRICS.collect()
        return METRICS.expose()

    def op_traces(self, req):
        """This process's recent finished spans (the dbnode half of a
        cross-process trace: merge with the coordinator's /debug/traces by
        traceId to see the full tree)."""
        return TRACER.dump(limit=req.get("limit") or 256)

    def op_cache_stats(self, req):
        """Decoded-block cache debug/status: hit/miss/eviction counters,
        resident bytes vs budget (m3_tpu/cache/)."""
        return self.db.cache_stats()

    def op_resident_stats(self, req):
        """HBM-resident compressed pool debug/status: admissions,
        pages/bytes/occupancy, eviction + invalidation counters, the
        upload/streamed byte counters warm-scan zero-transfer checks key
        on, and the per-shard heat split (m3_tpu/resident/)."""
        return self.db.resident_stats()

    def op_resident_clear(self, req):
        """Drop every resident-pool entry (operator/debug surface):
        lets tools/check_resident.py exercise eviction churn and the
        read-through re-admission path against a live node. Duplicate-
        safe — clearing an empty pool clears nothing."""
        return {"dropped": self.db.resident_clear()}

    def op_index_stats(self, req):
        """Device-index-tier debug/status (m3_tpu/index/device/):
        admissions/evictions/search routing counters, device bytes vs
        budget, per-namespace segment counts, postings-cache
        effectiveness. Also refreshes the device-memory split gauges so
        ``m3tpu_device_memory_bytes{kind="index"}`` is current in the
        next scrape (the profiling sampler refreshes them on its own
        slower cadence)."""
        from ..profiling import collect_device_memory

        collect_device_memory(self.db)
        return self.db.index_stats()

    def op_profile(self, req):
        """Continuous-profiling surface (m3_tpu/profiling/): this
        process's wall-clock folded-stack profile over the last
        ``seconds`` — what the coordinator's /debug/pprof/fleet merge
        pulls from every placement node."""
        from ..profiling import process_profile

        return process_profile(seconds=req.get("seconds"))

    def op_device_profile(self, req):
        """A jax.profiler capture of THIS running process, and its device
        memory: ``action`` ``start`` (with ``dir``, a directory on this
        node), ``stop`` (writes the ``.xplane.pb`` there; ``python -m
        m3_tpu.profiling.gaps <dir>`` reduces it) or ``stat``. Every
        answer carries the fullest device's ``peak_bytes_in_use`` and
        ``bytes_in_use``. While a capture runs every request is sampled,
        so ``traces`` holds the window's span trees as well."""
        from .. import profiling

        action = req.get("action", "stat")
        if action == "start":
            out = profiling.start_capture(str(req["dir"]))
        elif action == "stop":
            out = profiling.stop_capture()
        elif action == "stat":
            running = profiling.capture_dir()
            out = {"capturing": running is not None, "dir": running}
        else:
            raise ValueError(f"device_profile: unknown action {action!r}")
        return {**out, **profiling.device_stat()}

    def op_flush(self, req):
        """Operator/CI flush: seal buffered blocks before the cutoff
        (the mediator does this on its own cadence; tools/check_resident
        drives it explicitly to make seal-time admission observable)."""
        flushed = self.db.flush(req["ns"], req["flush_before"])
        return [[f.namespace, f.shard, f.block_start, f.volume] for f in flushed]

    def op_snapshot(self, req):
        """Operator/CI snapshot: capture un-flushed buffers so commit-log
        replay is bounded (the mediator snapshots on its own cadence;
        tools/check_crash.py drives it explicitly to reach the
        snapshot:pre-cleanup crash point deterministically)."""
        return {"records": self.db.snapshot(req["ns"])}

    def op_scrub(self, req):
        """Operator/CI scrub: one digest-verify pass over sealed filesets
        (the background Scrubber daemon runs the same verification on its
        own paced cadence). Corrupt/torn volumes quarantine — duplicate-
        safe: a re-run re-verifies what's left."""
        return self.db.scrub(req.get("ns"))

    def op_repair(self, req):
        """Operator/CI repair: checksum-diff the given shards against peer
        endpoints and merge only differing blocks (storage/repair.py).
        Duplicate-safe — a converged shard streams nothing on re-run."""
        from ..storage.repair import repair_database
        from .client import RemoteNode

        peers = [RemoteNode.connect(ep) for ep in req["peers"]]
        try:
            res = repair_database(
                self.db, req["ns"], peers, shard_ids=req.get("shards")
            )
        finally:
            for peer in peers:
                peer.close()
        return {
            "shards_repaired": res.shards_repaired,
            "blocks_compared": res.blocks_compared,
            "blocks_streamed": res.blocks_streamed,
            "points_merged": res.points_merged,
            "points_skipped_cold": res.points_skipped_cold,
            "peer_errors": res.peer_errors,
        }

    def op_scan_totals(self, req):
        """Raw-sample scan-and-aggregate over matched series (block
        granularity): routed to the decode-from-HBM path when every
        matched block is resident, streamed otherwise — the wire face of
        M3Storage.scan_totals. ``matchers``: [[name, op, value], ...].
        ``explain``: also record and return the per-(series, block)
        routing decisions (query/stats.py add_routing) so CI can assert
        WHICH decoder served the scan, not just the path."""
        from ..query import stats
        from ..query.m3_storage import M3Storage
        from ..query.promql import Matcher

        matchers = [
            Matcher(str(n), str(op), str(v)) for n, op, v in req["matchers"]
        ]
        storage = M3Storage(self.db, req["ns"])
        if not req.get("explain"):
            return storage.scan_totals(matchers, req["start"], req["end"])
        st = stats.start("EXPLAIN scan_totals")
        if st is not None:
            st.record_routing = True
            st.namespace = str(req["ns"])
        scan = TRACER.stage("query.eval")
        try:
            with scan:
                out = storage.scan_totals(matchers, req["start"], req["end"])
        finally:
            if st is not None:
                stats.finish(st, scan.seconds)
        if st is not None:
            out["routing"] = list(st.routing)
        return out

    def op_query_range(self, req):
        """Local PromQL evaluation over this node's database — the wire
        face of the one-dispatch fused query pipeline (query/plan.py).
        The per-namespace engine is CACHED so the plan cache warms across
        requests. ``force_staged`` runs the parity probe (device plans
        disabled for this evaluation); the response carries the full
        QueryStats record — deviceDispatches, plan hit/miss/fallback
        counts, and (with ``explain``) per-series routing reasons — so
        CI can assert a warm eligible query is exactly ONE dispatch and
        bit-identical to the staged path."""
        import numpy as np

        from ..query import plan as query_plan
        from ..query import stats

        eng = self._query_engine(req["ns"])
        st = stats.start(f"wire:{req['query']}")
        if st is not None:
            st.namespace = str(req["ns"])
            if req.get("explain"):
                st.record_routing = True
        # durationSecs is this stage's wall time: the evaluation alone, as
        # it always was; reply.build is the request's, beside it
        evaluate = TRACER.stage("query.eval")
        err = None
        try:
            with evaluate:
                if req.get("force_staged"):
                    with query_plan.force_staged():
                        r = eng.query_range(
                            req["query"], req["start"], req["end"], req["step"]
                        )
                else:
                    r = eng.query_range(
                        req["query"], req["start"], req["end"], req["step"]
                    )
            with TRACER.stage("reply.build"):
                values = np.asarray(r.values, np.float64)
                reply = {
                    "values": [list(map(float, row)) for row in values],
                    "metas": [
                        [[bytes(k), bytes(v)] for k, v in m.tags]
                        for m in r.metas
                    ],
                }
        except Exception as exc:
            err = f"{type(exc).__name__}: {exc}"
            raise
        finally:
            if st is not None:
                stats.finish(st, evaluate.seconds, error=err)
        reply["stats"] = st.to_dict() if st is not None else {}
        return reply

    def _query_engine(self, ns: str):
        """Cached per-namespace Engine over the LOCAL database (bounded
        by the namespaces the database actually serves, so wire input
        can't grow the dict)."""
        engines = getattr(self, "_query_engines", None)
        if engines is None:
            engines = self._query_engines = {}
        eng = engines.get(ns)
        if eng is None:
            if ns not in self.db.namespaces:
                raise ValueError(f"unknown namespace {ns!r}")
            from ..query.engine import Engine
            from ..query.m3_storage import M3Storage

            eng = engines[ns] = Engine(M3Storage(self.db, ns))
        return eng

    def op_owned_shards(self, req):
        return sorted(self.assigned_shards)

    def op_assign_shards(self, req):
        """AssignShardSet (database.go:386): the control plane pushes shard
        ownership; peers bootstrap is driven by the caller via stream_shard."""
        self.assigned_shards = set(req["shards"])
        return True


class RpcServer:
    """Threaded TCP front end for any service exposing handle(req)->result.

    Serves the data plane (NodeService) and the control plane (cluster KV
    service) over the same framing."""

    def __init__(
        self, service, host: str = "127.0.0.1", port: int = 0,
        component: str = "rpc", max_inflight: int | None = None,
        fault_plan=None,
    ):
        self.service = service
        # every RPC server front end gets the observability middleware:
        # per-op metrics, trace adoption, and a universal `metrics` scrape op
        svc = RpcMiddleware(service, component=component,
                            max_inflight=max_inflight)
        self.middleware = svc
        # deterministic fault-injection seam: an explicit plan, or one from
        # the M3_TPU_FAULT_PLAN env var for spawned chaos processes; None
        # (the default) costs nothing per request
        fault_plan = fault_plan if fault_plan is not None else plan_from_env()
        self.fault_plan = fault_plan
        # live connections, force-closed on stop() so blocked long-polls and
        # pooled client sockets see a reset (SIGKILL semantics) instead of
        # silently talking to a stopped server
        conns: set = set()
        conns_lock = threading.Lock()
        self._conns, self._conns_lock = conns, conns_lock
        recv_wait = METRICS.counter(
            "rpc_recv_wait_seconds_total",
            "seconds connection handlers sat between a reply sent and the "
            "next frame's first bytes",
            labels={"component": component},
        )

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                self.request.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                with conns_lock:
                    conns.add(self.request)
                served = "-"  # the op this connection last served
                try:
                    while True:
                        try:
                            # idle between a reply sent and the next
                            # frame's first bytes: outside any request, so
                            # a counter and a profiler annotation (a
                            # server starved by its clients, not one busy
                            # in Python), never a span; labelled with the
                            # op just served, which tells a write client's
                            # think time from an idle pooled connection
                            with TRACER.stage("rpc.recv_wait", op=served) as idle:
                                n = wire.recv_header(self.request)
                            recv_wait.inc(idle.seconds)
                            with TRACER.stage("wire.decode") as dec:
                                req = wire.loads(wire.recv_exact(self.request, n))
                                ops = svc.handles_for(
                                    str(req.get("op")) if isinstance(req, dict) else "?")
                                dec.op = served = ops.label
                        except (ConnectionError, OSError):
                            return
                        ops.request_bytes.inc(n + 4)
                        if fault_plan is not None:
                            action, delay = fault_plan.decide(str(req.get("op")))
                            if delay > 0.0:
                                time.sleep(delay)
                            if action == "drop":
                                # the request vanishes: close the connection
                                # without a reply — the client sees the same
                                # reset a crashed/partitioned server produces
                                return
                            if action == "error":
                                try:
                                    wire.send_frame(self.request, {
                                        "ok": False,
                                        "error": "UnavailableError: injected",
                                        "etype": "UnavailableError",
                                    })
                                    continue
                                except (ConnectionError, OSError):
                                    return
                        try:
                            result = svc.handle(req)
                            resp = {"ok": True, "result": result}
                        except Exception as exc:  # per-request isolation
                            resp = {
                                "ok": False,
                                "error": f"{type(exc).__name__}: {exc}",
                                "etype": type(exc).__name__,
                            }
                        try:
                            with TRACER.stage("wire.encode", op=ops.label):
                                frame = wire.pack_frame(wire.dumps(resp))
                                self.request.sendall(frame)
                        except (ConnectionError, OSError):
                            return
                        ops.response_bytes.inc(len(frame))
                finally:
                    with conns_lock:
                        conns.discard(self.request)

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self.host, self.port = self._server.server_address
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="m3tpu-node-server", daemon=True
        )
        self._thread.start()

    def serve_forever(self) -> None:
        self._server.serve_forever()

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        with self._conns_lock:
            for sock in list(self._conns):
                try:
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    sock.close()
                except OSError:
                    pass
            self._conns.clear()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None


class NodeServer(RpcServer):
    """TCP front end for a NodeService."""

    def __init__(self, service, host: str = "127.0.0.1", port: int = 0,
                 component: str = "dbnode", **kwargs):
        super().__init__(service, host=host, port=port, component=component,
                         **kwargs)
