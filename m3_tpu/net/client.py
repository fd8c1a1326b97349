"""RemoteNode: socket-backed node stub with a connection pool, budgeted
retries, and a per-host circuit breaker.

Reference: /root/reference/src/dbnode/client/ — host queues and connection
pools (session.go:505 Open, host_queue.go) plus x/retry (backoff + jitter +
retry budgets) and per-host connection health checking. Each RemoteNode
keeps a small pool of persistent connections; transport failures are
retried (with decorrelated-jitter backoff and a per-client retry budget)
ONLY for ops in wire.IDEMPOTENT_OPS, every call carries a propagated
deadline, and consecutive transport failures open a circuit breaker that
backs ``is_up`` — so the Session's down-replica accounting fires for remote
nodes instead of paying a timeout per fan-out. Remote errors surface as
exceptions so consistency accounting treats them like any replica failure.

RemoteNode implements the same surface as testing/cluster.Node, so a Session
works identically over in-process nodes and sockets.
"""

from __future__ import annotations

import socket
import threading
import time

from ..utils.instrument import DEFAULT as METRICS
from ..utils.trace import NOOP_SPAN, TRACER
from ..utils.xtime import Unit
from . import wire
from .resilience import (
    BreakerOpenError,
    CircuitBreaker,
    DeadlineExceededError,
    RetryPolicy,
)

# failures of the transport itself (vs typed errors from a living server):
# these count against the peer's circuit breaker. ValueError covers a
# corrupt frame — the connection is unusable either way.
TRANSPORT_ERRORS = (ConnectionError, OSError, ValueError)


class RemoteError(RuntimeError):
    def __init__(self, etype: str, message: str) -> None:
        super().__init__(message)
        self.etype = etype


class RpcClient:
    """Generic pooled request/response client over the wire framing; the
    base for RemoteNode (data plane) and RemoteKVStore (control plane)."""

    def __init__(
        self,
        host: str,
        port: int,
        pool_size: int = 4,
        timeout: float = 10.0,
        retry_policy: RetryPolicy | None = None,
        breaker: CircuitBreaker | None = None,
    ) -> None:
        self.host = host
        self.port = port
        self.timeout = timeout
        self.retry_policy = (
            retry_policy if retry_policy is not None else RetryPolicy()
        )
        self.breaker = (
            breaker if breaker is not None else CircuitBreaker(peer=f"{host}:{port}")
        )
        self._pool: list[socket.socket] = []
        self._pool_lock = threading.Lock()
        self._pool_size = pool_size

    @classmethod
    def connect(cls, endpoint: str, **kwargs):
        """Build a client from a 'host:port' endpoint string (the one
        parser for placement/discovery endpoints)."""
        host, port = endpoint.rsplit(":", 1)
        return cls(host, int(port), **kwargs)

    # -- connection pool --

    def _connect(self) -> socket.socket:
        sock = socket.create_connection((self.host, self.port), timeout=self.timeout)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return sock

    def _acquire(self) -> socket.socket:
        with self._pool_lock:
            if self._pool:
                return self._pool.pop()
        return self._connect()

    def _release(self, sock: socket.socket) -> None:
        with self._pool_lock:
            if len(self._pool) < self._pool_size:
                self._pool.append(sock)
                return
        sock.close()

    def close(self) -> None:
        with self._pool_lock:
            for sock in self._pool:
                sock.close()
            self._pool.clear()

    def _call(self, op: str, _retry: bool = True, _timeout: float | None = None, **args):
        # trace propagation: when this RPC happens inside a traced request
        # (a span is active on this thread), it gets its own client span and
        # the context rides the wire so the server joins the same trace —
        # the per-process spans stitch into one tree (Dapper propagation).
        # Untraced calls (no active span) pay nothing. Retries happen INSIDE
        # this one span (tagged retried=N) — a retry is one logical call,
        # not a second nested rpc.client span.
        if TRACER.active() and op not in wire.UNTRACED_OPS:
            span = TRACER.span(f"rpc.client.{op}", peer=f"{self.host}:{self.port}")
        else:
            span = NOOP_SPAN
        with span:
            return self._call_attempts(op, _retry, _timeout, args, span)

    def _call_attempts(self, op: str, _retry: bool, _timeout: float | None,
                       args: dict, span):
        """Attempt loop: budgeted transparent retries for IDEMPOTENT ops
        only (transport failures and typed retryable rejections); every
        attempt is gated by the peer's circuit breaker and bounded by one
        shared per-call deadline that also rides the wire."""
        budget = _timeout if _timeout is not None else self.timeout
        # ambient-deadline propagation (resilience.deadline_scope): a call
        # made under a caller-supplied deadline (coordinator HTTP timeout)
        # never budgets past what the caller will wait for — the tightened
        # budget also rides the wire as the _deadline frame, so the server
        # refuses work the client has already abandoned.
        from .resilience import remaining_time

        ambient = remaining_time()
        if ambient is not None:
            budget = min(budget, max(ambient, 0.0))
        # m3lint: disable=M3L004 -- the wire _deadline frame is wall-clock by protocol (must mean the same instant in another process)
        deadline = time.time() + budget
        retryable = _retry and op in wire.IDEMPOTENT_OPS
        attempt = 0
        prev_backoff = 0.0
        while True:
            if not self.breaker.allow():
                raise BreakerOpenError(
                    f"circuit open for {self.host}:{self.port} ({op})"
                )
            try:
                result = self._call_once(op, args, deadline)
            except TRANSPORT_ERRORS as exc:
                self.breaker.record_failure()
                err: Exception = exc
            except RemoteError as exc:
                # the server is alive and answered — that is breaker-success
                self.breaker.record_success()
                if exc.etype not in wire.RETRYABLE_ETYPES:
                    raise
                err = exc
            except BaseException:
                # an abort that says nothing about the peer (deadline
                # expired before sending, KeyboardInterrupt): release any
                # half-open probe slot allow() claimed, or the breaker
                # would stay probing forever and never admit another call
                self.breaker.release()
                raise
            else:
                self.breaker.record_success()
                self.retry_policy.on_success()
                return result
            attempt += 1
            if (
                not retryable
                or time.time() >= deadline  # m3lint: disable=M3L004 -- compares against the wall-clock wire deadline
                or not self.retry_policy.allow_retry(attempt)
            ):
                raise err
            METRICS.counter(
                "rpc_retries_total",
                "transparent RPC-layer retries of idempotent ops",
                labels={"op": op},
            ).inc()
            span.set_tag("retried", attempt)
            prev_backoff = self.retry_policy.backoff(attempt, prev_backoff)
            if prev_backoff > 0.0:
                remaining = deadline - time.time()  # m3lint: disable=M3L004 -- remaining budget against the wall-clock wire deadline
                if remaining <= 0:
                    raise err
                time.sleep(min(prev_backoff, remaining))

    def _call_once(self, op: str, args: dict, deadline: float):
        """One wire round trip; the deadline bounds the socket wait and is
        propagated in the frame so the server can refuse expired work."""
        remaining = deadline - time.time()  # m3lint: disable=M3L004 -- remaining budget against the wall-clock wire deadline
        if remaining <= 0:
            raise DeadlineExceededError(
                f"deadline expired before sending {op!r} to {self.host}:{self.port}"
            )
        req = wire.inject_trace({"op": op, **args}, TRACER.current_context())
        wire.inject_deadline(req, deadline)
        # tenant propagation (query/tenants.py): a call made under a
        # tenant context carries the identity so the server attributes
        # its work (decode device-seconds, per-tenant rpc counters) to
        # the same caller. query/__init__ is empty, so this import pulls
        # no jax-adjacent weight into the net layer.
        from ..query.tenants import current as current_tenant

        wire.inject_tenant(req, current_tenant())
        sock = self._acquire()
        try:
            sock.settimeout(remaining)
            wire.send_frame(sock, req)
            resp = wire.recv_frame(sock)
            sock.settimeout(self.timeout)
        except BaseException:
            sock.close()
            raise
        self._release(sock)
        if not resp.get("ok"):
            raise RemoteError(resp.get("etype", ""), resp.get("error", "remote error"))
        return resp.get("result")


class RemoteNode(RpcClient):
    def __init__(
        self,
        host: str,
        port: int,
        node_id: str | None = None,
        pool_size: int = 4,
        timeout: float = 10.0,
        **kwargs,
    ) -> None:
        super().__init__(host, port, pool_size=pool_size, timeout=timeout,
                         **kwargs)
        self.id = node_id or f"{host}:{port}"
        self._shards_cache: tuple[float, set[int]] | None = None

    # -- node surface (mirrors testing/cluster.Node) --

    @property
    def is_up(self) -> bool:
        # backed by the per-host circuit breaker: False only while the
        # breaker is open with its recovery window still running, so the
        # Session's down-replica accounting skips a dead host instead of
        # paying its connect/read timeout on every fan-out. Once the
        # window elapses (or a background HealthProber closes the breaker)
        # traffic resumes via the half-open probe.
        return self.breaker.available()

    def health(self) -> dict:
        return self._call("health")

    @staticmethod
    def _selfmon_args(ns) -> dict:
        """Reserved-namespace writes carry the wire `selfmon` marker: the
        server re-establishes the collector's writer context around
        dispatch (a thread-local cannot cross the socket — and the
        session's host-queue flusher threads aren't even the collector's
        thread client-side). Only the self-scrape pipeline addresses these
        namespaces; in-process accidental paths (downsampler output,
        remote-write relabels) hit the bare Database surface and raise."""
        from ..selfmon.guard import is_reserved

        return {"selfmon": True} if is_reserved(ns) else {}

    def write(self, ns, sid, t, v, unit=Unit.SECOND):
        return self._call("write", ns=ns, sid=sid, t=t, v=v, unit=int(unit),
                          **self._selfmon_args(ns))

    def write_batch(self, ns, entries):
        return self._call(
            "write_batch", ns=ns, entries=[list(e) for e in entries],
            **self._selfmon_args(ns),
        )

    def write_tagged(self, ns, tags, t, v, unit=Unit.SECOND):
        return self._call(
            "write_tagged",
            ns=ns,
            tags=[[n, v2] for n, v2 in tags],
            t=t,
            v=v,
            unit=int(unit),
            **self._selfmon_args(ns),
        )

    def write_tagged_batch(self, ns, entries):
        """entries: (tags, t, v, unit) — one framed RPC, per-entry errors."""
        return self._call(
            "write_tagged_batch",
            ns=ns,
            entries=[
                [[[n, v2] for n, v2 in tags], t, v, int(unit)]
                for tags, t, v, unit in entries
            ],
            **self._selfmon_args(ns),
        )

    def read(self, ns, sid, start, end):
        return wire.dps_from_wire(
            self._call("fetch", ns=ns, sid=sid, start=start, end=end)
        )

    def fetch_blocks(self, ns, sid, start, end):
        return self._call("fetch_blocks", ns=ns, sid=sid, start=start, end=end)

    def fetch_tagged(self, ns, query, start, end, limit=None):
        return wire.series_from_wire(
            self._call(
                "fetch_tagged",
                ns=ns,
                query=wire.query_to_wire(query),
                start=start,
                end=end,
                limit=limit,
            )
        )

    def query_ids(self, ns, query, start, end, limit=None, force_host=False):
        extra = {"force_host": True} if force_host else {}
        return self._call(
            "query_ids",
            ns=ns,
            query=wire.query_to_wire(query),
            start=start,
            end=end,
            limit=limit,
            **extra,
        )

    def aggregate_query(self, ns, query, start, end, field_filter=None):
        out = self._call(
            "aggregate_query",
            ns=ns,
            query=wire.query_to_wire(query),
            start=start,
            end=end,
            field_filter=[bytes(f) for f in field_filter] if field_filter else None,
        )
        return {bytes(k): {bytes(v) for v in vs} for k, vs in out}

    def stream_shard(self, ns, shard, exclude_blocks=None):
        """Decoded peer stream of one shard; ``exclude_blocks`` skips
        sealed blocks the caller already imported via migration (their
        buffered overlays still stream — only fileset content dedupes)."""
        args = {"ns": ns, "shard": shard}
        if exclude_blocks:
            args["exclude"] = sorted(exclude_blocks)
        return wire.series_from_wire(self._call("stream_shard", **args))

    def migrate_manifest(self, ns, shard) -> list:
        """Sealed-fileset inventory of a shard on this peer (the
        migration source's streamable file roles + byte sizes)."""
        return self._call("migrate_manifest", ns=ns, shard=shard)

    def migrate_fetch(
        self, ns, shard, block_start, volume, suffix, offset, max_bytes,
        _timeout=None,
    ) -> dict:
        """One resumable byte-range read of one fileset file role on this
        peer — deadline-bounded per chunk (``_timeout``) and transparently
        retried under the idempotent-op budget, so a partial transfer
        resumes at the byte offset rather than restarting the file."""
        return self._call(
            "migrate_fetch", _timeout=_timeout, ns=ns, shard=shard,
            block_start=block_start, volume=volume, suffix=suffix,
            offset=offset, max_bytes=max_bytes,
        )

    def block_metadata(self, ns, shard):
        return self._call("block_metadata", ns=ns, shard=shard)

    def stream_series_blocks(self, ns, shard, items):
        out = self._call(
            "stream_series_blocks",
            ns=ns,
            shard=shard,
            items=[[sid, bs] for sid, bs in items],
        )
        return [(sid, bs, wire.dps_from_wire(dps)) for sid, bs, dps in out]

    def cache_stats(self) -> dict:
        return self._call("cache_stats")

    def resident_stats(self) -> dict:
        """HBM-resident compressed pool stats (m3_tpu/resident/)."""
        return self._call("resident_stats")

    def resident_clear(self) -> dict:
        """Drop every resident-pool entry (operator/CI surface)."""
        return self._call("resident_clear")

    def index_stats(self) -> dict:
        """Device index tier + postings cache stats (m3_tpu/index/)."""
        return self._call("index_stats")

    def flush(self, ns, flush_before) -> list:
        """Seal buffered blocks before the cutoff (operator/CI surface)."""
        return self._call("flush", ns=ns, flush_before=flush_before)

    def snapshot(self, ns) -> dict:
        """Capture un-flushed buffers to a snapshot file (operator/CI
        surface; bounds commit-log replay)."""
        return self._call("snapshot", ns=ns)

    def scrub(self, ns=None) -> dict:
        """One digest-verify pass over sealed filesets; corrupt/torn
        volumes quarantine. {"scanned","quarantined","bytes"}."""
        return self._call("scrub", ns=ns)

    def repair(self, ns, peers, shards=None) -> dict:
        """Checksum-diff ``shards`` (all when None) against peer
        endpoint strings and merge differing blocks (operator/CI
        surface; the repair daemon runs the same path on a cadence)."""
        return self._call("repair", ns=ns, peers=list(peers), shards=shards)

    def scan_totals(self, ns, matchers, start, end, explain: bool = False) -> dict:
        """Raw-sample scan-and-aggregate; ``matchers``:
        [[name, op, value], ...] (see NodeService.op_scan_totals).
        ``explain`` adds the per-(series, block) routing record."""
        return self._call(
            "scan_totals", ns=ns, matchers=list(matchers), start=start,
            end=end, explain=explain,
        )

    def query_range(self, ns, query: str, start: int, end: int, step: int,
                    force_staged: bool = False, explain: bool = False) -> dict:
        """PromQL range evaluation on the node's LOCAL engine — the wire
        face of the fused device query pipeline. Returns {"values",
        "metas", "stats"}; ``force_staged`` is the bit-identity parity
        probe, ``explain`` adds per-series routing to the stats record."""
        return self._call(
            "query_range", ns=ns, query=query, start=start, end=end,
            step=step, force_staged=force_staged, explain=explain,
        )

    def metrics(self) -> str:
        """Prometheus text exposition of the remote process (the universal
        scrape op every RpcServer answers via the middleware)."""
        return self._call("metrics")

    def metrics_snapshot(self) -> dict:
        """Structured Registry.collect() snapshot of the remote process —
        what the self-scrape collector converts into stored series (same
        universal op, fmt="json")."""
        return self._call("metrics", fmt="json")

    def traces(self, limit: int = 256) -> list[dict]:
        """The remote process's recent spans — merge with other processes'
        dumps by traceId to reassemble a cross-process trace."""
        return self._call("traces", limit=limit)

    def profile(self, seconds: float | None = None) -> dict:
        """The remote process's wall-clock folded-stack profile over the
        last ``seconds`` (m3_tpu/profiling/): {"folded": {stack: count},
        "samples", "hz", ...} — the fleet profile merge pulls this from
        every peer."""
        return self._call("profile", seconds=seconds)

    def device_profile(self, action: str = "stat", dir: str | None = None) -> dict:
        """Start (into ``dir``, on the node), stop or stat a jax.profiler
        capture of the node's process; every answer carries the fullest
        device's peak and current bytes in use."""
        args = {"action": action}
        if dir is not None:
            args["dir"] = dir
        return self._call("device_profile", **args)

    def owned_shards(self, cache_secs: float = 1.0) -> set[int]:
        cached = self._shards_cache
        now = time.monotonic()
        if cached is not None and now - cached[0] < cache_secs:
            return cached[1]
        shards = set(self._call("owned_shards"))
        self._shards_cache = (now, shards)
        return shards

    def assign_shards(self, shards) -> None:
        self._shards_cache = None
        self._call("assign_shards", shards=sorted(shards))
