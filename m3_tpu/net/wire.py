"""Node RPC wire format: length-prefixed frames of a compact binary value
codec, plus (de)serialization of the index query AST and datapoints.

Reference surface: /root/reference/src/dbnode/generated/thrift/rpc.thrift:44-87
(write / writeTagged / fetch / fetchTagged / query plus batch variants) —
the reference speaks TChannel+Thrift; this framework defines its own framing:

    frame   := u32 little-endian payload length | payload
    payload := value
    value   := 'N' | 'T' | 'F'
             | 'i' i64 | 'd' f64
             | 'b' u32 len bytes | 's' u32 len utf8
             | 'l' u32 count value* | 'm' u32 count (value value)*

Every RPC request is a map {"op": str, ...args}; every response is a map
{"ok": bool, "result": ... | "error": str}.
"""

from __future__ import annotations

import struct
from io import BytesIO

from ..codec.m3tsz import Datapoint
from ..index.query import (
    AllQuery,
    ConjunctionQuery,
    DisjunctionQuery,
    FieldQuery,
    NegationQuery,
    Query,
    RegexpQuery,
    TermQuery,
)
from ..utils.xtime import Unit

_U32 = struct.Struct("<I")
_I64 = struct.Struct("<q")
_F64 = struct.Struct("<d")

MAX_FRAME = 256 * 1024 * 1024


def encode_value(v, out: BytesIO) -> None:
    if v is None:
        out.write(b"N")
    elif v is True:
        out.write(b"T")
    elif v is False:
        out.write(b"F")
    elif isinstance(v, int):
        out.write(b"i")
        out.write(_I64.pack(v))
    elif isinstance(v, float):
        out.write(b"d")
        out.write(_F64.pack(v))
    elif isinstance(v, (bytes, bytearray, memoryview)):
        b = bytes(v)
        out.write(b"b")
        out.write(_U32.pack(len(b)))
        out.write(b)
    elif isinstance(v, str):
        b = v.encode("utf-8")
        out.write(b"s")
        out.write(_U32.pack(len(b)))
        out.write(b)
    elif isinstance(v, (list, tuple)):
        out.write(b"l")
        out.write(_U32.pack(len(v)))
        for item in v:
            encode_value(item, out)
    elif isinstance(v, dict):
        out.write(b"m")
        out.write(_U32.pack(len(v)))
        for k, val in v.items():
            encode_value(k, out)
            encode_value(val, out)
    else:
        raise TypeError(f"unencodable type {type(v)!r}")


def decode_value(buf: bytes, pos: int = 0):
    tag = buf[pos : pos + 1]
    pos += 1
    if tag == b"N":
        return None, pos
    if tag == b"T":
        return True, pos
    if tag == b"F":
        return False, pos
    if tag == b"i":
        return _I64.unpack_from(buf, pos)[0], pos + 8
    if tag == b"d":
        return _F64.unpack_from(buf, pos)[0], pos + 8
    if tag in (b"b", b"s"):
        (n,) = _U32.unpack_from(buf, pos)
        pos += 4
        raw = buf[pos : pos + n]
        if len(raw) != n:
            raise ValueError("truncated value")
        return (raw if tag == b"b" else raw.decode("utf-8")), pos + n
    if tag == b"l":
        (n,) = _U32.unpack_from(buf, pos)
        pos += 4
        items = []
        for _ in range(n):
            item, pos = decode_value(buf, pos)
            items.append(item)
        return items, pos
    if tag == b"m":
        (n,) = _U32.unpack_from(buf, pos)
        pos += 4
        d = {}
        for _ in range(n):
            k, pos = decode_value(buf, pos)
            v, pos = decode_value(buf, pos)
            d[k] = v
        return d, pos
    raise ValueError(f"bad value tag {tag!r} at {pos - 1}")


def dumps(v) -> bytes:
    out = BytesIO()
    encode_value(v, out)
    return out.getvalue()


def loads(b: bytes):
    v, pos = decode_value(b, 0)
    if pos != len(b):
        raise ValueError(f"trailing bytes after value ({pos} != {len(b)})")
    return v


# --- framing over a socket/file-like ---


def pack_frame(payload: bytes) -> bytes:
    return _U32.pack(len(payload)) + payload


class FrameDecoder:
    """Incremental length-prefixed frame parser for streaming receivers
    (the one framing implementation; request/response paths use
    send_frame/recv_frame below)."""

    def __init__(self, max_frame: int = MAX_FRAME) -> None:
        self.max_frame = max_frame
        self._buf = bytearray()

    def feed(self, chunk: bytes) -> list[bytes]:
        self._buf.extend(chunk)
        out = []
        while len(self._buf) >= 4:
            (n,) = _U32.unpack_from(self._buf, 0)
            if n > self.max_frame:
                raise ValueError(f"frame too large: {n}")
            if len(self._buf) < 4 + n:
                break
            out.append(bytes(self._buf[4 : 4 + n]))
            del self._buf[: 4 + n]
        return out


def send_frame(sock, v) -> None:
    sock.sendall(pack_frame(dumps(v)))


def recv_exact(sock, n: int) -> bytes:
    chunks = []
    while n:
        chunk = sock.recv(min(n, 1 << 20))
        if not chunk:
            raise ConnectionError("peer closed")
        chunks.append(chunk)
        n -= len(chunk)
    return b"".join(chunks)


def recv_header(sock) -> int:
    """Block until the next frame's length prefix has come; its payload
    length (the server times this wait apart from the decoding)."""
    (n,) = _U32.unpack(recv_exact(sock, 4))
    if n > MAX_FRAME:
        raise ValueError(f"frame too large: {n}")
    return n


def recv_frame(sock):
    return loads(recv_exact(sock, recv_header(sock)))


# --- trace context propagation (Dapper-style; x/context StartSampledTraceSpan
# carried this through TChannel headers in the reference) ---

# reserved request-map key: [trace_id i64, parent span_id i64, sampled bool]
TRACE_KEY = "_trace"

# ops that pollers hammer (health checks, scrapes, shard-ownership probes):
# spans for them would be all noise. ONE list shared by client injection and
# server adoption — the exclusion must stay symmetric or traces end up
# half-stitched (server spans with no client parent, or vice versa).
UNTRACED_OPS = frozenset(
    {"health", "metrics", "traces", "cache_stats", "resident_stats",
     "index_stats", "owned_shards", "device_profile"}
)

# ops the RPC client may TRANSPARENTLY retry on a transport failure or a
# typed retryable rejection: re-executing them server-side changes nothing.
# Everything else (writes, KV mutations, lease ops) reaches the server at
# most once per caller-visible attempt — a broken connection mid-write is
# ambiguous (the op may have applied), so only a layer that understands the
# op's semantics (the Session's idempotent-upsert fan-out retry, the KV
# store's documented at-least-once contract) may send it again. The raft
# RPCs are idempotent by protocol — term/index consistency checks make a
# duplicate append/vote a no-op — and keep their pre-registry stale-socket
# retry behavior.
IDEMPOTENT_OPS = frozenset(
    {
        # data-plane reads + probes
        "health", "fetch", "fetch_blocks", "fetch_tagged", "query_ids",
        "aggregate_query", "stream_shard", "block_metadata",
        "stream_series_blocks", "scan_totals", "query_range", "owned_shards",
        # shard-handoff migration reads: the manifest lists immutable
        # sealed filesets, and a fetch is a byte-range read of one
        # fileset file — re-reading the same range is duplicate-safe, so
        # transfers survive transport failures via the normal budgeted
        # retry machinery
        "migrate_manifest", "migrate_fetch",
        # debug / observability ('profile' reads the process's folded
        # stack table — sampling continues regardless, duplicate-safe)
        "metrics", "traces", "cache_stats", "resident_stats", "index_stats",
        "lg_poll", "profile",
        # operator ops that re-apply to the same state ('device_profile':
        # a second start into the same directory, or a second stop,
        # changes nothing)
        "flush", "assign_shards", "resident_clear", "scrub", "repair",
        "snapshot", "device_profile",
        # raft protocol (duplicate-safe by design)
        "raft_vote", "raft_append", "raft_snapshot", "raft_status",
        # KV reads (mutations ride RemoteKVStore's own failover contract);
        # kv_watch is a long-poll read — re-asking "anything newer than
        # version V?" is duplicate-safe by construction
        "kv_get", "kv_keys", "kv_get_prefix", "kv_lease_get", "kv_watch",
    }
)

# RemoteError etypes that are safe to retry for idempotent ops: the server
# REFUSED the request (deadline already expired, load shed, injected fault)
# without touching state. Raised as net.resilience.UnavailableError
# server-side; RetryableError is the raft KV service's pre-existing
# no-leader-yet rejection. DiskFullError (storage/faults.py) is the
# commit-log ENOSPC shed: the write was rejected before any WAL append, so
# the client may retry it elsewhere (or later, once space frees) — the SLO
# plane sees it as unavailability, not data loss.
RETRYABLE_ETYPES = frozenset(
    {"UnavailableError", "RetryableError", "DiskFullError"}
)


def inject_trace(req: dict, ctx: dict | None) -> dict:
    """Attach a tracer context (utils.trace.Tracer.current_context()) to an
    RPC request map; no-op when there is no active sampled span."""
    if ctx is not None:
        req[TRACE_KEY] = [int(ctx["trace_id"]), int(ctx["span_id"]),
                          bool(ctx.get("sampled", True))]
    return req


def extract_trace(req: dict) -> dict | None:
    """Pop the trace context off an incoming request map (popped so op
    handlers never see the reserved key). Malformed fields → None: a bad
    peer must not break the request."""
    raw = req.pop(TRACE_KEY, None)
    if not isinstance(raw, list) or len(raw) != 3:
        return None
    tid, sid, sampled = raw
    if not isinstance(tid, int) or not isinstance(sid, int):
        return None
    return {"trace_id": tid, "span_id": sid, "sampled": bool(sampled)}


# --- tenant propagation (the identity half of per-tenant cost
# attribution, query/tenants.py: the coordinator's HTTP layer sets a
# thread-local tenant context, and it must survive the socket hop so
# dbnode-side decode work is attributed to the same caller) ---

# reserved request-map key: the caller's tenant id (str)
TENANT_KEY = "_tenant"


def inject_tenant(req: dict, tenant: str | None) -> dict:
    """Attach the active tenant identity to an RPC request map; no-op
    when no tenant context is active (intra-fleet traffic stays
    unattributed rather than paying a frame field per call)."""
    if tenant is not None:
        req[TENANT_KEY] = str(tenant)
    return req


def extract_tenant(req: dict) -> str | None:
    """Pop the tenant off an incoming request map (popped so op handlers
    never see the reserved key). Malformed → None, like extract_trace;
    VALIDATION (charset/length/cardinality) is the receiver's job —
    query/tenants.normalize collapses junk into the capped overflow
    tenant."""
    raw = req.pop(TENANT_KEY, None)
    if not isinstance(raw, str) or not raw:
        return None
    return raw


# --- deadline propagation (x/context deadlines over TChannel in the
# reference; "The Tail at Scale" cancellation discipline: a server must not
# burn cycles on a request whose caller already gave up) ---

# reserved request-map key: absolute wall-clock deadline, seconds since the
# unix epoch (wall clock, not monotonic — it must mean the same thing in
# another process; peers are assumed clock-synced to well under typical
# timeouts, as in the reference)
DEADLINE_KEY = "_deadline"


def inject_deadline(req: dict, deadline: float | None) -> dict:
    """Attach an absolute wall-clock deadline to an RPC request map."""
    if deadline is not None:
        req[DEADLINE_KEY] = float(deadline)
    return req


def extract_deadline(req: dict) -> float | None:
    """Pop the deadline off an incoming request map (popped so op handlers
    never see the reserved key). Malformed → None, like extract_trace."""
    raw = req.pop(DEADLINE_KEY, None)
    if isinstance(raw, bool) or not isinstance(raw, (int, float)):
        return None
    return float(raw)


# --- query AST <-> wire values ---


def query_to_wire(q: Query):
    if isinstance(q, TermQuery):
        return {"t": "term", "f": q.field, "v": q.value}
    if isinstance(q, RegexpQuery):
        return {"t": "regexp", "f": q.field, "p": q.pattern}
    if isinstance(q, FieldQuery):
        return {"t": "field", "f": q.field}
    if isinstance(q, AllQuery):
        return {"t": "all"}
    if isinstance(q, ConjunctionQuery):
        return {"t": "conj", "q": [query_to_wire(s) for s in q.queries]}
    if isinstance(q, DisjunctionQuery):
        return {"t": "disj", "q": [query_to_wire(s) for s in q.queries]}
    if isinstance(q, NegationQuery):
        return {"t": "neg", "q": query_to_wire(q.query)}
    raise TypeError(f"unknown query type {type(q)!r}")


def query_from_wire(w) -> Query:
    t = w["t"]
    if t == "term":
        return TermQuery(w["f"], w["v"])
    if t == "regexp":
        return RegexpQuery(w["f"], w["p"])
    if t == "field":
        return FieldQuery(w["f"])
    if t == "all":
        return AllQuery()
    if t == "conj":
        return ConjunctionQuery(tuple(query_from_wire(s) for s in w["q"]))
    if t == "disj":
        return DisjunctionQuery(tuple(query_from_wire(s) for s in w["q"]))
    if t == "neg":
        return NegationQuery(query_from_wire(w["q"]))
    raise ValueError(f"unknown query tag {t!r}")


# --- datapoints / series results ---


def dps_to_wire(dps) -> list:
    return [
        [dp.timestamp, dp.value, int(dp.unit), dp.annotation or b""] for dp in dps
    ]


def dps_from_wire(w) -> list[Datapoint]:
    return [
        Datapoint(t, v, Unit(u), bytes(a) if a else None) for t, v, u, a in w
    ]


def series_to_wire(result) -> list:
    """[(sid, tags, dps)] -> wire (tags as [[name, value], ...])."""
    return [
        [sid, [[n, v] for n, v in tags], dps_to_wire(dps)]
        for sid, tags, dps in result
    ]


def series_from_wire(w) -> list:
    return [
        (sid, tuple((n, v) for n, v in tags), dps_from_wire(dps))
        for sid, tags, dps in w
    ]
