"""One client: a process of its own with its own ``RemoteNode``.

Started by ``run.py`` as ``python client.py <spec.pickle>``; obeys one-line
JSON commands on stdin and answers each with one JSON line on stdout. It is
handed its requests (for writes: its series and their slice of the seeded
matrix, from which it builds every tick's entry list BEFORE it says READY)
and hands back its records; the parent compares. Nothing the benchmark
computes per request sits between a send and the next: replies are kept as
they came and reduced only on ``dump``, after the window. For the window a
query client turns its cyclic collector off: it keeps every reply on
purpose, and a collector walking that growing heap would run its full
passes inside timed requests, longer as the window goes on.

The client's wire codec (``m3_tpu/net/client.py``) stays in the path: it is
what a coordinator runs in front of a dbnode. This process never imports
jax.

In a ``live`` mix a query client makes the range of every request at the
send, ending at now by the wall clock and the paced writer's schedule
(``traffic.end_tick``), and records the end tick it asked for; a write
client paces its ticks on the wall clock (``pace_secs``).

``fault`` in the spec plants a fault for ``tests/test_faults*.py`` (never
set by ``run.py``'s command line): ``alter_reply`` changes one value of
one reply where it is received, ``drop_batch`` acknowledges one tick
without sending it.
"""

from __future__ import annotations

import gc
import json
import os
import pickle
import resource
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

from m3_tpu.net.client import RemoteNode  # noqa: E402

from reference import rows_by_host  # noqa: E402
from traffic import end_tick  # noqa: E402


def wait_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


class Client:
    def usage(self, _cmd: dict) -> dict:
        """This process's peak resident set (Linux reports it in KiB)."""
        return {"peak_rss_bytes": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}


class WriteClient(Client):
    def __init__(self, spec: dict, node: RemoteNode) -> None:
        self.node = node
        self.ns = spec["ns"]
        self.tags = spec["tags"]          # per series: ((k, v), ...)
        self.sids = spec["sids"]          # per series: bytes
        self.shards = spec["shards"]      # per series: dbnode shard
        self.vals = spec["vals"]          # float64[n_series, n_ticks]
        self.t0, self.dt = spec["t0"], spec["interval_nanos"]
        self.fault = spec.get("fault")
        sids = self.sids
        # every tick's entry list, built once, before READY
        self.ticks = [
            [[sid, self.t0 + j * self.dt, v]
             for sid, v in zip(sids, self.vals[:, j].tolist())]
            for j in range(self.vals.shape[1])
        ]
        self.sent: list[tuple[int, float, float]] = []  # (tick, send, ack)

    def register(self, _cmd: dict) -> dict:
        """Tick 0 through ``write_tagged``: carries each series' tags to
        the index and returns its id, which must be the id computed here."""
        t = self.t0
        for i, tags in enumerate(self.tags):
            sid = bytes(self.node.write_tagged(
                self.ns, tags, t, float(self.vals[i, 0])))
            if sid != self.sids[i]:
                raise RuntimeError(f"series {i}: the dbnode's id differs")
        return {"registered": len(self.tags)}

    def write(self, cmd: dict) -> dict:
        """Ticks [first, last] in time order, one ``write_batch`` a tick;
        ``shard`` narrows to one dbnode shard's series, ``ns`` to another
        namespace (warm-up replays). Stops at ``t_end``. With
        ``pace_secs`` tick j is due at ``t_go + (j - first) * pace_secs``
        (open loop: a late tick goes at once, and every tick goes)."""
        first, last = cmd["first"], cmd["last"]
        ns = cmd.get("ns", self.ns)
        record = cmd.get("record", False)
        shard = cmd.get("shard")
        keep = None
        if shard is not None:
            keep = [i for i, s in enumerate(self.shards) if s == shard]
        t_go = cmd.get("t_go", 0.0)
        wait_until(t_go)
        t_end = cmd.get("t_end", float("inf"))
        pace = cmd.get("pace_secs")
        write_batch = self.node.write_batch
        n = 0
        dropped = False
        for j in range(first, last + 1):
            entries = self.ticks[j]
            if keep is not None:
                entries = [entries[i] for i in keep]
            if pace is not None:
                wait_until(t_go + (j - first) * pace)
            t_send = time.perf_counter()
            if pace is None and t_send >= t_end:
                break
            if self.fault == "drop_batch" and record and not dropped and (
                    j > first or pace is not None):
                dropped = True  # acknowledged here, never sent
            else:
                write_batch(ns, entries)
            t_ack = time.perf_counter()
            if record:
                self.sent.append((j, t_send, t_ack))
            n += 1
        return {"ticks": n, "rows": n * (len(keep) if keep is not None
                                         else len(self.sids))}

    def dump(self, cmd: dict) -> dict:
        with open(cmd["path"], "wb") as f:
            pickle.dump({"sent": np.asarray(self.sent, np.float64).reshape(-1, 3)}, f)
        return {"records": len(self.sent)}


class QueryClient(Client):
    def __init__(self, spec: dict, node: RemoteNode) -> None:
        self.node = node
        self.ns = spec["ns"]
        # {"warmup": [(query, start, end, step), ...], "window": [...]};
        # in a live mix (query, span, step): the range is made at the send
        self.requests = spec["requests"]
        # a live mix: t0, interval_nanos, first_tick, last_tick, pace_secs
        self.live = spec.get("live")
        self.timeout_s = spec["timeout_s"]
        self.fault = spec.get("fault")
        self.raw: list = []  # (index, send, recv, reply or None, error, end tick)

    def query(self, cmd: dict) -> dict:
        reqs = self.requests[cmd["which"]][:cmd.get("limit")]
        record = cmd.get("record", False)
        self.node.timeout = cmd.get("timeout", self.timeout_s)
        if record:
            gc.collect()
            gc.freeze()
            gc.disable()
        t_go = cmd.get("t_go", float("inf"))  # none: warm-up, before any window
        if "t_go" in cmd:
            wait_until(t_go)
        t_end = cmd.get("t_end", float("inf"))
        query_range = self.node.query_range
        ns = self.ns
        n = failed = 0
        live = self.live
        for i, req in enumerate(reqs):
            t_send = time.perf_counter()
            if t_send >= t_end:
                break
            k_end = None
            if live is not None:  # ends at now
                k_end = end_tick(t_send, t_go, live["pace_secs"], live["first_tick"],
                                 live["last_tick"])
                end = live["t0"] + k_end * live["interval_nanos"]
                req = (req[0], end - req[1], end, req[2])
            query, start, end, step = req
            try:
                resp = query_range(ns, query, start, end, step)
                err = None
            except Exception as exc:  # a failed request is a record, not a crash
                resp, err = None, f"{type(exc).__name__}: {exc}"
                failed += 1
            t_recv = time.perf_counter()
            if record:
                self.raw.append((i, t_send, t_recv, resp, err, k_end))
            n += 1
        if record:
            gc.enable()
        if "t_end" in cmd and n == len(reqs) and time.perf_counter() < t_end:
            return {"error": f"ran out of requests ({n}) before the window closed"}
        return {"requests": n, "failed": failed}

    def dump(self, cmd: dict) -> dict:
        """Reduce the kept replies (after the window): per request the
        times, hostname -> row, and the server's own stats."""
        out = []
        for k, (i, t_send, t_recv, resp, err, k_end) in enumerate(self.raw):
            rows, stats = {}, {}
            if resp is not None:
                rows = rows_by_host(resp)
                st = resp.get("stats") or {}
                stats = {key: st.get(key) for key in (
                    "durationSecs", "deviceDispatches", "planHits", "planMisses",
                    "planFallbacks", "planCoalesced", "residentHits",
                    "residentMisses", "indexDeviceHits", "indexDeviceMisses",
                    "seriesScanned", "bytesScanned", "stages")}
                if self.fault == "alter_reply" and k == len(self.raw) // 2 and rows:
                    first = next(iter(rows.values()))
                    first[len(first) // 2] += 1.0
            out.append({"i": i, "send": t_send, "recv": t_recv, "rows": rows,
                        "stats": stats, "error": err, "end_tick": k_end})
        with open(cmd["path"], "wb") as f:
            pickle.dump({"replies": out}, f)
        return {"records": len(out)}


def main() -> int:
    with open(sys.argv[1], "rb") as f:
        spec = pickle.load(f)
    node = RemoteNode.connect(spec["endpoint"], timeout=spec["timeout_s"])
    client = (WriteClient if spec["kind"] == "write" else QueryClient)(spec, node)
    print(json.dumps({"ready": True, "pid": os.getpid()}), flush=True)
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "exit":
            break
        try:
            resp = getattr(client, cmd["cmd"])(cmd)
        except Exception as exc:  # reported to the parent, which fails the run
            resp = {"error": f"{type(exc).__name__}: {exc}"}
        print(json.dumps(resp), flush=True)
    node.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
