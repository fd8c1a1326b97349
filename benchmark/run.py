#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Starts one device-tier dbnode (the only process that holds the chip), makes
the data from ``--seed`` over the configuration's fixed fleet, loads it over
the wire with the commit log on, seals it, warms the cell's own shapes (all
of that is ``setup_s``), drives the cell's traffic from client processes
for ``--seconds`` (closed-loop queriers or writers; in a ``live`` mix
queriers beside a paced writer, over one sealed block and the open one),
compares every answer with the plain reference, kills the dbnode on every
way out and prints one JSON object as its last line. ``BENCHMARK.json``
names; files under ``benchmark/`` hold (README.md).

``--trace 1`` keeps the profiler on for the window's first
``TRACE_SLICE_SECS`` only: the clients run on to ``--seconds``.

``--rehearse`` is the sandbox mode: any platform, at most 64 hosts, result
printed after the word REHEARSAL and never as the result line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import fleet  # noqa: E402
import reference  # noqa: E402
import traffic as traffic_mod  # noqa: E402
from client import wait_until  # noqa: E402
from node import SCRATCH_NS, Node, cpu_seconds  # noqa: E402

NANOS = fleet.NANOS
REHEARSAL_MAX_HOSTS = 64
# what --trace 1 traces: stopping the profiler costs the dbnode about 130 us
# a device event, and a whole window of a fast cell holds millions
TRACE_SLICE_SECS = 10.0


def say(line: str) -> None:
    print(line, flush=True)


class Checks:
    """Every number compared, beside its limit."""

    def __init__(self) -> None:
        self.items: dict[str, list] = {}

    def add(self, name: str, value, limit) -> None:
        self.items[name] = [value, limit]

    @property
    def ok(self) -> bool:
        return all(v is not None and v <= lim for v, lim in self.items.values())

    def lines(self) -> list[str]:
        return [
            f"check {name}: {v} (limit {lim}) {'ok' if v is not None and v <= lim else 'FAIL'}"
            for name, (v, lim) in self.items.items()
        ]


# ---------------------------------------------------------------------------
# client processes
# ---------------------------------------------------------------------------


class Client:
    def __init__(self, spec: dict, path: str) -> None:
        with open(path, "wb") as f:
            pickle.dump(spec, f, protocol=pickle.HIGHEST_PROTOCOL)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "client.py"), path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=ROOT,
        )
        self.pid = self.proc.pid
        self.path = path

    def recv(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"client {self.pid} died (exit {self.proc.poll()})")
        resp = json.loads(line)
        if "error" in resp:
            raise RuntimeError(f"client {self.pid}: {resp['error']}")
        return resp

    def send(self, **cmd) -> None:
        self.proc.stdin.write(json.dumps(cmd) + "\n")
        self.proc.stdin.flush()

    def call(self, **cmd) -> dict:
        self.send(**cmd)
        return self.recv()

    def dump(self) -> dict:
        out = self.path + ".out"
        self.call(cmd="dump", path=out)
        with open(out, "rb") as f:
            return pickle.load(f)  # written by our own client process

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send(cmd="exit")
                self.proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait(timeout=10)
        for stream in (self.proc.stdin, self.proc.stdout):
            try:
                stream.close()
            except OSError:
                pass


def all_calls(clients: list[Client], cmds: list[dict]) -> list[dict]:
    """Send each client its command, then wait for every answer."""
    for c, cmd in zip(clients, cmds):
        c.send(**cmd)
    return [c.recv() for c in clients]


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------


class Cell:
    """State of one run: what the readers of per-layer metrics see as
    ``ctx`` (plain attributes and dicts, no program objects)."""

    def __init__(self, bench: dict, workload: dict, seed: int, seconds: float,
                 trace: bool, rehearse: bool, hosts: int | None, fault: str | None):
        self.bench, self.workload = bench, workload
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.rehearse, self.fault = rehearse, fault
        self.cfg = fleet.load_config(workload["config"])
        self.traffic = fleet.load_json("traffic", workload["traffic"] + ".json")
        if rehearse:
            self.cfg["hosts"] = min(hosts or self.cfg["hosts"], REHEARSAL_MAX_HOSTS)
        self.checks = Checks()
        self.phases: dict[str, float] = {}
        self.window: dict = {}
        self.counters: dict = {}
        self.trace_summary: dict | None = None
        self.clients: list[Client] = []
        self.node: Node | None = None
        self.trace_dir: str | None = None
        self._vals: np.ndarray | None = None
        self.t0 = fleet.t0_nanos(self.cfg)
        self.n_points = fleet.points_per_block(self.cfg)
        self.n_ticks = traffic_mod.total_ticks(self.cfg, self.traffic, self.n_points, seconds)
        self.dt = self.cfg["interval_secs"] * NANOS

    # -- fleet ---------------------------------------------------------

    def build_fleet(self) -> None:
        from m3_tpu.utils.hash import shard_for
        from m3_tpu.utils.serialize import encode_tags

        cfg = self.cfg
        self.hosts = fleet.hosts(cfg)
        self.table = fleet.series_table(cfg)
        self.row_of = {(h, m): i for i, (h, m, _) in enumerate(self.table)}
        self.tags = [fleet.series_tags(self.hosts[h], metric)
                     for h, metric, _ in self.table]
        self.sids = [bytes(encode_tags(t)) for t in self.tags]
        n_shards = cfg["dbnode"]["num_shards"]
        self.shards = [shard_for(sid, n_shards) for sid in self.sids]
        self.shard_counts = np.bincount(self.shards, minlength=n_shards).tolist()
        say(f"fleet (fleet_seed {cfg['fleet_seed']}, the same in every run): "
            f"{cfg['hosts']} hosts, {len(self.table)} series")
        say("shard series counts: " + " ".join(map(str, self.shard_counts)))
        say("shard padded lanes:  " + " ".join(
            str(traffic_mod.pow2ceil(c)) for c in self.shard_counts))

    # -- write clients ---------------------------------------------------

    def spawn_writers(self, workers: int, first: int, last: int) -> list[Client]:
        """Worker w owns the series of the shards s with s % workers == w;
        it is handed the ticks ``first .. last - 1`` and counts them from 0
        (``c.tick0`` is its tick 0)."""
        vals = self.values()[:, first:last]
        clients = []
        for w in range(workers):
            mine = [i for i, s in enumerate(self.shards) if s % workers == w]
            spec = {
                "kind": "write", "endpoint": self.node.endpoint,
                "ns": self.cfg["namespace"], "timeout_s": self.traffic.get("timeout_s", 30),
                "tags": [self.tags[i] for i in mine],
                "sids": [self.sids[i] for i in mine],
                "shards": [self.shards[i] for i in mine],
                "vals": vals[mine], "t0": self.t0 + first * self.dt,
                "interval_nanos": self.dt, "fault": self.fault,
            }
            c = Client(spec, os.path.join(self.node.base, f"writer{first}-{w}.pickle"))
            c.series, c.tick0 = mine, first
            clients.append(c)
            self.clients.append(c)
        for c in clients:
            c.recv()  # READY: every tick's entries are built
        return clients

    def values(self) -> np.ndarray:
        """The run's whole matrix, every tick it will write a column."""
        if self._vals is None:
            self._vals = fleet.values(self.cfg, self.seed, self.n_ticks)
        return self._vals

    # -- the hook ----------------------------------------------------------

    def stat(self) -> dict:
        st = self.node.hook(cmd="stat")
        expo = self.node.client.metrics()
        st["m3tpu_jit_compiles"] = int(metric_total(expo, "m3tpu_jit_compiles_total"))
        return st

    def procs_cpu(self, clients: list[Client]) -> dict:
        out = {"dbnode": cpu_seconds(self.node.pid)}
        for k, c in enumerate(clients):
            out[f"client{k}"] = cpu_seconds(c.pid)
        return out

    def open_window(self, clients: list[Client]) -> float:
        """Counters, CPU seconds and the trace at the window's start;
        returns the instant at which every client starts."""
        self.window["stat0"] = self.stat()
        if self.trace:
            # not under the node's directory: it is reduced after the node has gone
            self.trace_dir = tempfile.mkdtemp(prefix="m3bench-trace-")
            self.node.hook(cmd="trace_start", dir=self.trace_dir)
        self.window["cpu0"] = self.procs_cpu(clients)
        t_go = time.perf_counter() + 0.25
        self.phases["setup_s"] = t_go - T_PROCESS
        return t_go

    def drive(self, clients: list[Client], cmds: list[dict], t_go: float,
              t_end: float) -> list[dict]:
        """The window: every client its command, every answer awaited.
        A traced run stops the profiler ``TRACE_SLICE_SECS`` into the
        window while the clients run on; the traced seconds count from
        ``t_go`` (nothing is sent before it) to the instant the stop is
        asked for, and the trace is cut there when it is reduced."""
        for c, cmd in zip(clients, cmds):
            c.send(**cmd)
        if self.trace:
            wait_until(min(t_go + TRACE_SLICE_SECS, t_end))
            self.window["trace_until"] = time.perf_counter()
            self.window["traced_s"] = self.window["trace_until"] - t_go
            self.node.hook(cmd="trace_stop")
            self.window["trace_stop_s"] = time.perf_counter() - self.window["trace_until"]
        self.window["t_go"], self.window["t_end"] = t_go, t_end
        return [c.recv() for c in clients]

    def close_window(self, clients: list[Client]) -> None:
        cpu1 = self.procs_cpu(clients)
        self.window["cpu_s"] = {k: cpu1[k] - v for k, v in self.window["cpu0"].items()}
        self.window["client_peak_rss_bytes"] = [
            c.call(cmd="usage")["peak_rss_bytes"] for c in clients]
        s0, s1 = self.window["stat0"], self.stat()
        self.window["stat1"] = s1
        self.counters["compiles_in_window"] = s1["compiles"] - s0["compiles"]
        say("programs before the window, by name: " + " ".join(
            f"{k}x{v}" for k, v in sorted(s0["names"].items())))
        new = {k: v - s0["names"].get(k, 0) for k, v in s1["names"].items()
               if v != s0["names"].get(k, 0)}
        if new:
            say("programs inside the window, by name: " + " ".join(
                f"{k}x{v}" for k, v in sorted(new.items())))
        say(f"window: compiles before it {s0['compiles']}, inside "
            f"{self.counters['compiles_in_window']} (jax backend-compile events; "
            f"m3tpu_jit_* counted {s1['m3tpu_jit_compiles'] - s0['m3tpu_jit_compiles']}), "
            f"cache hits {s1['cache_hits'] - s0['cache_hits']}; device bytes in use "
            f"at its start {s0.get('bytes_in_use')}, at its close {s1.get('bytes_in_use')}")
        say("window: CPU seconds " + " ".join(
            f"{k} {v:.2f}" for k, v in self.window["cpu_s"].items()))
        if self.trace:
            say(f"trace: the window's first {self.window['traced_s']:.3f}s, stopped in "
                f"{self.window['trace_stop_s']:.1f}s while the clients ran on; the readers "
                "see the replies of that slice")

    # -- phases shared by the kinds ---------------------------------------

    def load_block(self) -> None:
        """Set-up of a query cell: the whole block over the wire (tick 0
        through write_tagged, the rest as time-major write_batch), then
        the seal, then the admission checks."""
        cfg, node = self.cfg, self.node
        t = time.perf_counter()
        writers = self.spawn_writers(cfg["load_workers"], 0, self.n_points)
        all_calls(writers, [{"cmd": "register"}] * len(writers))
        all_calls(writers, [{"cmd": "write", "first": 1, "last": self.n_points - 1}] * len(writers))
        for c in writers:
            c.close()
            self.clients.remove(c)
        self.phases["load_s"] = time.perf_counter() - t
        self.counters["load_points"] = len(self.table) * self.n_points
        self.seal(self.t0 + cfg["block_secs"] * NANOS)

    def seal(self, before: int) -> None:
        node, ns = self.node, self.cfg["namespace"]
        t = time.perf_counter()
        flushed = node.client.flush(ns, before)
        self.phases["seal_s"] = time.perf_counter() - t
        rs = node.client.resident_stats()
        ix = node.client.index_stats()
        ixns = ix.get("namespaces", {}).get(ns, {})
        self.counters["resident"] = {k: rs.get(k) for k in (
            "entries", "bytes", "admissions", "device_admissions", "upload_bytes",
            "rejections", "evictions")}
        self.counters["index"] = {"admissions": ix.get("admissions"), **{
            k: ixns.get(k) for k in ("device_resident_segments", "sealed_segments")}}
        say(f"seal: {self.phases['seal_s']:.1f}s, {len(flushed)} filesets; resident "
            f"{self.counters['resident']}; index {self.counters['index']}")
        blocks = round((before - self.t0) / (self.cfg["block_secs"] * NANOS))
        want = len(self.table) * blocks
        not_resident = want - (rs.get("entries") or 0) + (rs.get("rejections") or 0) \
            + (rs.get("evictions") or 0)
        self.checks.add("blocks_not_device_resident", not_resident, 0)
        seg_missing = int(not (ixns.get("device_resident_segments", 0) >= 1
                               and ixns.get("device_resident_segments")
                               == ixns.get("sealed_segments")))
        self.checks.add("index_segments_not_on_device", seg_missing, 0)

    # -- a query cell ------------------------------------------------------

    def run_query(self) -> None:
        tr = self.traffic
        self.load_block()
        plan = traffic_mod.query_plan(self.cfg, tr, self.t0, self.n_points, self.seed,
                                      seconds=self.seconds)
        clients = self.spawn_queriers(plan)
        self.say_setup()

        t_go = self.open_window(clients)
        t_end = t_go + self.seconds
        res = self.drive(clients, [self.window_queries(t_go, t_end)] * len(clients),
                         t_go, t_end)
        self.close_window(clients)
        mem = self.window["stat1"].get("peak_bytes")
        self.judge_replies(clients, plan, res)

        # after the window: a plain selector over the whole block for
        # series of every value class the segment holds
        rb_not_device = self.read_back_selectors(self.n_points, "the whole block")
        self.checks.add("readback_not_served_by_device", rb_not_device, 0)
        self.window["memory_peak_bytes"] = mem

    def spawn_queriers(self, plan: dict, live: dict | None = None) -> list[Client]:
        """The query clients, warmed up: the cell's own shapes, through
        the clients' own connections; first sight of a shape compiles, so
        no time limit. The first request goes from one client alone, so
        that one thread of the dbnode makes the plan program and not four
        at once."""
        cfg, tr, node = self.cfg, self.traffic, self.node
        t = time.perf_counter()
        clients = []
        for w in range(tr["workers"]):
            spec = {"kind": "query", "endpoint": node.endpoint, "ns": cfg["namespace"],
                    "timeout_s": tr["timeout_s"], "fault": self.fault, "live": live,
                    "requests": {k: plan[k][w].wire() if live is None
                                 else plan[k][w].wire_now() for k in plan}}
            c = Client(spec, os.path.join(node.base, f"querier{w}.pickle"))
            clients.append(c)
            self.clients.append(c)
        for c in clients:
            c.recv()
        clients[0].call(cmd="query", which="warmup", limit=1, timeout=1500.0)
        all_calls(clients, [{"cmd": "query", "which": "warmup", "timeout": 1500.0}] * len(clients))
        self.phases["warmup_s"] = time.perf_counter() - t
        return clients

    def window_queries(self, t_go: float, t_end: float) -> dict:
        return {"cmd": "query", "which": "window", "record": True, "t_go": t_go,
                "t_end": t_end, "timeout": self.traffic["timeout_s"]}

    def judge_replies(self, clients: list[Client], plan: dict, res: list[dict]) -> list[dict]:
        """Every reply of the window against the reference, and the
        window's latencies; returns them all. A request of a live mix is
        compared as the request it became at the end tick the client
        recorded. The readers of a traced run are left the replies of the
        traced slice (``window["replies"]``): the profiler's stop runs in
        the dbnode beside the rest of the window and slows it."""
        tr = self.traffic
        vals = self.values()
        replies = []
        bad = failed = not_device = 0
        by_class: dict[str, list] = {}
        for w, c in enumerate(clients):
            for rec in c.dump()["replies"]:
                req = plan["window"][w][rec["i"]]
                if rec["end_tick"] is not None:
                    req = traffic_mod.at_end_tick(req, self.t0, self.dt, rec["end_tick"])
                rec["latency_s"] = rec["recv"] - rec["send"]
                if rec["error"] is not None:
                    failed += 1
                    rec["latency_s"] = float(tr["timeout_s"])
                    if failed <= 3:
                        say(f"failed: {req['query']} {rec['error']}")
                else:
                    bad += self.compare_reply(vals, req, rec["rows"])
                    if not served_by_device(rec["stats"]):
                        not_device += 1
                        if not_device <= 3:
                            say(f"not served by the device: {req['query']} stats {rec['stats']}")
                by_class.setdefault(f"{req['fn']}({req['metric']})", []).append(rec["latency_s"])
                replies.append(rec)
        self.window["replies"] = replies if not self.trace else [
            r for r in replies if r["recv"] <= self.window["trace_until"]]
        self.window["attempted"] = sum(r["requests"] for r in res)
        self.window["failed"] = failed
        self.checks.add("window_requests_failed", failed, 0)
        self.checks.add("window_reply_cells_differ", bad, 0)
        self.checks.add("window_replies_not_served_by_device", not_device, 0)

        t_go = self.window["t_go"]
        lat = np.asarray([r["latency_s"] for r in replies]) * 1e3
        srv = np.asarray([(r["stats"].get("durationSecs") or 0) * 1e3 for r in replies])
        order = np.argsort([r["send"] for r in replies])
        half = len(order) // 2
        split = self.window["split"] = {
            "requests": [r["requests"] for r in res],
            "server_p50_ms": float(np.median(srv)),
            "rest_p50_ms": float(np.median(lat - srv)),
            "first_half_p50_ms": float(np.median(lat[order[:half]])),
            "second_half_p50_ms": float(np.median(lat[order[half:]])),
        }
        say(f"window: {len(lat)} requests, first-half median "
            f"{split['first_half_p50_ms']:.1f} ms, second-half median "
            f"{split['second_half_p50_ms']:.1f} ms")
        hit = np.asarray([bool(r["stats"].get("planHits") or r["stats"].get("planCoalesced")) for r in replies])
        for what, mask in (("plan hits", hit), ("plan misses", ~hit)):
            if mask.any():
                say(f"window: {int(mask.sum())} {what}, median {np.median(lat[mask]):.1f} ms, "
                    f"server median {np.median([r['stats'].get('durationSecs') or 0 for r, m in zip(replies, mask) if m]) * 1e3:.1f} ms")
        if len(by_class) > 1:
            say("window: requests and median ms by class: " + "; ".join(
                f"{k} {len(v)} {np.median(v) * 1e3:.1f}" for k, v in sorted(by_class.items())))
        if 0 < (~hit).sum() <= 8:
            say("window: plan misses sent at (s into the window) " + " ".join(
                f"{r['send'] - t_go:.2f}" for r, h in zip(replies, hit) if not h))
        stages: dict[str, float] = {}
        for r in replies:
            for k, v in (r["stats"].get("stages") or {}).items():
                stages[k] = stages.get(k, 0.0) + v
        if stages:
            say("window: the server's stages, mean ms a request: " + " ".join(
                f"{k} {v * 1e3 / len(replies):.2f}"
                for k, v in sorted(stages.items(), key=lambda kv: -kv[1])))
        self.window["latencies_ms"] = lat
        self.e2e = {
            "query_p50_ms": float(np.percentile(lat, 50)),
            "query_p95_ms": float(np.percentile(lat, 95)),
        }
        return replies

    def read_back_selectors(self, n_ticks: int, what: str) -> int:
        """A plain selector over ticks ``0 .. n_ticks - 1``, every sample
        a step, for ``readback_per_class`` series of every value class
        (check ``readback_cells_differ``); returns how many of them no
        device dispatch served."""
        cfg, node = self.cfg, self.node
        t = time.perf_counter()
        rb_bad = rb_not_device = 0
        rb = traffic_mod.readback_requests(
            cfg, self.table, self.t0, n_ticks, self.seed, self.traffic["readback_per_class"])
        for req in rb:
            resp = node.client.query_range(
                cfg["namespace"], req["query"], req["start"], req["end"], req["step"])
            rb_bad += self.compare_reply(self.values(), req, reference.rows_by_host(resp))
            rb_not_device += int(not served_by_device(resp.get("stats") or {}))
        self.phases["readback_s"] = time.perf_counter() - t
        self.checks.add("readback_cells_differ", rb_bad, 0)
        say(f"read-back: {len(rb)} selectors over {what} "
            f"({sorted({r['class'] for r in rb})}) in {self.phases['readback_s']:.1f}s, "
            f"{rb_not_device} not served by the device")
        return rb_not_device

    def compare_reply(self, vals, req: dict, rows: dict) -> int:
        hosts = ([req["host"]] if req["host"] is not None
                 else list(range(self.cfg["hosts"])))
        idx = np.asarray([self.row_of[(h, req["metric"])] for h in hosts])
        want = reference.answer(vals, idx, req)
        return reference.mismatches(rows, [f"host_{h}" for h in hosts], want)

    # -- a live cell -------------------------------------------------------

    def run_live(self) -> None:
        """One sealed block and ``open_ticks`` of the open one, then the
        queriers beside the writer in real time: tick ``first + i`` is due
        at ``t_go + i * interval_secs``."""
        cfg, tr = self.cfg, self.traffic
        pace = cfg["interval_secs"]
        first = self.n_points + tr["open_ticks"]
        self.load_block()
        t = time.perf_counter()
        writers = self.spawn_writers(tr["writer"]["workers"], self.n_points, self.n_ticks)
        # the open block's first tick carries the tags, as the sealed one's
        # did: the index is kept by block, and a range that lies in the open
        # block alone resolves its series there
        all_calls(writers, [{"cmd": "register"}] * len(writers))
        all_calls(writers, [{"cmd": "write", "first": 1, "last": tr["open_ticks"] - 1}] * len(writers))
        self.phases["open_s"] = time.perf_counter() - t
        plan = traffic_mod.query_plan(cfg, tr, self.t0, self.n_points, self.seed,
                                      seconds=self.seconds)
        queriers = self.spawn_queriers(plan, live={
            "t0": self.t0, "interval_nanos": self.dt, "pace_secs": pace,
            "first_tick": first, "last_tick": self.n_ticks - 1})
        self.say_setup()

        clients = writers + queriers
        t_go = self.open_window(clients)
        t_end = t_go + self.seconds
        paced = {"cmd": "write", "first": tr["open_ticks"], "record": True, "t_go": t_go,
                 "last": self.n_ticks - self.n_points - 1, "pace_secs": pace}
        res = self.drive(clients, [paced] * len(writers)
                         + [self.window_queries(t_go, t_end)] * len(queriers), t_go, t_end)
        self.close_window(clients)
        mem = self.window["stat1"].get("peak_bytes")

        # the writers' acknowledgements: latency from the instant a tick
        # was due, and which sample each request could ask for
        sent = [c.dump()["sent"] for c in writers]
        acked_to = [first + len(s_) for s_ in sent]
        acked_at = np.full((len(writers), self.n_ticks - first), np.inf)
        ack_ms, late = [], 0.0
        for w, (c, s_) in enumerate(zip(writers, sent)):
            ticks = s_[:, 0].astype(np.int64) + c.tick0
            due = t_go + (ticks - first) * pace
            acked_at[w, ticks - first] = s_[:, 2]
            ack_ms.append((s_[:, 2] - due) * 1e3)
            late = max(late, float((s_[:, 1] - due).max(initial=0.0)))
        self.window["ack_ms"] = np.concatenate(ack_ms)
        say(f"window: the paced writer sent {sorted(len(s_) for s_ in sent)} ticks "
            f"({first}..), acknowledged after a median {np.median(self.window['ack_ms']):.1f} ms "
            f"from due, the latest send {late * 1e3:.1f} ms late")

        replies = self.judge_replies(queriers, plan, res[len(writers):])
        past = reference.ends_past_acknowledged(
            [r["end_tick"] if r["end_tick"] is not None else -1 for r in replies],
            [r["send"] for r in replies], first, acked_at)
        self.checks.add("window_requests_ending_past_the_acknowledged", past, 0)
        ends = np.asarray([r["end_tick"] for r in replies if r["end_tick"] is not None])
        if len(ends):
            say("window: requests by the end tick they asked for: " + " ".join(
                f"{k}x{n}" for k, n in zip(*np.unique(ends, return_counts=True))))

        # after the window, before anything else is sealed: the selectors
        # over the sealed block and the open one up to the last tick every
        # writer had acknowledged, and every acknowledged point of a
        # seeded sample of series through ``read``
        self.read_back_selectors(min(acked_to), "the sealed block and the open one")
        self.read_back_points(self.sample_series(writers, acked_to), "readback")
        self.window["memory_peak_bytes"] = mem

    # -- a write cell ------------------------------------------------------

    def run_write(self) -> None:
        cfg, tr, node = self.cfg, self.traffic, self.node
        n_ticks = self.n_ticks
        plan = traffic_mod.write_plan(
            self.shard_counts, cfg["dbnode"]["ingest_sync_batch"], self.n_points, tr["blocks"])
        k = plan["warmup_ticks"]
        say(f"write plan: warm-up ticks 0..{k - 1} of the traffic itself, "
            f"{len(plan['replays'])} boundary replays in namespace {SCRATCH_NS!r}; "
            f"tiles per shard (lanes, slots): {plan['tiles']}")
        t = time.perf_counter()
        clients = self.spawn_writers(tr["workers"], 0, n_ticks)
        self.phases["build_s"] = time.perf_counter() - t
        t = time.perf_counter()
        all_calls(clients, [{"cmd": "register"}] * len(clients))
        all_calls(clients, [{"cmd": "write", "first": 1, "last": k - 1}] * len(clients))
        workers = len(clients)
        for shard, first, last in plan["replays"]:
            clients[shard % workers].call(
                cmd="write", ns=SCRATCH_NS, shard=shard, first=first, last=last)
        self.phases["warmup_s"] = time.perf_counter() - t
        log0 = node.commitlog_bytes()
        self.say_setup()

        t_go = self.open_window(clients)
        t_end = t_go + self.seconds
        self.drive(clients, [{"cmd": "write", "first": k, "last": n_ticks - 1, "record": True,
                              "t_go": t_go, "t_end": t_end}] * len(clients), t_go, t_end)
        self.close_window(clients)
        log1 = node.commitlog_bytes()
        mem = self.window["stat1"].get("peak_bytes")

        sent = [c.dump()["sent"] for c in clients]
        acked_to = []  # per client: ticks [0, n) acknowledged
        points = 0
        for c, s in zip(clients, sent):
            n = k + len(s)
            if len(s) and not np.array_equal(s[:, 0], np.arange(k, n)):
                raise RuntimeError("a client's ticks were not sent in order")
            acked_to.append(n)
            points += len(s) * len(c.series)
        allsent = np.concatenate(sent)
        span = float(allsent[:, 2].max() - allsent[:, 1].min())
        ack_ms = (allsent[:, 2] - allsent[:, 1]) * 1e3
        self.window.update(points=points, span_s=span, ack_ms=ack_ms,
                           attempted=len(allsent), failed=0,
                           commitlog_bytes=log1 - log0, memory_peak_bytes=mem)
        order = np.argsort(allsent[:, 1])
        half = len(order) // 2
        say(f"window: {len(allsent)} batches, {points} points in {span:.2f}s; ack "
            f"first-half median {np.median(ack_ms[order[:half]]):.1f} ms, second-half "
            f"median {np.median(ack_ms[order[half:]]):.1f} ms; ticks acknowledged "
            f"per client {acked_to} of {n_ticks}")
        self.e2e = {"ingest_points_per_s": points / span}

        # the acknowledged points of a seeded sample of series, read back
        # before the seal (ingest buffer path) and again after a flush
        # (device-resident block)
        sample = self.sample_series(clients, acked_to)
        self.read_back_points(sample, "readback_before_seal")
        # the first block only: the read-back then spans a device-resident
        # block and the open one, and every run pays one block's seal, not two
        self.seal(self.t0 + cfg["block_secs"] * NANOS)
        self.read_back_points(sample, "readback_after_flush")

    def sample_series(self, writers: list[Client], acked_to: list[int]) -> list[tuple[int, int]]:
        """(series, ticks acknowledged) of ``readback_series`` series drawn
        from the seed, every writer's share alike."""
        rng = fleet.rng_for(self.seed, fleet.STREAM_READBACK)
        sample = []
        for w, c in enumerate(writers):
            pick = rng.choice(len(c.series), size=min(
                self.traffic["readback_series"] // len(writers), len(c.series)), replace=False)
            sample += [(c.series[int(i)], acked_to[w]) for i in pick]
        return sample

    def read_back_points(self, sample: list[tuple[int, int]], name: str) -> None:
        """Every acknowledged point of the sampled series through ``read``
        (check ``<name>_points_differ``, phase ``<name>_s``)."""
        t = time.perf_counter()
        vals = self.values()
        end = self.t0 + self.n_ticks * self.dt
        bad = 0
        for i, n in sample:
            dps = self.node.client.read(self.cfg["namespace"], self.sids[i], self.t0, end)
            bad += reference.read_mismatches(
                [d.timestamp for d in dps], [d.value for d in dps],
                self.t0 + self.dt * np.arange(n), vals[i, :n])
        self.checks.add(f"{name}_points_differ", bad, 0)
        self.phases[f"{name}_s"] = time.perf_counter() - t
        say(f"read-back ({name}): every acknowledged point of {len(sample)} series in "
            f"{self.phases[f'{name}_s']:.1f}s")

    def say_setup(self) -> None:
        say("set-up phases (s): " + " ".join(
            f"{k[:-2]} {v:.1f}" for k, v in self.phases.items()))

    # -- the whole run -------------------------------------------------------

    def run(self) -> dict | None:
        """Returns the result object, or None where no result may be
        printed (no accelerator, too few chips)."""
        t = time.perf_counter()
        self.node = Node(self.cfg)
        self.phases["dbnode_up_s"] = time.perf_counter() - t
        device = self.node.device
        say(f"dbnode pid {self.node.pid} DEVICE {device} up in "
            f"{self.phases['dbnode_up_s']:.1f}s")
        if device is None:
            say("FAIL the dbnode printed no DEVICE marker")
            return None
        platform, count, kind = device
        self.device_kind = kind
        if not self.rehearse and platform != "tpu":
            say(f"FAIL platform is {platform!r}, not tpu: no result "
                "(--rehearse runs the phases off the chip)")
            return None
        if count < self.workload["chips"]:
            say(f"FAIL the cell asks for {self.workload['chips']} chips, jax found {count}")
            return None
        self.build_fleet()
        {"write": self.run_write, "query": self.run_query,
         "live": self.run_live}[self.traffic["kind"]]()
        # the program's state is freed before anything else is read
        self.close()
        if self.trace:
            self.trace_summary = reduce_trace(self.trace_dir, self.window["traced_s"])
        return self.result(platform, count, kind)

    def close(self) -> None:
        for c in self.clients:
            c.close()
        self.clients = []
        if self.node is not None:
            self.node.close()
            self.node = None

    def result(self, platform: str, count: int, kind: str) -> dict:
        bench = self.bench
        name = self.workload["name"]
        device = {"platform": platform, "kind": kind, "count": count,
                  "memory_peak_bytes": self.window.get("memory_peak_bytes")}
        metrics: dict = {}
        out: dict = {}
        if self.trace:
            ts = self.trace_summary or {}
            if "error" in ts:
                say(f"trace: {ts['error']}; planes {ts.get('planes')}")
            else:
                device["busy_s"] = ts["busy_s"]
                device["window_s"] = self.window["traced_s"]
                out["breakdown"] = {"device_ops": ts["device_ops"],
                                    "idle_gaps": ts["idle_gaps"]}
                say(f"trace: busy {ts['busy_s']:.3f}s of {self.window['traced_s']:.3f}s, "
                    f"{ts['n_ops']} device operations, xplane {ts['xplane_bytes']} bytes; "
                    f"collected for {ts['collected_s']:.3f}s from the first operation, the "
                    f"{ts['n_ops_after']} operations after the slice's end left out")
                say("trace: busy share by second: " + " ".join(
                    f"{b:.2f}" for b in ts["by_second"]))
            for m in bench["per_layer"]:
                if name not in m.get("workloads", [name]):
                    continue
                layer = fleet.load_json("layers", m["name"] + ".json")
                value = load_reader(layer["reader"]).read(self, layer)
                if value is not None:
                    metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        else:
            values = dict(self.e2e, setup_s=self.phases["setup_s"])
            for m in bench["end_to_end"]:
                if name in m.get("workloads", [name]) and m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        for k, v in self.e2e.items():
            say(f"end-to-end {k}: {v}")
        say(f"end-to-end setup_s: {self.phases['setup_s']}")
        return {"correct": self.checks.ok, "attempted": self.window["attempted"],
                "failed": self.window["failed"], "metrics": metrics, "device": device,
                **out, "harness": self.harness(), "compared": self.checks.items}

    def harness(self) -> dict:
        """What the load generator itself cost, and where a request's time
        went: for whoever re-bounds a metric (README.md)."""
        cpu = self.window["cpu_s"]
        return {"cpu_count": os.cpu_count(), "dbnode_cpu_s": cpu["dbnode"],
                "client_cpu_s": [v for k, v in cpu.items() if k != "dbnode"],
                "client_peak_rss_bytes": self.window["client_peak_rss_bytes"],
                **self.window.get("split", {})}


def metric_total(expo: str, name: str) -> float:
    total = 0.0
    for line in expo.splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            total += float(line.rsplit(" ", 1)[1])
    return total


def served_by_device(stats: dict) -> bool:
    """The guarantee the configuration states: a device dispatch of the
    plan program (its own, or the one it was coalesced onto: an identical
    request already in flight), no fallback to the host path."""
    return bool(((stats.get("deviceDispatches") or 0) >= 1
                 or (stats.get("planCoalesced") or 0) >= 1)
                and (stats.get("planFallbacks") or 0) == 0)


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(HERE, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reduce_trace(trace_dir: str, slice_s: float) -> dict:
    """The reduction runs in a process of its own, on the CPU: this one
    never imports jax. The trace is cut ``slice_s`` after its first device
    operation (``trace_reduce.py``)."""
    out = trace_dir + ".json"
    try:
        subprocess.run(
            [sys.executable, os.path.join(HERE, "trace_reduce.py"), trace_dir, out,
             repr(slice_s)],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), check=True, timeout=240,
            cwd=ROOT)
        with open(out) as f:
            return json.load(f)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
        if os.path.exists(out):
            os.remove(out)


def run_cell(workload: str | dict, seed: int, seconds: float, trace: bool,
             rehearse: bool = False, hosts: int | None = None,
             fault: str | None = None) -> dict | None:
    """``workload`` names a cell of ``BENCHMARK.json``; a test may hand in
    the cell itself (``name``, ``config``, ``traffic``, ``chips``)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if isinstance(workload, str) and workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = Cell(bench, cells[workload] if isinstance(workload, str) else workload,
                seed, seconds, trace, rehearse, hosts, fault)
    try:
        result = cell.run()
    except BaseException:
        if cell.node is not None:
            say("--- dbnode stderr tail ---\n" + cell.node.stderr_tail())
        raise
    finally:
        cell.close()
        if cell.trace_dir:  # a run that died before its trace was reduced
            shutil.rmtree(cell.trace_dir, ignore_errors=True)
    for line in cell.checks.lines():
        say(line)
        print(line, file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox mode: any platform, at most "
                    f"{REHEARSAL_MAX_HOSTS} hosts, no result line")
    ap.add_argument("--hosts", type=int, default=None,
                    help="with --rehearse only: hosts of the fleet")
    args = ap.parse_args(argv)
    if args.hosts is not None and not args.rehearse:
        ap.error("--hosts goes with --rehearse")
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      rehearse=args.rehearse, hosts=args.hosts)
    if result is None:
        return 1
    if args.rehearse:
        say("REHEARSAL " + json.dumps(result))
        say("rehearsal over: no result line")
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
