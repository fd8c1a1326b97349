"""Multi-process cluster fixture: real node processes on localhost sockets,
coordinated through a real networked control plane.

Reference: /root/reference/src/dbnode/integration + dtest — the reference's
integration tier runs real node binaries against each other with etcd (or a
fake) as the control plane. Here:

- one `python -m m3_tpu.services.kvnode` subprocess is the control plane
  (etcd's role);
- each node is a `python -m m3_tpu.services.dbnode --kv-endpoint ...`
  subprocess that advertises itself, heartbeats, watches the placement and
  peers-bootstraps gained shards — the fixture never pushes shard
  assignments; it only writes the placement into the KV, exactly like an
  operator using the placement API.
"""

from __future__ import annotations

import os
import queue as _queue
import signal
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

from ..client.session import Session
from ..cluster.kv_service import RemoteKVStore
from ..cluster.placement import PlacementService, build_initial_placement
from ..cluster.topology import ConsistencyLevel, TopologyMap
from ..net.client import RemoteNode

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))


def stderr_tail(path: str, n_bytes: int = 4000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(f.tell() - n_bytes, 0))
            return f.read().decode("utf-8", "replace")
    except OSError as exc:
        return f"<unreadable: {exc}>"


def _spawn_listening(cmd: list[str], what: str, timeout: float = 120.0,
                     collect: dict | None = None,
                     expect_markers: set[str] | None = None,
                     env_extra: dict | None = None,
                     stderr_path: str | None = None):
    """Start a subprocess that prints LISTENING <host> <port>; returns
    (proc, host, port). Named marker lines (``expect_markers``, e.g.
    {"MSG_LISTENING"}) printed before/after it are collected into
    ``collect`` as (host, port), read from the same pump (reading
    proc.stdout directly would race the pump thread that owns the pipe).
    A ``DEVICE <platform> <count> <kind>`` marker (a dbnode with a device
    tier on) is collected as ``collect["DEVICE"] = (platform, count,
    kind)`` whenever ``collect`` is given.

    The child's JAX platform is the CALLER's choice: it inherits this
    process's environment (tests and the CPU gates export
    ``JAX_PLATFORMS=cpu``) overlaid with ``env_extra`` — nothing here
    defaults a child onto the CPU. Its stderr goes to ``stderr_path``
    (default ``<tmp>/m3tpu-<what>-<pid>.stderr``, kept for the
    post-mortem) and the tail is quoted when it dies at start-up."""
    expect_markers = expect_markers or set()

    def _maybe_collect(parts) -> None:
        if collect is None or not parts:
            return
        if parts[0] == "DEVICE" and len(parts) >= 4 and parts[2].isdigit():
            collect["DEVICE"] = (parts[1], int(parts[2]), " ".join(parts[3:]))
        elif (
            len(parts) == 3
            and parts[0] in expect_markers
            and parts[2].isdigit()
        ):
            collect[parts[0]] = (parts[1], int(parts[2]))
    env = dict(os.environ)
    if env_extra:
        env.update(env_extra)
    if stderr_path is None:
        stderr_path = os.path.join(
            tempfile.gettempdir(), f"m3tpu-{what}-{os.getpid()}.stderr"
        )
    with open(stderr_path, "ab") as err_f:
        proc = subprocess.Popen(
            cmd,
            stdout=subprocess.PIPE,
            stderr=err_f,
            text=True,
            env=env,
            cwd=_REPO_ROOT,
        )
    proc.stderr_path = stderr_path

    def _died() -> RuntimeError:
        return RuntimeError(
            f"{what} died at startup; stderr tail ({stderr_path}):\n"
            + stderr_tail(stderr_path)
        )
    # a reader thread owns the (buffered) pipe; the main thread waits on a
    # queue with a deadline, so a child hanging before LISTENING (or a line
    # already sitting in the TextIOWrapper buffer, which select(2) on the
    # raw fd cannot see) can neither block nor be missed
    lines: _queue.Queue = _queue.Queue()

    def _pump():
        for ln in proc.stdout:
            lines.put(ln)
        lines.put(None)

    threading.Thread(target=_pump, daemon=True).start()
    deadline = time.monotonic() + timeout
    line = ""
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            proc.kill()
            raise TimeoutError(f"{what} did not start: {line!r}")
        try:
            item = lines.get(timeout=min(remaining, 1.0))
        except _queue.Empty:
            if proc.poll() is not None:
                raise _died()
            continue
        if item is None:
            raise _died()
        line = item
        _maybe_collect(line.split())
        if line.startswith("LISTENING"):
            break
    _, host, port_s = line.split()
    # expected markers may follow LISTENING: wait until all are present
    if expect_markers:
        wait_until = time.monotonic() + 10
        while time.monotonic() < wait_until and not expect_markers <= set(collect or {}):
            try:
                item = lines.get(timeout=0.2)
            except _queue.Empty:
                continue
            if item is None:
                break
            _maybe_collect(item.split())
    return proc, host, int(port_s)


@dataclass
class ProcNode:
    node_id: str
    proc: subprocess.Popen
    client: RemoteNode
    # (platform, count, kind) from the child's DEVICE marker; None when
    # the node runs no device tier
    device: tuple | None = None

    @property
    def endpoint(self) -> str:
        return f"{self.client.host}:{self.client.port}"

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None

    def kill(self) -> None:
        if self.alive:
            self.proc.kill()
            self.proc.wait(timeout=10)
        self.client.close()

    def terminate(self) -> None:
        if self.alive:
            self.proc.send_signal(signal.SIGTERM)
            self.proc.wait(timeout=10)
        self.client.close()


def spawn_kv_quorum(n: int, base_dir: str, what: str = "kvnode"):
    """Spawn an n-replica raft kvnode quorum (etcd-cluster role). Returns
    (procs, endpoints): every replica is configured with the full member
    map over the raft_configure RPC and the call blocks until a leader is
    elected."""
    procs, endpoints = [], {}
    for i in range(n):
        nid = f"kv{i}"
        proc, host, port = _spawn_listening(
            [
                sys.executable, "-m", "m3_tpu.services.kvnode",
                "--port", "0", "--raft", "--node-id", nid,
                "--data-dir", os.path.join(base_dir, nid),
            ],
            f"{what}-{nid}",
        )
        procs.append(proc)
        endpoints[nid] = f"{host}:{port}"
    from ..net.client import RpcClient

    clients = []
    try:
        for nid, ep in endpoints.items():
            c = RpcClient.connect(ep)
            clients.append(c)
            c._call("raft_configure", members=endpoints)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            leaders = set()
            for c in clients:
                try:
                    st = c._call("raft_status")
                except Exception:
                    continue
                if st["role"] == "leader":
                    leaders.add(st["id"])
            if len(leaders) == 1:
                return procs, list(endpoints.values())
            time.sleep(0.05)
        raise TimeoutError("kv quorum did not elect a leader")
    except BaseException:
        for p in procs:
            p.kill()
        raise
    finally:
        for c in clients:
            c.close()


@dataclass
class ProcCluster:
    num_nodes: int = 3
    num_shards: int = 8
    replica_factor: int = 3
    block_size_secs: int = 2 * 3600
    heartbeat_timeout: float = 2.0
    base_dir: str | None = None
    extra_args: list = field(default_factory=list)
    # env-var overlays for spawned dbnode processes: extra_env applies to
    # every node, node_env[node_id] to one — the seam chaos runs use to
    # install per-node fault plans (testing/faults.env_with_plan)
    extra_env: dict = field(default_factory=dict)
    node_env: dict = field(default_factory=dict)
    nodes: dict = field(default_factory=dict)
    kv_replicas: int = 1  # >1: raft quorum of standalone kvnodes
    # embedded seeds: every dbnode ALSO runs a raft KV replica in-process
    # (server.go:266-324 embedded etcd) — no standalone kvnode at all
    embedded_kv: bool = False

    def __post_init__(self) -> None:
        self.base_dir = self.base_dir or tempfile.mkdtemp(prefix="m3tpu-proc-")
        if self.embedded_kv:
            self._start_embedded()
            return
        if self.kv_replicas > 1:
            self.kv_procs, kv_eps = spawn_kv_quorum(
                self.kv_replicas, os.path.join(self.base_dir, "kv")
            )
            self.kv_endpoint = ",".join(kv_eps)
        else:
            kv_proc, kv_host, kv_port = _spawn_listening(
                [sys.executable, "-m", "m3_tpu.services.kvnode", "--port", "0"],
                "kvnode",
            )
            self.kv_procs = [kv_proc]
            self.kv_endpoint = f"{kv_host}:{kv_port}"
        try:
            self.kv = RemoteKVStore.connect(self.kv_endpoint)
            self.placement_svc = PlacementService(self.kv)

            ids = [f"node{i}" for i in range(self.num_nodes)]
            for nid in ids:
                self.nodes[nid] = self._spawn(nid)
            placement = build_initial_placement(
                ids, self.num_shards, self.replica_factor
            )
            for nid in ids:
                placement.instances[nid].endpoint = self.nodes[nid].endpoint
            self.placement_svc.set(placement)
            self.wait_for_shards()
        except BaseException:
            # a half-started cluster must not orphan its processes — the
            # fixture object never reaches the caller, so close() would
            # never run
            self.close()
            raise

    def _start_embedded(self) -> None:
        """Seed-node deployment: each dbnode carries an embedded raft KV
        replica; the fixture collects every seed's KV endpoint, configures
        the quorum, then writes the placement like an operator."""
        from ..net.client import RpcClient

        self.kv_procs = []
        ids = [f"node{i}" for i in range(self.num_nodes)]
        kv_members: dict[str, str] = {}
        try:
            for nid in ids:
                collect: dict = {}
                cmd = [
                    sys.executable, "-m", "m3_tpu.services.dbnode",
                    "--base-dir", os.path.join(self.base_dir, nid),
                    "--port", "0", "--node-id", nid,
                    "--num-shards", str(self.num_shards),
                    "--block-size-secs", str(self.block_size_secs),
                    "--heartbeat-timeout", str(self.heartbeat_timeout),
                    "--no-mediator", "--embed-kv",
                    *self.extra_args,
                ]
                proc, host, port = _spawn_listening(
                    cmd, nid, collect=collect, expect_markers={"KV_LISTENING"},
                    env_extra={**self.extra_env, **self.node_env.get(nid, {})},
                )
                kh, kp = collect["KV_LISTENING"]
                kv_members[f"kv-{nid}"] = f"{kh}:{kp}"
                self.nodes[nid] = ProcNode(
                    nid, proc, RemoteNode(host, port, node_id=nid),
                    device=collect.get("DEVICE"),
                )
            for ep in kv_members.values():
                c = RpcClient.connect(ep)
                c._call("raft_configure", members=kv_members)
                c.close()
            # wait for a single leader across the embedded quorum
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                leaders = set()
                for ep in kv_members.values():
                    c = RpcClient.connect(ep)
                    try:
                        st = c._call("raft_status")
                        if st["role"] == "leader":
                            leaders.add(st["id"])
                    except Exception:
                        # m3lint: disable=M3L007 -- raft_status probe of a seed that may not be up yet; the wait loop retries
                        pass
                    finally:
                        c.close()
                if len(leaders) == 1:
                    break
                time.sleep(0.05)
            else:
                raise TimeoutError("embedded KV quorum did not elect")
            self.kv_endpoint = ",".join(kv_members.values())
            self.kv = RemoteKVStore.connect(self.kv_endpoint)
            self.placement_svc = PlacementService(self.kv)
            placement = build_initial_placement(
                ids, self.num_shards, self.replica_factor
            )
            for nid in ids:
                placement.instances[nid].endpoint = self.nodes[nid].endpoint
            self.placement_svc.set(placement)
            self.wait_for_shards()
        except BaseException:
            self.close()
            raise

    @property
    def placement(self):
        return self.placement_svc.get()

    def _spawn(self, node_id: str, port: int = 0) -> ProcNode:
        cmd = [
            sys.executable,
            "-m",
            "m3_tpu.services.dbnode",
            "--base-dir",
            os.path.join(self.base_dir, node_id),
            "--port",
            str(port),
            "--node-id",
            node_id,
            "--num-shards",
            str(self.num_shards),
            "--block-size-secs",
            str(self.block_size_secs),
            "--kv-endpoint",
            self.kv_endpoint,
            "--heartbeat-timeout",
            str(self.heartbeat_timeout),
            "--no-mediator",
            *self.extra_args,
        ]
        collect: dict = {}
        proc, host, port_n = _spawn_listening(
            cmd, node_id, collect=collect,
            env_extra={**self.extra_env, **self.node_env.get(node_id, {})},
        )
        client = RemoteNode(host, port_n, node_id=node_id)
        return ProcNode(node_id, proc, client, device=collect.get("DEVICE"))

    def spawn_spare(self, node_id: str) -> ProcNode:
        """A node process that advertises + heartbeats but owns no shards
        until the placement says so (the replacement pool)."""
        pn = self._spawn(node_id)
        self.nodes[node_id] = pn
        return pn

    def wait_for_shards(self, timeout: float = 30.0) -> None:
        """Block until every placed, live node's served shard set matches
        the placement (watch propagation is asynchronous)."""
        deadline = time.monotonic() + timeout
        while True:
            p = self.placement_svc.get()
            pending = []
            for nid, inst in (p.instances if p else {}).items():
                pn = self.nodes.get(nid)
                if pn is None or not pn.alive:
                    continue
                try:
                    owned = pn.client.owned_shards(cache_secs=0.0)
                except Exception:
                    pending.append((nid, "unreachable"))
                    continue
                want = set(inst.shards)
                if owned != want:
                    pending.append((nid, f"{sorted(owned)} != {sorted(want)}"))
            if not pending:
                return
            if time.monotonic() > deadline:
                raise TimeoutError(f"shard propagation timed out: {pending}")
            time.sleep(0.05)

    def restart(self, node_id: str) -> None:
        """Kill + respawn a node on a fresh port (data dir persists, so the
        node bootstraps from its WAL/filesets); the placement's endpoint is
        updated via CAS as an operator would."""
        self.nodes[node_id].kill()
        self.nodes[node_id] = self._spawn(node_id)
        while True:
            p, version = self.placement_svc.get_versioned()
            if p is None or node_id not in p.instances:
                break
            p.instances[node_id].endpoint = self.nodes[node_id].endpoint
            try:
                self.placement_svc.check_and_set(p, version)
                break
            except ValueError:
                continue
        self.wait_for_shards()

    def session(
        self,
        write_cl: ConsistencyLevel = ConsistencyLevel.MAJORITY,
        read_cl: ConsistencyLevel = ConsistencyLevel.MAJORITY,
    ) -> Session:
        p = self.placement_svc.get()
        nodes = {}
        for nid, inst in p.instances.items():
            pn = self.nodes.get(nid)
            if pn is not None:
                nodes[nid] = pn.client
            elif inst.endpoint:
                nodes[nid] = RemoteNode.connect(inst.endpoint, node_id=nid)
        return Session(
            topology=TopologyMap(p),
            nodes=nodes,
            write_consistency=write_cl,
            read_consistency=read_cl,
        )

    def kill_kv_leader(self) -> int:
        """SIGKILL the raft leader among the KV replicas (control-plane
        fault injection); returns the index of the killed process."""
        from ..net.client import RpcClient

        for i, ep in enumerate(self.kv_endpoint.split(",")):
            c = RpcClient.connect(ep)
            try:
                st = c._call("raft_status")
            except Exception:
                continue
            finally:
                c.close()
            if st["role"] == "leader":
                self.kv_procs[i].kill()
                self.kv_procs[i].wait(timeout=10)
                return i
        raise RuntimeError("no KV leader found")

    def close(self) -> None:
        for pn in self.nodes.values():
            pn.kill()
        try:
            if getattr(self, "kv", None) is not None:
                self.kv.close()
        finally:
            for proc in getattr(self, "kv_procs", []):
                if proc.poll() is None:
                    proc.kill()
                    proc.wait(timeout=10)
