"""The system under test as the benchmark starts it: one device-tier dbnode
(``m3_tpu.testing.proc_cluster.ProcCluster``, one node, embedded KV), with
the benchmark's hook (``hook/sitecustomize.py``) on the child's path.

This process never imports jax: a chip belongs to one process, and that
process is the dbnode.
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CLK_TCK = os.sysconf("SC_CLK_TCK")
SCRATCH_NS = "warm"  # the write cell's warm-up replays block boundaries here


def cpu_seconds(pid: int) -> float:
    """utime + stime of one process, from /proc."""
    with open(f"/proc/{pid}/stat") as f:
        rest = f.read().rsplit(")", 1)[1].split()
    return (int(rest[11]) + int(rest[12])) / CLK_TCK


def dir_bytes(path: str) -> int:
    total = 0
    for root, _, files in os.walk(path):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(root, name))
            except OSError:
                pass  # a segment rotated away between listing and stat
    return total


class Node:
    """One running dbnode and the benchmark's ways in: the wire client,
    the hook, /proc."""

    def __init__(self, cfg: dict, trace_hook: bool = True) -> None:
        from m3_tpu.net.client import RemoteNode
        from m3_tpu.testing.proc_cluster import ProcCluster

        d = cfg["dbnode"]
        self.ns = cfg["namespace"]
        self.base = tempfile.mkdtemp(prefix="m3bench-")
        self.hook_dir = os.path.join(self.base, "hook")
        os.makedirs(self.hook_dir)
        args = [
            "--namespace", self.ns, "--namespace", SCRATCH_NS,
            "--resident-bytes", str(d["resident_bytes"]),
            "--index-device-bytes", str(d["index_device_bytes"]),
            "--ingest-lanes", str(d["ingest_lanes"]),
            "--ingest-slots", str(d["ingest_slots"]),
            "--ingest-sync-batch", str(d["ingest_sync_batch"]),
            "--commitlog-sync", d["commitlog_sync"],
        ]
        if d["device_ingest"]:
            args.append("--device-ingest")
        env = {}
        if trace_hook:
            path = os.path.join(HERE, "hook")
            if os.environ.get("PYTHONPATH"):
                path += os.pathsep + os.environ["PYTHONPATH"]
            env = {"PYTHONPATH": path, "M3BENCH_HOOK_DIR": self.hook_dir}
        self.cluster = None
        self._hook = None
        try:
            self.cluster = ProcCluster(
                num_nodes=1, num_shards=d["num_shards"],
                replica_factor=d["replica_factor"],
                block_size_secs=d["block_secs"], embedded_kv=True,
                base_dir=os.path.join(self.base, "data"),
                extra_args=args, extra_env=env,
            )
            pn = self.cluster.nodes["node0"]
            self.pid = pn.proc.pid
            self.proc = pn.proc
            self.device = pn.device  # (platform, count, kind) or None
            self.endpoint = pn.endpoint
            # generous timeout: a seal and each first query pay their jit
            # compiles inside the call
            self.client = RemoteNode.connect(self.endpoint, timeout=1500.0)
            if trace_hook:
                self._connect_hook()
        except BaseException:
            self.close()
            raise

    # -- the hook --

    def _connect_hook(self) -> None:
        port_file = os.path.join(self.hook_dir, "hook.port")
        deadline = time.monotonic() + 30
        while not os.path.exists(port_file):
            if time.monotonic() > deadline:
                raise TimeoutError("the dbnode's benchmark hook did not come up")
            time.sleep(0.05)
        with open(port_file) as f:
            port = int(f.read())
        self._hook = socket.create_connection(("127.0.0.1", port), timeout=600)
        self._hook_file = self._hook.makefile("rwb")

    def hook(self, **req) -> dict:
        self._hook_file.write((json.dumps(req) + "\n").encode())
        self._hook_file.flush()
        resp = json.loads(self._hook_file.readline())
        if "error" in resp:
            raise RuntimeError(f"hook {req.get('cmd')}: {resp['error']}")
        return resp

    # -- readings --

    def commitlog_bytes(self, settle: float = 1.6) -> int:
        """Bytes of the namespace's commit log once the write-behind queue
        has drained: polled until the size has stood still for ``settle``
        seconds (sync mode ``interval`` fsyncs at least once a second while
        anything is pending)."""
        path = os.path.join(self.base, "data", "node0", "commitlogs", self.ns)
        last, since = dir_bytes(path), time.monotonic()
        while time.monotonic() - since < settle:
            time.sleep(0.2)
            now = dir_bytes(path)
            if now != last:
                last, since = now, time.monotonic()
        return last

    def stderr_tail(self) -> str:
        from m3_tpu.testing.proc_cluster import stderr_tail

        path = getattr(self.proc, "stderr_path", None)
        return stderr_tail(path) if path else ""

    def close(self) -> None:
        if self._hook is not None:
            try:
                self._hook.close()
            except OSError:
                pass
            self._hook = None
        if getattr(self, "client", None) is not None:
            self.client.close()
        if self.cluster is not None:
            self.cluster.close()  # kills the dbnode and waits for it
            self.cluster = None
        shutil.rmtree(self.base, ignore_errors=True)  # commit logs + filesets
