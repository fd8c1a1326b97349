"""m3dbnode-equivalent service binary: a runnable storage node process.

Reference: /root/reference/src/cmd/services/m3dbnode/main/main.go:42 — the
node process wires config → Database → bootstrap → RPC server → background
mediator. Run:

    python -m m3_tpu.services.dbnode --base-dir /var/lib/m3tpu --port 9000 \
        --node-id node0 --shards 0,1,2,3 --namespace default

Prints ``LISTENING <host> <port>`` on stdout once serving (process managers
and the multi-process test fixture wait for it). With a device tier on
(``--resident-bytes``, ``--index-device-bytes``, ``--device-ingest``) the
line before it is ``DEVICE <platform> <count> <kind>``, and a machine
without a TPU is a start-up error unless ``JAX_PLATFORMS`` names ``cpu``.
"""

from __future__ import annotations

import argparse
import gc
import signal
import sys

from ..net.server import NodeServer, NodeService
from ..storage.database import Database, NamespaceOptions
from ..storage.mediator import Mediator, MediatorOptions
from ..storage.series import NANOS

# allocations of container objects, less deallocations, that start a pass
# of the collector's youngest generation (700 by default). A query's reply
# holds thousands of containers at once (each series' tags, meta and
# values; 400 series a dashboard panel), so at 700 every reply set off
# passes that promoted it while it was still in flight, and those
# promotions soon called for a full pass over the whole heap (jax's, the
# index's, the open block's series buffers), which stops every handler
# thread at once for tens of milliseconds. At 100,000 a pass waits for
# that much growth, and a reply dies young.
_GC_GEN0_THRESHOLD = 100_000


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="m3tpu-dbnode", description=__doc__)
    p.add_argument("--base-dir", required=True)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--node-id", default="node0")
    p.add_argument("--num-shards", type=int, default=8)
    p.add_argument("--shards", default="", help="csv of owned shard ids")
    p.add_argument("--namespace", action="append", default=[])
    p.add_argument("--block-size-secs", type=int, default=2 * 3600)
    p.add_argument("--retention-secs", type=int, default=2 * 24 * 3600)
    p.add_argument("--no-cold-writes", action="store_true")
    p.add_argument("--no-mediator", action="store_true")
    p.add_argument("--no-bootstrap", action="store_true")
    p.add_argument(
        "--cache-bytes",
        type=int,
        default=256 * 1024 * 1024,
        help="decoded-block cache byte budget (0 disables the cache); "
        "stats are served on the cache_stats debug op",
    )
    p.add_argument(
        "--resident-bytes",
        type=int,
        default=0,
        help="HBM-resident compressed pool byte budget (0 disables the "
        "mode): sealed blocks' m3tsz bytes stay device-resident and warm "
        "scans decode from HBM (m3_tpu/resident/); stats on the "
        "resident_stats debug op",
    )
    p.add_argument(
        "--resident-side-bytes",
        type=int,
        default=0,
        help="byte budget for the pool's per-chunk side planes (the "
        "chunk-parallel decoder's device-resident metadata). Default 0 "
        "sizes them to --resident-bytes — i.e. total pool HBM is up to "
        "2x --resident-bytes; set this explicitly to cap it",
    )
    p.add_argument(
        "--index-device-bytes",
        type=int,
        default=0,
        help="device byte budget for the HBM-resident inverted index "
        "(0 disables the tier): sealed index segments' term dictionaries "
        "and postings admit at seal time and term/regexp/set-algebra "
        "resolution runs as batched kernels (m3_tpu/index/device/); "
        "stats on the index_stats debug op",
    )
    p.add_argument(
        "--device-ingest",
        action="store_true",
        help="device-side ingest (m3_tpu/ingest/): write batches mirror "
        "into per-shard (series_lane, slot) column planes; sealed blocks "
        "device-encode through the batched m3tsz kernel (m3_tpu/ops/"
        "encode.py) and admit born-resident with zero admission upload",
    )
    p.add_argument(
        "--ingest-lanes",
        type=int,
        default=1024,
        help="series lanes per ingest window plane (--device-ingest)",
    )
    p.add_argument(
        "--ingest-slots",
        type=int,
        default=1024,
        help="datapoint slots per ingest lane (--device-ingest)",
    )
    p.add_argument(
        "--ingest-sync-batch",
        type=int,
        default=8192,
        help="staged rows per shard that trigger a batched column-plane "
        "sync to device (--device-ingest)",
    )
    p.add_argument(
        "--commitlog-sync",
        choices=["every", "interval", "none"],
        default="interval",
        help="commit-log durability mode (storage.database."
        "COMMITLOG_SYNC_MODES): 'every' fsyncs before acking each write "
        "(zero acked loss on a hard kill), 'interval' acks from the OS "
        "buffer and fsyncs on a cadence (default; loss bounded by the "
        "flush interval), 'none' leaves syncing to segment rotation "
        "(loss bounded by the open segment)",
    )
    p.add_argument(
        "--scrub-interval",
        type=float,
        default=0.0,
        help="background fileset scrub cadence in seconds (0 disables): "
        "digest-verifies sealed volumes and quarantines corruption "
        "(storage/repair.py Scrubber); counts ride "
        "m3tpu_storage_corruption_total",
    )
    p.add_argument(
        "--scrub-bytes-per-sec",
        type=int,
        default=32 * 1024 * 1024,
        help="scrub read-rate bound in bytes/sec (0 = unpaced)",
    )
    p.add_argument(
        "--scrub-iops",
        type=int,
        default=0,
        help="scrub file-open rate bound in opens/sec (0 = unpaced); "
        "paces alongside --scrub-bytes-per-sec — whichever budget is "
        "further behind wins, so many tiny filesets can't dodge pacing",
    )
    p.add_argument(
        "--quarantine-retention-secs",
        type=float,
        default=0.0,
        help="prune quarantined fileset volumes older than this many "
        "seconds at the end of each scrub pass (0 = keep forever); "
        "prunes count m3tpu_storage_quarantine_pruned_total and drop "
        "the quarantine gauge",
    )
    p.add_argument(
        "--selfmon-interval",
        type=float,
        default=0.0,
        help="self-scrape interval in seconds (0 disables): this node's "
        "metrics registry is stored as series in its own reserved _m3tpu "
        "namespace through the normal write path (m3_tpu/selfmon/)",
    )
    p.add_argument(
        "--selfmon-retention-secs",
        type=int,
        default=24 * 3600,
        help="retention of the reserved self-monitoring namespace",
    )
    p.add_argument(
        "--profile-hz",
        type=float,
        default=None,
        help="wall-clock stack-sampler rate (m3_tpu/profiling/): the "
        "always-on continuous profiler served on the `profile` debug op; "
        "default M3_TPU_PROFILE_HZ (19), 0 disables",
    )
    p.add_argument(
        "--kv-endpoint",
        default="",
        help="host:port of the control-plane KV server; enables dynamic "
        "topology: the node advertises itself, heartbeats, watches its "
        "placement, and peers-bootstraps gained shards",
    )
    p.add_argument("--heartbeat-timeout", type=float, default=10.0)
    p.add_argument(
        "--no-migration",
        action="store_true",
        help="disable warm residency migration on shard handoff (gained "
        "shards then rebuild purely from the decoded peers stream)",
    )
    p.add_argument(
        "--migration-chunk-bytes",
        type=int,
        default=1 << 20,
        help="byte-range size of one resumable migrate_fetch chunk",
    )
    p.add_argument(
        "--migration-chunk-timeout",
        type=float,
        default=5.0,
        help="per-chunk deadline of migration fetches; a dead source "
        "costs at most this long before the next replica resumes",
    )
    p.add_argument(
        "--max-inflight",
        type=int,
        default=0,
        help="load-shedding cap on concurrent in-flight RPCs (0 = uncapped; "
        "past the cap requests fast-fail with a typed retryable "
        "unavailable error instead of queueing into collapse); also "
        "settable via M3_TPU_RPC_MAX_INFLIGHT",
    )
    # embedded seed control plane (server.go:266-324 embedded etcd role):
    # this node ALSO runs a raft KV replica; N seed nodes form the quorum
    p.add_argument("--embed-kv", action="store_true",
                   help="run an embedded raft KV replica in this process")
    p.add_argument("--embed-kv-port", type=int, default=0)
    p.add_argument("--kv-node-id", default="",
                   help="raft member id (default: kv-<node-id>)")
    p.add_argument("--kv-members", default="",
                   help="full member map id=host:port,... (else the fixture "
                   "or operator sends raft_configure to each seed)")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    gc.set_threshold(_GC_GEN0_THRESHOLD, *gc.get_threshold()[1:])

    from .. import device

    device.configure_compile_cache()
    device.install_compile_counters()
    device_marker = None
    if args.resident_bytes > 0 or args.index_device_bytes > 0 or args.device_ingest:
        # a device tier on a machine with no chip is an error, not a
        # host TSDB that quietly carries on (raises -> non-zero exit)
        device_marker = "DEVICE %s %d %s" % device.require_device()

    # embedded seed KV replica (server.go:266-324): starts SERVING first —
    # the quorum only forms once a majority of seeds are up, so everything
    # that needs the control plane is deferred until a leader exists
    kv_server = None
    kv_raft = None
    if args.embed_kv:
        import os as _os

        from ..cluster.kv import KVStore
        from ..cluster.raft import RaftKVService, RaftNode
        from ..net.server import RpcServer

        kv_raft = RaftNode(
            args.kv_node_id or f"kv-{args.node_id}",
            KVStore(),
            data_dir=_os.path.join(args.base_dir, "kv"),
        )
        kv_server = RpcServer(
            RaftKVService(kv_raft), port=args.embed_kv_port, component="kv"
        )
        kv_server.start()
        self_kv_ep = f"{kv_server.host}:{kv_server.port}"
        print(f"KV_LISTENING {kv_server.host} {kv_server.port}", flush=True)
        if args.kv_members:
            members = dict(kv.split("=", 1) for kv in args.kv_members.split(","))
            kv_raft.configure(members, self_endpoint=self_kv_ep)
        elif kv_raft.members:
            # RESTART of a configured seed: rejoin the recovered membership
            # immediately so the quorum (and the namespace registry below)
            # is available BEFORE bootstrap
            kv_raft.configure(kv_raft.members, self_endpoint=self_kv_ep)
        if not args.kv_endpoint:
            # the node's own control-plane client talks to its LOCAL seed
            # (leader redirects route writes; watches serve locally)
            args.kv_endpoint = self_kv_ep

    from ..cache import CacheOptions
    from ..index.device import IndexDeviceOptions
    from ..ingest import IngestOptions
    from ..resident import ResidentOptions

    db = Database(
        args.base_dir,
        num_shards=args.num_shards,
        cache_options=CacheOptions(
            enabled=args.cache_bytes > 0, max_bytes=max(args.cache_bytes, 0)
        ),
        resident_options=ResidentOptions(
            enabled=args.resident_bytes > 0,
            max_bytes=max(args.resident_bytes, 0),
            side_bytes=max(args.resident_side_bytes, 0),
        ),
        index_device_options=IndexDeviceOptions(
            enabled=args.index_device_bytes > 0,
            max_bytes=max(args.index_device_bytes, 0),
        ),
        ingest_options=(
            IngestOptions(lanes=args.ingest_lanes, slots=args.ingest_slots,
                          sync_batch=args.ingest_sync_batch)
            if args.device_ingest
            else None
        ),
        commitlog_sync=args.commitlog_sync,
    )
    opts = NamespaceOptions(
        retention_nanos=args.retention_secs * NANOS,
        block_size_nanos=args.block_size_secs * NANOS,
        cold_writes_enabled=not args.no_cold_writes,
    )
    for ns in args.namespace or ["default"]:
        db.create_namespace(ns, opts)
    if args.selfmon_interval > 0:
        # created BEFORE bootstrap so stored self telemetry recovers across
        # restarts like any namespace
        from ..selfmon import RESERVED_NS

        db.create_namespace(
            RESERVED_NS,
            NamespaceOptions(
                retention_nanos=args.selfmon_retention_secs * NANOS,
                block_size_nanos=min(
                    args.block_size_secs, 3600
                ) * NANOS,
            ),
        )

    # dynamic namespaces (namespace/dynamic.go): the control-plane registry
    # is applied BEFORE bootstrap so registered namespaces recover their
    # data, and watched after so admin-created namespaces appear live.
    # EMBEDDED-SEED mode defers ALL control-plane wiring until the quorum
    # has a leader (the quorum can't form until a majority of seed
    # processes are up) — registry namespaces then appear via the watch.
    kv = None
    ns_registry = None
    state: dict = {"cluster_db": None, "hb_stop": None}

    def _apply_registry(reg: dict) -> None:
        for name, rec in reg.items():
            if name in db.namespaces:
                continue
            db.create_namespace(
                name,
                NamespaceOptions(
                    retention_nanos=int(rec["retention_nanos"]),
                    block_size_nanos=int(rec["block_size_nanos"]),
                    cold_writes_enabled=bool(
                        rec.get("cold_writes_enabled", True)
                    ),
                ),
            )

    if args.kv_endpoint and not args.embed_kv:
        from ..cluster.kv_service import RemoteKVStore
        from ..cluster.namespaces import NamespaceRegistry

        kv = RemoteKVStore.connect(args.kv_endpoint)
        ns_registry = NamespaceRegistry(kv)
        _apply_registry(ns_registry.get_all())
    elif args.embed_kv and kv_raft.members:
        # a RECONFIGURED seed (restart or --kv-members): wait for the
        # quorum and apply the registry BEFORE bootstrap, so
        # registry-created namespaces recover their persisted data —
        # create_namespace after bootstrap would leave them empty
        import time as _t

        deadline = _t.monotonic() + 60
        while _t.monotonic() < deadline and kv_raft.leader_id is None:
            _t.sleep(0.05)
        if kv_raft.leader_id is not None:
            from ..cluster.kv_service import RemoteKVStore
            from ..cluster.namespaces import NamespaceRegistry

            kv = RemoteKVStore.connect(args.kv_endpoint)
            ns_registry = NamespaceRegistry(kv)
            try:
                _apply_registry(ns_registry.get_all())
            except Exception as exc:
                print(f"WARN registry fetch at bootstrap failed: {exc}", flush=True)

    if not args.no_bootstrap:
        db.bootstrap()

    mediator = None
    if not args.no_mediator:
        mediator = Mediator(db, MediatorOptions())
        mediator.start()

    scrubber = None
    if args.scrub_interval > 0:
        from ..storage.repair import Scrubber

        scrubber = Scrubber(
            db,
            interval=args.scrub_interval,
            bytes_per_sec=args.scrub_bytes_per_sec,
            iops=args.scrub_iops,
            quarantine_retention_secs=args.quarantine_retention_secs,
            phase_key=args.node_id,
        )
        scrubber.start()

    shards = {int(s) for s in args.shards.split(",") if s.strip()}
    service = NodeService(db, node_id=args.node_id, assigned_shards=shards)
    server = NodeServer(
        service, host=args.host, port=args.port,
        max_inflight=args.max_inflight or None,
    )

    selfmon = None
    if args.selfmon_interval > 0:
        from ..selfmon import RESERVED_NS, DatabaseSink, SelfMonCollector

        selfmon = SelfMonCollector(
            DatabaseSink(db, RESERVED_NS),
            interval=args.selfmon_interval,
            instance=args.node_id,
            component="dbnode",
        ).start()

    # always-on continuous profiler (m3_tpu/profiling/): folded stacks on
    # the `profile` op, device-memory split gauges refreshed on its
    # schedule; m3tpu_profile_* health rides the selfmon pipeline above
    from ..profiling import start_sampler

    profiler = start_sampler(hz=args.profile_hz, instance=args.node_id, db=db)

    def wire_control_plane() -> None:
        """Dynamic topology via the networked control plane (server.go:
        embedded etcd + topology watch + KV runtime reconfig)."""
        nonlocal kv, ns_registry
        import threading

        from ..cluster.placement import PlacementService
        from ..cluster.services import ServiceInstance, Services
        from ..storage.cluster_db import ClusterDatabase
        from ..storage.runtime import RuntimeOptionsManager

        if kv is None:
            from ..cluster.kv_service import RemoteKVStore
            from ..cluster.namespaces import NamespaceRegistry

            kv = RemoteKVStore.connect(args.kv_endpoint)
            ns_registry = NamespaceRegistry(kv)
            _apply_registry(ns_registry.get_all())

        # live namespace adds (bootstrap already applied the current set)
        ns_registry.watch(_apply_registry)

        # KV-watched runtime knobs (server.go:1007-1268 runtime reconfig)
        runtime_mgr = RuntimeOptionsManager(kv)
        runtime_mgr.watch(db.apply_runtime_options)

        services = Services(kv, heartbeat_timeout=args.heartbeat_timeout)
        endpoint = f"{server.host}:{server.port}"
        services.advertise("m3db", ServiceInstance(args.node_id, endpoint))
        hb_stop = state["hb_stop"] = threading.Event()

        from ..utils.instrument import DEFAULT as METRICS

        hb_errors = METRICS.counter(
            "heartbeat_errors_total",
            "control-plane heartbeats swallowed by KV hiccups (a "
            "persistently failing loop means this node looks dead to the "
            "failure detector)",
        )

        def hb_loop() -> None:
            interval = max(args.heartbeat_timeout / 3.0, 0.05)
            while not hb_stop.wait(interval):
                try:
                    services.heartbeat("m3db", args.node_id)
                except Exception:
                    # KV hiccups must not kill the node — but count every
                    # swallow so /metrics shows a heartbeat loop that is
                    # failing persistently (M3L007)
                    hb_errors.inc()

        threading.Thread(target=hb_loop, daemon=True, name="heartbeat").start()
        cluster_db = state["cluster_db"] = ClusterDatabase(
            db, args.node_id, PlacementService(kv), node_service=service,
            migration_enabled=not args.no_migration,
            migration_chunk_bytes=args.migration_chunk_bytes,
            migration_chunk_timeout=args.migration_chunk_timeout,
        )
        cluster_db.start()

    if args.kv_endpoint and not args.embed_kv:
        wire_control_plane()
    elif args.embed_kv:
        import threading as _threading
        import time as _time

        def _wire_when_quorum() -> None:
            deadline = _time.monotonic() + 300
            while _time.monotonic() < deadline:
                st = kv_raft.status()
                if st["leader"] is not None and st["members"]:
                    break
                _time.sleep(0.1)
            try:
                wire_control_plane()
            except Exception as exc:  # control plane down: node still serves
                print(f"WARN embedded control-plane wiring failed: {exc}",
                      flush=True)

        _threading.Thread(
            target=_wire_when_quorum, daemon=True, name="kv-seed-wire"
        ).start()

    def shutdown(signum, frame):
        # SystemExit propagates out of serve_forever's select loop; the
        # finally block below closes the database cleanly
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, shutdown)
    signal.signal(signal.SIGINT, shutdown)

    if device_marker is not None:
        print(device_marker, flush=True)
    print(f"LISTENING {server.host} {server.port}", flush=True)
    try:
        server.serve_forever()
    finally:
        if profiler is not None:
            profiler.stop()
        if selfmon is not None:
            selfmon.stop()
        if state["hb_stop"] is not None:
            state["hb_stop"].set()
        if state["cluster_db"] is not None:
            state["cluster_db"].stop()
        if kv is not None:
            kv.close()
        if kv_raft is not None:
            kv_raft.stop()
        if kv_server is not None:
            kv_server.stop()
        if scrubber is not None:
            scrubber.stop()
        if mediator is not None:
            mediator.stop()
        db.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
