"""m3aggregator-equivalent service binary.

Reference: /root/reference/src/cmd/services/m3aggregator/main/main.go — the
aggregator process wires config → rawtcp ingest server → flush manager →
downstream handler. Run:

    python -m m3_tpu.services.aggregator --port 6000 \
        --forward 127.0.0.1:9000 --forward-namespace default

Flushed aggregates forward to a dbnode's RPC write_batch (suffixed IDs), or
count locally when no --forward is given. Prints ``LISTENING <host> <port>``
once serving.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading
import time

from ..aggregator.aggregator import Aggregator
from ..aggregator.server import AggregatorIngestServer
from ..metrics.policy import StoragePolicy
from ..storage.series import NANOS


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="m3tpu-aggregator", description=__doc__)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--num-shards", type=int, default=16)
    p.add_argument("--policy", action="append", default=[], help="e.g. 10s:2d")
    p.add_argument("--flush-interval-secs", type=float, default=1.0)
    p.add_argument("--forward", default="", help="dbnode host:port for output")
    p.add_argument("--forward-namespace", default="default")
    p.add_argument(
        "--msg-consumer",
        default="",
        help="m3msg consumer endpoint host:port (the coordinator's "
        "--msg-listen): flushed aggregates ride the message bus with "
        "at-least-once acks instead of direct dbnode writes",
    )
    p.add_argument(
        "--msg-max-unacked",
        type=int,
        default=4096,
        help="m3msg backpressure watermark (0 = unbounded): when more "
        "than this many produced messages still await consumer acks, a "
        "flush first attempts one redelivery sweep and then PARKS the "
        "whole batch in the aggregator's pending queue for the next "
        "pass instead of growing the unacked queue without bound",
    )
    p.add_argument(
        "--kv-endpoint",
        default="",
        help="control-plane KV for replicated HA: leased leader election "
        "per --election-scope + shared flush times (followers keep warm "
        "state and take over without re-emitting windows)",
    )
    p.add_argument("--instance-id", default="agg0")
    p.add_argument("--election-scope", default="default")
    p.add_argument("--election-lease-secs", type=float, default=10.0)
    p.add_argument(
        "--selfmon-interval",
        type=float,
        default=0.0,
        help="self-scrape interval in seconds (0 disables): the "
        "aggregator's own metrics registry rides the m3msg bus to the "
        "coordinator (requires --msg-consumer) tagged __selfmon__, and "
        "lands in the coordinator's reserved _m3tpu namespace — the "
        "push-model twin of the coordinator's RPC pull (which can also "
        "scrape this process via --debug-port + --selfmon-peer)",
    )
    p.add_argument(
        "--debug-port",
        type=int,
        default=-1,
        help="serve health/metrics/profile RPC ops on this port (0 = "
        "ephemeral, -1 = disabled); prints DEBUG_LISTENING <host> <port> "
        "— the aggregator's Prometheus scrape + continuous-profiling "
        "surface (the ingest stream is one-way)",
    )
    p.add_argument(
        "--profile-hz",
        type=float,
        default=None,
        help="wall-clock stack-sampler rate (m3_tpu/profiling/), served "
        "on the debug port's `profile` op; default M3_TPU_PROFILE_HZ "
        "(19), 0 disables",
    )
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from .. import device

    device.configure_compile_cache()
    device.install_compile_counters()
    forward_node = None
    producer = None
    if args.forward:
        from ..net.client import RemoteNode

        forward_node = RemoteNode.connect(args.forward)
    if args.msg_consumer:
        # aggregator flush → m3msg producer → coordinator ingest
        # (aggregator/handler/ + msg/producer; serve.go wiring)
        from ..metrics.encoding import AggregatedMessage, encode_aggregated_batch
        from ..msg.bus import ConsumerService, Producer, Topic
        from ..msg.transport import RemoteConsumer
        from ..utils.hash import shard_for

        host, port = args.msg_consumer.rsplit(":", 1)
        topic = Topic(
            "aggregated_metrics",
            num_shards=args.num_shards,
            consumer_services=[ConsumerService("coordinator")],
        )
        producer = Producer(topic)
        producer.register(
            RemoteConsumer("coordinator", "coordinator0", host, int(port))
        )

    flushed_count = [0]
    backpressure_parks = [0]

    def handler(metrics):
        if producer is not None and args.msg_max_unacked > 0:
            # backpressure BEFORE any produce, so the park is atomic for
            # the batch: Aggregator.flush re-queues it in _pending_emit
            # (or a follower mirror re-emits it) — nothing is half-sent
            if producer.num_unacked > args.msg_max_unacked:
                producer.retry_unacked()
                if producer.num_unacked > args.msg_max_unacked:
                    backpressure_parks[0] += 1
                    raise RuntimeError(
                        f"m3msg backpressure: {producer.num_unacked} "
                        f"unacked > --msg-max-unacked={args.msg_max_unacked}"
                    )
        flushed_count[0] += len(metrics)
        if producer is not None:
            by_shard: dict[int, list] = {}
            for m in metrics:
                by_shard.setdefault(shard_for(m.id, args.num_shards), []).append(
                    AggregatedMessage(
                        m.id, m.time_nanos, m.value, m.policy, m.agg_type
                    )
                )
            for shard, msgs in by_shard.items():
                producer.produce(shard, encode_aggregated_batch(msgs))
        if forward_node is not None:
            forward_node.write_batch(
                args.forward_namespace,
                [(m.suffixed_id, m.time_nanos, m.value) for m in metrics],
            )

    # replicated HA over the networked control plane (election_mgr.go +
    # follower_flush_mgr.go): leased election decides the emitter; shared
    # flush times let a takeover resume exactly where the leader stopped
    election = flush_times = None
    kv = None
    if args.kv_endpoint:
        from ..aggregator.election import ElectionManager, FlushTimesStore
        from ..cluster.kv_service import RemoteKVStore

        kv = RemoteKVStore.connect(args.kv_endpoint)
        election = ElectionManager(
            kv, args.election_scope, args.instance_id,
            lease_secs=args.election_lease_secs,
        )
        flush_times = FlushTimesStore(kv, scope=args.election_scope)

    policies = tuple(StoragePolicy.parse(s) for s in args.policy) or ()
    agg = Aggregator(
        num_shards=args.num_shards,
        default_policies=policies,
        flush_handler=handler,
        election=election,
        flush_times=flush_times,
    )
    server = AggregatorIngestServer(agg, host=args.host, port=args.port)

    debug_server = None
    if args.debug_port >= 0:
        from ..net.server import DebugService, RpcServer

        debug_server = RpcServer(
            DebugService({"role": "aggregator", "instance": args.instance_id}),
            host=args.host,
            port=args.debug_port,
            component="aggregator",
        )
        debug_server.start()

    selfmon = None
    if args.selfmon_interval > 0:
        if producer is None:
            print(
                "WARN --selfmon-interval needs --msg-consumer (no bus to "
                "push telemetry on); self-scrape disabled",
                file=sys.stderr,
            )
        else:
            from ..selfmon import MsgSink, SelfMonCollector

            selfmon = SelfMonCollector(
                MsgSink(producer, args.num_shards),
                interval=args.selfmon_interval,
                instance=args.instance_id,
                component="aggregator",
            ).start()

    # always-on continuous profiler: the aggregator has no storage, so
    # the device-memory accountant only tracks live jax buffers
    from ..profiling import start_sampler

    profiler = start_sampler(hz=args.profile_hz, instance=args.instance_id)

    stop = threading.Event()
    flush_errors = [0]

    def flush_loop():
        while not stop.wait(args.flush_interval_secs):
            try:
                agg.flush(time.time_ns())
                if producer is not None:
                    producer.retry_unacked()  # at-least-once redelivery sweep
            except Exception as exc:
                # keep the loop alive (mediator-style resilience); drained
                # aggregates stay in agg._pending_emit and retry next pass
                flush_errors[0] += 1
                print(f"flush error ({flush_errors[0]}): {exc}", file=sys.stderr)

    flusher = threading.Thread(target=flush_loop, name="m3tpu-agg-flush", daemon=True)
    flusher.start()

    def shutdown(signum, frame):
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, shutdown)
    signal.signal(signal.SIGINT, shutdown)

    print(f"LISTENING {server.host} {server.port}", flush=True)
    if debug_server is not None:
        print(f"DEBUG_LISTENING {debug_server.host} {debug_server.port}", flush=True)
    try:
        server.serve_forever()
    finally:
        stop.set()
        if profiler is not None:
            profiler.stop()
        if selfmon is not None:
            selfmon.stop()
        agg.flush(time.time_ns() + 10**12)  # drain on shutdown
        if producer is not None:
            producer.retry_unacked()
        if forward_node is not None:
            forward_node.close()
        if debug_server is not None:
            debug_server.stop()
        if kv is not None:
            kv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
