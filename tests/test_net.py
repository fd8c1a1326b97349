"""Network data plane tests: wire codec, socket node service, and a real
multi-process cluster (separate python processes on localhost sockets) for
quorum/node-down/restart behavior (rpc.thrift:44-87 surface,
tchannelthrift/node/service.go:449,626, dtest-style process cluster)."""

import math

import numpy as np
import pytest

from m3_tpu.codec.m3tsz import Datapoint
from m3_tpu.index.query import conj, disj, neg, regexp, term
from m3_tpu.net import wire
from m3_tpu.utils.xtime import Unit

NANOS = 1_000_000_000
T0 = 1_600_000_000 * NANOS
HOUR = 3600 * NANOS


def test_wire_value_roundtrip():
    vals = [
        None,
        True,
        False,
        0,
        -(2**62),
        2**62,
        1.5,
        math.inf,
        b"",
        b"\x00\xffbytes",
        "unicode ☃",
        [1, [2, b"x"], {"a": None}],
        {"op": "write", "t": 123, "nested": {"k": [True, 2.5]}},
    ]
    for v in vals:
        assert wire.loads(wire.dumps(v)) == v
    # NaN needs special compare
    out = wire.loads(wire.dumps(float("nan")))
    assert math.isnan(out)


def _grid(dtype, shape, seed=38):
    rng = np.random.default_rng(seed)
    dtype = np.dtype(dtype)
    if dtype == np.bool_:
        return np.asarray(rng.random(shape) < 0.5)
    if dtype.kind == "f":
        return np.asarray(rng.standard_normal(shape), dtype)
    info = np.iinfo(dtype)
    return np.asarray(rng.integers(info.min, info.max, shape, dtype=dtype, endpoint=True))


def _nan_grid():
    """A float64 reply grid holding a NaN with a payload of its own."""
    a = _grid(np.float64, (400, 61))
    a[200, 30] = np.uint64(0x7FF8_0000_DEAD_BEEF).view(np.float64)
    return a


ARRAY_CASES = [
    pytest.param(_grid(dtype, shape), id=f"{dtype.str[1:]}-{shape}")
    for dtype in wire.ARRAY_DTYPES for shape in [(), (0,), (0, 61), (400, 61)]
] + [
    pytest.param(_grid(np.float64, (61, 400)).T, id="f8-not-contiguous"),
    pytest.param(_grid(np.float64, (400, 61)).astype(">f8"), id="f8-big-endian"),
    pytest.param(_grid(np.int32, (3, 5)).astype(">i4"), id="i4-big-endian"),
    pytest.param(_nan_grid(), id="f8-nan-payload"),
]


@pytest.mark.parametrize("v", ARRAY_CASES)
def test_wire_array_value_roundtrip(v):
    got = wire.loads(wire.dumps({"values": v, "n": 1}))["values"]
    assert got.shape == v.shape and got.dtype == v.dtype.newbyteorder("<")
    # bit for bit, NaN payloads included
    bits = f"u{v.dtype.itemsize}"
    assert np.array_equal(got.view(bits), v.astype(got.dtype).view(bits))
    assert got.flags.writeable and got.base is None
    # a short buffer is refused, wherever it is cut: tag, header, shape, data
    raw = wire.dumps(v)
    for cut in (1, 2, 3, len(raw) - 1):
        with pytest.raises(ValueError):
            wire.loads(raw[:cut])


@pytest.mark.parametrize("v", [np.array(["x", None], dtype=object), np.array(["ab"]),
                               np.zeros(2, np.complex128)], ids=["object", "str", "complex"])
def test_wire_array_value_refuses_other_dtypes(v):
    with pytest.raises(TypeError):
        wire.dumps({"values": v})


def test_wire_query_roundtrip():
    q = conj(
        term(b"name", b"cpu"),
        disj(regexp(b"host", b"web-.*"), term(b"host", b"db0")),
        neg(term(b"dc", b"east")),
    )
    assert wire.query_from_wire(wire.query_to_wire(q)) == q


def test_wire_datapoints_roundtrip():
    dps = [
        Datapoint(T0, 1.5),
        Datapoint(T0 + NANOS, -2.0, Unit.MILLISECOND),
        Datapoint(T0 + 2 * NANOS, 3.0, Unit.SECOND, b"ann"),
    ]
    assert wire.dps_from_wire(wire.dps_to_wire(dps)) == dps


@pytest.fixture
def served_db(tmp_path):
    from m3_tpu.net.client import RemoteNode
    from m3_tpu.net.server import NodeServer, NodeService
    from m3_tpu.storage.database import Database, NamespaceOptions

    db = Database(str(tmp_path), num_shards=4)
    db.create_namespace("default", NamespaceOptions(block_size_nanos=HOUR))
    db.bootstrap()
    server = NodeServer(NodeService(db, node_id="n0", assigned_shards={0, 1, 2, 3}))
    server.start()
    client = RemoteNode("127.0.0.1", server.port, node_id="n0")
    yield db, client
    client.close()
    server.stop()
    db.close()


def test_node_service_roundtrip(served_db):
    db, client = served_db
    assert client.health()["bootstrapped"] is True
    assert client.owned_shards() == {0, 1, 2, 3}

    client.write("default", b"plain", T0 + NANOS, 42.0)
    dps = client.read("default", b"plain", T0, T0 + HOUR)
    assert [(dp.timestamp, dp.value) for dp in dps] == [(T0 + NANOS, 42.0)]

    tags = ((b"host", b"a"), (b"name", b"cpu"))
    sid = client.write_tagged("default", tags, T0 + 2 * NANOS, 7.0)
    assert isinstance(sid, bytes)
    res = client.fetch_tagged("default", term(b"name", b"cpu"), T0, T0 + HOUR)
    assert len(res) == 1
    got_sid, got_tags, got_dps = res[0]
    assert got_sid == sid and got_tags == tags
    assert [dp.value for dp in got_dps] == [7.0]

    ids = client.query_ids("default", term(b"host", b"a"), T0, T0 + HOUR)
    assert [bytes(d) for d, _ in ids["docs"]] == [sid] and ids["exhaustive"]

    streamed = client.stream_shard("default", db.namespaces["default"].shard_for(sid).id)
    assert any(s[0] == sid for s in streamed)


def test_node_service_remote_errors_are_per_request(served_db):
    from m3_tpu.net.client import RemoteError

    db, client = served_db
    with pytest.raises(RemoteError):
        client.write("nope", b"x", T0, 1.0)  # unknown namespace
    # the connection survives the failed request
    client.write("default", b"x", T0 + NANOS, 1.0)
    assert len(client.read("default", b"x", T0, T0 + HOUR)) == 1


def test_session_fetch_gates_on_touched_shard_only():
    """Weak #8 fix: a fully-down shard fails only reads that touch it."""
    from m3_tpu.cluster.topology import ConsistencyLevel
    from m3_tpu.client.session import ConsistencyError
    from m3_tpu.testing.cluster import LocalCluster
    from m3_tpu.utils.hash import shard_for

    cluster = LocalCluster(num_nodes=3, num_shards=6, replica_factor=1)
    session = cluster.session(
        write_cl=ConsistencyLevel.ONE, read_cl=ConsistencyLevel.ONE
    )
    # find two ids on shards owned by different nodes
    placement = cluster.placement_svc.get()

    def owner(sid):
        shard = shard_for(sid, 6)
        return placement.instances_for_shard(shard)[0].id

    ids = [f"s{i}".encode() for i in range(64)]
    a = next(s for s in ids if owner(s) == "node0")
    b = next(s for s in ids if owner(s) == "node1")
    session.write(a, T0 + NANOS, 1.0)
    session.write(b, T0 + NANOS, 2.0)

    cluster.nodes["node1"].is_up = False
    # shard of `a` is healthy: fetch succeeds
    assert [dp.value for dp in session.fetch(a, T0, T0 + HOUR)] == [1.0]
    # shard of `b` has zero live replicas: only ITS fetch fails
    with pytest.raises(ConsistencyError):
        session.fetch(b, T0, T0 + HOUR)


def test_multiprocess_cluster_quorum_and_restart(tmp_path):
    from m3_tpu.client.session import ConsistencyError
    from m3_tpu.testing.proc_cluster import ProcCluster

    cluster = ProcCluster(
        num_nodes=3, num_shards=4, replica_factor=3, base_dir=str(tmp_path)
    )
    try:
        session = cluster.session()
        tags = ((b"host", b"w1"), (b"name", b"reqs"))
        sid = session.write_tagged(tags, T0 + NANOS, 1.0)
        session.write(sid, T0 + 2 * NANOS, 2.0)

        res = session.fetch_tagged(term(b"name", b"reqs"), T0, T0 + HOUR)
        assert len(res) == 1
        assert [dp.value for dp in res[0][2]] == [1.0, 2.0]

        # kill one process: majority quorum still holds over sockets
        cluster.nodes["node2"].kill()
        session.write(sid, T0 + 3 * NANOS, 3.0)
        res = session.fetch_tagged(term(b"name", b"reqs"), T0, T0 + HOUR)
        assert [dp.value for dp in res[0][2]] == [1.0, 2.0, 3.0]

        # kill a second: majority (2/3) is unreachable
        cluster.nodes["node1"].kill()
        with pytest.raises(ConsistencyError):
            session.write(sid, T0 + 4 * NANOS, 4.0)
        with pytest.raises(ConsistencyError):
            session.fetch_tagged(term(b"name", b"reqs"), T0, T0 + HOUR)

        # restart node1: it bootstraps from its WAL and serves reads again.
        # the failed write above still landed on node0 (partial applies are
        # not undone, as in the reference), so the merged read includes 4.0
        cluster.restart("node1")
        session = cluster.session()
        res = session.fetch_tagged(term(b"name", b"reqs"), T0, T0 + HOUR)
        assert [dp.value for dp in res[0][2]] == [1.0, 2.0, 3.0, 4.0]
    finally:
        cluster.close()
