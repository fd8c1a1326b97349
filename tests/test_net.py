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


def _fleet_tags(hosts=400):
    """TSBS cpu-only labels: 10 host tags and the metric name a host, a few
    values of each region, os and team shared across the fleet."""
    rng = np.random.default_rng(40)
    out = []
    for h in range(hosts):
        tags = {
            b"__name__": b"cpu_usage_user", b"hostname": b"host_%d" % h,
            b"region": b"region-%d" % rng.integers(3), b"datacenter": b"dc-%d" % rng.integers(9),
            b"rack": b"%d" % rng.integers(100), b"os": b"Ubuntu%d.04LTS" % rng.integers(15, 17),
            b"arch": b"x%d" % rng.choice([64, 86]), b"team": b"team-%d" % rng.integers(4),
            b"service": b"%d" % rng.integers(20), b"service_version": b"%d" % rng.integers(2),
            b"service_environment": b"env-%d" % rng.integers(3),
        }
        out.append(tuple(sorted(tags.items())))
    return out


TAG_TABLE_CASES = [
    pytest.param([], id="no-series"),
    pytest.param([((b"__name__", b"cpu"), (b"host", b"a"))], id="one-series"),
    pytest.param([(), ((b"a", b"1"),), ((b"a", b"1"), (b"b", b"2"), (b"c", b""))],
                 id="ragged"),
    pytest.param([((b"x", b"x"), (b"x", b"\x00\xff")),
                  ((b"x", "☃".encode()), ("é".encode(), b"x"))], id="repeated-non-ascii"),
    pytest.param(_fleet_tags(), id="fleet-400x11"),
]


@pytest.mark.parametrize("series", TAG_TABLE_CASES)
def test_wire_tag_table_decodes_as_the_lists_it_replaces(series):
    table = wire.tag_table(series)
    raw = wire.dumps(table)
    assert raw[:1] == b"g"
    got = wire.loads(wire.dumps({"metas": table, "n": 1}))["metas"]
    # equal to, and of the types of, the same labels sent as lists of 'b'
    want = wire.loads(wire.dumps([[[k, v] for k, v in tags] for tags in series]))
    assert got == want
    assert type(got) is list and all(type(s) is list for s in got)
    pairs = [p for s in got for p in s]
    assert all(type(p) is list and len(p) == 2 for p in pairs)
    assert all(type(x) is bytes for p in pairs for x in p)
    assert len({id(p) for p in pairs}) == len(pairs)  # no pair list is shared
    # the distinct strings are written once
    strings = {x for tags in series for pair in tags for x in pair}
    assert len(raw) == 9 + sum(4 + len(x) for x in strings) + 4 * len(series) + 8 * len(pairs)
    # a short buffer is refused, wherever it is cut
    cuts = range(1, len(raw)) if len(raw) < 512 else (1, 3, 5, 9, len(raw) // 2, len(raw) - 1)
    for cut in cuts:
        with pytest.raises(ValueError):
            wire.loads(raw[:cut])


def test_wire_tag_table_refuses_an_index_past_its_strings():
    raw = bytearray(wire.dumps(wire.tag_table([((b"a", b"b"),)])))
    assert raw[-4:] == b"\x01\x00\x00\x00"  # the value's index: the second string
    raw[-4:] = b"\x02\x00\x00\x00"
    with pytest.raises(ValueError):
        wire.loads(bytes(raw))
    with pytest.raises(ValueError):
        wire.tag_table([((b"a", b"b", b"c"),)])


def test_wire_plain_bytes_stay_a_b_value():
    assert wire.dumps(b"g\x00") == b"b\x02\x00\x00\x00g\x00"
    assert wire.dumps(bytearray(b"g")) == b"b\x01\x00\x00\x00g"
    assert wire.loads(wire.dumps([b"g", b""])) == [b"g", b""]


def test_tag_table_memo_hits_by_identity_under_contention():
    """Handler threads share one memo: each gets the table of the metas it
    passed, never another set's, while the sets outnumber the entries."""
    import sys
    import threading

    from m3_tpu.block.core import SeriesMeta
    from m3_tpu.net import server

    memo = server.TagTableMemo()
    fleet = _fleet_tags(8)
    sets = [[SeriesMeta(tags=tags + ((b"set", b"%d" % k),)) for tags in fleet]
            for k in range(server._TAG_TABLE_MEMO_ENTRIES + 16)]
    # the same tags in new objects miss: the memo keys on identity alone
    twin = [SeriesMeta(tags=m.tags) for m in sets[0]]
    assert memo.table(sets[0]) is memo.table(sets[0])
    assert memo.table(twin) is not memo.table(sets[0])
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for k in rng.integers(len(sets), size=300):
                got = wire.loads(memo.table(sets[k]).encoded)
                assert got == [[list(p) for p in m.tags] for m in sets[k]]
        except Exception as exc:  # read by the main thread
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(s,)) for s in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
    assert len(memo._entries) <= server._TAG_TABLE_MEMO_ENTRIES


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
