"""Tracing + debug dump (reference: x/context opentracing wiring,
x/debug/debug.go zip dump)."""

import io
import json
import urllib.request
import zipfile

import pytest

from m3_tpu.utils.trace import Tracer


def test_span_nesting_and_timing():
    tr = Tracer()
    with tr.span("outer", op="write") as outer:
        with tr.span("inner"):
            pass
    spans = tr.dump()
    assert [s["name"] for s in spans] == ["inner", "outer"]
    inner, outer_d = spans
    assert inner["parentId"] == outer_d["spanId"]
    assert inner["traceId"] == outer_d["traceId"]
    assert outer_d["parentId"] is None
    assert outer_d["durationNanos"] >= inner["durationNanos"] >= 0
    assert outer_d["tags"] == {"op": "write"}


def test_span_error_capture():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("failing"):
            raise ValueError("boom")
    (span,) = tr.dump()
    assert span["error"] == "ValueError: boom"


def test_sampling_zero_records_nothing():
    tr = Tracer(sample_rate=0.0)
    with tr.span("never"):
        pass
    assert tr.dump() == []
    assert tr.started == 1


def test_ring_buffer_bounded():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    spans = tr.dump()
    assert len(spans) == 4
    assert [s["name"] for s in spans] == ["s6", "s7", "s8", "s9"]


@pytest.fixture(scope="module")
def server():
    from m3_tpu.services.coordinator import Coordinator, serve

    coord = Coordinator()
    srv, port = serve(coord)
    yield f"http://127.0.0.1:{port}", coord
    srv.shutdown()


def test_debug_traces_route(server):
    import time

    base, _ = server
    urllib.request.urlopen(f"{base}/health").read()  # pollers are NOT traced
    urllib.request.urlopen(f"{base}/api/v1/labels").read()
    # the labels response can arrive a beat before the server records its
    # span — poll briefly rather than racing the span exit
    deadline = time.monotonic() + 5.0
    while True:
        out = json.loads(urllib.request.urlopen(f"{base}/debug/traces").read())
        spans = out["spans"]
        traced = any(
            s["name"] == "http.get" and s["tags"].get("path") == "/api/v1/labels"
            for s in spans
        )
        if traced or time.monotonic() > deadline:
            break
        time.sleep(0.02)
    assert traced
    assert not any(s["tags"].get("path") == "/health" for s in spans)


def test_debug_dump_zip(server):
    base, _ = server
    raw = urllib.request.urlopen(f"{base}/debug/dump").read()
    z = zipfile.ZipFile(io.BytesIO(raw))
    names = set(z.namelist())
    assert {"stacks.txt", "metrics.txt", "traces.json",
            "namespaces.json", "placement.json"} <= names
    assert b"thread" in z.read("stacks.txt")
    ns = json.loads(z.read("namespaces.json"))
    assert "default" in ns


# --- stages (utils/trace.py) and the profile reduction (profiling/gaps.py) ---


class _Record:
    """What a stage needs of a request record (QueryStats has it)."""

    def __init__(self):
        self.stages, self.current_stage, self.seen = {}, None, []

    def add_stage(self, name, secs):
        self.stages[name] = self.stages.get(name, 0.0) + secs


def test_stage_feeds_table_record_and_span_only_when_sampled():
    import time

    from m3_tpu.utils.instrument import Registry

    reg = Registry(prefix="m3tpu_")
    tr = Tracer(registry=reg)
    rec = _Record()
    tr.bind_record(rec)
    with tr.request("write_batch"):  # no context, no capture: unsampled
        with tr.stage("write.route") as sg:
            assert rec.current_stage == "write.route"
        assert rec.current_stage == "rpc.server.write_batch"
    tr.bind_record(None)
    assert tr.dump() == [] and tr.sampled == 0
    assert sg.seconds == rec.stages["write.route"] > 0
    table = tr.stage_table()
    assert table[("write_batch", "write.route")][2] == 1
    assert table[("write_batch", "rpc.server.write_batch")][0] >= sg.seconds
    # the thread-CPU clock is read only where the request is sampled
    assert table[("write_batch", "rpc.server.write_batch")][1] == 0
    with tr.stage("commitlog.fsync", op="commitlog"):  # outside any request
        pass
    assert ("commitlog", "commitlog.fsync") in tr.stage_table()
    assert 'm3tpu_stage_calls_total{op="write_batch",stage="write.route"} 1.0' in reg.expose()
    # sampled three ways: a sampled wire context, an open span, a capture
    with tr.request("flush", {"trace_id": 9, "span_id": 4, "sampled": True}):
        with tr.stage("seal.encode"):
            pass
    with tr.span("outer"):
        with tr.stage("seal.admit"):
            pass
    tr.capturing = True
    with tr.request("query_range"):
        t_end = time.thread_time() + 0.02  # CPU, not wall: a loaded host
        while time.thread_time() < t_end:  # may run this thread half the time
            pass
    with tr.request("health", spans=False):
        pass
    tr.capturing = False
    wall, cpu, _calls = tr.stage_table()[("query_range", "rpc.server.query_range")]
    assert 0.01 <= cpu <= wall + 0.005
    spans = {s["name"]: s for s in tr.dump()}
    assert set(spans) == {"rpc.server.flush", "seal.encode", "outer", "seal.admit",
                          "rpc.server.query_range"}
    assert spans["seal.encode"]["parentId"] == spans["rpc.server.flush"]["spanId"]
    assert spans["seal.encode"]["traceId"] == f"{9:016x}"
    assert spans["seal.admit"]["parentId"] == spans["outer"]["spanId"]


def test_stage_table_rows_are_capped():
    from m3_tpu.utils import trace
    from m3_tpu.utils.instrument import Registry

    tr = Tracer(registry=Registry())
    for i in range(trace._MAX_STAGE_ROWS + 50):
        with tr.stage("wire.decode", op=f"bogus_{i}"):
            pass
    table = tr.stage_table()
    assert len(table) <= trace._MAX_STAGE_ROWS + 1
    assert table[("_overflow", "wire.decode")][2] == 50


def test_gaps_innermost_and_overlap():
    from m3_tpu.profiling import gaps

    segs = gaps.innermost([(0, 100, "rpc.server.x"), (10, 40, "write.route"),
                           (20, 30, "ingest.sync"), (60, 90, "write.buffer")])
    assert segs == [
        (0, 10, "rpc.server.x"), (10, 20, "write.route"), (20, 30, "ingest.sync"),
        (30, 40, "write.route"), (40, 60, "rpc.server.x"), (60, 90, "write.buffer"),
        (90, 100, "rpc.server.x"),
    ]
    assert gaps.overlap(segs, [(5, 25), (85, 200)]) == {
        "rpc.server.x": 5 + 10, "write.route": 10, "ingest.sync": 5, "write.buffer": 5}
    assert gaps.merge([(5, 9), (0, 6), (20, 30)]) == [(0, 9), (20, 30)]


def test_gaps_attributes_idle_to_the_open_stage_or_no_stage(tmp_path):
    """A capture the test makes itself on the CPU backend: a jitted call,
    a sleep inside a stage (while a second thread sleeps in a stage of its
    own), the call again, a sleep with no stage open, the call a third
    time."""
    import threading
    import time

    import jax
    import jax.numpy as jnp

    from m3_tpu.profiling import gaps
    from m3_tpu.utils.trace import TRACER

    fn = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256), jnp.float32)
    fn(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    def writer():
        with TRACER.stage("commitlog.fsync", op="commitlog"):
            time.sleep(0.04)

    other = threading.Thread(target=writer)
    try:
        with TRACER.request("write_batch"):
            fn(x).block_until_ready()
            with TRACER.stage("write.buffer"):
                other.start()
                time.sleep(0.08)
                other.join(timeout=10)
            fn(x).block_until_ready()
        time.sleep(0.05)
        fn(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    out = gaps.reduce_trace(str(tmp_path))
    by_stage = out["gap_seconds_by_stage"]
    assert 0.075 <= by_stage["write.buffer"] <= 0.12
    # thread seconds: both threads' lines are named "python" in the capture
    assert 0.035 <= by_stage["commitlog.fsync"] <= 0.08 and out["threads"] == 2
    assert 0.045 <= by_stage["no_stage"] <= 0.09
    assert by_stage.get("rpc.server.write_batch", 0.0) < 0.02
    assert 0.12 <= out["idle_s"] <= 0.25 and out["busy_s"] > 0
    st = out["stages"]
    assert st["write.buffer"]["calls"] == 1
    assert st["write.buffer"]["self_s"] == pytest.approx(st["write.buffer"]["total_s"])
    root = st["rpc.server.write_batch"]
    assert root["self_s"] == pytest.approx(root["total_s"] - st["write.buffer"]["total_s"])
