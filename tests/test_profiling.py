"""Continuous profiling (m3_tpu/profiling/): the host-tier stack sampler
(determinism, bounded tables, retention, folded golden), the device tier
(HLO cost capture — CPU-backend tolerant — and the device-memory split),
the fleet merge (dead peers counted, per-instance tags), the per-shard
heat satellite, and the selfmon round-trip of m3tpu_profile_*."""

import gc
import weakref

import numpy as np
import pytest

from m3_tpu import profiling
from m3_tpu.profiling import (
    StackSampler,
    collect_device_memory,
    collect_fleet_profile,
    folded_text,
    merge_profiles,
    process_profile,
)
from m3_tpu.profiling.sampler import OVERFLOW_STACK, TRUNCATED_FRAME, fold_frames
from m3_tpu.utils.instrument import KernelProfiler, Registry

NANOS = 1_000_000_000
T0 = 1_600_000_000 * NANOS


# --- fake frames: fold_frames only touches f_code/f_back ---


class _Code:
    def __init__(self, filename, name):
        self.co_filename = filename
        self.co_name = name


class _Frame:
    def __init__(self, name, filename="proj/pkg/mod.py", back=None):
        self.f_code = _Code(filename, name)
        self.f_back = back


def _chain(*names):
    """Build a leaf frame whose f_back chain is names root->leaf."""
    frame = None
    for name in names:
        frame = _Frame(name, back=frame)
    return frame


def _counter_value(reg, name, labels=None):
    fam = reg.collect().get(name)
    if not fam:
        return 0.0
    want = labels or {}
    return sum(
        c["value"]
        for c in fam["children"]
        if all(c["labels"].get(k) == v for k, v in want.items())
    )


# --- host tier: sampler ---


def test_fold_frames_root_first_and_truncation():
    stack, truncated = fold_frames(_chain("root", "mid", "leaf"), max_depth=8)
    assert stack == "proj/pkg/mod.py:root;proj/pkg/mod.py:mid;proj/pkg/mod.py:leaf"
    assert truncated == 0
    # deeper than max_depth: LEAF-most frames kept behind the marker
    stack, truncated = fold_frames(_chain("a", "b", "c", "d", "e"), max_depth=2)
    assert truncated == 3
    parts = stack.split(";")
    assert parts[0] == TRUNCATED_FRAME
    assert [p.split(":")[1] for p in parts[1:]] == ["d", "e"]


def test_sampler_determinism_with_injected_clock():
    """Same fake frames + same clock sequence -> bit-identical tables on
    two independent samplers (the reproducibility contract)."""

    def run():
        reg = Registry(prefix="m3tpu_")
        now = [0.0]
        s = StackSampler(
            hz=0, bucket_seconds=10.0, window_seconds=60.0,
            clock=lambda: now[0], registry=reg,
        )
        for tick in range(25):
            now[0] = tick * 0.25
            s.sample_once(
                frames={
                    1: _chain("serve", "fetch", "decode"),
                    2: _chain("serve", "flush" if tick % 3 else "seal"),
                }
            )
        return s.profile(seconds=60)

    a, b = run(), run()
    assert a["folded"] == b["folded"] and a["samples"] == b["samples"]
    assert a["samples"] == 50  # 25 ticks x 2 threads


def test_bounded_table_and_truncation_counters():
    reg = Registry(prefix="m3tpu_")
    s = StackSampler(
        hz=0, max_stacks=2, max_depth=3, clock=lambda: 0.0, registry=reg
    )
    s.sample_once(now=0.0, frames={1: _chain("a", "x")})
    s.sample_once(now=0.0, frames={1: _chain("b", "x")})
    # third DISTINCT stack in the same bucket folds into [overflow]
    s.sample_once(now=0.0, frames={1: _chain("c", "x")})
    folded = s.profile()["folded"]
    assert folded[OVERFLOW_STACK] == 1 and len(folded) == 3
    assert _counter_value(reg, "m3tpu_profile_stacks_truncated_total") == 1
    # deep stack: frame truncation is counted
    s.sample_once(now=0.0, frames={1: _chain("a", "x", "y", "z", "w")})
    assert _counter_value(reg, "m3tpu_profile_frames_truncated_total") == 2
    assert _counter_value(reg, "m3tpu_profile_samples_total") == 4


def test_windowed_retention_drops_old_buckets():
    reg = Registry(prefix="m3tpu_")
    now = [5.0]
    s = StackSampler(
        hz=0, bucket_seconds=10.0, window_seconds=30.0,
        clock=lambda: now[0], registry=reg,
    )
    s.sample_once(frames={1: _chain("old")})
    now[0] = 95.0
    s.sample_once(frames={1: _chain("new")})  # eviction runs here
    folded = s.profile(seconds=600)  # clamped to the window
    assert [k.split(":")[-1] for k in folded["folded"]] == ["new"]
    # a narrower ask only merges covering buckets
    assert s.profile(seconds=10)["folded"]


def test_profile_golden_contains_synthetic_hot_frame():
    """A REAL sample (sys._current_frames) of this thread must fold a
    stack through the known hot frame, root-first."""
    s = StackSampler(hz=0, clock=lambda: 0.0)

    def _synthetic_hot_frame_xyz():
        return s.sample_once(now=0.0)

    assert _synthetic_hot_frame_xyz() >= 1
    folded = s.profile()["folded"]
    hot = [st for st in folded if "_synthetic_hot_frame_xyz" in st]
    assert hot, list(folded)
    stack = hot[0]
    # root-first folded order: the test fn sits above the hot helper,
    # which sits above the sampler's own collection frame
    assert stack.index("test_profile_golden") < stack.index(
        "_synthetic_hot_frame_xyz"
    ) < stack.index("sample_once")


def test_a_real_sample_keeps_no_sampled_frame_alive():
    """A sample of live threads must not leave their frames in a reference
    cycle: a call that was sampled frees its locals when it returns, by
    reference counting alone, and no pass of the collector is needed."""
    s = StackSampler(hz=0, clock=lambda: 0.0)

    class Local:
        pass

    refs = []

    def sampled_call():
        held = Local()
        refs.append(weakref.ref(held))
        assert s.sample_once(now=0.0) >= 1

    was = gc.isenabled()
    gc.disable()
    try:
        sampled_call()
        assert refs[0]() is None
    finally:
        if was:
            gc.enable()


def test_folded_text_format():
    assert folded_text({"a;b": 3, "c": 5}) == "c 5\na;b 3\n"
    assert folded_text({}) == ""


def test_sampler_errors_counted_never_raised():
    reg = Registry(prefix="m3tpu_")
    s = StackSampler(hz=0, clock=lambda: 0.0, registry=reg)

    class Boom:
        @property
        def f_code(self):
            raise RuntimeError("torn frame")

        f_back = None

    class BoomFrames(dict):
        def items(self):
            raise RuntimeError("no frames")

    assert s.sample_once(now=0.0, frames=BoomFrames()) == 0
    assert s.sample_once(now=0.0, frames={1: Boom()}) == 0
    assert _counter_value(reg, "m3tpu_profile_errors_total") == 2


def test_process_profile_install_surface():
    prev = profiling.installed()
    try:
        profiling.install(None)
        empty = process_profile()
        assert empty["enabled"] is False and empty["folded"] == {}
        s = StackSampler(hz=0, instance="me", clock=lambda: 0.0)
        s.sample_once(now=0.0, frames={1: _chain("f")})
        profiling.install(s)
        assert process_profile()["samples"] == 1
        # the dbnode wire op serves the same shape
        from m3_tpu.net.server import NodeService

        out = NodeService(None).op_profile({"seconds": 30})
        assert out["instance"] == "me" and out["samples"] == 1
    finally:
        profiling.install(prev)


# --- device tier: HLO cost capture (CPU tolerant) + memory split ---


def test_kernel_cost_capture_once_per_signature():
    import jax
    import jax.numpy as jnp

    reg = Registry(prefix="m3tpu_")
    prof = KernelProfiler("cost_probe", registry=reg, sample_rate=1.0)
    assert prof.capture_costs  # sampling on => cost capture on
    fn = jax.jit(lambda x: (x * 2.0 + 1.0).sum())
    x = jnp.zeros((32, 32))
    with prof.dispatch(("k", x.shape), cost=(fn, (x,), {})) as d:
        d.done(fn(x))
    captures = _counter_value(
        reg, "m3tpu_kernel_cost_captures_total", {"kernel": "cost_probe"}
    )
    errors = _counter_value(
        reg, "m3tpu_kernel_cost_errors_total", {"kernel": "cost_probe"}
    )
    # CPU-backend tolerant: a backend without cost analysis counts an
    # error instead of raising; when it works, flops/bytes are recorded
    assert captures + errors == 1
    if captures:
        cost = prof.cost_analysis()
        (row,) = cost.values()
        assert row["flops"] >= 0.0 and row["bytes_accessed"] >= 0.0
        assert _counter_value(
            reg, "m3tpu_kernel_flops", {"kernel": "cost_probe"}
        ) == row["flops"]
    # same signature again: not a compile, no second capture
    with prof.dispatch(("k", x.shape), cost=(fn, (x,), {})) as d:
        d.done(fn(x))
    assert _counter_value(
        reg, "m3tpu_kernel_cost_captures_total", {"kernel": "cost_probe"}
    ) + _counter_value(
        reg, "m3tpu_kernel_cost_errors_total", {"kernel": "cost_probe"}
    ) == 1


def test_kernel_cost_capture_off_by_default():
    reg = Registry(prefix="m3tpu_")
    prof = KernelProfiler("cost_off", registry=reg, sample_rate=0.0)
    assert not prof.capture_costs
    assert prof.capture_cost("k", None) is None  # no-op, no error counted
    assert _counter_value(
        reg, "m3tpu_kernel_cost_errors_total", {"kernel": "cost_off"}
    ) == 0


def test_kernel_cost_env_zero_forces_capture_off(monkeypatch):
    # M3_TPU_PROFILE_COST=0 must win over an active sampling rate (the
    # documented opt-out of the extra per-signature AOT compile)
    monkeypatch.setenv("M3_TPU_PROFILE_COST", "0")
    reg = Registry(prefix="m3tpu_")
    prof = KernelProfiler("cost_forced_off", registry=reg, sample_rate=1.0)
    assert not prof.capture_costs
    monkeypatch.setenv("M3_TPU_PROFILE_COST", "1")
    prof = KernelProfiler("cost_forced_on", registry=reg, sample_rate=0.0)
    assert prof.capture_costs


def test_kernel_cost_capture_tolerates_broken_lowerable():
    reg = Registry(prefix="m3tpu_")
    prof = KernelProfiler("cost_broken", registry=reg, capture_costs=True)

    class NotLowerable:
        pass

    assert prof.capture_cost("k", NotLowerable()) is None
    assert _counter_value(
        reg, "m3tpu_kernel_cost_errors_total", {"kernel": "cost_broken"}
    ) == 1


def test_device_memory_split(tmp_path):
    from m3_tpu.resident import ResidentOptions
    from m3_tpu.storage.database import Database, NamespaceOptions

    db = Database(
        str(tmp_path), num_shards=2, commitlog_enabled=False,
        resident_options=ResidentOptions(max_bytes=1 << 22),
    )
    db.create_namespace("d", NamespaceOptions())
    try:
        # before any admission: the lazy pool buffer must NOT be forced
        # into existence by accounting
        out = collect_device_memory(db)
        assert out["resident_pool"] == 0
        sid = db.write_tagged("d", ((b"__name__", b"g"),), T0, 1.0)
        db.write_batch("d", [(sid, T0 + i * 10 * NANOS, float(i)) for i in range(64)])
        db.flush("d", T0 + 4 * 3600 * NANOS)
        out = collect_device_memory(db)
        assert out["resident_pool"] > 0
        assert out["total_live_jax_bytes"] >= out["resident_pool"]
        assert set(out) >= {"resident_pool", "decoded_cache", "other"}
        # the gauges published for exposition / selfmon
        from m3_tpu.utils.instrument import DEFAULT

        fam = DEFAULT.collect()["m3tpu_device_memory_bytes"]
        kinds = {c["labels"]["kind"]: c["value"] for c in fam["children"]}
        assert kinds["resident_pool"] == out["resident_pool"]
    finally:
        db.close()
    # db-less processes (aggregator) still account live buffers
    assert "other" in collect_device_memory(None)


# --- fleet tier: merge with per-instance tags + dead peers ---


def _prof(folded):
    return {"enabled": True, "folded": folded, "samples": sum(folded.values())}


def test_merge_profiles_by_stack_with_instance_tags():
    merged = merge_profiles(
        [
            ("node0", _prof({"serve;decode": 3, "serve;flush": 1})),
            ("node1", _prof({"serve;decode": 2})),
        ]
    )
    assert merged["folded"] == {"serve;decode": 5, "serve;flush": 1}
    assert merged["byInstance"]["serve;decode"] == {"node0": 3, "node1": 2}


def test_fleet_profile_merges_and_counts_dead_peer():
    class Peer:
        def profile(self, seconds=None):
            return _prof({"serve;decode": 4})

    class DeadPeer:
        def profile(self, seconds=None):
            raise ConnectionError("down")

    out = collect_fleet_profile(
        "coord0", _prof({"http;render": 2}),
        {"node0": Peer(), "node1": DeadPeer()}, seconds=30,
    )
    assert out["instances"] == ["coord0", "node0"]
    assert list(out["errors"]) == ["node1"]
    assert "down" in out["errors"]["node1"]
    assert out["folded"] == {"http;render": 2, "serve;decode": 4}
    assert out["samples"] == 6


def test_coordinator_fleet_profile_surface(tmp_path):
    from m3_tpu.services.coordinator import Coordinator

    prev = profiling.installed()
    coord = None
    try:
        coord = Coordinator(base_dir=str(tmp_path))
        coord.instance_id = "coordX"
        s = StackSampler(hz=0, instance="coordX", clock=lambda: 0.0)
        s.sample_once(now=0.0, frames={1: _chain("http", "render")})
        profiling.install(s)

        class Peer:
            def profile(self, seconds=None):
                return _prof({"rpc;decode": 7})

        coord.peer_source = lambda: {"nodeY": Peer()}
        out = coord.fleet_profile(seconds=15)
        assert set(out["instances"]) == {"coordX", "nodeY"}
        assert out["folded"]["rpc;decode"] == 7
        assert any("render" in st for st in out["folded"])

        # a broken topology source must be visible, not silently served
        # as a healthy single-node fleet
        def broken():
            raise RuntimeError("placement watch torn")

        coord.peer_source = broken
        out = coord.fleet_profile(seconds=15)
        assert out["instances"] == ["coordX"]
        assert "placement watch torn" in out["errors"]["peer_source"]
    finally:
        profiling.install(prev)
        if coord is not None:
            coord.db.close()


# --- satellite: per-shard residency heat ---


def test_shard_heat_cap_and_counters():
    from m3_tpu.resident.heat import OVERFLOW_SHARD, ShardHeat

    reg = Registry(prefix="m3tpu_")
    heat = ShardHeat(registry=reg, cap=2)
    heat.charge(0, hits=3)
    heat.charge(1, misses=1, streamed_bytes=100)
    heat.charge(7, hits=1)  # past the cap: collapses into __overflow__
    dump = heat.dump()
    assert dump["0"]["hits"] == 3
    assert dump["1"]["misses"] == 1 and dump["1"]["streamedBytes"] == 100
    assert dump[OVERFLOW_SHARD]["hits"] == 1 and "7" not in dump
    assert _counter_value(reg, "m3tpu_resident_shard_overflow_total") == 1
    assert _counter_value(
        reg, "m3tpu_resident_shard_hits_total", {"shard": "0"}
    ) == 3


def test_shard_heat_through_query_routing(tmp_path):
    """The integration seam: resident fetches charge hits per shard,
    buffered overlays charge misses, the streamed scan fallback charges
    per-shard bytes — all visible in resident_stats' shard_heat."""
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.query.promql import Matcher
    from m3_tpu.resident import ResidentOptions
    from m3_tpu.storage.database import Database, NamespaceOptions

    db = Database(
        str(tmp_path), num_shards=2, commitlog_enabled=False,
        resident_options=ResidentOptions(max_bytes=1 << 22),
    )
    db.create_namespace("h", NamespaceOptions())
    try:
        for i in range(8):
            tags = ((b"__name__", b"heat_gauge"), (b"series", b"%02d" % i))
            sid = db.write_tagged("h", tags, T0, float(i))
            db.write_batch(
                "h", [(sid, T0 + (j + 1) * 10 * NANOS, float(j)) for j in range(32)]
            )
        db.flush("h", T0 + 4 * 3600 * NANOS)
        storage = M3Storage(db, "h")
        matchers = [Matcher("__name__", "=", "heat_gauge")]
        span = (T0, T0 + 40 * 10 * NANOS)

        base = {k: dict(v) for k, v in db.resident_stats()["shard_heat"].items()}

        out = storage.scan_totals(matchers, *span)
        assert out["path"] == "resident"
        heat = db.resident_stats()["shard_heat"]
        hits = sum(v["hits"] for v in heat.values()) - sum(
            v["hits"] for v in base.values()
        )
        assert hits >= 8  # one lane per series, across both shards

        # buffered overlay forces the streamed path: miss + streamed bytes
        db.write_tagged("h", ((b"__name__", b"heat_gauge"),
                              (b"series", b"00")), T0 + 33 * 10 * NANOS, 5.0)
        out = storage.scan_totals(matchers, *span)
        assert out["path"] == "streamed"
        heat = db.resident_stats()["shard_heat"]
        assert sum(v["misses"] for v in heat.values()) > sum(
            v["misses"] for v in base.values()
        )
        assert sum(v["streamedBytes"] for v in heat.values()) > sum(
            v["streamedBytes"] for v in base.values()
        )
    finally:
        db.close()


# --- selfmon round-trip: m3tpu_profile_* stored and queryable ---


def test_profile_metrics_selfmon_roundtrip(tmp_path):
    from m3_tpu.query.engine import Engine
    from m3_tpu.query.m3_storage import M3Storage
    from m3_tpu.selfmon import RESERVED_NS, DatabaseSink, SelfMonCollector
    from m3_tpu.storage.database import Database, NamespaceOptions

    db = Database(str(tmp_path), num_shards=2)
    db.create_namespace(RESERVED_NS, NamespaceOptions())
    db.bootstrap()
    try:
        reg = Registry(prefix="m3tpu_")
        s = StackSampler(hz=0, clock=lambda: 0.0, registry=reg)
        for _ in range(3):
            s.sample_once(now=0.0, frames={1: _chain("serve", "decode")})
        coll = SelfMonCollector(
            DatabaseSink(db), interval=3600, instance="node0",
            component="dbnode", registry=reg, clock=lambda: T0,
        )
        written, errors = coll.scrape_once()
        assert errors == 0 and written > 0
        eng = Engine(M3Storage(db, RESERVED_NS))
        r = eng.query_instant("m3tpu_profile_samples_total", T0 + NANOS)
        assert len(r.metas) == 1
        assert float(np.asarray(r.values)[0, -1]) == 3.0
        # profiler health is alertable: the error counter rides along
        r = eng.query_instant("m3tpu_profile_errors_total", T0 + NANOS)
        assert len(r.metas) == 1
        assert float(np.asarray(r.values)[0, -1]) == 0.0
    finally:
        db.close()
