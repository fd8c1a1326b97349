"""TSBS devops lanes through the dbnode's normal path, every device tier on.

A 4-host fleet of the benchmark's own configuration ``tsbs-devops-1node``
(101 fields a host: small integer gauges, byte gauges beyond int32,
float64 percents, int64 counters, constants) is written through the
served ops (``write_tagged``, ``write_batch``), sealed with device ingest
on, and asked back through ``query_range``; every answer must equal numpy
over the generator's matrix bit for bit (``benchmark/reference.py``: the
comparison that decides the cell's ``correct``). The seal's counters must
account for every lane, and two seeds whose plan windows differ in width
must both answer exactly.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import fleet  # noqa: E402  (benchmark/fleet.py)
import reference  # noqa: E402
import traffic  # noqa: E402

from m3_tpu.net import wire  # noqa: E402
from m3_tpu.utils.instrument import DEFAULT as METRICS  # noqa: E402

HOSTS = 4
# seeds whose float lanes' widest chunk spans differ by a window word at
# this size (found by running them; the test asserts that they still do)
SEED, OTHER_SEED = 2_900_000_011, 2_900_000_014
# one metric of every value class the configuration has
METRIC_OF = {
    "small_gauge": "cpu_usage_user",
    "bytes_gauge": "mem_used",
    "pct_f64": "mem_used_percent",
    "counter": "net_bytes_sent",
    "const_big": "mem_total",
}
FNS = ("selector", "max_over_time", "min_over_time")


def family(name: str) -> dict:
    """{labels tuple: value} of one metric family of the process."""
    fam = METRICS.collect().get("m3tpu_" + name, {"children": []})
    return {tuple(sorted(c["labels"].items())): c["value"] for c in fam["children"]}


def grown(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items() if v != before.get(k, 0.0)}


class Served:
    """One device-tier database behind the RPC middleware, the fleet
    loaded and sealed, and the counters' growth over the seal."""

    def __init__(self, base: str, seed: int) -> None:
        from m3_tpu.index.device.store import IndexDeviceOptions
        from m3_tpu.ingest import IngestOptions
        from m3_tpu.net.server import NodeService, RpcMiddleware
        from m3_tpu.resident.pool import ResidentOptions
        from m3_tpu.storage.database import Database, NamespaceOptions

        cfg = fleet.load_config("tsbs-devops-1node")
        cfg["hosts"] = HOSTS
        self.cfg = cfg
        self.ns = cfg["namespace"]
        self.t0 = fleet.t0_nanos(cfg)
        self.n = fleet.points_per_block(cfg)
        self.table = fleet.series_table(cfg)
        self.vals = fleet.values(cfg, seed, self.n)
        self.row_of = {(h, m): i for i, (h, m, _) in enumerate(self.table)}
        self.db = Database(
            base, num_shards=cfg["dbnode"]["num_shards"],
            resident_options=ResidentOptions(max_bytes=64 << 20),
            index_device_options=IndexDeviceOptions(max_bytes=64 << 20),
            ingest_options=IngestOptions(lanes=128, slots=1024, sync_batch=8192),
        )
        self.db.create_namespace(
            self.ns, NamespaceOptions(block_size_nanos=cfg["block_secs"] * fleet.NANOS))
        self.mw = RpcMiddleware(NodeService(self.db), component="dbnode")
        hosts = fleet.hosts(cfg)
        dt = cfg["interval_secs"] * fleet.NANOS
        sids = []
        for i, (h, metric, _) in enumerate(self.table):
            tags = [[k, v] for k, v in fleet.series_tags(hosts[h], metric)]
            sids.append(bytes(self.call(
                op="write_tagged", ns=self.ns, tags=tags, t=self.t0,
                v=float(self.vals[i, 0]))))
        for j in range(1, self.n):
            self.call(op="write_batch", ns=self.ns, entries=[
                [sid, self.t0 + j * dt, v] for sid, v in zip(sids, self.vals[:, j].tolist())])
        names = ("seal_lanes_total", "seal_host_lanes_total", "resident_chunks_total",
                 "stage_calls_total")
        before = {name: family(name) for name in names}
        self.call(op="flush", ns=self.ns,
                  flush_before=self.t0 + cfg["block_secs"] * fleet.NANOS)
        self.sealed = {name: grown(before[name], family(name)) for name in names}

    def call(self, **req):
        return self.mw.handle(req)

    def ask(self, fn: str, metric: str) -> tuple[dict, dict]:
        """One request of the cell's shape (all hosts, an hour, 61 steps)
        and the cells of its reply that differ from the reference."""
        cls = {"fn": fn, "metric": metric, "range_secs": 300, "step_secs": 60,
               "span_secs": 3600, "hosts": "all"}
        req = traffic._query_request(self.cfg, self.t0, cls, None, 120)
        # the reply as a client reads it, off the wire
        reply = wire.loads(wire.dumps(self.call(
            op="query_range", ns=self.ns, query=req["query"],
            start=req["start"], end=req["end"], step=req["step"])))
        idx = np.asarray([self.row_of[(h, metric)] for h in range(HOSTS)])
        bad = reference.mismatches(
            reference.rows_by_host(reply), [f"host_{h}" for h in range(HOSTS)],
            reference.answer(self.vals, idx, req))
        return reply, {"differ": bad, "cells": HOSTS * req["n_steps"]}

    def close(self) -> None:
        self.db.close()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    s = Served(str(tmp_path_factory.mktemp("devops")), SEED)
    yield s
    s.close()


def test_wire_ops_used_here_exist():
    from m3_tpu.net.server import NodeService

    for op in ("write_tagged", "write_batch", "flush", "query_range"):
        assert hasattr(NodeService, "op_" + op)


@pytest.mark.parametrize("fn", FNS)
@pytest.mark.parametrize("cls", sorted(METRIC_OF))
def test_every_value_class_answers_bit_for_bit(served, cls, fn):
    assert {c for _, _, c in served.table} == set(METRIC_OF)
    reply, got = served.ask(fn, METRIC_OF[cls])
    assert got["differ"] == 0, (cls, fn, got)
    st = reply["stats"]
    assert st["planFallbacks"] == 0 and st["deviceDispatches"] >= 1, st


def test_reply_is_plan_served_and_says_what_it_decoded(served):
    from m3_tpu.index.device.kernels import pad_pow2

    reply, got = served.ask("max_over_time", "mem_used_percent")
    assert got["differ"] == 0
    st = reply["stats"]
    assert st["planHits"] + st["planMisses"] >= 1 and st["planFallbacks"] == 0
    assert st["residentMisses"] == 0 and st["indexDeviceMisses"] == 0
    # the lanes decoded are the power-of-two bucket of what matched (never
    # under 8), not the segment's series; each at the window of the
    # segment's widest lane: the float64 percents
    assert st["planSeriesMatched"] == HOSTS
    assert st["planLanesDecoded"] == pad_pow2(HOSTS, 8)
    assert st["planLanesDecoded"] < len(served.table)
    gauges = {name: family("query_plan_" + name)[()] for name in (
        "window_words", "chunks", "decode_slots", "gather_words")}
    assert st["planWindowWords"] == gauges["window_words"] > 60
    assert gauges["decode_slots"] == st["planLanesDecoded"]
    assert gauges["gather_words"] == (
        gauges["decode_slots"] * gauges["chunks"] * gauges["window_words"])


def test_seal_counters_add_up_and_name_every_refusal(served):
    n_series = len(served.table)
    lanes = served.sealed["seal_lanes_total"]
    by_encoder: dict[str, float] = {}
    for labels, v in lanes.items():
        enc = dict(labels)["encoder"]
        by_encoder[enc] = by_encoder.get(enc, 0) + v
    assert sum(by_encoder.values()) == n_series, lanes
    # small gauges are the device encoder's; so is a float64 percent until
    # its walk touches a clip (0.0 or 100.0 in a float lane: mixed_mode);
    # int64 magnitudes (counters, byte gauges, constants) overflow int32
    # and fall to the host codec, but for the few that start under 2^31
    per_host = {c: sum(1 for _, _, k in served.table[:101] if k == c) for c in METRIC_OF}
    reasons = {dict(k)["reason"]: v for k, v in served.sealed["seal_host_lanes_total"].items()}
    assert sum(reasons.values()) == by_encoder["host"]
    assert set(reasons) <= {"int_overflow", "diff_overflow", "mixed_mode"}, reasons
    dev_float = lanes[(("encoder", "device"), ("kind", "float"))]
    assert dev_float + reasons.get("mixed_mode", 0) == HOSTS * per_host["pct_f64"]
    assert 0 < dev_float and reasons["int_overflow"] > HOSTS * per_host["counter"] * 0.9
    assert lanes[(("encoder", "device"), ("kind", "int"))] >= HOSTS * per_host["small_gauge"]
    # every chunk admitted carries a body; the three add up to the pool's
    chunks = {dict(k)["body"]: v for k, v in served.sealed["resident_chunks_total"].items()}
    pool = served.db.resident_pool
    with pool._lock:
        want = sum(e.n_chunks for e in pool._od.values())
    assert sum(chunks.values()) == want and chunks.get("float_fast", 0) > 0, chunks
    stages = {dict(k)["stage"] for k in served.sealed["stage_calls_total"]}
    assert {"seal.encode", "seal.encode.device", "seal.encode.host"} <= stages


def widest_window(served) -> int:
    """Window words the segment's widest chunk needs, before the plan
    rounds them (query/plan.py ``_bucket_window_words``)."""
    from m3_tpu.ops.chunked import window_words

    pool = served.db.resident_pool
    with pool._lock:
        return window_words(max(e.max_span_bits for e in pool._od.values()))


def test_two_seeds_whose_windows_differ_run_one_plan_and_answer_exactly(served, tmp_path):
    first, got = served.ask("max_over_time", "mem_used_percent")
    assert got["differ"] == 0
    other = Served(str(tmp_path / "other"), OTHER_SEED)
    try:
        second, got = other.ask("max_over_time", "mem_used_percent")
        assert got["differ"] == 0
        for fn in ("selector", "min_over_time"):
            assert other.ask(fn, "net_bytes_sent")[1]["differ"] == 0
        # the widest chunk follows the values by a word; the plan's window,
        # hence the compiled program and its device time, does not
        assert widest_window(served) != widest_window(other)
    finally:
        other.close()
    assert first["stats"]["planWindowWords"] == second["stats"]["planWindowWords"]
    assert first["stats"]["planWindowWords"] >= widest_window(served)


@pytest.mark.parametrize("cw,want", [(6, 6), (14, 14), (15, 15), (17, 18), (27, 28),
                                     (33, 36), (64, 64), (73, 80), (76, 80), (80, 80),
                                     (81, 88), (129, 144)])
def test_plan_window_keeps_four_significant_bits(cw, want):
    from m3_tpu.query.plan import _bucket_window_words

    assert _bucket_window_words(cw) == want


# -- exact selections over float64 (query/functions/temporal_fused.py) ------


def _oracle(name: str, values: np.ndarray, window: int) -> np.ndarray:
    out = np.full(values.shape, np.nan)
    for t in range(values.shape[1]):
        w = values[:, max(t - window + 1, 0): t + 1]
        for s in range(values.shape[0]):
            seen = w[s][~np.isnan(w[s])]
            if len(seen):
                out[s, t] = {"max_over_time": seen.max, "min_over_time": seen.min,
                             "last_over_time": lambda: seen[-1]}[name]()
    return out


def _samples(kind: str) -> np.ndarray:
    rng = np.random.default_rng(29)
    shape = (7, 41)
    if kind == "float64":
        v = rng.normal(0.0, 50.0, shape)
    elif kind == "neighbours":  # floats one ulp apart: equal in f32
        v = np.nextafter(63.25, 64.0) + np.zeros(shape)
        v[:, ::2] = 63.25
        v[:, 5::7] = np.nextafter(63.25, 0.0)
    elif kind == "int64":
        v = rng.integers(2**33, 2**40, shape).astype(np.float64)
    elif kind == "signs":
        v = rng.choice(np.asarray([-np.inf, -1e300, -1.5, -5e-324, 0.0, 5e-324, 2.5, np.inf]), shape)
    else:
        raise ValueError(kind)
    v[rng.random(shape) < 0.3] = np.nan
    v[3] = np.nan  # a series with no sample at all
    return v


@pytest.mark.parametrize("window", (1, 5, 6, 60))
@pytest.mark.parametrize("kind", ("float64", "neighbours", "int64", "signs"))
@pytest.mark.parametrize("name", ("max_over_time", "min_over_time", "last_over_time"))
def test_selection_over_float64_is_bit_exact(name, kind, window):
    from m3_tpu.query.functions import temporal_fused as TF

    v = _samples(kind)
    assert not TF.f32_exact(v) or kind == "signs"
    got = TF.select_over_time(name, v, window)
    want = _oracle(name, v, window)
    assert got.dtype == np.float64
    both_nan = np.isnan(got) & np.isnan(want)
    assert (both_nan | (got == want)).all(), np.argwhere(~(both_nan | (got == want)))[:4]
    # the engine takes this path for samples f32 cannot hold
    assert TF.exact_selection(name, v) == (not TF.f32_exact(v))


def test_selection_over_f32_exact_samples_keeps_the_f32_path():
    from m3_tpu.query.functions import temporal_fused as TF

    v = np.random.default_rng(3).integers(0, 101, (5, 30)).astype(np.float64)
    v[1, 4:9] = np.nan
    assert TF.f32_exact(v) and not TF.exact_selection("max_over_time", v)
    out = np.asarray(TF.temporal_apply("max_over_time", v, 6, 10.0))
    assert out.dtype == np.float32
    want = _oracle("max_over_time", v, 6)
    assert ((np.isnan(out) & np.isnan(want)) | (out == want)).all()
