"""The benchmark's ``live`` kind at 8 hosts, without a node: the plan of
``dashboard-now-2q.json`` (end ticks from a fake clock, the admissible end
against a made-up acknowledgement log, the reference over two blocks) and
the controls that must fail its comparison, by the very helpers
``benchmark/tests/test_controls_live.py`` runs at the cell's own 400 hosts;
and the readers of the cell's per-layer metrics over made-up replies.
"""

from __future__ import annotations

import importlib.util
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
for path in (BENCH, os.path.join(BENCH, "tests")):
    if path not in sys.path:
        sys.path.insert(0, path)

import fleet  # noqa: E402  (benchmark/fleet.py)
import test_controls_live as controls  # noqa: E402  (benchmark/tests/)

CONFIG, TRAFFIC, HOSTS = "tsbs-cpu-only-400-live-1node", "dashboard-now-2q", 8


@pytest.fixture
def small(monkeypatch):
    """``controls.live_run`` on the cell's configuration and mix at 8 hosts
    (the read-back sample capped at the fleet, as a rehearsal caps it)."""
    load_config, load_json = fleet.load_config, fleet.load_json

    def config(name):
        return dict(load_config(name), hosts=HOSTS)

    def json_file(*parts):
        out = load_json(*parts)
        if parts[0] == "traffic" and "readback_series" in out:
            out["readback_series"] = min(out["readback_series"], HOSTS * 10)
        return out

    monkeypatch.setattr(fleet, "load_config", config)
    monkeypatch.setattr(fleet, "load_json", json_file)
    monkeypatch.setattr(controls, "CONFIG", CONFIG)
    monkeypatch.setattr(controls, "TRAFFIC", TRAFFIC)
    return controls.live_run


def test_the_mix_is_dashboard_now_with_two_clients():
    a = fleet.load_json("traffic", "dashboard-now.json")
    b = fleet.load_json("traffic", TRAFFIC + ".json")
    assert b["workers"] == 2 and a["workers"] == 4
    assert {k: v for k, v in a.items() if k not in ("workers", "why")} == {
        k: v for k, v in b.items() if k not in ("workers", "why")}


@pytest.mark.parametrize("seed", controls.SEEDS)
def test_the_reference_agrees_with_itself(small, seed):
    got = small(seed)
    first = 720 + 180
    assert got.pop("end_ticks") == list(range(first - 1, first + 4))
    assert set(got.values()) == {0}, got


@pytest.mark.parametrize("seed", controls.SEEDS)
@pytest.mark.parametrize("case", ["stale", "tick lost in the window"])
def test_a_broken_reference_fails_the_window_and_the_readbacks(small, seed, case):
    broken = controls.stale if case == "stale" else controls.lost_in_window
    got = small(seed, broken)
    assert got["window_reply_cells_differ"] > 0
    assert got["readback_cells_differ"] > 0 and got["readback_points_differ"] > 0
    assert got["window_requests_ending_past_the_acknowledged"] == 0


@pytest.mark.parametrize("seed", controls.SEEDS)
def test_a_late_acknowledgement_trips_its_own_check_only(small, seed):
    got = small(seed, ack_secs=15.0)
    assert got.pop("window_requests_ending_past_the_acknowledged") > 0
    got.pop("end_ticks")
    assert set(got.values()) == {0}, got


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(BENCH, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Ctx:
    def __init__(self, replies, busy_s=None):
        self.cfg = fleet.load_config(CONFIG)
        self.traffic = fleet.load_json("traffic", TRAFFIC + ".json")
        self.window = {"replies": replies}
        self.counters = {"resident": {"entries": 4000, "bytes": 1_800_000}}
        self.trace_summary = {"busy_s": busy_s}
        self.device_kind = "TPU v5 lite"


def reply(n_steps: int, end_tick: int, stages=None, series=400):
    return {"error": None, "end_tick": end_tick,
            "rows": {f"host_{h}": [0.0] * n_steps for h in range(series)},
            "stats": {"stages": stages or {}}}


def test_live_roofline_counts_the_sealed_part_where_the_fetch_reaches_it():
    live = reader("live_roofline")
    cfg, tr = fleet.load_config(CONFIG), fleet.load_json("traffic", TRAFFIC + ".json")
    per_block = 1_800_000 / 4000
    # a panel ending at tick 900: 61 steps of 6 ticks back to 540, less 5
    # minutes of range and 5 of lookback: 480 .. 906, half sealed
    panel = live.reply_bytes(cfg, tr["classes"], per_block, reply(61, 900)["rows"], 900)
    assert panel == 400 * (per_block + 16.0 * (901 - 720)) + 8.0 * 400 * 61
    # lastpoint: 31 steps of one tick back to 870, less 5 minutes: 840 ..
    # 901, all open
    last = live.reply_bytes(cfg, tr["classes"], per_block, reply(31, 900)["rows"], 900)
    assert last == 400 * 16.0 * (901 - 840) + 8.0 * 400 * 31
    share = live.read(Ctx([reply(61, 900), reply(31, 900)], busy_s=0.01), None)
    assert share == pytest.approx(100.0 * (panel + last) / 819e9 / 0.01)
    assert live.read(Ctx([reply(61, 900)]), None) is None  # nothing traced


def test_plan_overlay_reads_the_stage_and_nothing_where_no_reply_has_it():
    ov = reader("plan_overlay")
    got = ov.read(Ctx([reply(61, 900, {"plan.overlay": 0.0002}),
                       reply(31, 900, {"plan.overlay": 0.0004}),
                       reply(31, 900, {"plan.overlay": 0.0009})]), None)
    assert got == pytest.approx(0.4)
    assert ov.read(Ctx([reply(61, 900, {"fetch": 1.0})]), None) is None
