"""The controls: the reference put in the program's place, broken the way
that would tempt a later PR, at the cells' own sizes. Each must FAIL the
very comparison ``run.py`` makes; the straight reference must pass it.

- ``stale``    every answer one scrape interval old (a read served from a
               state one tick behind: breaks "an acknowledged write is
               readable at once")
- ``float32``  values carried in float32 (breaks "m3tsz is lossless ...
               read back bit for bit as float64"). TSBS's cpu fields are
               integers in [0, 100], exact in float32: in the cpu-only
               cells this control CANNOT fail, each cell's ``why`` says so,
               and ``stale`` is the control that fails them. A mixed
               fleet (every value class of the generator) shows that the
               comparison does catch float32 where the data can tell
- ``newest_batch_lost``  the newest acknowledged batch not applied (breaks
               "an acknowledged write is in the commit log and readable")

Pure numpy over the seeded matrix: run with ``python3 -m pytest
benchmark/tests/test_controls.py`` (no dbnode, no chip; a few seconds at
4,000 series x 720 points). They live beside the benchmark, outside
``tests/``, so tier-1 neither gains nor loses by them.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import fleet  # noqa: E402
import reference  # noqa: E402
import traffic as traffic_mod  # noqa: E402

SEEDS = (11, 2_200_000_033, 3_000_000_019)
N_WINDOW = 120  # about what four clients finish in a 50 s window

with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as _f:
    CELLS = {w["name"]: w for w in json.load(_f)["workloads"]}


def stale(vals):
    return np.concatenate([vals[:, :1], vals[:, :-1]], axis=1)


def float32(vals):
    return vals.astype(np.float32).astype(np.float64)


def query_cell_mismatches(cell: str, seed: int, broken) -> dict:
    """Cells that differ, window replies and read-back apart, when the
    answers come from ``broken(vals)`` instead of the program."""
    w = CELLS[cell]
    cfg = fleet.load_config(w["config"])
    tr = fleet.load_json("traffic", w["traffic"] + ".json")
    n = fleet.points_per_block(cfg)
    vals = fleet.values(cfg, seed, n)
    served = broken(vals)
    table = fleet.series_table(cfg)
    row_of = {(h, m): i for i, (h, m, _) in enumerate(table)}
    plan = traffic_mod.query_plan(cfg, tr, fleet.t0_nanos(cfg), n, seed)
    window = [r for reqs in plan["window"] for r in reqs[: N_WINDOW // tr["workers"]]]
    rb = traffic_mod.readback_requests(cfg, table, fleet.t0_nanos(cfg), n, seed,
                                       tr["readback_per_class"])
    out = {}
    for name, reqs in (("window", window), ("readback", rb)):
        bad = 0
        for req in reqs:
            hosts = [req["host"]] if req["host"] is not None else list(range(cfg["hosts"]))
            idx = np.asarray([row_of[(h, req["metric"])] for h in hosts])
            got = reference.answer(served, idx, req)
            rows = {f"host_{h}": got[k] for k, h in enumerate(hosts)}
            bad += reference.mismatches(rows, [f"host_{h}" for h in hosts],
                                        reference.answer(vals, idx, req))
        out[name] = bad
    return out


QUERY_CELLS = [c for c, w in CELLS.items() if w["traffic"] != "remote-write"]
WRITE_CELLS = [c for c, w in CELLS.items() if w["traffic"] == "remote-write"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", QUERY_CELLS)
def test_reference_agrees_with_itself(cell, seed):
    assert query_cell_mismatches(cell, seed, lambda v: v) == {"window": 0, "readback": 0}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", QUERY_CELLS)
def test_stale_by_one_interval_fails(cell, seed):
    bad = query_cell_mismatches(cell, seed, stale)
    print(cell, seed, "stale:", bad)
    assert bad["window"] > 0 and bad["readback"] > 0


def small_integers_only(cell: str) -> bool:
    """Every value class of the cell's configuration is an integer gauge
    below 2^24: exact in float32, so precision is not separable there."""
    cfg = fleet.load_config(CELLS[cell]["config"])
    return all(spec["kind"] == "gauge_int" and spec["hi"] < 2 ** 24
               for spec in cfg["classes"].values())


SMALL_INTEGER_QUERY_CELLS = [c for c in QUERY_CELLS if small_integers_only(c)]
OTHER_QUERY_CELLS = [c for c in QUERY_CELLS if not small_integers_only(c)]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", SMALL_INTEGER_QUERY_CELLS)
def test_float32_is_not_separable_over_small_integers(cell, seed):
    """Recorded, not hidden: integers below 2^24 are exact in float32.
    Chosen by the configuration's value classes, not by name, so a later
    cell over small integers is held to it the PR it arrives."""
    assert query_cell_mismatches(cell, seed, float32) == {"window": 0, "readback": 0}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", OTHER_QUERY_CELLS)
def test_float32_fails_every_other_query_cell(cell, seed):
    """A query cell whose data can tell float32 from float64 must fail the
    float32 control (``test_controls_devops.py`` reads how widely)."""
    bad = query_cell_mismatches(cell, seed, float32)
    print(cell, seed, "float32:", bad)
    assert bad["window"] > 0 and bad["readback"] > 0


MIXED = {
    "hosts": 8, "interval_secs": 10, "block_secs": 7200,
    "classes": {
        "cpu_pct": {"kind": "gauge_int", "lo": 0, "hi": 100, "step": 3},
        "bytes_gauge": {"kind": "gauge_int", "lo": 2 ** 30, "hi": 2 ** 34, "step": 2 ** 20},
        "pct_f64": {"kind": "percent", "lo": 0.0, "hi": 100.0, "step": 1.0},
        "counter": {"kind": "counter", "base_lo": 2 ** 24, "base_hi": 2 ** 40, "inc_hi": 1000},
        "const_big": {"kind": "constant", "lo": 2 ** 34, "hi": 2 ** 38},
    },
    "measurements": [{"name": "m", "fields": {
        "a": "cpu_pct", "b": "bytes_gauge", "c": "pct_f64", "d": "counter", "e": "const_big"}}],
}


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_fails_every_class_that_holds_more_than_float32(seed):
    n = fleet.points_per_block(MIXED)
    vals = fleet.values(MIXED, seed, n)
    table = fleet.series_table(MIXED)
    rb = traffic_mod.readback_requests(MIXED, table, fleet.t0_nanos(MIXED), n, seed, 2)
    row_of = {(h, m): i for i, (h, m, _) in enumerate(table)}
    bad: dict[str, int] = {}
    for req in rb:
        idx = np.asarray([row_of[(req["host"], req["metric"])]])
        want = reference.answer(vals, idx, req)
        assert reference.mismatches({"h": want[0]}, ["h"], want) == 0
        got = reference.answer(float32(vals), idx, req)
        bad[req["class"]] = bad.get(req["class"], 0) + reference.mismatches(
            {"h": got[0]}, ["h"], want)
    print(seed, "float32 over a mixed fleet:", bad)
    assert bad.pop("cpu_pct") == 0
    assert bad and all(v > 0 for v in bad.values())


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", WRITE_CELLS)
def test_newest_acknowledged_batch_lost_fails(cell, seed):
    w = CELLS[cell]
    cfg = fleet.load_config(w["config"])
    tr = fleet.load_json("traffic", w["traffic"] + ".json")
    n = fleet.points_per_block(cfg) * tr["blocks"]
    vals = fleet.values(cfg, seed, n)
    t = fleet.t0_nanos(cfg) + cfg["interval_secs"] * fleet.NANOS * np.arange(n)
    rng = fleet.rng_for(seed, fleet.STREAM_READBACK)
    sample = rng.choice(len(vals), size=tr["readback_series"], replace=False)
    ok = sum(reference.read_mismatches(t, vals[i], t, vals[i]) for i in sample)
    lost = sum(reference.read_mismatches(t[:-1], vals[i, :-1], t, vals[i]) for i in sample)
    stale_v = sum(reference.read_mismatches(t, stale(vals)[i], t, vals[i]) for i in sample)
    print(cell, seed, "newest batch lost:", lost, "stale:", stale_v)
    assert ok == 0
    assert lost == len(sample)  # one point of every sampled series
    assert stale_v > 0
