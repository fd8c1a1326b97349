"""The controls of ``devops.haystack``, at the cell's own size (40 hosts,
4,040 series x 720 points), three seeds: the straight reference passes
the comparison ``run.py`` makes; one scrape interval stale fails it; and
**float32 fails it**, in the window (``max_over_time`` over float64
percents) and in the read-back of every value class that holds more than
float32 can (byte gauges, float64 percents, counters, constants). The
cpu-only cells cannot make that last control fail (``test_controls.py``);
this one is where a program that carries values in float32 anywhere
between ingest and reply stops being ``correct``.

``test_controls.py``'s ``test_float32_is_not_separable_over_small_integers``
holds only the cells whose value classes are all small integers (PR 32);
this cell is held by its ``test_float32_fails_every_other_query_cell``
and, for how widely, by the cases here.

    python3 -m pytest benchmark/tests/test_controls_devops.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_controls import SEEDS, float32, query_cell_mismatches, stale  # noqa: E402

import fleet  # noqa: E402  (benchmark/fleet.py, on the path by test_controls)
import traffic as traffic_mod  # noqa: E402

CELL = "devops.haystack"


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_agrees_with_itself(seed):
    assert query_cell_mismatches(CELL, seed, lambda v: v) == {"window": 0, "readback": 0}


@pytest.mark.parametrize("seed", SEEDS)
def test_stale_by_one_interval_fails(seed):
    bad = query_cell_mismatches(CELL, seed, stale)
    print(CELL, seed, "stale:", bad)
    assert bad["window"] > 0 and bad["readback"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_fails_window_and_readback(seed):
    bad = query_cell_mismatches(CELL, seed, float32)
    print(CELL, seed, "float32:", bad)
    assert bad["window"] > 0 and bad["readback"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_readback_draws_every_value_class(seed):
    """Three series of each of the five value classes, whole block."""
    cfg = fleet.load_config("tsbs-devops-1node")
    n = fleet.points_per_block(cfg)
    rb = traffic_mod.readback_requests(
        cfg, fleet.series_table(cfg), fleet.t0_nanos(cfg), n, seed, 3)
    classes = [r["class"] for r in rb]
    assert sorted(set(classes)) == sorted(cfg["classes"]) and len(classes) == 15
    assert all(r["n_steps"] == n for r in rb)
