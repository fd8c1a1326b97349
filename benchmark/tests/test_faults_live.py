"""Planted faults in the live kind: a real dbnode at 4 hosts on the CPU,
the whole of a run (one sealed block, 180 open ticks, the paced writer
beside four queriers, the comparison and both read-backs) driven through
``run.run_cell`` in rehearsal mode with the cell handed in: no cell of
``BENCHMARK.json`` names ``dashboard-now`` yet.

Without a fault every COMPARISON check reads 0; ``alter_reply`` (one value
of one reply of the window changed where it is received) fails
``window_reply_cells_differ``; ``drop_batch`` (the paced writer
acknowledges the window's first tick without sending it) fails the
window's cells too (the requests sent from ten seconds on end on that tick
or after it, and the ``selector`` class hands back every sample) and both
read-backs: the window runs past the second tick for that. Nothing here asserts on the served-by-device check
(``window_replies_not_served_by_device``): today's program answers every
such request from the staged path (ROADMAP M2), and the PR that mends that
edits no test here.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_faults_live.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

CELL = {"name": "cpu-only.dashboard-now", "config": "tsbs-cpu-only-400-1node",
        "traffic": "dashboard-now", "chips": 1}
SERVED_BY_DEVICE = {"window_replies_not_served_by_device"}
CASES = [
    (None, ()),
    ("alter_reply", ("window_reply_cells_differ",)),
    ("drop_batch", ("window_reply_cells_differ", "readback_cells_differ",
                    "readback_points_differ")),
]


@pytest.mark.parametrize("fault,failing", CASES)
def test_fault_is_caught(fault, failing):
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        pytest.skip("a rehearsal: set JAX_PLATFORMS=cpu")
    result = run.run_cell(CELL, seed=3_500_000_007, seconds=13.0, trace=False,
                          rehearse=True, hosts=4, fault=fault)
    assert result is not None
    compared = {k: v for k, v in result["compared"].items() if k not in SERVED_BY_DEVICE}
    print(fault, compared)
    assert "window_requests_ending_past_the_acknowledged" in compared
    failed = {k for k, (value, limit) in compared.items() if value > limit}
    assert failed == set(failing), compared
    if failing:
        assert result["correct"] is False
