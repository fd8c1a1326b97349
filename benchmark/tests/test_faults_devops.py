"""Planted faults in ``devops.haystack``: a real dbnode at 4 hosts (404
series of every value class) on the CPU, the whole run driven through
``run.run_cell`` in rehearsal mode, as ``test_faults.py`` does for the
cpu-only cells. The clean run must be ``correct`` (float64 percents
through ``max_over_time``, and counters, byte gauges and constants in the
read-back, all bit for bit); ``alter_reply`` (one value of one reply of
the window changed where it is received) must not be.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_faults_devops.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

CASES = [
    ("devops.haystack", None, True, None),
    ("devops.haystack", "alter_reply", False, "window_reply_cells_differ"),
]


@pytest.mark.parametrize("cell,fault,correct,failing", CASES)
def test_fault_is_caught(cell, fault, correct, failing):
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        pytest.skip("a rehearsal: set JAX_PLATFORMS=cpu")
    result = run.run_cell(cell, seed=2_900_000_007, seconds=4.0, trace=False,
                          rehearse=True, hosts=4, fault=fault)
    assert result is not None
    assert result["correct"] is correct, result["compared"]
    if failing is not None:
        value, limit = result["compared"][failing]
        assert value > limit
        others = {k: v for k, v in result["compared"].items() if k != failing}
        print(cell, fault, "failed", failing, value, "others", others)
