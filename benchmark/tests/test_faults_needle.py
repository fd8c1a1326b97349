"""Planted faults in ``cpu-only.needle``: a real dbnode at 4 hosts on the
CPU, the whole run driven through ``run.run_cell`` in rehearsal mode, as
``test_faults.py`` does for the other cpu-only cells. Every request asks
for one host, and every one is a plan miss over the segment's tables.
The clean run must be ``correct``; ``alter_reply`` (one value of one
reply of the window changed where it is received) must not be. The
cell's configuration is the query deployment read one host at a time, so
its file must keep every number of the query deployment's.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_faults_needle.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import fleet  # noqa: E402
import run  # noqa: E402

# what the drill-down configuration may say in its own words
OWN_KEYS = {"name", "source", "deployment", "size_note", "assumed"}

CASES = [
    ("cpu-only.needle", None, True, None),
    ("cpu-only.needle", "alter_reply", False, "window_reply_cells_differ"),
]


@pytest.mark.parametrize("cell,fault,correct,failing", CASES)
def test_fault_is_caught(cell, fault, correct, failing):
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        pytest.skip("a rehearsal: set JAX_PLATFORMS=cpu")
    result = run.run_cell(cell, seed=3_900_000_007, seconds=4.0, trace=False,
                          rehearse=True, hosts=4, fault=fault)
    assert result is not None
    assert result["correct"] is correct, result["compared"]
    if failing is not None:
        value, limit = result["compared"][failing]
        assert value > limit
        others = {k: v for k, v in result["compared"].items() if k != failing}
        print(cell, fault, "failed", failing, value, "others", others)


def test_drilldown_is_the_query_deployment():
    drill = fleet.load_config("tsbs-cpu-only-400-drilldown-1node")
    query = fleet.load_config("tsbs-cpu-only-400-1node")
    assert set(drill) == set(query)
    differ = {k for k in drill if drill[k] != query[k]}
    assert differ <= OWN_KEYS, sorted(differ - OWN_KEYS)
    assert drill["assumed"][:len(query["assumed"])] == query["assumed"]
