"""Planted faults: a real dbnode at 4 hosts on the CPU, the whole of a run
driven through ``run.run_cell`` in rehearsal mode (which skips only the
harness's look for a chip), with the timed path broken underneath; each
must come out ``correct: false``, and the same run without the fault
``correct: true``.

The faults a cell of this system can have: an answer altered where it is
produced (``alter_reply``: one value of one reply of the window) and an
acknowledged batch that never reached the node (``drop_batch``). A step
that returns its state unchanged, half a batch left out of a mean and an
exchange between chips left out have no counterpart here: no training
step, no mean over a batch, one chip.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_faults.py -q

About a minute a case on the CPU. Beside the benchmark, outside ``tests/``:
tier-1 neither gains nor loses by them.
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

CASES = [
    ("cpu-only.haystack", None, True, None),
    ("cpu-only.haystack", "alter_reply", False, "window_reply_cells_differ"),
    ("cpu-only.remote-write", None, True, None),
    ("cpu-only.remote-write", "drop_batch", False, "readback_before_seal_points_differ"),
]


@pytest.mark.parametrize("cell,fault,correct,failing", CASES)
def test_fault_is_caught(cell, fault, correct, failing):
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        pytest.skip("a rehearsal: set JAX_PLATFORMS=cpu")
    result = run.run_cell(cell, seed=2_600_000_007, seconds=4.0, trace=False,
                          rehearse=True, hosts=4, fault=fault)
    assert result is not None
    assert result["correct"] is correct, result["compared"]
    if failing is not None:
        value, limit = result["compared"][failing]
        assert value > limit
        others = {k: v for k, v in result["compared"].items() if k != failing}
        print(cell, fault, "failed", failing, value, "others", others)
