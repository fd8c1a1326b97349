"""The controls of the live kind, at ``dashboard-now``'s own sizes (400
hosts, 4,000 series, one sealed block + 180 open ticks + the window's 5),
three seeds: the reference put in the program's place behind a fake clock
(requests sent at an even rate over a 50 s window, the writers' ticks
acknowledged ``ACK_SECS`` after they were due) and broken the way that
would tempt a later PR. Each must FAIL the very functions ``run.py``'s
live kind compares with; the straight reference must pass them all.

- ``stale``   every answer one scrape interval old: must fail the window's
              cells, the read-back selectors and the points read back
- ``newest acknowledged tick lost``   the last tick every writer had
              acknowledged is not there when it is read back: one point of
              every sampled series
- ``a tick of the window lost``   the window's first tick is acknowledged
              and never stored (a selector then hands back the sample
              before it): must fail the window's cells, which the requests
              sent ten seconds later end on, and both read-backs
- ``ending past the acknowledged``   the harness at fault, not the program:
              acknowledgements later than the pace, so that requests ask
              for a sample not yet acknowledged: trips its own check and no
              other (the reference has the sample)

Pure numpy: ``python3 -m pytest benchmark/tests/test_controls_live.py -q``.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_controls import SEEDS, stale  # noqa: E402  (puts benchmark/ on the path)

import fleet  # noqa: E402
import reference  # noqa: E402
import traffic as traffic_mod  # noqa: E402

CONFIG, TRAFFIC = "tsbs-cpu-only-400-1node", "dashboard-now"
SECONDS = 50.0
N_WINDOW = 120     # requests compared: a window on today's program holds about as many
ACK_SECS = 0.045   # the write cell's acknowledgement, section 5 of PERF.md


def live_run(seed: int, broken=lambda v: v, ack_secs: float = ACK_SECS,
             lose_newest: bool = False) -> dict:
    """Every number the live kind compares, with ``broken(vals)`` in the
    program's place."""
    cfg = fleet.load_config(CONFIG)
    tr = fleet.load_json("traffic", TRAFFIC + ".json")
    t0, dt = fleet.t0_nanos(cfg), cfg["interval_secs"] * fleet.NANOS
    n = fleet.points_per_block(cfg)
    n_ticks = traffic_mod.total_ticks(cfg, tr, n, SECONDS)
    first, pace = n + tr["open_ticks"], cfg["interval_secs"]
    vals = fleet.values(cfg, seed, n_ticks)
    served = broken(vals)
    table = fleet.series_table(cfg)
    row_of = {(h, m): i for i, (h, m, _) in enumerate(table)}

    def differ(req: dict) -> int:
        hosts = [req["host"]] if req["host"] is not None else list(range(cfg["hosts"]))
        idx = np.asarray([row_of[(h, req["metric"])] for h in hosts])
        got = reference.answer(served, idx, req)
        return reference.mismatches({f"host_{h}": got[k] for k, h in enumerate(hosts)},
                                    [f"host_{h}" for h in hosts], reference.answer(vals, idx, req))

    # the window: worker w sends its i-th request at an even rate, t_go = 0
    plan = traffic_mod.query_plan(cfg, tr, t0, n, seed, seconds=SECONDS)
    per_worker = N_WINDOW // tr["workers"]
    cells, ends, sends = 0, [], []
    for w, reqs in enumerate(plan["window"]):
        for i in range(per_worker):
            t_send = SECONDS * (i + w / tr["workers"]) / per_worker
            req = reqs[i]
            k = traffic_mod.end_tick(t_send, 0.0, pace, first, n_ticks - 1)
            cells += differ(traffic_mod.at_end_tick(req, t0, dt, k))
            ends.append(k)
            sends.append(t_send)
    acked_at = np.tile(pace * np.arange(n_ticks - first) + ack_secs, (tr["writer"]["workers"], 1))
    past = reference.ends_past_acknowledged(ends, sends, first, acked_at)

    # after the window: every writer had every tick acknowledged
    acked = n_ticks - int(lose_newest)
    readback = sum(differ(r) for r in traffic_mod.readback_requests(
        cfg, table, t0, n_ticks, seed, tr["readback_per_class"]))
    t = t0 + dt * np.arange(n_ticks)
    rng = fleet.rng_for(seed, fleet.STREAM_READBACK)
    sample = rng.choice(len(vals), size=tr["readback_series"], replace=False)
    points = sum(reference.read_mismatches(t[:acked], served[i, :acked], t, vals[i]) for i in sample)
    return {"window_reply_cells_differ": cells, "readback_cells_differ": readback,
            "readback_points_differ": points,
            "window_requests_ending_past_the_acknowledged": past,
            "end_ticks": sorted(set(ends))}


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_agrees_with_itself(seed):
    got = live_run(seed)
    first = 720 + 180
    assert got.pop("end_ticks") == list(range(first - 1, first + 4))
    assert set(got.values()) == {0}, got


@pytest.mark.parametrize("seed", SEEDS)
def test_stale_by_one_interval_fails_window_and_readbacks(seed):
    got = live_run(seed, stale)
    print(seed, "stale:", got)
    assert got["window_reply_cells_differ"] > 0
    assert got["readback_cells_differ"] > 0 and got["readback_points_differ"] > 0
    assert got["window_requests_ending_past_the_acknowledged"] == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_newest_acknowledged_tick_lost_fails(seed):
    got = live_run(seed, lose_newest=True)
    print(seed, "newest acknowledged tick lost:", got)
    assert got["readback_points_differ"] == 256  # one point of every sampled series


def lost_in_window(vals: np.ndarray) -> np.ndarray:
    first = 720 + 180
    out = vals.copy()
    out[:, first] = out[:, first - 1]
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_tick_lost_inside_the_window_fails_the_window(seed):
    got = live_run(seed, lost_in_window)
    print(seed, "the window's first tick lost:", got)
    assert got["window_reply_cells_differ"] > 0
    assert got["readback_cells_differ"] > 0 and got["readback_points_differ"] > 0
    assert got["window_requests_ending_past_the_acknowledged"] == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_request_ending_past_the_acknowledged_trips_its_own_check_only(seed):
    got = live_run(seed, ack_secs=15.0)  # acknowledged later than the next tick is due
    print(seed, "acknowledged 15 s after due:", got)
    assert got.pop("window_requests_ending_past_the_acknowledged") > 0
    got.pop("end_ticks")
    assert set(got.values()) == {0}, got
