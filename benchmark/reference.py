"""The plain reference and the comparison that decides ``correct``.

numpy over the very matrix ``fleet.values`` made; imports nothing of the
program and takes nothing the program has made. Semantics are the served
engine's (M3's): a range query first consolidates samples to the step grid
(value at step t = the last sample in (t - lookback, t]; here every step
lies on a sample) and a ``*_over_time`` function then reduces the steps
t - range .. t, both ends included.

Every comparison here is exact (limit 0): ingest to decode is lossless
(values travel as float64 bit patterns), ``max``/``min`` are selections and
a plain selector hands back stored values. The controls in
``tests/test_controls*.py`` put a broken reference in the program's place
and must fail these same functions.
"""

from __future__ import annotations

import numpy as np


def step_samples(vals: np.ndarray, first_idx: int, stride: int,
                 n_steps: int, window_steps: int) -> np.ndarray:
    """[S, n_steps, window_steps + 1] sample values each output step's
    window sees: step j reads samples first_idx + j*stride - k*stride,
    k = window_steps .. 0."""
    steps = first_idx + stride * np.arange(n_steps)
    offs = stride * np.arange(-window_steps, 1)
    return vals[:, steps[:, None] + offs[None, :]]


FUNCS = {
    "selector": lambda w: w[..., -1],
    "max_over_time": lambda w: w.max(axis=-1),
    "min_over_time": lambda w: w.min(axis=-1),
}


def answer(vals: np.ndarray, rows: np.ndarray, req: dict) -> np.ndarray:
    """float64[len(rows), n_steps]: what the request must return for the
    series ``rows`` of the matrix. ``req`` carries sample-index
    arithmetic only (``first_idx``, ``stride``, ``n_steps``,
    ``window_steps``) and the function name."""
    w = step_samples(vals[rows], req["first_idx"], req["stride"],
                     req["n_steps"], req["window_steps"])
    return FUNCS[req["fn"]](w).astype(np.float64)


def rows_by_host(reply: dict) -> dict:
    """hostname -> float64 row of one ``query_range`` reply."""
    rows = {}
    for meta, row in zip(reply["metas"], reply["values"]):
        tags = {bytes(k): bytes(v) for k, v in meta}
        rows[tags[b"hostname"].decode()] = np.asarray(row, np.float64)
    return rows


def mismatches(got: dict, want_hosts: list[str], want: np.ndarray) -> int:
    """Cells of a reply that differ from the reference: ``got`` maps
    hostname -> float64 row. A missing or unexpected series counts every
    cell it has (or should have had); a row of another length likewise."""
    bad = 0
    seen = set()
    for host, row in zip(want_hosts, want):
        g = got.get(host)
        seen.add(host)
        if g is None or g.shape != row.shape:
            bad += row.size
            continue
        # bit-for-bit: NaN never equals, and none is expected
        bad += int(np.count_nonzero(g != row))
    for host, g in got.items():
        if host not in seen:
            bad += max(int(np.size(g)), 1)
    return bad


def ends_past_acknowledged(end_ticks, sends, first_tick: int, acked_at: np.ndarray) -> int:
    """Requests of a live window that asked for a sample some writer had
    not had acknowledged when the request was sent: the harness's own
    pacing at fault, not the program. ``acked_at[w, k - first_tick]`` is
    the instant writer w's tick k was acknowledged (infinite where it
    never was); a tick before ``first_tick`` was acknowledged in set-up."""
    end_ticks = np.asarray(end_ticks, np.int64)
    sends = np.asarray(sends, np.float64)
    window = end_ticks >= first_tick
    rel = end_ticks[window] - first_tick
    acked_at = np.asarray(acked_at, np.float64)
    late = np.full(rel.shape, np.inf)
    inside = rel < acked_at.shape[1]
    late[inside] = acked_at[:, rel[inside]].max(axis=0)
    return int(np.count_nonzero(late >= sends[window]))


def read_mismatches(got_t, got_v, want_t: np.ndarray, want_v: np.ndarray) -> int:
    """Points of one series' read-back that differ from what was
    acknowledged: a missing, extra, moved or altered point counts one."""
    got_t = np.asarray(got_t, np.int64)
    got_v = np.asarray(got_v, np.float64)
    if got_t.shape != want_t.shape:
        both = min(len(got_t), len(want_t))
        bad = abs(len(got_t) - len(want_t))
        return bad + int(np.count_nonzero(
            (got_t[:both] != want_t[:both]) | (got_v[:both] != want_v[:both])))
    return int(np.count_nonzero((got_t != want_t) | (got_v != want_v)))
