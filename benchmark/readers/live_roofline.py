"""Share of the HBM roofline the live cell's queries of the traced slice
reached, in %: the bytes they HAD to move over the chip's HBM bandwidth
(peaks.json, keyed by device_kind; an unknown kind is an error) over the
traced device-busy seconds. The bytes of one reply (``needed_bytes``):
the sealed part as ``query_roofline`` counts it (the resident pool's
bytes per entry for each series, where the fetch reaches the sealed
block), plus 16 B (a u64 time and a float64 value) for each open-block
sample in the fetch up to the end tick, per series, plus 8 B a result
cell. A reply's class is the one of the mix whose step count its rows
have; its fetch is the engine's: the first step less the function's
range and the 5 minute lookback, to a step past the last. The same work
whatever implements it."""

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOKBACK_SECS = 300  # the engine's default lookback (query/engine.py)


def reply_bytes(cfg: dict, classes: list, per_block: float, rows: dict,
                end_tick: int) -> float:
    """Bytes one reply of a live mix had to move (module docstring)."""
    cells = sum(len(row) for row in rows.values())
    if not rows or end_tick is None:
        return 8.0 * cells
    n_steps = len(next(iter(rows.values())))
    cls = next((c for c in classes
                if c["span_secs"] // c["step_secs"] + 1 == n_steps), None)
    if cls is None:
        return 8.0 * cells
    dt = cfg["interval_secs"]
    n_block = cfg["block_secs"] // dt
    step = cls["step_secs"] // dt
    first = end_tick - (n_steps - 1) * step
    lo = first - (cls.get("range_secs", 0) + LOOKBACK_SECS) // dt
    hi = end_tick + step  # the fetch ends a step past the last step
    sealed = per_block if lo < n_block else 0.0
    open_samples = max(min(hi, end_tick + 1) - max(lo, n_block), 0)
    return len(rows) * (sealed + 16.0 * open_samples) + 8.0 * cells


def needed_bytes(ctx) -> float | None:
    res = ctx.counters.get("resident") or {}
    if not res.get("entries") or not res.get("bytes"):
        return None
    per_block = res["bytes"] / res["entries"]
    classes = ctx.traffic["classes"]
    total = sum(
        reply_bytes(ctx.cfg, classes, per_block, r["rows"], r.get("end_tick"))
        for r in ctx.window.get("replies", ()) if r["error"] is None)
    return total or None


def read(ctx, layer):
    ts = ctx.trace_summary or {}
    if not ts.get("busy_s"):
        return None
    need = needed_bytes(ctx)
    if need is None:
        return None
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    kind = ctx.device_kind
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return 100.0 * need / peaks[kind]["hbm_bytes_per_s"] / ts["busy_s"]
