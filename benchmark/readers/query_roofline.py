"""Share of the HBM roofline the queries of the traced slice of the window
reached (a traced run's ``window["replies"]``), in %: the bytes
they HAD to move (the compressed bytes of the matched series' blocks in
range, by the resident pool's own bytes per entry, plus each result's
float64 cells), over the chip's HBM bandwidth (peaks.json, keyed by
device_kind; an unknown kind is an error), over the traced device-busy
seconds. The same work whatever implements it."""

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def needed_bytes(ctx) -> float | None:
    res = ctx.counters.get("resident") or {}
    if not res.get("entries") or not res.get("bytes"):
        return None
    per_block = res["bytes"] / res["entries"]
    total = 0.0
    for r in ctx.window.get("replies", ()):
        if r["error"] is not None:
            continue
        cells = sum(len(row) for row in r["rows"].values())
        total += len(r["rows"]) * per_block + 8.0 * cells
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
    # the replies counted are the traced slice's, and so is the trace
    return 100.0 * need / peaks[kind]["hbm_bytes_per_s"] / ts["busy_s"]
