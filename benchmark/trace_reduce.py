"""From the profiler's trace of the dbnode to numbers: device-busy seconds
(the union of the intervals in which an operation ran, averaged over the
chips used), the operations that took most time, and the longest idle gaps
named by the program that ran before each.

Run as a process of its own (``python trace_reduce.py <trace dir> <out.json>``)
after the dbnode has gone: it imports jax only for ``ProfileData`` and
touches no device (the benchmark starts it with ``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import glob
import json
import os
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def union_seconds(intervals: list[tuple[int, int]]) -> tuple[float, list[tuple[int, int]]]:
    """Total length of the union of [start, end) intervals (ns) in
    seconds, and the merged intervals."""
    merged: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged) / 1e9, merged


def reduce_plane(plane) -> dict | None:
    lines = {ln.name: ln for ln in plane.lines}
    ops_line = lines.get(OPS_LINE)
    if ops_line is None:
        return None
    ops: dict[str, float] = {}
    intervals = []
    for ev in ops_line.events:
        s = int(ev.start_ns)
        d = int(ev.duration_ns)
        intervals.append((s, s + d))
        ops[ev.name] = ops.get(ev.name, 0.0) + d / 1e9
    if not intervals:
        return None
    busy_s, merged = union_seconds(intervals)
    # what ran before each gap: the module (jitted program) whose span
    # ends last before the gap opens; the op's own name where the trace
    # has no module line
    mods = sorted(
        ((int(ev.start_ns) + int(ev.duration_ns), ev.name)
         for ev in lines[MODULES_LINE].events) if MODULES_LINE in lines else
        ((e, "op") for _, e in merged))
    gaps: dict[str, float] = {}
    k = 0
    last = "start"
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        while k < len(mods) and mods[k][0] <= e0 + 1000:
            last = mods[k][1]
            k += 1
        name = "after_" + last.split("(")[0]
        gaps[name] = gaps.get(name, 0.0) + (s1 - e0) / 1e9
    return {"busy_s": busy_s, "ops": ops, "gaps": gaps, "n_ops": len(intervals),
            "first_ns": merged[0][0], "last_ns": merged[-1][1]}


def reduce_trace(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        return {"error": f"no .xplane.pb under {trace_dir}"}
    data = ProfileData.from_file(paths[-1])
    planes = []
    names = []
    for plane in data.planes:
        names.append([plane.name, [ln.name for ln in plane.lines]])
        if plane.name.startswith("/device:TPU:"):
            red = reduce_plane(plane)
            if red is not None:
                planes.append(red)
    out: dict = {"planes": names, "xplane_bytes": os.path.getsize(paths[-1])}
    if not planes:
        out["error"] = "no device plane with operations in the trace"
        return out
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for p in planes:
        for k, v in p["ops"].items():
            ops[k] = ops.get(k, 0.0) + v / len(planes)
        for k, v in p["gaps"].items():
            gaps[k] = gaps.get(k, 0.0) + v / len(planes)
    top = lambda d: [[k[:64], v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    out.update(
        busy_s=sum(p["busy_s"] for p in planes) / len(planes),
        chips=len(planes), n_ops=sum(p["n_ops"] for p in planes),
        span_s=max(p["last_ns"] - p["first_ns"] for p in planes) / 1e9,
        device_ops=top(ops), idle_gaps=top(gaps),
    )
    return out


if __name__ == "__main__":
    result = reduce_trace(sys.argv[1])
    with open(sys.argv[2], "w") as f:
        json.dump(result, f)
