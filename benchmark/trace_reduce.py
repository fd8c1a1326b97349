"""From the profiler's trace of the dbnode to numbers: device-busy seconds
(the union of the intervals in which an operation ran, averaged over the
chips used), the operations that took most time, and the longest idle gaps
named by the program that ran before each.

Run as a process of its own (``python trace_reduce.py <trace dir> <out.json>
[<slice seconds>]``) after the dbnode has gone: it imports jax only for
``ProfileData`` and touches no device (the benchmark starts it with
``JAX_PLATFORMS=cpu``).

With ``<slice seconds>`` the trace is cut that long after its first device
operation: the profiler goes on collecting for a while after it is told to
stop, and the harness counts the replies and the seconds up to the instant
it asked (``run.py`` ``drive``), so what ran after it belongs to neither.
The first operation of a query window follows the window's start by the
few milliseconds the first request takes to reach the device. (The trace's
clock starts with the profiler's session: it is neither the host's realtime
clock nor its monotonic one, so the window's start cannot be placed in it.)
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


def busy_by_second(merged: list[tuple[int, int]], first: int) -> list[float]:
    """Busy share of each whole second after ``first`` (ns)."""
    out: list[float] = []
    for s, e in merged:
        while s < e:
            k = (s - first) // 10**9
            edge = min(e, first + (k + 1) * 10**9)
            out += [0.0] * (k + 1 - len(out))
            out[k] += (edge - s) / 1e9
            s = edge
    return out


def reduce_plane(plane, slice_ns: int | None = None) -> dict | None:
    lines = {ln.name: ln for ln in plane.lines}
    ops_line = lines.get(OPS_LINE)
    if ops_line is None:
        return None
    events = [(int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns), ev.name)
              for ev in ops_line.events]
    if not events:
        return None
    first = min(s for s, _, _ in events)
    cut = max(e for _, e, _ in events) if slice_ns is None else first + slice_ns
    ops: dict[str, float] = {}
    intervals = []
    for s, e, name in events:
        if s >= cut:
            continue  # collected after the slice's end
        e = min(e, cut)
        intervals.append((s, e))
        ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
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
            "n_ops_after": len(events) - len(intervals),
            "collected_s": (max(e for _, e, _ in events) - first) / 1e9,
            "by_second": busy_by_second(merged, first),
            "first_ns": merged[0][0], "last_ns": merged[-1][1]}


def reduce_trace(trace_dir: str, slice_s: float | None = None) -> dict:
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
            red = reduce_plane(plane, None if slice_s is None else int(slice_s * 1e9))
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
        n_ops_after=sum(p["n_ops_after"] for p in planes),
        collected_s=max(p["collected_s"] for p in planes),
        by_second=planes[0]["by_second"],
        span_s=max(p["last_ns"] - p["first_ns"] for p in planes) / 1e9,
        device_ops=top(ops), idle_gaps=top(gaps),
    )
    return out


if __name__ == "__main__":
    result = reduce_trace(sys.argv[1], float(sys.argv[3]) if len(sys.argv) > 3 else None)
    with open(sys.argv[2], "w") as f:
        json.dump(result, f)
