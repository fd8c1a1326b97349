"""From a profiler capture to "what the host was doing while the device idled".

    python -m m3_tpu.profiling.gaps <trace dir> [out.json]

A capture taken with the ``device_profile`` op (or any ``jax.profiler``
trace of a process that runs this program) holds the device's operations
and the program's stage annotations (``utils/trace.py``) on one clock. This
reduction takes the device plane's busy union, and for every idle gap
between two device operations sums, per stage name, the seconds the host
threads' stage annotations overlap it — on each thread the innermost open
stage wins — and ``no_stage`` where no thread has a stage open. It also
gives each stage's total and self seconds over the capture (self: its time
less what its child stages cover).

Gap seconds by stage are THREAD seconds: two threads inside stages during
one idle second give two. ``no_stage_s`` is wall seconds.

It reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` and touches
no device. A TPU's operations are its ``/device:TPU:n`` planes' ``XLA
Ops`` lines; where the capture has no such plane (the CPU backend) the
operations are the host plane's events that carry an ``hlo_op`` stat.
"""

from __future__ import annotations

import glob
import json
import os
import sys

from ..utils.trace import is_stage_name

OPS_LINE = "XLA Ops"
NO_STAGE = "no_stage"


def merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """The union of [start, end) intervals, as sorted disjoint intervals."""
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def innermost(events: list[tuple[int, int, str]]) -> list[tuple[int, int, str]]:
    """One thread's nested stage events (start, end, name) cut into
    disjoint segments, each named by the innermost stage open in it."""
    out: list[tuple[int, int, str]] = []
    stack: list[tuple[int, int, str]] = []
    cursor = 0

    def close_until(t: int) -> None:
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            _, e, name = stack.pop()
            if e > cursor:
                out.append((cursor, e, name))
                cursor = e

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close_until(s)
        if stack:
            # a child never outlives its parent on one thread; clock
            # rounding may say otherwise by a nanosecond
            e = min(e, stack[-1][1])
            if s > cursor:
                out.append((cursor, s, stack[-1][2]))
        cursor = max(cursor, s)
        if e > s:
            stack.append((s, e, name))
    close_until(sys.maxsize)
    return out


def overlap(segments: list[tuple], gaps: list[tuple[int, int]]) -> dict:
    """{name: ns} of sorted disjoint named ``segments`` inside sorted
    disjoint ``gaps`` (segments without a name count under ``""``)."""
    out: dict[str, int] = {}
    k = 0
    for seg in segments:
        s, e = seg[0], seg[1]
        name = seg[2] if len(seg) > 2 else ""
        while k < len(gaps) and gaps[k][1] <= s:
            k += 1
        j = k
        while j < len(gaps) and gaps[j][0] < e:
            lo, hi = max(s, gaps[j][0]), min(e, gaps[j][1])
            if hi > lo:
                out[name] = out.get(name, 0) + hi - lo
            j += 1
    return out


def latest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _span(ev) -> tuple[int, int]:
    s = int(ev.start_ns)
    return s, s + int(ev.duration_ns)


def read_planes(data) -> tuple[list[list[tuple[int, int]]], dict[str, list]]:
    """(busy intervals of each device, {thread: stage events}) of a
    ``ProfileData``."""
    devices: list[list[tuple[int, int]]] = []
    threads: dict[str, list] = {}
    host_lines = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [_span(ev) for ev in line.events]
                    if ops:
                        devices.append(merge(ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_lines.append(line)
                stages = [(*_span(ev), ev.name) for ev in line.events
                          if is_stage_name(ev.name)]
                if stages:
                    # every Python thread's line is named "python"
                    threads[f"{plane.name}/{line.name}#{len(host_lines)}"] = stages
    if not devices:
        cpu_ops = [_span(ev) for line in host_lines for ev in line.events
                   if any(k == "hlo_op" for k, _ in ev.stats)]
        if cpu_ops:
            devices.append(merge(cpu_ops))
    return devices, threads


def reduce_profile(data) -> dict:
    devices, threads = read_planes(data)
    if not devices:
        return {"error": "no device operations in the capture",
                "planes": [p.name for p in data.planes]}
    stages: dict[str, dict] = {}
    segments: dict[str, list] = {}
    covered: list[tuple[int, int]] = []
    for thread, events in threads.items():
        segs = segments[thread] = innermost(events)
        for s, e, name in events:
            row = stages.setdefault(name, {"total_s": 0.0, "self_s": 0.0, "calls": 0})
            row["total_s"] += (e - s) / 1e9
            row["calls"] += 1
        for s, e, name in segs:
            stages[name]["self_s"] += (e - s) / 1e9
        covered += [(s, e) for s, e, _ in segs]
    covered = merge(covered)
    by_stage: dict[str, float] = {}
    busy_s = idle_s = window_s = no_stage_s = 0.0
    for busy in devices:
        gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:]) if s1 > e0]
        gap_ns = sum(e - s for s, e in gaps)
        busy_s += sum(e - s for s, e in busy) / 1e9
        idle_s += gap_ns / 1e9
        window_s += (busy[-1][1] - busy[0][0]) / 1e9
        no_stage_s += (gap_ns - overlap(covered, gaps).get("", 0)) / 1e9
        for segs in segments.values():
            for name, ns in overlap(segs, gaps).items():
                by_stage[name] = by_stage.get(name, 0.0) + ns / 1e9
    n = len(devices)
    gap_seconds = {k: v / n for k, v in sorted(by_stage.items(), key=lambda kv: -kv[1])}
    gap_seconds[NO_STAGE] = no_stage_s / n
    return {
        "devices": n, "threads": len(threads),
        "window_s": window_s / n, "busy_s": busy_s / n, "idle_s": idle_s / n,
        "no_stage_share": (no_stage_s / idle_s) if idle_s else 0.0,
        "gap_seconds_by_stage": gap_seconds,
        "stages": dict(sorted(stages.items(), key=lambda kv: -kv[1]["total_s"])),
    }


def reduce_trace(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    path = latest_xplane(trace_dir)
    out = reduce_profile(ProfileData.from_file(path))
    out["xplane"] = path
    return out


def main(argv: list[str]) -> int:
    if len(argv) not in (1, 2):
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    out = reduce_trace(argv[0])
    text = json.dumps(out, indent=1)
    if len(argv) == 2:
        with open(argv[1], "w") as f:
            f.write(text)
    print(text)
    return 1 if "error" in out else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
