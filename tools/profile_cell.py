#!/usr/bin/env python
"""A builder's reading of one benchmark cell through the PROGRAM's own
instruments (not part of the benchmark, and outside its ``paths``):

    chiprun -- python tools/profile_cell.py --workload cpu-only.remote-write \\
        --seed 2700000101 --seconds 50 --out chiprun_out/profile_write.json

Runs the cell as ``benchmark/run.py`` does (same node, clients, traffic,
checks), with two additions around the measured window: a capture of the
dbnode through its ``device_profile`` op, and the ``metrics`` exposition at
the window's start and close. After the run it reduces the capture with
``m3_tpu.profiling.gaps`` and writes one JSON object:

- ``stages``: per (op, stage) the window's wall seconds, thread-CPU seconds
  and calls (``m3tpu_stage_*``), with milliseconds a call;
  ``stages_before_window`` and ``stages_after_window`` the same for set-up
  (load, seal, warm-up) and for what follows the window (the write cell's
  seal and read-backs): wall and calls only, no capture runs there;
- ``counters``: the window's growth of the commit-log, RPC byte, recv-wait
  and compile counters, and the stack sampler's overhead ratio at its close;
- ``labelled``: set-up's seal by encoder and refusal reason
  (``m3tpu_seal_lanes_total``, ``m3tpu_seal_host_lanes_total``) and the
  admitted chunks by decode body (``m3tpu_resident_chunks_total``), child by
  child at the window's close; the plan's dimensions are among ``counters``
  (``m3tpu_query_plan_*``);
- ``compiles_before_window``: jax's own events as the benchmark's hook
  counts them beside the program's ``m3tpu_jit_compiles`` (they must agree);
- ``gaps``: idle seconds of the device by host stage, each stage's total
  and self seconds over the capture;
- ``result``: the benchmark's own result object for the run.

The capture is ON for the whole window (every request sampled), so the
end-to-end numbers of such a run are not the benchmark's.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)

import run as bench_run  # noqa: E402  (benchmark/run.py)

FAMILIES = (
    "m3tpu_commitlog_bytes_total", "m3tpu_commitlog_entries_total",
    "m3tpu_commitlog_fsyncs_total", "m3tpu_commitlog_fsync_seconds_total",
    "m3tpu_rpc_recv_wait_seconds_total", "m3tpu_rpc_request_bytes_total",
    "m3tpu_rpc_response_bytes_total", "m3tpu_jit_compiles_total",
    "m3tpu_jit_cache_hits_total", "m3tpu_profile_samples_total",
    "m3tpu_profile_overhead_seconds_total",
)
GAUGES = ("m3tpu_profile_overhead_ratio", "m3tpu_commitlog_queue_depth",
          "m3tpu_device_peak_bytes_in_use",
          # the plan the window ran (PR 29): every lane of the segment pays
          # the widest lane's window
          "m3tpu_query_plan_window_words", "m3tpu_query_plan_chunks",
          "m3tpu_query_plan_decode_slots", "m3tpu_query_plan_gather_words")
# read child by child at the window's close: set-up's seal by encoder and
# refusal reason, the admitted chunks by decode body (PR 29)
LABELLED = ("m3tpu_seal_lanes_total", "m3tpu_seal_host_lanes_total",
            "m3tpu_resident_chunks_total")


def parse_exposition(text: str) -> dict[str, float]:
    """{``name{labels}``: value} of a Prometheus text exposition."""
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            key, _, value = line.rpartition(" ")
            out[key] = float(value)
    return out


def family_total(samples: dict[str, float], name: str) -> float:
    return sum(v for k, v in samples.items() if k == name or k.startswith(name + "{"))


def stage_rows(m0: dict, m1: dict) -> list[dict]:
    rows = {}
    for field, family in (("wall_s", "m3tpu_stage_seconds_total"),
                          ("cpu_s", "m3tpu_stage_cpu_seconds_total"),
                          ("calls", "m3tpu_stage_calls_total")):
        for key, value in m1.items():
            if key.startswith(family + "{"):
                grown = value - m0.get(key, 0.0)
                if grown:
                    rows.setdefault(key[len(family):], {})[field] = grown
    out = []
    for labels, row in rows.items():
        op, stage = (part.split("=", 1)[1].strip('"')
                     for part in labels.strip("{}").split(","))
        calls = row.get("calls", 0.0)
        wall, cpu = row.get("wall_s", 0.0), row.get("cpu_s", 0.0)
        out.append({"op": op, "stage": stage, "wall_s": wall, "cpu_s": cpu,
                    "calls": calls,
                    "wall_ms_per_call": 1e3 * wall / calls if calls else None,
                    "cpu_ms_per_call": 1e3 * cpu / calls if calls else None})
    return sorted(out, key=lambda r: -r["wall_s"])


class ProfiledCell(bench_run.Cell):
    """The benchmark's cell, with the program's capture and two scrapes
    around its window."""

    capture_dir: str = ""
    metrics_end: dict | None = None

    def open_window(self, clients):
        self.metrics0 = parse_exposition(self.node.client.metrics())
        self.node.client.device_profile("start", dir=self.capture_dir)
        return super().open_window(clients)

    def close_window(self, clients):
        super().close_window(clients)
        self.profile_stat = self.node.client.device_profile("stop")
        self.metrics1 = parse_exposition(self.node.client.metrics())

    def close(self):
        if self.node is not None and self.metrics_end is None:
            try:
                self.metrics_end = parse_exposition(self.node.client.metrics())
            except Exception:  # a run that is dying: close what is open
                self.metrics_end = {}
        super().close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True, help="where the JSON object goes")
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform, a small fleet (benchmark/run.py --rehearse)")
    ap.add_argument("--hosts", type=int, default=None)
    args = ap.parse_args(argv)
    # the reduction below runs from ROOT, whatever this process was started
    # from: a relative --out must name the same file in both
    args.out = os.path.abspath(args.out)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    cell = ProfiledCell(bench, cells[args.workload], args.seed, args.seconds,
                        False, args.rehearse, args.hosts, None)
    cell.capture_dir = tempfile.mkdtemp(prefix="m3profile-")
    try:
        result = cell.run()
    except BaseException:
        if cell.node is not None:
            bench_run.say("--- dbnode stderr tail ---\n" + cell.node.stderr_tail())
        raise
    finally:
        cell.close()
    if result is None:
        return 1
    # the reduction in a process of its own, on the CPU: this one never
    # imports jax (a chip belongs to one process)
    gaps_path = args.out + ".gaps.json"
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    proc = subprocess.run(
        [sys.executable, "-m", "m3_tpu.profiling.gaps", cell.capture_dir, gaps_path],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True, timeout=900)
    gaps = {"error": f"gaps exited {proc.returncode}: {proc.stderr[-2000:]}"}
    if os.path.exists(gaps_path):
        with open(gaps_path) as f:
            gaps = json.load(f)
        os.remove(gaps_path)
    shutil.rmtree(cell.capture_dir, ignore_errors=True)
    m0, m1 = cell.metrics0, cell.metrics1
    s0 = cell.window["stat0"]
    out = {
        "workload": args.workload, "seed": args.seed,
        "stages": stage_rows(m0, m1),
        "stages_before_window": stage_rows({}, m0),
        "stages_after_window": stage_rows(m1, cell.metrics_end or m1),
        "counters": {
            **{name: family_total(m1, name) - family_total(m0, name) for name in FAMILIES},
            **{name: family_total(m1, name) for name in GAUGES},
        },
        "labelled": {key: value for key, value in sorted(m1.items())
                     if key.startswith(tuple(name + "{" for name in LABELLED))},
        "compiles_before_window": {
            "jax_events_hook": s0["compiles"], "m3tpu_jit_compiles": s0["m3tpu_jit_compiles"]},
        "device_profile_stop": cell.profile_stat,
        "window": {k: cell.window.get(k) for k in ("points", "span_s", "cpu_s", "attempted")},
        "gaps": gaps,
        "result": result,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=float)
    bench_run.say(f"profile of {args.workload} written to {args.out}")
    for row in out["stages"][:40]:
        bench_run.say("stage {op:>14} {stage:<26} wall {wall_s:9.3f}s cpu {cpu_s:9.3f}s "
                      "calls {calls:8.0f}".format(**row))
    for k, v in (gaps.get("gap_seconds_by_stage") or {}).items():
        bench_run.say(f"gap {k:<28} {v:9.3f}s")
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
