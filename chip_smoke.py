#!/usr/bin/env python
"""Chip smoke: the served path, once, on one named device.

    python chip_smoke.py [--scale HOSTS] [--seed N] [--chips 1|4]

Starts ``python -m m3_tpu.services.dbnode`` with every device tier on
(resident pool, device index, device ingest) and a cluster-mode
coordinator in front, loads TSBS cpu-only shaped data over the wire
(``--scale`` hosts x 10 ``cpu_*`` metrics, the 10 TSBS host tags, 10 s
interval, one full 2 h block = 720 integer points per series), seals,
checks that every block and the index segment were admitted to the device,
and answers a few queries through the normal wire ops — each compared with
the same op under ``force_staged`` and with numbers computed HERE with
numpy from the seeded data, to the bounds TOLERANCE.md states.

This process never imports jax: a chip belongs to one process, and that
process is the dbnode. The device is read off the child's
``DEVICE <platform> <count> <kind>`` marker. Any failed check, a dead
child, or a platform other than ``tpu`` exits non-zero with the child's
stderr tail. The sandbox rehearsal is

    JAX_PLATFORMS=cpu python chip_smoke.py --scale 8

which runs every phase and then fails only at the platform check.

``--chips 4`` runs ONLY the replicated deployment (RF=3, majority writes
behind the coordinator, one dbnode process per chip); see
``replicated_phase``. The last line of stdout is the result object.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time
import urllib.parse
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

NANOS = 1_000_000_000
NS = "tsbs"
BLOCK_SECS = 2 * 3600
INTERVAL_SECS = 10
POINTS = BLOCK_SECS // INTERVAL_SECS  # 720: one full block per series
T0 = 222_223 * BLOCK_SECS * NANOS  # 2020-09-13T14:00:00Z, block-aligned
RESIDENT_BYTES = 1 << 30
INDEX_DEVICE_BYTES = 256 << 20
COMMITLOG_SYNC = "interval"
REHEARSAL_MAX_SCALE = 64  # a non-TPU platform may only rehearse this small
DEFAULT_SCALE = 1000
REPLICATED_SCALE = 16  # --chips 4: every sample rides the MAJORITY write path

# TSBS cpu-only (timescale/tsbs cmd/tsbs_generate_data, use-case cpu-only):
# 10 cpu fields per host, 10 host tags
METRICS = (
    "usage_user", "usage_system", "usage_idle", "usage_nice", "usage_iowait",
    "usage_irq", "usage_softirq", "usage_steal", "usage_guest",
    "usage_guest_nice",
)
REGIONS = (
    "us-east-1", "us-west-1", "us-west-2", "eu-west-1", "eu-central-1",
    "ap-southeast-1", "ap-southeast-2", "ap-northeast-1", "sa-east-1",
)
OSES = ("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")
ARCHES = ("x64", "x86")
TEAMS = ("SF", "NYC", "LON", "CHI")
ENVIRONMENTS = ("production", "staging", "test")

# query grid shared by the PromQL checks: 60 s steps, every step on a sample
Q_START = T0 + 600 * NANOS
Q_STEP = 60 * NANOS
Q_END = T0 + (BLOCK_SECS - 60) * NANOS
Q_STEPS = (Q_END - Q_START) // Q_STEP + 1

FAILURES: list[str] = []


def say(line: str) -> None:
    print(line, flush=True)


def check(ok: bool, what: str) -> bool:
    say(("PASS " if ok else "FAIL ") + what)
    if not ok:
        FAILURES.append(what)
    return bool(ok)


# ---------------------------------------------------------------------------
# seeded data + the numpy reference
# ---------------------------------------------------------------------------


def host_tags(scale: int, seed: int) -> list[dict]:
    rng = np.random.default_rng(seed)
    hosts = []
    for h in range(scale):
        region = REGIONS[rng.integers(len(REGIONS))]
        hosts.append({
            "hostname": f"host_{h}",
            "region": region,
            "datacenter": region + "abc"[rng.integers(3)],
            "rack": str(rng.integers(100)),
            "os": OSES[rng.integers(len(OSES))],
            "arch": ARCHES[rng.integers(len(ARCHES))],
            "team": TEAMS[rng.integers(len(TEAMS))],
            "service": str(rng.integers(20)),
            "service_version": str(rng.integers(2)),
            "service_environment": ENVIRONMENTS[rng.integers(len(ENVIRONMENTS))],
        })
    return hosts


def series_values(n_series: int, seed: int) -> np.ndarray:
    """int64[n_series, POINTS]: bounded integer random walk in [0, 100]
    (the TSBS cpu fields' clamped walk, kept integral)."""
    rng = np.random.default_rng(seed + 1)
    out = np.empty((n_series, POINTS), np.int64)
    cur = rng.integers(0, 101, n_series)
    for j in range(POINTS):
        out[:, j] = cur
        cur = np.clip(cur + rng.integers(-3, 4, n_series), 0, 100)
    return out


def series_tags(host: dict, metric: str) -> tuple:
    tags = dict(host, __name__="cpu_" + metric)
    return tuple((k.encode(), v.encode()) for k, v in sorted(tags.items()))


# the step grid lies on sample times: step j reads sample ON_GRID[j]
GRID_STRIDE = Q_STEP // (INTERVAL_SECS * NANOS)
ON_GRID = (Q_START - T0) // (INTERVAL_SECS * NANOS) + GRID_STRIDE * np.arange(Q_STEPS)


def grid_windows(vals: np.ndarray, window_steps: int) -> np.ndarray:
    """[S, Q_STEPS, window] samples the engine's window sees at each output
    step: a window of w grid points ending at step j is w samples Q_STEP
    apart."""
    offs = GRID_STRIDE * np.arange(-(window_steps - 1), 1)
    return vals[:, ON_GRID[:, None] + offs[None, :]]


def ref_rate(vals: np.ndarray, range_secs: int) -> np.ndarray:
    """Prometheus extrapolated rate over windows whose first and last
    samples sit exactly on the range edges (no extrapolation): last - first
    plus the counter-reset corrections, over the range."""
    w = grid_windows(vals, range_secs * NANOS // Q_STEP + 1).astype(np.float64)
    drops = w[..., 1:] < w[..., :-1]
    corr = np.where(drops, w[..., :-1], 0.0).sum(axis=-1)
    return (w[..., -1] - w[..., 0] + corr) / float(range_secs)


def ref_max_over_time(vals: np.ndarray, range_secs: int) -> np.ndarray:
    return grid_windows(vals, range_secs * NANOS // Q_STEP + 1).max(axis=-1)


# ---------------------------------------------------------------------------
# wire helpers
# ---------------------------------------------------------------------------


def metric_total(expo: str, name: str) -> float:
    total = 0.0
    for line in expo.splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            total += float(line.rsplit(" ", 1)[1])
    return total


def compile_stats(node) -> tuple[int, float]:
    expo = node.metrics()
    return (
        int(metric_total(expo, "m3tpu_jit_compiles_total")),
        metric_total(expo, "m3tpu_jit_compile_seconds_total"),
    )


def rows_by_host(resp: dict) -> dict[str, np.ndarray]:
    out = {}
    for meta, row in zip(resp["metas"], resp["values"]):
        tags = {bytes(k): bytes(v) for k, v in meta}
        out[tags[b"hostname"].decode()] = np.asarray(row, np.float64)
    return out


def same_values(a: dict, b: dict) -> bool:
    if a["metas"] != b["metas"] or len(a["values"]) != len(b["values"]):
        return False
    return all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(a["values"], b["values"])
    )


def device_marker(stdout: str) -> tuple | None:
    """(platform, count, kind) from a child's ``DEVICE`` line, if any."""
    for line in stdout.splitlines():
        parts = line.split()
        if parts[:1] == ["DEVICE"] and len(parts) >= 4:
            return parts[1], int(parts[2]), " ".join(parts[3:])
    return None


def stderr_tails(procs) -> str:
    from m3_tpu.testing.proc_cluster import stderr_tail

    out = []
    for what, proc in procs:
        path = getattr(proc, "stderr_path", None)
        if path:
            out.append(f"--- {what} stderr tail ({path}) ---\n{stderr_tail(path)}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# phase 0: kernel parity in a child of its own (exits before the dbnode
# starts, so the chip is free again)
# ---------------------------------------------------------------------------


def kernel_parity_child() -> None:
    """Mosaic-lowered kernels vs their jnp references on whatever device jax
    finds (interpret mode / unfused off-TPU, so the rehearsal runs it too).
    Run as ``python -c "import chip_smoke; chip_smoke.kernel_parity_child()"``."""
    import functools

    import jax

    from m3_tpu import device
    from m3_tpu.ops import fused
    from m3_tpu.ops.chunked import build_chunked, tile_chunked
    from m3_tpu.parallel.scan import (
        chunked_device_args,
        chunked_scan_aggregate,
        chunked_scan_aggregate_packed,
    )
    from m3_tpu.query.functions.temporal_fused import FUSABLE, fused_temporal
    from m3_tpu.storage.fs import CHUNK_K
    from m3_tpu.utils.synthetic import synthetic_streams

    cache = device.configure_compile_cache()
    print("DEVICE %s %d %s" % device.require_device(), flush=True)
    print(f"compile cache: {cache}", flush=True)

    # the served decode+aggregate kernel at the served chunk size
    streams = synthetic_streams(32, POINTS, seed=11)
    batch = tile_chunked(build_chunked(streams, k=CHUNK_K), 1024)
    args = chunked_device_args(batch)
    dims = dict(s=batch.num_series, c=batch.num_chunks, k=batch.k)
    want = jax.jit(functools.partial(chunked_scan_aggregate, **dims))(args)
    # TOLERANCE.md, aggregation: two f32 paths, each k*ulp per chunk plus
    # O(log C) + O(log S) tree terms — 2e-5 relative covers both sides
    rtol = 2e-5
    packed = fused.pack_lane_inputs(batch)
    assert packed.tile_flags.sum() > 0, "no fast tiles classified"
    got = jax.jit(functools.partial(
        chunked_scan_aggregate_packed, n=packed.n, **dims,
        interpret=not device.on_tpu(),
    ))(packed.windows4, packed.lanes4, packed.tile_flags)
    assert int(got.total_count) == int(want.total_count)
    np.testing.assert_allclose(
        float(got.total_sum), float(want.total_sum), rtol=rtol)
    np.testing.assert_allclose(
        np.asarray(got.series_sum), np.asarray(want.series_sum), rtol=rtol)
    np.testing.assert_array_equal(
        np.asarray(got.series_count), np.asarray(want.series_count))
    print(f"KERNEL_PARITY packed lane aggregates k={CHUNK_K} ok", flush=True)

    # is block_until_ready a barrier here? (every host timing that ends in
    # it relies on that.) Enqueue a chain of dependent matmuls (~0.4 s on a
    # v5e); if the wait returns only when the work is done, the scalar
    # fetch after it is immediate. The fetch's own slice program is compiled
    # by the warm-up, so it is not what the last interval times.
    import jax.numpy as jnp

    iters = 4000 if device.on_tpu() else 20

    @jax.jit
    def busy(x):
        return jax.lax.fori_loop(0, iters, lambda _, a: jnp.tanh(a @ a) * 0.5, x)

    x = jnp.ones((2048, 2048), jnp.float32)
    float(busy(x)[0, 0])  # compile + warm, the fetch path included
    t0 = time.perf_counter()
    y = busy(x)
    t1 = time.perf_counter()
    jax.block_until_ready(y)
    t2 = time.perf_counter()
    float(y[0, 0])
    t3 = time.perf_counter()
    print(f"BARRIER dispatch {1e3 * (t1 - t0):.1f}ms block_until_ready "
          f"{1e3 * (t2 - t1):.1f}ms fetch-after {1e3 * (t3 - t2):.1f}ms",
          flush=True)
    assert (t3 - t2) < 0.5 * (t2 - t1) + 0.1, (
        "block_until_ready returned before the work finished")

    # the fused temporal kernel vs the unfused jnp graph (TOLERANCE.md,
    # round-5 additions: 1e-4 abs+rel, 5e-3 abs for stddev/stdvar)
    rng = np.random.default_rng(3)
    vals = rng.normal(100, 10, (256, 720)).astype(np.float32)
    vals[rng.random((256, 720)) < 0.02] = np.nan
    for name in sorted(FUSABLE):
        out = np.asarray(fused_temporal(vals, 7, 10.0, (name,))[0])
        ref = np.asarray(FUSABLE[name](vals, 7, 10.0))
        both_nan = np.isnan(out) & np.isnan(ref)
        atol = 5e-3 if name.startswith("std") else 1e-4
        close = np.abs(out - ref) <= atol + 1e-4 * np.abs(ref)
        assert np.all(both_nan | close), name
    print(f"KERNEL_PARITY fused temporal x{len(FUSABLE)} ok", flush=True)


def kernel_parity_phase() -> tuple:
    t0 = time.perf_counter()
    res = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke; chip_smoke.kernel_parity_child()"],
        cwd=HERE, capture_output=True, text=True, timeout=900,
    )
    device = device_marker(res.stdout)
    for line in res.stdout.splitlines():
        say("  [kernel-parity] " + line)
    ok = res.returncode == 0 and res.stdout.count("KERNEL_PARITY") == 2
    if not ok:
        say("--- kernel-parity stderr tail ---\n" + res.stderr[-4000:])
    check(ok, f"kernel parity phase ({time.perf_counter() - t0:.1f}s)")
    return device


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------


def dbnode_args(n_series_per_node: int, num_shards: int) -> list[str]:
    # every series of a shard gets an ingest lane (pow2 over the expected
    # per-shard count, with headroom for hash skew)
    per_shard = max(n_series_per_node // num_shards, 1)
    lanes = max(1024, 1 << math.ceil(math.log2(per_shard * 1.25)))
    return [
        "--namespace", NS,
        "--resident-bytes", str(RESIDENT_BYTES),
        "--index-device-bytes", str(INDEX_DEVICE_BYTES),
        "--device-ingest", "--ingest-lanes", str(lanes),
        "--commitlog-sync", COMMITLOG_SYNC,
    ]


def load(node, hosts: list[dict], vals: np.ndarray) -> list[bytes]:
    """Register each series once (write_tagged carries the tags to the
    index and returns the id), then time-major (sid, t, v) batches — the
    shape a remote-write stream has."""
    sids = []
    s = 0
    for host in hosts:
        for metric in METRICS:
            sids.append(bytes(node.write_tagged(
                NS, series_tags(host, metric), T0, float(vals[s, 0]))))
            s += 1
    for j in range(1, POINTS):
        t = T0 + j * INTERVAL_SECS * NANOS
        col = vals[:, j].astype(np.float64).tolist()
        node.write_batch(NS, [(sid, t, v) for sid, v in zip(sids, col)])
    return sids


def query_checks(node, http: str | None, hosts, vals) -> dict:
    """The few queries, each cold then warm, vs force_staged and numpy.
    Returns timings + the warm query's stats for the summary lines."""
    n_hosts = len(hosts)
    user = np.arange(n_hosts) * len(METRICS) + METRICS.index("usage_user")
    host_of = {h["hostname"]: i for i, h in enumerate(hosts)}
    span = dict(start=Q_START, end=Q_END, step=Q_STEP)
    block = dict(start=T0, end=T0 + BLOCK_SECS * NANOS)
    times: dict[str, tuple] = {}

    def timed(label, fn):
        c0 = compile_stats(node)
        t0 = time.perf_counter()
        cold = fn()
        t1 = time.perf_counter()
        c1 = compile_stats(node)
        warm = fn()
        t2 = time.perf_counter()
        c2 = compile_stats(node)
        times[label] = (t1 - t0, t2 - t1)
        say(f"  {label}: cold {t1 - t0:.3f}s ({c1[0] - c0[0]} compiles, "
            f"{c1[1] - c0[1]:.1f}s compiling), warm {t2 - t1:.3f}s "
            f"({c2[0] - c1[0]} compiles)")
        check(c2[0] == c1[0], f"{label}: warm run compiled nothing")
        return cold, warm

    # 1) scan_totals: one needle series (sum exact), then a whole metric
    needle_host = f"host_{min(7, n_hosts - 1)}"
    ni = user[host_of[needle_host]]
    m_needle = [["__name__", "=", "cpu_usage_user"], ["hostname", "=", needle_host]]
    _, got = timed("scan_totals needle",
                   lambda: node.scan_totals(NS, m_needle, **block))
    check(got["path"] == "resident", "scan_totals needle served from residency")
    check(
        (got["series"], got["count"], got["sum"], got["min"], got["max"])
        == (1, POINTS, float(vals[ni].sum()), float(vals[ni].min()),
            float(vals[ni].max())),
        "scan_totals needle: count/sum/min/max exact vs numpy",
    )
    _, got = timed(
        "scan_totals metric",
        lambda: node.scan_totals(NS, [["__name__", "=", "cpu_usage_user"]], **block),
    )
    ref = vals[user]
    check(got["path"] == "resident", "scan_totals metric served from residency")
    check(
        (got["series"], got["count"], got["min"], got["max"])
        == (n_hosts, n_hosts * POINTS, float(ref.min()), float(ref.max())),
        "scan_totals metric: series/count/min/max exact vs numpy",
    )
    # TOLERANCE.md: per-series int sums exact (< 2^24); cross-series tree
    # sum O(log S) ulp
    bound = max(math.ceil(math.log2(max(n_hosts, 2))), 1) * 2.0 ** -23
    rel = abs(got["sum"] - float(ref.sum())) / float(ref.sum())
    check(rel <= bound,
          f"scan_totals metric: f32 total within {bound:.2e} rel (got {rel:.2e})")

    def promql(label, q, want: dict, exact: bool):
        cold, warm = timed(label, lambda: node.query_range(NS, q, **span))
        staged = node.query_range(NS, q, **span, force_staged=True)
        check(same_values(cold, warm) and same_values(warm, staged),
              f"{label}: cold == warm == force_staged, bit for bit")
        rows = rows_by_host(warm)
        ok = set(rows) == set(want)
        worst = 0.0
        for host, row in rows.items() if ok else ():
            w = want[host]
            if row.shape != w.shape or np.isnan(row).any():
                ok = False
                break
            if exact:
                ok = ok and bool(np.array_equal(row, w))
            else:
                # TOLERANCE.md, temporal functions: f32 vs an f64 oracle
                # at rtol 2e-4
                err = np.abs(row - w) / np.maximum(np.abs(w), 1e-12)
                err = np.where(w == 0, np.abs(row), err)
                worst = max(worst, float(err.max()))
        if not exact:
            ok = ok and worst <= 2e-4
        check(ok, f"{label}: {len(want)} series x {Q_STEPS} steps "
              + ("exact vs numpy" if exact else
                 f"within rtol 2e-4 of numpy (worst {worst:.2e})"))
        return warm

    # 2) needle selector — the warm eligible query of the dispatch contract
    q_needle = 'cpu_usage_user{hostname="%s"}' % needle_host
    warm = promql("query_range needle", q_needle,
                  {needle_host: vals[ni, ON_GRID].astype(np.float64)}, True)
    st = warm["stats"]
    check(st.get("deviceDispatches") == 1 and st.get("planHits", 0) >= 1
          and st.get("planFallbacks") == 0,
          f"warm needle: one device dispatch, plan hit, no fallback ({ {k: st.get(k) for k in ('deviceDispatches', 'planHits', 'planMisses', 'planFallbacks')} })")

    # 3) rate over a region slice (f32 temporal path)
    region = hosts[min(7, n_hosts - 1)]["region"]
    in_region = [i for i, h in enumerate(hosts) if h["region"] == region]
    rr = ref_rate(vals[user[in_region]], 120)
    promql("query_range rate",
           'rate(cpu_usage_user{region="%s"}[2m])' % region,
           {hosts[i]["hostname"]: rr[n] for n, i in enumerate(in_region)}, False)

    # 4) max_over_time over every host (a selection: exact)
    mm = ref_max_over_time(vals[user], 300).astype(np.float64)
    promql("query_range max_over_time", "max_over_time(cpu_usage_user[5m])",
           {h["hostname"]: mm[i] for i, h in enumerate(hosts)}, True)

    # 5) the needle again through the coordinator's HTTP query_range
    if http is not None:
        url = (f"{http}/api/v1/query_range?query={urllib.parse.quote(q_needle)}"
               f"&start={Q_START // NANOS}&end={Q_END // NANOS}"
               f"&step={Q_STEP // NANOS}&namespace={NS}")
        t0 = time.perf_counter()
        with urllib.request.urlopen(url, timeout=600) as r:
            body = json.loads(r.read())
        say(f"  coordinator HTTP query_range: {time.perf_counter() - t0:.3f}s")
        result = body.get("data", {}).get("result", [])
        ok = body.get("status") == "success" and len(result) == 1
        if ok:
            got_v = np.asarray([float(v) for _, v in result[0]["values"]])
            got_t = np.asarray([float(t) for t, _ in result[0]["values"]])
            ok = (
                result[0]["metric"].get("hostname") == needle_host
                and np.array_equal(got_v, vals[ni, ON_GRID].astype(np.float64))
                and np.array_equal(
                    got_t, (Q_START + Q_STEP * np.arange(Q_STEPS)) / NANOS)
            )
        check(ok, "coordinator HTTP query_range needle exact vs numpy")
    return times


def seal_and_check_admission(node, n_series: int, who: str = "") -> float:
    t0 = time.perf_counter()
    flushed = node.flush(NS, T0 + BLOCK_SECS * NANOS)
    seal_s = time.perf_counter() - t0
    say(f"{who}seal: {seal_s:.1f}s, {len(flushed)} filesets")
    rs = node.resident_stats()
    ix = node.index_stats()
    say(f"{who}resident: entries {rs.get('entries')} admissions "
        f"{rs.get('admissions')} device_admissions "
        f"{rs.get('device_admissions')} upload_bytes "
        f"{rs.get('upload_bytes')} bytes {rs.get('bytes')} rejections "
        f"{rs.get('rejections')} evictions {rs.get('evictions')}")
    say(f"{who}index: admissions {ix.get('admissions')} namespaces "
        f"{ix.get('namespaces', {}).get(NS)}")
    check(rs.get("entries") == n_series and rs.get("rejections") == 0
          and rs.get("evictions") == 0,
          f"{who}every sealed block admitted to the resident pool ({n_series})")
    check(rs.get("device_admissions") == n_series,
          f"{who}every block born resident (device-encoded, no stream upload)")
    ixns = ix.get("namespaces", {}).get(NS, {})
    check(ix.get("admissions", 0) >= 1
          and ixns.get("device_resident_segments", 0) >= 1
          and ixns.get("device_resident_segments")
          == ixns.get("sealed_segments"),
          f"{who}index segment admitted to the device tier")
    return seal_s


def spawn_coordinator(kv_endpoint: str):
    """A cluster-mode coordinator on the host CPU: it holds no chip."""
    from m3_tpu.testing.proc_cluster import _spawn_listening

    proc, host, port = _spawn_listening(
        [sys.executable, "-m", "m3_tpu.services.coordinator", "--cluster",
         "--kv-endpoint", kv_endpoint, "--namespace", NS, "--port", "0"],
        "coordinator", env_extra={"JAX_PLATFORMS": "cpu"},
    )
    return proc, f"http://{host}:{port}"


def served_phase(scale: int, seed: int) -> tuple | None:
    from m3_tpu.net.client import RemoteNode
    from m3_tpu.testing.proc_cluster import ProcCluster

    hosts = host_tags(scale, seed)
    n_series = scale * len(METRICS)
    vals = series_values(n_series, seed)
    say(f"scale {scale}: {n_series} series x {POINTS} points = "
        f"{n_series * POINTS} points (TSBS cpu-only, seed {seed})")
    say(f"dbnode: --resident-bytes {RESIDENT_BYTES} --index-device-bytes "
        f"{INDEX_DEVICE_BYTES} --device-ingest --block-size-secs {BLOCK_SECS} "
        f"--commitlog-sync {COMMITLOG_SYNC}")

    base = tempfile.mkdtemp(prefix="m3tpu-chip-smoke-")
    cluster = coordinator = None
    procs: list = []
    try:
        t0 = time.perf_counter()
        cluster = ProcCluster(
            num_nodes=1, num_shards=8, replica_factor=1,
            block_size_secs=BLOCK_SECS, embedded_kv=True, base_dir=base,
            extra_args=dbnode_args(n_series, 8),
        )
        pn = cluster.nodes["node0"]
        procs.append(("dbnode", pn.proc))
        device = pn.device
        coordinator, http = spawn_coordinator(cluster.kv_endpoint)
        procs.append(("coordinator", coordinator))
        say(f"dbnode pid {pn.proc.pid} (this script: pid {os.getpid()}) "
            f"DEVICE {device}; coordinator on the host CPU; "
            f"up in {time.perf_counter() - t0:.1f}s")
        check(device is not None, "dbnode printed its DEVICE marker")

        # generous RPC timeout: the seal and each first query pay their
        # jit compiles inside the call
        node = RemoteNode.connect(pn.endpoint, timeout=1500.0)
        t0 = time.perf_counter()
        sids = load(node, hosts, vals)
        load_s = time.perf_counter() - t0
        say(f"load: {load_s:.1f}s ({n_series * POINTS / load_s:.0f} points/s "
            "over the wire, commit log on)")

        seal_s = seal_and_check_admission(node, n_series)

        t0 = time.perf_counter()
        times = query_checks(node, http, hosts, vals)
        say(f"queries: {time.perf_counter() - t0:.1f}s")

        # an acknowledged write after seal is read back
        t_new = T0 + (BLOCK_SECS + INTERVAL_SECS) * NANOS
        node.write_batch(NS, [(sids[0], t_new, 42.0)])
        back = node.read(NS, sids[0], t_new, t_new + NANOS)
        check([(d.timestamp, d.value) for d in back] == [(t_new, 42.0)],
              "acknowledged write after seal read back")

        expo = node.metrics()
        check(metric_total(expo, "m3tpu_kernel_dispatches_total") > 0,
              "m3tpu_kernel_dispatches_total > 0")
        check(metric_total(expo, "m3tpu_query_plan_errors_total") == 0,
              "zero plan-cache errors")
        total = compile_stats(node)
        say(f"dbnode jit compiles: {total[0]} ({total[1]:.1f}s)")
        say(f"summary scale={scale} load_s={load_s:.1f} seal_s={seal_s:.1f} "
            + " ".join(f"{k.replace(' ', '_')}_cold_s={v[0]:.3f} "
                       f"{k.replace(' ', '_')}_warm_s={v[1]:.3f}"
                       for k, v in times.items()))
        for what, proc in procs:
            check(proc.poll() is None, f"{what} still alive at the end")
        if FAILURES:
            say(stderr_tails(procs))
        return device
    except Exception as exc:
        FAILURES.append(f"{type(exc).__name__}: {exc}")
        say(f"FAIL {type(exc).__name__}: {exc}")
        say(stderr_tails(procs))
        return None
    finally:
        if coordinator is not None:
            coordinator.kill()
            coordinator.wait(timeout=10)
        if cluster is not None:
            cluster.close()
        shutil.rmtree(base, ignore_errors=True)  # commit logs + filesets


def chip_env(i: int) -> dict:
    """Bind one process to chip ``i`` of a multi-chip host (one process
    per chip). Harmless off the TPU."""
    port = str(8476 + i)
    return {
        "TPU_VISIBLE_CHIPS": str(i),
        "TPU_VISIBLE_DEVICES": str(i),  # the same, by its older name
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": "localhost:" + port,
        "TPU_PROCESS_PORT": port,
        "CLOUD_TPU_TASK_ID": "0",
    }


def remote_write(http: str, hosts, vals) -> None:
    """Prometheus remote write through the coordinator (snappy protobuf):
    the session behind it writes every sample at MAJORITY."""
    from m3_tpu.gen import prompb_pb2 as prompb
    from m3_tpu.utils.snappy import compress

    times_ms = [(T0 + j * INTERVAL_SECS * NANOS) // 1_000_000
                for j in range(POINTS)]
    s = 0
    for host in hosts:
        req = prompb.WriteRequest()
        for metric in METRICS:
            ts = req.timeseries.add()
            for k, v in series_tags(host, metric):
                ts.labels.add(name=k.decode(), value=v.decode())
            for t, v in zip(times_ms, vals[s].tolist()):
                ts.samples.add(timestamp=t, value=float(v))
            s += 1
        r = urllib.request.Request(
            http + "/api/v1/prom/remote/write",
            data=compress(req.SerializeToString()), method="POST")
        with urllib.request.urlopen(r, timeout=600) as resp:
            if resp.status != 200:
                raise RuntimeError(f"remote write: HTTP {resp.status}")


def replicated_phase(scale: int, seed: int) -> tuple | None:
    """The deployment M3 documents, on one four-chip host: three dbnode
    processes, each bound to its own chip with every device tier on,
    placement in the embedded KV, RF=3, writes at MAJORITY through a
    cluster-mode coordinator; the parent and the coordinator hold no chip.
    Runs ONLY this phase."""
    from m3_tpu.net.client import RemoteNode
    from m3_tpu.rules.rules import encode_tags_id
    from m3_tpu.testing.proc_cluster import ProcCluster

    hosts = host_tags(scale, seed)
    n_series = scale * len(METRICS)
    vals = series_values(n_series, seed)
    ids = [f"node{i}" for i in range(3)]
    say(f"replicated: RF=3, MAJORITY writes via the coordinator, scale "
        f"{scale}: {n_series} series x {POINTS} points = {n_series * POINTS} "
        f"points (seed {seed}); commit log {COMMITLOG_SYNC}")
    base = tempfile.mkdtemp(prefix="m3tpu-chip-smoke-rf3-")
    cluster = coordinator = None
    procs: list = []
    try:
        cluster = ProcCluster(
            num_nodes=3, num_shards=8, replica_factor=3,
            block_size_secs=BLOCK_SECS, embedded_kv=True, base_dir=base,
            extra_args=dbnode_args(n_series, 8),
            node_env={nid: chip_env(i) for i, nid in enumerate(ids)},
        )
        for i, nid in enumerate(ids):
            pn = cluster.nodes[nid]
            procs.append((nid, pn.proc))
            say(f"{nid} pid {pn.proc.pid} TPU_VISIBLE_CHIPS={i} "
                f"DEVICE {pn.device}")
        # a chip belongs to one process: three live dbnodes that each
        # report one device are on three different chips
        check(all(cluster.nodes[n].device is not None
                  and cluster.nodes[n].device[1] == 1 for n in ids),
              "each replica holds exactly one device of its own")
        coordinator, http = spawn_coordinator(cluster.kv_endpoint)
        procs.append(("coordinator", coordinator))

        t0 = time.perf_counter()
        remote_write(http, hosts, vals)
        load_s = time.perf_counter() - t0
        say(f"load: {load_s:.1f}s ({n_series * POINTS / load_s:.0f} points/s "
            "acked at MAJORITY through the coordinator)")

        nodes = {nid: RemoteNode.connect(cluster.nodes[nid].endpoint,
                                         timeout=1500.0) for nid in ids}
        for nid in ids:
            seal_and_check_admission(nodes[nid], n_series, who=nid + " ")
        for i, nid in enumerate(ids):
            say(f"{nid}: queries")
            query_checks(nodes[nid], http if i == 0 else None, hosts, vals)

        # a write acknowledged at MAJORITY is read back from ALL replicas
        t_new = (T0 + (BLOCK_SECS + INTERVAL_SECS) * NANOS)
        tags = series_tags(hosts[0], METRICS[0])
        body = json.dumps({
            "tags": {k.decode(): v.decode() for k, v in tags},
            "timestamp": t_new / NANOS, "value": 42.0,
        }).encode()
        r = urllib.request.Request(http + "/api/v1/json/write", data=body,
                                   method="POST")
        with urllib.request.urlopen(r, timeout=60) as resp:
            acked = json.loads(resp.read()).get("ok") is True
        check(acked, "write acknowledged through the coordinator")
        sid = encode_tags_id(tags)
        deadline = time.monotonic() + 15
        seen: dict = {}
        while time.monotonic() < deadline and len(seen) < len(ids):
            for nid in ids:
                back = nodes[nid].read(NS, sid, t_new, t_new + NANOS)
                if [(d.timestamp, d.value) for d in back] == [(t_new, 42.0)]:
                    seen[nid] = True
            time.sleep(0.1)
        check(len(seen) == len(ids),
              f"acknowledged write read back from all replicas ({sorted(seen)})")
        for what, proc in procs:
            check(proc.poll() is None, f"{what} still alive at the end")
        if FAILURES:
            say(stderr_tails(procs))
    except Exception as exc:
        FAILURES.append(f"{type(exc).__name__}: {exc}")
        say(f"FAIL {type(exc).__name__}: {exc}")
        say(stderr_tails(procs))
        return None
    finally:
        if coordinator is not None:
            coordinator.kill()
            coordinator.wait(timeout=10)
        if cluster is not None:
            cluster.close()
        shutil.rmtree(base, ignore_errors=True)  # commit logs + filesets
    # the chips are free again: one unbound process reports the host's
    # devices as jax sees them
    res = subprocess.run(
        [sys.executable, "-c", "from m3_tpu import device; "
         "print('DEVICE %s %d %s' % device.require_device())"],
        cwd=HERE, capture_output=True, text=True, timeout=300,
    )
    device = device_marker(res.stdout)
    say(f"host devices: {device}" if device is not None
        else "FAIL device probe: " + res.stderr[-2000:])
    return device


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=None,
                    help="TSBS hosts (x10 metrics = series); default "
                    f"{DEFAULT_SCALE}, or {REPLICATED_SCALE} with --chips 4")
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    if args.scale is None:
        args.scale = REPLICATED_SCALE if args.chips == 4 else DEFAULT_SCALE

    t_start = time.perf_counter()
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache")
    warm_cache = os.path.isdir(cache) and bool(os.listdir(cache))
    say(f"compile cache {cache} ({'warm' if warm_cache else 'empty'})")

    if args.chips == 4:
        device = replicated_phase(args.scale, args.seed)
        if device is not None and device[0] == "tpu":
            check(device[1] == 4, f"four chips on the host (found {device[1]})")
    else:
        device = kernel_parity_phase()
        if device is not None and device[0] != "tpu" \
                and args.scale > REHEARSAL_MAX_SCALE:
            say(f"FAIL platform is {device[0]!r}, not tpu (a rehearsal off "
                f"the chip takes --scale <= {REHEARSAL_MAX_SCALE})")
            return 1
        if device is not None:
            device = served_phase(args.scale, args.seed)

    say(f"total {time.perf_counter() - t_start:.1f}s")
    if device is None or FAILURES:
        say(f"{len(FAILURES)} check(s) FAILED: {FAILURES}")
        return 1
    platform, count, kind = device
    if platform != "tpu":
        say(f"FAIL every phase ran, but on platform {platform!r}: the "
            "result line is for a TPU only")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
