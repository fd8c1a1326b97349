#!/usr/bin/env python3
"""Checks of ``BENCHMARK.json`` and the files it names that need no chip.

    python3 benchmark/selfcheck.py

- every name has only letters, digits, ``_``, ``.``, ``-`` (at most 64);
  every unit also ``/`` and ``%`` (at most 16);
- every ``moves`` names an end-to-end metric that every cell reporting the
  per-layer metric reports too;
- every file a name points at exists (configuration, traffic mix, layer
  file, reader);
- two seeds give byte-identical series ids, the same series count in every
  shard and the same index terms, and different sample values, request
  draws and read-back samples;
- a one-host query class asks for every host of the fleet once, warm-up
  included, before it asks for any host again, at every seed;
- the request list follows the window: a client that answers in 5 ms has
  requests left when a window of ``run_seconds`` closes, and the first
  ``requests_per_worker`` requests of every worker are the ones the
  generator dealt before the list was extended (``DEALT_BEFORE``: digests
  taken from PR 30's ``traffic.py``);
- every ``live`` mix under ``traffic/``, named by a cell or not, has the
  keys of its kind, and against every configuration that holds its
  metrics: the open block does not roll over inside the window, no range
  reaches behind the first loaded tick, the classes come in equal shares
  and two seeds deal them in different orders.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import fleet  # noqa: E402
import traffic as traffic_mod  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SEEDS = (1, 2_500_000_011)
FAST_CLIENT_SECS = 0.005  # a fifth of the fastest cell's median today
# sha256 (16 hex digits) over warm-up and the first requests_per_worker
# window requests of every worker, as PR 30's generator dealt them
DEALT_BEFORE = {
    ("haystack", 1): "b49d7cba27f5f88f",
    ("haystack", 2_500_000_011): "420a6b08897c42d2",
    ("devops-haystack", 1): "234adba342ba96b9",
    ("devops-haystack", 2_500_000_011): "4cdb2f7e203d509b",
}


def dealt_digest(plan: dict, n_base: int) -> str:
    h = hashlib.sha256()
    for phase in ("warmup", "window"):
        for reqs in plan[phase]:
            for r in reqs[:n_base]:
                h.update(repr(tuple(r[k] for k in (
                    "query", "start", "end", "step", "host", "first_idx", "stride",
                    "n_steps", "window_steps", "fn", "metric"))).encode())
    return h.hexdigest()[:16]


def problems() -> list[str]:
    bad: list[str] = []
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = ([c["name"] for c in bench["configs"]] + list(cells) + list(e2e)
             + [m["name"] for m in bench["per_layer"]]
             + [w["config"] for w in cells.values()]
             + [w["traffic"] for w in cells.values()]
             + [k for c in bench["configs"] for k in c["reduced"]])
    bad += [f"name {n!r} has a character outside letters, digits, _ . -" for n in names
            if not NAME.match(n)]
    bad += [f"unit {m['unit']!r} of {m['name']}" for m in bench["end_to_end"] + bench["per_layer"]
            if not UNIT.match(m["unit"])]

    def reported_in(metric: dict) -> set[str]:
        return set(metric.get("workloads", cells))

    for m in bench["per_layer"]:
        target = e2e.get(m["moves"])
        if target is None:
            bad.append(f"{m['name']} moves {m['moves']!r}, which is no end-to-end metric")
        elif not reported_in(m) <= reported_in(target):
            bad.append(f"{m['name']} is reported in {sorted(reported_in(m) - reported_in(target))}, "
                       f"which do not report {m['moves']}")
        layer_file = os.path.join(HERE, "layers", m["name"] + ".json")
        if not os.path.exists(layer_file):
            bad.append(f"missing {layer_file}")
            continue
        layer = fleet.load_json("layers", m["name"] + ".json")
        if layer["layer"] != m["layer"] or layer["name"] != m["name"]:
            bad.append(f"layers/{m['name']}.json disagrees with BENCHMARK.json")
        if not os.path.exists(os.path.join(HERE, "readers", layer["reader"] + ".py")):
            bad.append(f"missing readers/{layer['reader']}.py")
    for c in bench["configs"]:
        if not os.path.exists(os.path.join(ROOT, c["file"])):
            bad.append(f"missing {c['file']}")
        elif fleet.load_config(c["name"])["reduced"] != c["reduced"]:
            bad.append(f"{c['file']}: reduced differs from BENCHMARK.json")
    for w in cells.values():
        if not os.path.exists(os.path.join(HERE, "traffic", w["traffic"] + ".json")):
            bad.append(f"missing traffic/{w['traffic']}.json")
        if w["config"] not in {c["name"] for c in bench["configs"]}:
            bad.append(f"cell {w['name']} names no configuration")

    # the fleet does not follow the seed; the samples and the draws do
    from m3_tpu.utils.hash import shard_for
    from m3_tpu.utils.serialize import encode_tags

    for w in cells.values():
        cfg = fleet.load_config(w["config"])
        tr = fleet.load_json("traffic", w["traffic"] + ".json")
        seen = []
        for seed in SEEDS:
            hosts = fleet.hosts(cfg)
            table = fleet.series_table(cfg)
            sids = [bytes(encode_tags(fleet.series_tags(hosts[h], m))) for h, m, _ in table]
            counts = np.bincount([shard_for(s, cfg["dbnode"]["num_shards"]) for s in sids],
                                 minlength=cfg["dbnode"]["num_shards"]).tolist()
            terms = {(k, v) for h, m, _ in table for k, v in fleet.series_tags(hosts[h], m)}
            n = fleet.points_per_block(cfg)
            vals = fleet.values(cfg, seed, traffic_mod.total_ticks(cfg, tr, n, bench["run_seconds"]))
            if tr["kind"] in ("query", "live"):
                plan = traffic_mod.query_plan(cfg, tr, fleet.t0_nanos(cfg), n, seed)
                # the list follows the window, and starts as it always did
                n_base = tr.get("requests_per_worker", 0)
                sized = traffic_mod.query_plan(cfg, tr, fleet.t0_nanos(cfg), n, seed,
                                               seconds=bench["run_seconds"])
                draws = [(r["query"], r["start"]) for reqs in sized["window"] for r in reqs[:50]]
                short = [len(reqs) for reqs in sized["window"]
                         if len(reqs) * FAST_CLIENT_SECS <= bench["run_seconds"]]
                if short:
                    bad.append(f"{w['name']}: a client that answers in {FAST_CLIENT_SECS * 1e3:.0f} ms "
                               f"runs out of its {short[0]} requests inside the window")
                want = DEALT_BEFORE.get((w["traffic"], seed))
                if want is not None and {dealt_digest(plan, n_base),
                                         dealt_digest(sized, n_base)} != {want}:
                    bad.append(f"{w['name']} seed {seed}: the first {n_base} requests a worker "
                               "are not the ones the generator dealt before")
                tail = [(r["query"], r["start"]) for reqs in sized["window"]
                        for r in reqs[n_base:n_base + 50]]
                if tr["kind"] == "query" and len(set(tail)) < len(tail) // 2:
                    bad.append(f"{w['name']} seed {seed}: the requests after the first "
                               f"{n_base} repeat each other")
                rb = [r["query"] for r in traffic_mod.readback_requests(
                    cfg, table, fleet.t0_nanos(cfg), n, seed, tr["readback_per_class"])]
            else:
                draws = None
                rb = fleet.rng_for(seed, fleet.STREAM_READBACK).choice(len(sids), 32).tolist()
            seen.append((sids, counts, len(terms), vals, draws, rb))
        a, b = seen
        if a[0] != b[0]:
            bad.append(f"{w['name']}: series ids differ between seeds")
        if a[1] != b[1] or a[2] != b[2]:
            bad.append(f"{w['name']}: shard counts or index terms differ between seeds")
        if np.array_equal(a[3], b[3]):
            bad.append(f"{w['name']}: two seeds gave the same sample values")
        if a[4] is not None and a[4] == b[4]:
            bad.append(f"{w['name']}: two seeds gave the same request draws")
        if a[5] == b[5]:
            bad.append(f"{w['name']}: two seeds read back the same sample")
        print(f"{w['name']}: {len(a[0])} series, shard counts {a[1]}, "
              f"{a[2]} index terms, the same at seeds {SEEDS}; values and draws differ")

    bad += live_problems(bench)

    # a one-host class (no cell has one yet: PERF.md section 7, the needle)
    one_host = {"workers": 4, "warmup_per_worker": 5, "requests_per_worker": 100,
                "align_secs": 10, "classes": [{
                    "fn": "max_over_time", "metric": "cpu_usage_user", "range_secs": 300,
                    "step_secs": 60, "span_secs": 3600, "hosts": 1}]}
    fleet_of = {"hosts": 400, "interval_secs": 10}
    for seed in SEEDS:
        plan = traffic_mod.query_plan(fleet_of, one_host, 0, 720, seed)
        dealt = [r["host"] for k in ("warmup", "window") for reqs in plan[k]
                 for r in reqs[:95]]
        if sorted(dealt) != list(range(400)):
            bad.append(f"seed {seed}: a one-host class asks for a host again before "
                       "it has gone once round the fleet")
    return bad


def live_problems(bench: dict) -> list[str]:
    """Every ``live`` mix under ``traffic/`` against every configuration
    whose fleet holds the metrics its classes ask for."""
    bad: list[str] = []
    configs = [fleet.load_config(c["name"]) for c in bench["configs"]]
    for name in sorted(os.listdir(os.path.join(HERE, "traffic"))):
        tr = fleet.load_json("traffic", name)
        if tr.get("kind") != "live":
            continue
        missing = [k for k in ("open_ticks", "workers", "timeout_s", "warmup_per_worker",
                               "readback_per_class", "readback_series", "classes")
                   if k not in tr] + ["writer.workers"] * (
                       not (tr.get("writer") or {}).get("workers", 0) > 0)
        if missing or not tr["open_ticks"] >= 1:
            bad.append(f"traffic/{name}: a live mix needs {missing or 'open_ticks >= 1'}")
            continue
        metrics = {c["metric"] for c in tr["classes"]}
        fits = [cfg for cfg in configs
                if metrics <= {m for _, m, _ in fleet.series_table(dict(cfg, hosts=1))}]
        if not fits:
            bad.append(f"traffic/{name}: no configuration holds {sorted(metrics)}")
        for cfg in fits:
            n = fleet.points_per_block(cfg)
            n_ticks = traffic_mod.total_ticks(cfg, tr, n, bench["run_seconds"])
            first = n + tr["open_ticks"]
            if n_ticks > 2 * n:
                bad.append(f"traffic/{name} on {cfg['name']}: the open block rolls over "
                           f"inside the window ({n_ticks} ticks, a block holds {n})")
            orders = []
            for seed in SEEDS:
                plan = traffic_mod.query_plan(cfg, tr, fleet.t0_nanos(cfg), n, seed,
                                              seconds=bench["run_seconds"])
                reqs = plan["window"][0]
                orders.append(reqs.which[:50].tolist())
                share = np.bincount(reqs.which, minlength=len(tr["classes"])) / len(reqs)
                if share.max() - share.min() > 0.001:
                    bad.append(f"traffic/{name} seed {seed}: classes in shares {share.tolist()}")
                for req in reqs[:len(tr["classes"]) * 4]:
                    # the earliest end a request can ask for: the window's first
                    low = traffic_mod.at_end_tick(
                        req, 0, 1, first - traffic_mod.LIVE_LAG_TICKS)["first_idx"] \
                        - req["window_steps"] * req["stride"]
                    if low < 0:
                        bad.append(f"traffic/{name} on {cfg['name']}: {req['query']} reaches "
                                   f"{-low} ticks behind the first loaded")
            if orders[0] == orders[1]:
                bad.append(f"traffic/{name}: two seeds dealt the classes in the same order")
            print(f"traffic/{name} on {cfg['name']}: {n_ticks} ticks ({n} sealed, "
                  f"{tr['open_ticks']} open, {n_ticks - first} in the window), "
                  f"{len(tr['classes'])} classes in equal shares")
    return bad


if __name__ == "__main__":
    found = problems()
    for line in found:
        print("FAIL " + line)
    print("selfcheck: " + ("ok" if not found else f"{len(found)} problem(s)"))
    sys.exit(1 if found else 0)
