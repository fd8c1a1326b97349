"""The fleet and its samples: one general generator over a configuration's
``measurements`` table.

The FLEET (host names, the ten tags of every host, hence every series id,
its shard and the index's terms) is made from ``fleet_seed``, a constant of
the configuration's file: it is the same in every run, so every ``--seed``
is the same work. ``--seed`` feeds independent streams for (a) the sample
values, (b) the traffic's draws and (c) which series are read back.

Value classes (``classes`` table of the configuration):

- ``gauge_int``  clamped integer random walk in [lo, hi], step uniform
                 in -step..step
- ``percent``    clamped float64 random walk in [lo, hi] with fractional
                 steps (every value carries a full mantissa)
- ``counter``    int64 counter from a base in [base_lo, base_hi), increment
                 uniform in 0..inc_hi per interval
- ``constant``   one integer in [lo, hi) per series, never changing

Imports nothing of the program: the reference is computed from the very
matrix made here.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
NANOS = 1_000_000_000
# block-aligned epoch of the data: 2020-09-13T14:00:00Z for 2 h blocks
T0_BLOCKS = 222_223

# independent seed streams (numpy SeedSequence spawn keys)
STREAM_VALUES, STREAM_TRAFFIC, STREAM_READBACK, STREAM_TRAFFIC_MORE = 1, 2, 3, 4


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    cfg = load_json("configs", name + ".json")
    if cfg["name"] != name:
        raise ValueError(f"configs/{name}.json names itself {cfg['name']!r}")
    return cfg


def rng_for(seed: int, stream: int, *sub: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream, *sub]))


def t0_nanos(cfg: dict) -> int:
    return T0_BLOCKS * cfg["block_secs"] * NANOS


def points_per_block(cfg: dict) -> int:
    return cfg["block_secs"] // cfg["interval_secs"]


def hosts(cfg: dict) -> list[dict]:
    """The fixed fleet: ``hosts`` dicts of the ten TSBS host tags, drawn
    from ``fleet_seed`` alone."""
    rng = np.random.default_rng(np.random.SeedSequence([cfg["fleet_seed"], 0]))
    t = cfg["tags"]

    def pick(values):
        return values[int(rng.integers(len(values)))]

    out = []
    for h in range(cfg["hosts"]):
        region = pick(t["region"])
        out.append({
            "hostname": f"host_{h}",
            "region": region,
            "datacenter": region + pick(t["datacenter_suffix"]),
            "rack": str(int(rng.integers(t["rack"]))),
            "os": pick(t["os"]),
            "arch": pick(t["arch"]),
            "team": pick(t["team"]),
            "service": str(int(rng.integers(t["service"]))),
            "service_version": str(int(rng.integers(t["service_version"]))),
            "service_environment": pick(t["service_environment"]),
        })
    return out


def series_table(cfg: dict) -> list[tuple[int, str, str]]:
    """(host index, metric name, class name) of every series, host-major,
    in the order of the ``measurements`` table."""
    fields = [
        (m["name"] + "_" + f, cls)
        for m in cfg["measurements"]
        for f, cls in m["fields"].items()
    ]
    return [(h, name, cls) for h in range(cfg["hosts"]) for name, cls in fields]


def series_tags(host: dict, metric: str) -> tuple:
    tags = dict(host, __name__=metric)
    return tuple((k.encode(), v.encode()) for k, v in sorted(tags.items()))


def values(cfg: dict, seed: int, n_points: int) -> np.ndarray:
    """float64[n_series, n_points] from ``--seed``. Every value is an exact
    float64 (integers below 2^53, or a float64 walk), so a lossless store
    hands back the same bits."""
    rng = rng_for(seed, STREAM_VALUES)
    table = series_table(cfg)
    out = np.empty((len(table), n_points), np.float64)
    by_class: dict[str, list[int]] = {}
    for i, (_, _, cls) in enumerate(table):
        by_class.setdefault(cls, []).append(i)
    for cls in sorted(by_class):
        rows = np.asarray(by_class[cls])
        spec = cfg["classes"][cls]
        n = len(rows)
        kind = spec["kind"]
        if kind == "gauge_int":
            lo, hi, step = spec["lo"], spec["hi"], spec["step"]
            cur = rng.integers(lo, hi + 1, n)
            steps = rng.integers(-step, step + 1, (n_points, n))
            for j in range(n_points):
                out[rows, j] = cur
                cur = np.clip(cur + steps[j], lo, hi)
        elif kind == "percent":
            lo, hi, step = spec["lo"], spec["hi"], spec["step"]
            cur = rng.uniform(lo, hi, n)
            steps = rng.normal(0.0, step, (n_points, n))
            for j in range(n_points):
                out[rows, j] = cur
                cur = np.clip(cur + steps[j], lo, hi)
        elif kind == "counter":
            base = rng.integers(spec["base_lo"], spec["base_hi"], n)
            inc = rng.integers(0, spec["inc_hi"] + 1, (n, n_points))
            inc[:, 0] = 0
            out[rows] = base[:, None] + np.cumsum(inc, axis=1)
        elif kind == "constant":
            out[rows] = rng.integers(spec["lo"], spec["hi"], n)[:, None]
        else:
            raise ValueError(f"unknown value class kind {kind!r}")
    return out
