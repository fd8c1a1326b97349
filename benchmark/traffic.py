"""One general traffic generator: a traffic mix is a data file
(``traffic/<name>.json``), never code.

``kind: "query"`` — closed-loop range queries. ``classes`` lists query
classes ``{fn, metric, range_secs, step_secs, span_secs, hosts}`` (``hosts``
is 1 for one host of the whole fleet, or "all"); every worker gets
``warmup_per_worker`` warm-up requests and window requests for as long as
the window lasts (``requests_per_worker`` from the shared stream, then its
own), the classes in equal shares, each with a start drawn from the seed
on an ``align_secs`` grid. The hosts of the one-host classes are DEALT
from one permutation of the whole fleet drawn from the seed, without
replacement, the warm-up first and the window after it, round-robin over
the workers: TSBS draws each query's host from all of them, and so does
this, but every seed sends the same number of first sights of a host and
of repeats (none until the window has gone once round the fleet).

``kind: "write"`` — closed-loop remote write: every series once per
interval, tick after tick in time order, ``blocks`` blocks of data time.
Worker w owns the series of the dbnode shards s with s % workers == w (a
remote-write client owns a slice of the series by hash, and one ingest
buffer is fed by one client, in order).

``kind: "live"`` — the live edge: one sealed block and ``open_ticks`` ticks
of the open one loaded by set-up, then closed-loop queriers beside a
writer in real time. ``writer: {workers}``: the write clients send tick k
at ``t_go + (k - first) * interval_secs`` on the wall clock (the
configuration's scrape interval), open loop (a late tick goes at once,
none is skipped). The classes are a query file's, but no start is drawn:
every request ends at now. The client makes its range at the send, ending
``LIVE_LAG_TICKS`` behind the tick the writer's schedule has reached
(``end_tick``), and records that end tick, from which ``at_end_tick``
makes the request the reference answers.

The write warm-up is worked out here from what the configuration states
(``ingest_sync_batch``) and the fleet's per-shard series counts: a shard's
ingest buffer syncs to the device whenever ``sync_batch`` rows are staged,
with a tile padded to (pow2 lanes, pow2 slot tail); the first syncs of the
stretch see every steady-state tile, and the tiles that exist only where a
sync spans a block boundary are reached by replaying that stretch, shard by
shard, into a scratch namespace before the window.
"""

from __future__ import annotations

import numpy as np

from fleet import NANOS, STREAM_READBACK, STREAM_TRAFFIC, STREAM_TRAFFIC_MORE, rng_for

# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------


def _query_request(cfg: dict, t0: int, cls: dict, host, first_idx: int) -> dict:
    interval = cfg["interval_secs"]
    stride = cls["step_secs"] // interval
    n_steps = cls["span_secs"] // cls["step_secs"] + 1
    sel = cls["metric"]
    if host is not None:
        sel += '{hostname="host_%d"}' % host
    fn = cls["fn"]
    if fn == "selector":
        query, window_steps = sel, 0
    else:
        query = f"{fn}({sel}[{cls['range_secs']}s])"
        window_steps = cls["range_secs"] // cls["step_secs"]
    start = t0 + first_idx * interval * NANOS
    return {
        "query": query, "start": start,
        "end": start + (n_steps - 1) * cls["step_secs"] * NANOS,
        "step": cls["step_secs"] * NANOS,
        # what the reference needs: sample-index arithmetic only
        "fn": fn, "metric": cls["metric"], "host": host,
        "first_idx": first_idx, "stride": stride, "n_steps": n_steps,
        "window_steps": window_steps,
    }


def _start_slots(cfg: dict, n_points: int, cls: dict, align_secs: int) -> np.ndarray:
    """Sample indices a request of this class may start at: the whole
    range, look-back included, stays inside the loaded block."""
    interval = cfg["interval_secs"]
    lo = cls["range_secs"] if cls["fn"] != "selector" else 0
    hi = n_points * interval - cls["span_secs"] - interval
    grid = np.arange(0, hi + 1, align_secs)
    return grid[grid >= lo] // interval


class Requests:
    """One worker's requests of one phase, kept as their draws (class,
    host, start slot) and made into request dicts on demand: ``reqs[i]``,
    ``reqs[:n]``, iteration. ``wire()`` is what the client sends."""

    def __init__(self, cfg: dict, t0: int, classes: list[dict],
                 which: np.ndarray, hosts: np.ndarray, first_idx: np.ndarray) -> None:
        self.cfg, self.t0, self.classes = cfg, t0, classes
        self.which, self.hosts, self.first_idx = which, hosts, first_idx

    def __len__(self) -> int:
        return len(self.which)

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [self[k] for k in range(*i.indices(len(self)))]
        cls = self.classes[int(self.which[i])]
        host = int(self.hosts[i]) if cls["hosts"] == 1 else None
        return _query_request(self.cfg, self.t0, cls, host, int(self.first_idx[i]))

    def wire(self) -> list[tuple]:
        """(query, start, end, step) of every request, in order."""
        return [(r["query"], r["start"], r["end"], r["step"]) for r in self]

    def wire_now(self) -> list[tuple]:
        """(query, span, step) of every request of a live mix, in order:
        the client makes the range at the send."""
        return [(r["query"], r["end"] - r["start"], r["step"]) for r in self]


# No served request answers faster: one round trip through the dbnode's
# Python RPC server and one device dispatch. The window's list is sized by it.
LATENCY_FLOOR_SECS = 0.001


def query_plan(cfg: dict, traffic: dict, t0: int, n_points: int, seed: int,
               seconds: float | None = None) -> dict:
    """Per-worker ``Requests``: ``warmup`` then ``window``.

    The plan of a matcher is built at its first sight, which costs more
    than a later sight, so hosts drawn with replacement would mix the two
    in a ratio that follows the draw. Dealing them from one permutation of
    the fleet keeps the source's distribution (any host, each as likely)
    and gives every seed the same mix: request k over all workers, warm-up
    included, asks for host ``deal[k % hosts]``.

    The list follows the window: with ``seconds`` a worker gets as many
    window requests as a client answered in ``LATENCY_FLOOR_SECS`` could
    send, ``requests_per_worker`` at least. The first
    ``requests_per_worker`` are drawn as they always were (one stream, all
    workers in turn), so a seed is the work it was; those after them come
    from a stream of the worker's own."""
    rng = rng_for(seed, STREAM_TRAFFIC)
    classes = traffic["classes"]
    workers = traffic["workers"]
    for cls in classes:
        if cls["hosts"] not in (1, "all"):
            raise ValueError(f"query class hosts {cls['hosts']!r}: 1 or \"all\"")
    deal = rng.permutation(cfg["hosts"])
    align_secs = traffic.get("align_secs", cfg["interval_secs"])
    # a live mix draws no start: its requests end at now
    slots = [np.zeros(1, np.int64) if traffic.get("kind") == "live"
             else _start_slots(cfg, n_points, cls, align_secs) for cls in classes]

    def dealt_hosts(n: int, worker: int, dealt: int) -> np.ndarray:
        """The k-th request of a worker takes deal position
        ``dealt + k * workers + worker``."""
        return deal[(dealt + np.arange(n) * workers + worker) % len(deal)]

    def draw(n: int) -> tuple[np.ndarray, np.ndarray]:
        """Classes in equal shares and a start slot each, from the one
        stream all workers share: call for call what PR 26 drew."""
        which = np.arange(n) % len(classes)
        rng.shuffle(which)
        first = np.asarray([slots[c][rng.integers(len(slots[c]))] for c in which.tolist()],
                           dtype=np.int64)
        return which, first

    def more(n: int, worker: int) -> tuple[np.ndarray, np.ndarray]:
        """The same, from the worker's own stream: drawing them moves no
        other worker's requests."""
        own = rng_for(seed, STREAM_TRAFFIC_MORE, worker)
        which = np.arange(n) % len(classes)
        own.shuffle(which)
        first = np.zeros(n, np.int64)
        for c in range(len(classes)):
            mine = which == c
            first[mine] = slots[c][own.integers(len(slots[c]), size=int(mine.sum()))]
        return which, first

    n_warm = traffic["warmup_per_worker"]
    n_base = traffic.get("requests_per_worker", 0)
    n_window = n_base if seconds is None else max(
        n_base, int(np.ceil(seconds / LATENCY_FLOOR_SECS)))
    plan: dict = {"warmup": [], "window": []}
    for w in range(workers):
        which, first = draw(n_warm)
        plan["warmup"].append(Requests(cfg, t0, classes, which,
                                       dealt_hosts(n_warm, w, 0), first))
    for w in range(workers):
        which, first = (np.concatenate(pair) for pair in zip(
            draw(n_base), more(n_window - n_base, w)))
        plan["window"].append(Requests(cfg, t0, classes, which,
                                       dealt_hosts(n_window, w, n_warm * workers), first))
    return plan


def readback_requests(cfg: dict, table: list, t0: int, n_points: int,
                      seed: int, per_class: int) -> list[dict]:
    """A plain selector over the whole block, every sample a step, for
    ``per_class`` series of every value class the segment holds, drawn
    from the seed."""
    rng = rng_for(seed, STREAM_READBACK)
    by_class: dict[str, list[int]] = {}
    for i, (_, _, cls) in enumerate(table):
        by_class.setdefault(cls, []).append(i)
    reqs = []
    for cls in sorted(by_class):
        rows = by_class[cls]
        for i in rng.choice(len(rows), size=min(per_class, len(rows)), replace=False):
            host, metric, _ = table[rows[int(i)]]
            sel = {"fn": "selector", "metric": metric, "range_secs": 0,
                   "step_secs": cfg["interval_secs"],
                   "span_secs": (n_points - 1) * cfg["interval_secs"], "hosts": 1}
            req = _query_request(cfg, t0, sel, host, 0)
            req["class"] = cls
            reqs.append(req)
    return reqs


# ---------------------------------------------------------------------------
# the live edge
# ---------------------------------------------------------------------------


# A request that ends at now ends one tick behind the one the writer's
# schedule has reached: the newest sample every writer can have had
# acknowledged (the tick that is due is on its way).
LIVE_LAG_TICKS = 1


def total_ticks(cfg: dict, traffic: dict, n_points: int, seconds: float) -> int:
    """Ticks of data time one run makes: a block for a query mix,
    ``blocks`` of them for a write mix; for a live mix the sealed block,
    the ``open_ticks`` set-up writes and the ticks the writer's schedule
    (one every ``interval_secs``) reaches before the window closes."""
    if traffic["kind"] == "live":
        return n_points + traffic["open_ticks"] + int(
            np.ceil(seconds / cfg["interval_secs"]))
    return n_points * traffic.get("blocks", 1)


def end_tick(t_send: float, t_go: float, pace_secs: float, first_tick: int,
             last_tick: int) -> int:
    """The sample a request of a live mix ends on when it is sent at
    ``t_send``: ``LIVE_LAG_TICKS`` behind the tick the paced writer's
    schedule has reached (tick k is due at ``t_go + (k - first_tick) *
    pace_secs``, ``last_tick`` the last it sends). Before the window
    (warm-up) that is the last tick set-up wrote."""
    if t_send < t_go:
        return first_tick - 1
    return min(first_tick + int((t_send - t_go) // pace_secs), last_tick) - LIVE_LAG_TICKS


def at_end_tick(req: dict, t0: int, interval_nanos: int, end_tick: int) -> dict:
    """The request of a live mix as the client sent it, with its last
    step on sample ``end_tick``."""
    first_idx = end_tick - (req["n_steps"] - 1) * req["stride"]
    start = t0 + first_idx * interval_nanos
    return dict(req, first_idx=first_idx, start=start,
                end=start + (req["n_steps"] - 1) * req["step"])


# ---------------------------------------------------------------------------
# writes
# ---------------------------------------------------------------------------


def pow2ceil(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def sync_ticks(count: int, sync_batch: int, n_ticks: int) -> list[int]:
    """Ticks after which a shard of ``count`` series syncs its ingest
    buffer: whenever ``sync_batch`` rows have been staged since the last."""
    out, staged = [], 0
    for j in range(n_ticks):
        staged += count
        if staged >= sync_batch:
            out.append(j)
            staged = 0
    return out


def write_plan(shard_counts: list[int], sync_batch: int, n_points: int,
               blocks: int) -> dict:
    """The warm-up of a write cell: ``warmup_ticks`` of the traffic itself
    (until every shard has synced once, so every steady tile has been
    seen), and for each shard the stretch around each block boundary
    (``replays``: shard, first tick, last tick) to replay in the scratch
    namespace. ``tiles`` lists, per shard, the padded (lanes, slots) tiles
    the window will dispatch."""
    n_ticks = n_points * blocks
    warmup, replays, tiles = 1, [], []  # tick 0 is the registration
    for shard, count in enumerate(shard_counts):
        if count == 0:
            tiles.append([])
            continue
        syncs = sync_ticks(count, sync_batch, n_ticks)
        if not syncs:
            tiles.append([])
            continue
        warmup = max(warmup, syncs[0] + 1)
        nd = pow2ceil(count)
        seen = {(nd, pow2ceil(syncs[0] + 1))}
        prev = -1
        for s in syncs:
            for b in range(n_points, n_ticks, n_points):
                if prev + 1 < b <= s:  # this sync spans boundary b
                    replays.append((shard, prev + 1, s))
                    seen.add((nd, pow2ceil(b - prev - 1)))
                    seen.add((nd, pow2ceil(s - b + 1)))
            prev = s
        tiles.append(sorted(seen))
    return {"warmup_ticks": warmup, "replays": replays, "tiles": tiles}
