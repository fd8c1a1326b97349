"""The plan's overlay stage per reply (``plan.overlay`` in each reply's
``stats.stages``: the host part of reading the open block from the shards'
ingest planes — lane lookup, the staged tail's sync, the buffers' leases),
median over the window's replies (a traced run's: those of its traced
slice), in ms. None where no reply carries the stage: a program without
the overlay."""

import statistics


def read(ctx, layer):
    secs = [
        r["stats"]["stages"]["plan.overlay"] * 1e3
        for r in ctx.window.get("replies", ())
        if r["error"] is None and "plan.overlay" in (r["stats"].get("stages") or {})
    ]
    return statistics.median(secs) if secs else None
