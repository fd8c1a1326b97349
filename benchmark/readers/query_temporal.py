"""The engine's range function per reply that applied one
(``query.temporal`` in each reply's ``stats.stages``: from the range's grid
in hand to the built result, whichever path it took: the exact float64
selection on the host, or the device's turn of dispatch and read-back),
median over the window's replies (a traced run's: those of its traced
slice), in ms. None where no reply carries the stage: a program without
it, or a mix with no range function."""

import statistics


def read(ctx, layer):
    secs = [
        r["stats"]["stages"]["query.temporal"] * 1e3
        for r in ctx.window.get("replies", ())
        if r["error"] is None and "query.temporal" in (r["stats"].get("stages") or {})
    ]
    return statistics.median(secs) if secs else None
