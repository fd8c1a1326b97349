"""The blocking read-back of the range function's device result per reply
(``query.temporal.wait`` in each reply's ``stats.stages``: the device
queue ahead of it, the kernel or the eager operations, the transfer),
median over the window's replies that hold it (a traced run's: those of
its traced slice), in ms. None where no reply carries the stage: a
program without it, or a mix whose range functions all take the exact
float64 selection on the host."""

import statistics


def read(ctx, layer):
    secs = [
        r["stats"]["stages"]["query.temporal.wait"] * 1e3
        for r in ctx.window.get("replies", ())
        if r["error"] is None
        and "query.temporal.wait" in (r["stats"].get("stages") or {})
    ]
    return statistics.median(secs) if secs else None
