"""The part of the server's evaluation that no stage names, per reply: the
``query.eval`` stage less its direct children ``parse``, ``fetch`` and
``query.temporal`` (each summed over the reply's calls; the ``plan.*``
stages lie inside ``fetch`` and the ``query.temporal.*`` ones inside
``query.temporal``, so neither is taken off again), median over the
window's replies (a traced run's: those of its traced slice), in ms.
None where no reply carries ``query.temporal``: a program without that
stage, whose self time cannot be told from its range function."""

import statistics

CHILDREN = ("parse", "fetch", "query.temporal")


def read(ctx, layer):
    stages = [
        r["stats"].get("stages") or {}
        for r in ctx.window.get("replies", ())
        if r["error"] is None
    ]
    if not any("query.temporal" in s for s in stages):
        return None
    secs = [
        (s["query.eval"] - sum(s.get(c, 0.0) for c in CHILDREN)) * 1e3
        for s in stages
        if "query.eval" in s
    ]
    return statistics.median(secs) if secs else None
