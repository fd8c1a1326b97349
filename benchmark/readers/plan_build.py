"""The plan build per reply that built one (``plan.build`` in each reply's
``stats.stages``: a plan-cache miss, the matchers' part and, where it did
not hold, the segment's lane tables), median over the window's replies (a
traced run's: those of its traced slice), in ms. None where no reply of
the window built a plan."""

import statistics


def read(ctx, layer):
    secs = [
        r["stats"]["stages"]["plan.build"] * 1e3
        for r in ctx.window.get("replies", ())
        if r["error"] is None and "plan.build" in (r["stats"].get("stages") or {})
    ]
    return statistics.median(secs) if secs else None
