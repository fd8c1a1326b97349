"""The server's own seconds per query (QueryStats.durationSecs in each
reply), median over the window (in a traced run: over its traced slice),
in ms."""

import statistics


def read(ctx, layer):
    secs = [
        r["stats"]["durationSecs"] * 1e3
        for r in ctx.window.get("replies", ())
        if r["error"] is None and r["stats"].get("durationSecs") is not None
    ]
    return statistics.median(secs) if secs else None
