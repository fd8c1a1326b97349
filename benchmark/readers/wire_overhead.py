"""Client latency less the server's own seconds (QueryStats.durationSecs),
median over the window's replies (a traced run's: those of its traced
slice), in ms: wire codec both ways, socket,
dispatch to the handler, and the reply's encoding."""

import statistics


def read(ctx, layer):
    over = [
        (r["latency_s"] - r["stats"]["durationSecs"]) * 1e3
        for r in ctx.window.get("replies", ())
        if r["error"] is None and r["stats"].get("durationSecs") is not None
    ]
    return statistics.median(over) if over else None
