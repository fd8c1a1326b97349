"""Points per second of set-up's load phase (the whole block over the wire,
commit log on), client build and registration included."""


def read(ctx, layer):
    secs = ctx.phases.get("load_s")
    points = ctx.counters.get("load_points")
    return points / secs if secs and points else None
