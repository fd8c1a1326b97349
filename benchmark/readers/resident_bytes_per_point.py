"""Compressed bytes the resident pool holds per loaded point: the pool's
own ``bytes`` (m3tsz stream bytes of every admitted block, from the
dbnode's ``resident_stats`` after set-up's seal) over the points set-up
loaded. What a request's gathers and decode have to walk, per point."""


def read(ctx, layer):
    res = ctx.counters.get("resident") or {}
    points = ctx.counters.get("load_points")
    if not res.get("bytes") or not points:
        return None
    return res["bytes"] / points
