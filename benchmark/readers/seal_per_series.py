"""Set-up's ``flush`` seconds (device encode, pool admission, index
segment) over the series sealed, in ms."""


def read(ctx, layer):
    secs = ctx.phases.get("seal_s")
    if not secs or ctx.traffic["kind"] == "write":  # there the seal follows the window
        return None
    return secs * 1e3 / len(ctx.table)
