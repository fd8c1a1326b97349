"""Bytes the commit log grew by over the window, per acknowledged point:
its files' size after the window's last acknowledged batch has drained to
the log (size stood still for 1.6 s; sync mode interval fsyncs every
second) less the size settled the same way before the window."""


def read(ctx, layer):
    grown = ctx.window.get("commitlog_bytes")
    points = ctx.window.get("points")
    return grown / points if grown and points else None
