"""Device milliseconds a request: the traced device-busy seconds of the
window (the union of the device's operation intervals in the profiler's
trace of the dbnode) over the window's answered requests, x 1000. Every
request of a haystack cell is one plan program and one temporal kernel, so
this is what one request costs the device whatever the host adds."""


def read(ctx, layer):
    ts = ctx.trace_summary or {}
    answered = sum(1 for r in ctx.window.get("replies", ()) if r["error"] is None)
    if not ts.get("busy_s") or not answered:
        return None
    return 1e3 * ts["busy_s"] / answered
