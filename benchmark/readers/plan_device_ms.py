"""Device milliseconds a request: the traced device-busy seconds (the union
of the device's operation intervals in the profiler's trace of the dbnode)
over the answered requests of the traced slice of the window (a traced
run's ``window["replies"]``: sent and received before the stop), x 1000. Every
request of a haystack cell is one plan program and one temporal kernel, so
this is what one request costs the device whatever the host adds."""


def read(ctx, layer):
    ts = ctx.trace_summary or {}
    answered = sum(1 for r in ctx.window.get("replies", ()) if r["error"] is None)
    if not ts.get("busy_s") or not answered:
        return None
    return 1e3 * ts["busy_s"] / answered
