"""100 x (1 - device-busy seconds / traced seconds): busy is the union of
the device's operation intervals in the profiler's trace of the dbnode."""


def read(ctx, layer):
    ts = ctx.trace_summary or {}
    traced = ctx.window.get("traced_s")
    if "busy_s" not in ts or not traced:
        return None
    return 100.0 * (1.0 - ts["busy_s"] / traced)
