"""Programs the dbnode compiled (or fetched from the persistent cache)
between the window's start and its close: jax.monitoring's backend-compile
events, counted by the benchmark's hook inside the dbnode. The program's
own m3tpu_jit_* counters cover only its profiled kernels (not the ingest
tile scatter) and are printed beside this on an earlier line."""


def read(ctx, layer):
    n = ctx.counters.get("compiles_in_window")
    return None if n is None else float(n)
