"""Programs the dbnode compiled between the window's start and its close,
as the PROGRAM counts them: the growth of m3tpu_jit_compiles_total (summed
over its kernels) between the two scrapes Cell.stat() makes. The program
feeds that counter from jax's own backend-compile events
(m3_tpu/device.py install_compile_counters), so it should equal
compiles_in_window.*, which the benchmark's hook counts from the same
events. None only where a scrape lacks the key: a true 0 is reported."""


def read(ctx, layer):
    s0, s1 = ctx.window.get("stat0") or {}, ctx.window.get("stat1") or {}
    if "m3tpu_jit_compiles" not in s0 or "m3tpu_jit_compiles" not in s1:
        return None
    return float(s1["m3tpu_jit_compiles"] - s0["m3tpu_jit_compiles"])
