"""Share of the sealed lanes that the device encoder took, in %: the
resident pool's own ``device_admissions`` (lanes born resident from the
encode kernel's output) over its ``admissions`` (those plus the lanes the
host codec encoded and admission uploaded), both read from the dbnode's
``resident_stats`` after set-up's seal."""


def read(ctx, layer):
    res = ctx.counters.get("resident") or {}
    if not res.get("admissions") or res.get("device_admissions") is None:
        return None
    return 100.0 * res["device_admissions"] / res["admissions"]
