"""The one place that decides "is this the chip", and says so out loud.

Every Pallas call site asks :func:`on_tpu` (Mosaic kernels compile only
for the TPU; any other backend runs the kernel body in interpret mode or
takes the lax fallback). A process that was ASKED for a device tier calls
:func:`require_device` first: a machine without a chip is an error there,
never a quiet CPU run. :func:`configure_compile_cache` gives every
process of a checkout the same persistent compilation cache — the path
is part of the cache key, so it is fixed, never temporary.
:func:`install_compile_counters` makes ``m3tpu_jit_*`` count jax's own
compile events, so every program the process compiles is seen, whoever
jitted it.

jax imports are deferred: tools that never touch the device can import
this module for free.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def require_device() -> tuple[str, int, str]:
    """(platform, count, device_kind) of ``jax.devices()``. Raises unless
    the platform is ``tpu`` or ``JAX_PLATFORMS`` explicitly names ``cpu``
    (tests and the tools/check_*.py CPU gates set it; nothing else may)."""
    import jax

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    # the first platform JAX_PLATFORMS lists is the one jax defaults to
    chosen = os.environ.get("JAX_PLATFORMS", "").lower().split(",")[0].strip()
    if platform != "tpu" and chosen != "cpu":
        raise RuntimeError(
            f"a device tier was requested but jax found platform "
            f"{platform!r} ({len(devs)} x {kind}); run on a TPU, or set "
            "JAX_PLATFORMS=cpu to choose the CPU explicitly"
        )
    return platform, len(devs), kind


def configure_compile_cache() -> str:
    """Persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` wins
    (jax reads it itself; nothing is touched here); otherwise
    ``<checkout>/.jax_cache``. Returns the directory in effect."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_compile_counters_installed = False


def install_compile_counters() -> None:
    """Feed ``m3tpu_jit_compiles_total{kernel}``,
    ``m3tpu_jit_compile_seconds_total{kernel}`` and
    ``m3tpu_jit_cache_hits_total`` from jax.monitoring: one
    backend-compile event per program the process lowers and compiles or
    fetches from the persistent cache, labelled with the jitted
    function's name. Called on the main thread at a service's start,
    before anything is jitted (this is the process's first ``import
    jax``); calling it again changes nothing."""
    global _compile_counters_installed
    if _compile_counters_installed:
        return
    _compile_counters_installed = True
    from jax import monitoring

    from .utils.instrument import DEFAULT as METRICS

    cache_hits = METRICS.counter(
        "jit_cache_hits_total",
        "programs fetched from the persistent compilation cache",
    )

    def on_duration(event, duration, fun_name="?", **_kw):
        if event != _COMPILE_EVENT:
            return
        labels = {"kernel": str(fun_name)}
        METRICS.counter(
            "jit_compiles_total",
            "programs compiled or fetched from the persistent cache "
            "(jax backend-compile events)",
            labels,
        ).inc()
        METRICS.counter(
            "jit_compile_seconds_total",
            "seconds inside jax's backend compile",
            labels,
        ).inc(float(duration))

    def on_event(event, **_kw):
        if event == _CACHE_HIT_EVENT:
            cache_hits.inc()

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
