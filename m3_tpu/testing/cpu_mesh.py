"""Force JAX onto a virtual N-device CPU host mesh.

Single source of truth for the platform forcing the tests need: an env-var
overlay (for child processes, before their interpreter starts) and a
post-import ``jax.config.update`` (for the already-running interpreter).
Used by tests/conftest.py and __graft_entry__.dryrun_multichip.
"""

from __future__ import annotations

import os

# Env vars (besides JAX_PLATFORMS) the installed jax reads to pick or
# re-route the platform.
_PLATFORM_SELECTORS = (
    "JAX_PLATFORM_NAME",
    "TPU_LIBRARY_PATH",
)


def cpu_mesh_env(n_devices: int, base: dict | None = None) -> dict:
    """A copy of ``base`` (default os.environ) forcing an n-device CPU mesh.

    For spawning child processes: takes effect before any jax import there.
    """
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    for k in _PLATFORM_SELECTORS:
        env.pop(k, None)
    return env


def force_cpu_mesh(n_devices: int) -> None:
    """Force the CURRENT process onto an n-device CPU mesh.

    Must run before jax creates any backend. Applies both the env overlay
    (children inherit it) and the config override.
    """
    os.environ.update(
        {k: v for k, v in cpu_mesh_env(n_devices).items() if k in ("JAX_PLATFORMS", "XLA_FLAGS")}
    )
    for k in _PLATFORM_SELECTORS:
        os.environ.pop(k, None)

    import jax

    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    assert devs[0].platform == "cpu" and len(devs) >= n_devices, devs
