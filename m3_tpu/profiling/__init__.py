"""Continuous profiling (the x/debug pprof role, always-on):

- **host tier** — :class:`StackSampler` (sampler.py): a wall-clock
  stack sampler folding ``sys._current_frames()`` snapshots into a
  bounded, time-windowed folded-stack table, served at
  ``/debug/pprof/profile`` and the ``profile`` wire op;
- **device tier** — ``utils.instrument.KernelProfiler`` dispatch
  timing + compiled HLO cost analysis (flops / bytes accessed per
  kernel), plus the live device-memory split
  (``m3tpu_device_memory_bytes{kind}``, device.py), and a jax.profiler
  capture of a running process (the ``device_profile`` op, device.py)
  that ``python -m m3_tpu.profiling.gaps`` reduces to "what the host was
  doing while the device idled" (gaps.py);
- **fleet tier** — ``/debug/pprof/fleet`` merges every peer's folded
  stacks by frame with per-instance tags (merge.py).

Each service process installs its sampler here (``install``) so the
wire op handlers and debug HTTP routes — which cannot thread a handle
through every dispatch table — find it, mirroring how
``instrument.DEFAULT`` is the process registry. Profiler health is
self-metered as ``m3tpu_profile_*`` and flows into ``_m3tpu`` via the
selfmon collector, so a ruler rule can alert on the profiler itself.
"""

from __future__ import annotations

from .device import (
    capture_dir,
    collect_device_memory,
    device_stat,
    start_capture,
    stop_capture,
)
from .merge import collect_fleet_profile, merge_profiles
from .sampler import StackSampler, default_hz, folded_text

__all__ = [
    "StackSampler",
    "capture_dir",
    "collect_device_memory",
    "collect_fleet_profile",
    "default_hz",
    "device_stat",
    "folded_text",
    "install",
    "installed",
    "merge_profiles",
    "process_profile",
    "start_capture",
    "start_sampler",
    "stop_capture",
]

# the process's installed sampler (the instrument.DEFAULT pattern): op
# handlers and debug routes read it; services install at startup
_SAMPLER: StackSampler | None = None


def install(sampler: StackSampler | None) -> None:
    global _SAMPLER
    _SAMPLER = sampler


def installed() -> StackSampler | None:
    return _SAMPLER


def process_profile(seconds: float | None = None) -> dict:
    """The installed sampler's profile — the one shape the ``profile``
    wire op and every pprof route serve. A process without a sampler
    (profiling disabled) answers with an explicit empty profile instead
    of erroring: the fleet merge must see 'nothing here', not a hole."""
    sampler = _SAMPLER
    if sampler is None:
        return {
            "enabled": False,
            "instance": "",
            "hz": 0.0,
            "seconds": 0.0,
            "samples": 0,
            "folded": {},
        }
    return sampler.profile(seconds=seconds)


def start_sampler(
    hz: float | None = None, instance: str = "", db=None, **kwargs
) -> StackSampler | None:
    """Service-startup helper: build, start, and install the process
    sampler with device-memory accounting attached (``db`` may be None —
    the accountant still tracks live jax buffers). Returns None when the
    resolved rate is 0 (profiling off)."""
    hz = default_hz() if hz is None else max(float(hz), 0.0)
    if hz <= 0:
        return None
    sampler = StackSampler(
        hz=hz,
        instance=instance,
        memory=lambda: collect_device_memory(db),
        **kwargs,
    )
    sampler.start()
    install(sampler)
    return sampler
