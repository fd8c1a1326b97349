"""Fused temporal-function evaluation: one pallas kernel, one HBM pass.

Reference semantics: /root/reference/src/query/functions/temporal/
{rate.go, aggregation.go:62-267, functions.go:89-117} — the per-step window
loops. The unfused jnp formulations in ``temporal.py`` are correct but each
windowed reduction tree is a separate HBM round trip (~25 array passes for
``rate``: measured 1.4B dp/s at 102k x 720 on v5e). Here the whole [S, T]
row-block is staged into VMEM once and every shifted-window pass runs on
chip: the same jnp code, lowered by Mosaic inside the kernel, with HBM
traffic = read input + write outputs (measured 18B dp/s for rate+avg — a
10x win, bit-identical results).

Multiple functions over the same range vector fuse into one kernel with one
output per function (PromQL rarely needs this, but the aggregation tier's
rollup pipelines do).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ... import device
from ...utils.instrument import DEFAULT as METRICS
from ...utils.instrument import KernelProfiler
from . import temporal as T

# sampled block_until_ready-bounded dispatch timings under
# M3_TPU_PROFILE_SAMPLE_RATE (m3tpu_kernel_dispatch_seconds); the first
# call per static signature blocks on Mosaic compilation and stays out of
# them (the compile itself is counted by m3tpu_jit_*, from jax's events)
_JIT = KernelProfiler("temporal_fused")
_M_PROCESSED = METRICS.counter(
    "temporal_fused_input_bytes_total",
    "bytes of range-vector input through the fused temporal kernel",
)

# name -> (fn(values, window, step_seconds) -> [S, T]) — only functions whose
# math is pure elementwise/shift (Mosaic-lowerable); quantile_over_time's
# axis sort stays unfused.
FUSABLE = {
    "rate": lambda v, w, s: T.rate(v, w, s),
    "irate": lambda v, w, s: T.irate(v, w, s),
    "increase": lambda v, w, s: T.increase(v, w, s),
    "delta": lambda v, w, s: T.delta(v, w, s),
    "idelta": lambda v, w, s: T.idelta(v, w, s),
    # deriv/predict_linear stay unfused: their chunked window-gather
    # (_linreg_sums) doesn't lower under Mosaic
    "resets": lambda v, w, s: T.resets(v, w),
    "changes": lambda v, w, s: T.changes(v, w),
    "sum_over_time": lambda v, w, s: T.sum_over_time(v, w),
    "count_over_time": lambda v, w, s: T.count_over_time(v, w),
    "avg_over_time": lambda v, w, s: T.avg_over_time(v, w),
    "min_over_time": lambda v, w, s: T.min_over_time(v, w),
    "max_over_time": lambda v, w, s: T.max_over_time(v, w),
    "last_over_time": lambda v, w, s: T.last_over_time(v, w),
    "stddev_over_time": lambda v, w, s: T.stddev_over_time(v, w),
    "stdvar_over_time": lambda v, w, s: T.stdvar_over_time(v, w),
}

BLOCK_ROWS = 64  # VMEM budget: ~30 live [64, T] f32 intermediates ≈ 5.5MB @ T=720


@functools.partial(
    jax.jit, static_argnames=("funcs", "window", "step_seconds", "t_cols")
)
def _fused_call(values, funcs: tuple, window: int, step_seconds: float, t_cols: int):
    from jax.experimental import pallas as pl

    n_out = len(funcs)

    def kernel(x_ref, *out_refs):
        v = x_ref[...]
        for name, ref in zip(funcs, out_refs):
            ref[...] = FUSABLE[name](v, window, step_seconds).astype(jnp.float32)

    s = values.shape[0]
    spec = pl.BlockSpec((BLOCK_ROWS, t_cols), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(s // BLOCK_ROWS,),
        in_specs=[spec],
        out_specs=[spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((s, t_cols), jnp.float32)] * n_out,
    )(values)


def fused_temporal(values, window: int, step_seconds: float, funcs: tuple[str, ...]):
    """Evaluate ``funcs`` over the same [S, T] range matrix in one fused
    kernel on TPU; plain per-function evaluation elsewhere. Returns a tuple
    of [S, T] arrays in ``funcs`` order."""
    if not device.on_tpu() or any(f not in FUSABLE for f in funcs):
        v = jnp.asarray(values, jnp.float32)
        return tuple(FUSABLE[f](v, window, step_seconds) for f in funcs)
    v = jnp.asarray(values, jnp.float32)
    s, t = v.shape
    pad = (-s) % BLOCK_ROWS
    if pad:
        v = jnp.pad(v, ((0, pad), (0, 0)), constant_values=jnp.nan)
    _M_PROCESSED.inc(int(v.size) * 4)
    with _JIT.dispatch(
        (tuple(funcs), v.shape, int(window), float(step_seconds)),
        cost=(_fused_call,
              (v, tuple(funcs), int(window), float(step_seconds), t), {}),
    ) as d:
        outs = d.done(
            _fused_call(v, tuple(funcs), int(window), float(step_seconds), t)
        )
    if not isinstance(outs, (list, tuple)):
        outs = (outs,)
    if pad:
        outs = tuple(o[:s] for o in outs)
    return tuple(outs)


# ---------------------------------------------------------------------------
# exact selections over float64
# ---------------------------------------------------------------------------

# min / max / last of a window are SELECTIONS: the answer is one of the
# window's samples, so it can be exact in float64 though the device has no
# float64. The f32 path above selects among samples already rounded to
# f32, which is exact only where every sample is an f32 (TSBS cpu gauges:
# integers in [0, 100]); counters, byte gauges beyond 2^24 and float64
# percents are not, and for those numpy selects among the float64 samples
# where the engine already holds them, on the host.
SELECTIONS = ("min_over_time", "max_over_time", "last_over_time")


def f32_exact(values: np.ndarray) -> bool:
    """Whether every sample survives float64 -> float32 -> float64 (NaN,
    the missing sample, counts as surviving)."""
    with np.errstate(over="ignore"):  # beyond f32's range: inf, not equal
        narrowed = values.astype(np.float32)
    return np.array_equal(narrowed.astype(np.float64), values, equal_nan=True)


def select_over_time(name: str, values: np.ndarray, window: int) -> np.ndarray:
    """``min_over_time`` / ``max_over_time`` / ``last_over_time`` of a host
    float64[S, T] matrix (NaN = missing), bit for bit: float64[S, T].
    Window t covers columns [t-window+1, t]; ``window`` passes over the
    matrix, nothing larger than it in memory."""
    values = np.asarray(values, np.float64)
    t = values.shape[1]
    out = np.full_like(values, np.nan)
    for back in range(min(window, t) - 1, -1, -1):  # the oldest column first
        dst, src = out[:, back:], values[:, : t - back]
        if name == "last_over_time":
            np.copyto(dst, src, where=~np.isnan(src))
        elif name == "max_over_time":
            np.fmax(dst, src, out=dst)  # fmax / fmin: NaN only if both are
        else:
            np.fmin(dst, src, out=dst)
    return out


def temporal_apply(name: str, values, window: int, step_seconds: float):
    """Single-function entry used by the query engine: fused on TPU (the
    intermediates of even ONE rate call are ~25 HBM passes unfused),
    unfused elsewhere. A selection over host float64 samples that f32
    cannot hold is exact (:func:`select_over_time`)."""
    if (name in SELECTIONS and isinstance(values, np.ndarray)
            and values.dtype == np.float64 and values.ndim == 2
            and not f32_exact(values)):
        return select_over_time(name, values, window)
    if name in FUSABLE and device.on_tpu() and values.shape[0] >= BLOCK_ROWS:
        return fused_temporal(values, window, step_seconds, (name,))[0]
    v = jnp.asarray(values, jnp.float32)
    return FUSABLE[name](v, window, step_seconds)
