"""Host→device streaming pipeline: double-buffered upload + fused decode.

Reference mapping: SURVEY §2.7's "batched segment-upload RPC into device
HBM" / §7.5 fetch→pin→upload→kernel. At BASELINE config-5 scale (tens of
millions of series) the working set exceeds HBM, so scans stream: while the
device decodes batch N, batch N+1's packed arrays are already in flight
(`jax.device_put` is asynchronous), and batch N-P's results are drained to
bound in-flight memory at P batches.

Batches are the packed kernel layout (ops/fused.pack_lane_inputs) — the
same bytes filesets hold, so production reads go disk → packed host arrays
→ HBM without per-point host work.
"""

from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass
from typing import Iterable, Iterator

import jax

from .. import device
from ..ops import fused
from .scan import chunked_scan_aggregate_packed


@jax.jit
def _fold_totals(acc, tsum, tcnt, tmin, tmax):
    import jax.numpy as jnp

    from ..ops import u64

    a_sum, (c_hi, c_lo), a_min, a_max = acc
    has = tcnt > 0
    # count rides a (hi, lo) u32 pair: a plain i32 accumulator wraps past
    # 2^31 datapoints (~6 benchmark batches) and x64 is disabled
    c_hi, c_lo = u64.add((c_hi, c_lo), u64.from_u32(tcnt))
    return (
        a_sum + jnp.where(has, tsum, 0.0),
        (c_hi, c_lo),
        jnp.minimum(a_min, jnp.where(has, tmin, jnp.inf)),
        jnp.maximum(a_max, jnp.where(has, tmax, -jnp.inf)),
    )


@dataclass
class StreamTotals:
    """Cross-batch aggregate of the per-batch ScanAggregates totals.

    Folding stays ON DEVICE (a jitted scalar reduce per batch) — per-batch
    device→host scalar reads would serialize the pipeline on a sync each
    batch; the single transfer happens at finalize()."""

    _acc: tuple | None = None  # device accumulator (never downgraded)
    _final: tuple | None = None  # host snapshot cache for the properties
    batches: int = 0

    def fold(self, agg) -> None:
        import jax.numpy as jnp

        if self._acc is None:
            self._acc = (
                jnp.float32(0.0),
                (jnp.uint32(0), jnp.uint32(0)),
                jnp.float32(jnp.inf),
                jnp.float32(-jnp.inf),
            )
        self._acc = _fold_totals(
            self._acc, agg.total_sum, agg.total_count, agg.total_min, agg.total_max
        )
        self._final = None  # invalidate any snapshot taken mid-stream
        self.batches += 1

    def finalize(self) -> tuple:
        """One device→host transfer; safe to call mid-stream (the device
        accumulator is left untouched so further fold()s keep working)."""
        if self._final is None:
            if self._acc is None:
                self._final = (0.0, 0, float("inf"), float("-inf"))
            else:
                s, (c_hi, c_lo), lo, hi = jax.device_get(self._acc)
                self._final = (
                    float(s), (int(c_hi) << 32) | int(c_lo), float(lo), float(hi)
                )
        return self._final

    @property
    def total_sum(self) -> float:
        return self.finalize()[0]

    @property
    def total_count(self) -> int:
        return self.finalize()[1]

    @property
    def total_min(self) -> float:
        return self.finalize()[2]

    @property
    def total_max(self) -> float:
        return self.finalize()[3]


def packed_batches(batches: Iterable) -> Iterator[tuple]:
    """ChunkedBatch iterable → (windows4, lanes4, flags, n, s, c, k) host
    tuples."""
    for batch in batches:
        packed = fused.pack_lane_inputs(batch)
        yield (
            packed.windows4,
            packed.lanes4,
            packed.tile_flags,
            packed.n,
            batch.num_series,
            batch.num_chunks,
            batch.k,
            packed.order,
        )


def stream_aggregate(
    host_batches: Iterable[tuple], prefetch: int = 2, drain_times: list | None = None
) -> StreamTotals:
    """Stream (windows4, lanes4, tile_flags, n, s, c, k, lane_order) host
    batches (packed_batches output) through the packed kernel with
    ``prefetch`` batches in flight.

    Upload of batch N+1 overlaps compute of batch N (async dispatch); the
    oldest result is drained once the window exceeds ``prefetch``, bounding
    device memory to ~prefetch batches. ``drain_times`` (optional list)
    receives a perf_counter stamp per drained batch for steady-state timing.
    """
    import time as _time

    totals = StreamTotals()
    inflight: deque = deque()

    def drain_one():
        agg = inflight.popleft()
        totals.fold(agg)
        # the drain is a wait for this batch's compute: it bounds the
        # in-flight window, so the producer loop cannot run ahead and
        # buffer every pending upload in host RAM. block_until_ready IS a
        # barrier on the attached v5e (chip_smoke.py's kernel-parity phase
        # measures it: the fetch after the wait is immediate).
        jax.block_until_ready(agg.total_count)
        if drain_times is not None:
            drain_times.append(_time.perf_counter())

    for w4, l4, flags, n, s, c, k, order in host_batches:
        dev_w = jax.device_put(w4)
        dev_l = jax.device_put(l4)
        dev_f = jax.device_put(flags)
        # stage the upload to completion before dispatching the kernel:
        # the kernel (~ms) is far cheaper than the upload, and cross-batch
        # overlap comes from the inflight window below
        jax.block_until_ready((dev_w, dev_l, dev_f))
        fn = _jitted(n, s, c, k, order)
        inflight.append(fn(dev_w, dev_l, dev_f))
        if len(inflight) > prefetch:
            drain_one()
    while inflight:
        drain_one()
    return totals


@functools.lru_cache(maxsize=32)
def _jitted(n: int, s: int, c: int, k: int, lane_order: str = "c"):
    # Mosaic kernels are TPU-only; other backends run the kernel body in
    # Pallas interpret mode (same code path, no Mosaic lowering)
    interpret = not device.on_tpu()
    return jax.jit(
        functools.partial(
            chunked_scan_aggregate_packed, n=n, s=s, c=c, k=k,
            interpret=interpret, lane_order=lane_order,
        )
    )


def fileset_packed_batches(readers: Iterable, batch_series: int = 65536):
    """FilesetReader iterable → packed host batches straight off the side
    tables (no CPU prescan): the production fetch→upload path."""
    for reader in readers:
        sids = reader.series_ids
        for i in range(0, len(sids), batch_series):
            chunk = reader.chunked_batch(sids[i : i + batch_series])
            yield from packed_batches([chunk])
