"""The engine's range functions as stages of their own (``query.temporal``
and, by path, ``.select`` or ``.dispatch`` + ``.wait``), and the
benchmark's readers of those stages over made-up replies."""

from __future__ import annotations

import functools
import importlib.util
import os

import numpy as np
import pytest

from m3_tpu.query import stats
from m3_tpu.query.engine import Engine
from m3_tpu.query.functions import temporal_fused as TF

NANOS = 1_000_000_000
T0 = 1_600_000_000 * NANOS
STEP = 10 * NANOS
POINTS = 90
# 31 steps over the last five minutes of the samples, a window of 7
SPAN = (T0 + 590 * NANOS, T0 + 890 * NANOS, STEP)
QUERY = "max_over_time(m[1m])"
WINDOW = 7

TEMPORAL = ("query.temporal.select", "query.temporal.dispatch", "query.temporal.wait")


class _Samples:
    """The storage seam over one sample a step for every row of ``rows``."""

    def __init__(self, rows: np.ndarray) -> None:
        self.rows = rows

    def fetch(self, matchers, start, end):
        times = T0 + STEP * np.arange(self.rows.shape[1], dtype=np.int64)
        keep = (times >= start) & (times < end)
        return [(((b"__name__", b"m"), (b"s", b"%03d" % i)), times[keep], row[keep])
                for i, row in enumerate(self.rows)]


def _oracle(rows: np.ndarray) -> np.ndarray:
    """max over the window ending at each step of SPAN, in float64."""
    last = [int((t - T0) // STEP) for t in range(SPAN[0], SPAN[1] + 1, STEP)]
    return np.stack([rows[:, j - WINDOW + 1: j + 1].max(axis=1) for j in last], axis=1)


def _evaluate(rows: np.ndarray):
    st = stats.start(QUERY)
    assert st is not None
    try:
        r = Engine(_Samples(rows)).query_range(QUERY, *SPAN)
    finally:
        stats.finish(st, 0.0)
    return np.asarray(r.values), st.stages


@pytest.fixture
def on_the_chips_path(monkeypatch):
    """``temporal_apply`` as on the chip: the fused kernel, interpreted."""
    from jax.experimental import pallas

    import m3_tpu.device as device

    calls = []
    real = TF._fused_call

    def fused_call(*a, **kw):
        calls.append(a[0].shape)
        return real(*a, **kw)

    monkeypatch.setattr(device, "on_tpu", lambda: True)
    monkeypatch.setattr(pallas, "pallas_call",
                        functools.partial(pallas.pallas_call, interpret=True))
    monkeypatch.setattr(TF, "_fused_call", fused_call)
    try:
        yield calls
    finally:
        real.clear_cache()  # no later caller meets the interpreted kernel


@pytest.mark.parametrize("path,series,want", [
    # float64 samples that float32 cannot hold: the exact host selection
    ("select", 8, {"query.temporal.select"}),
    # float32-exact samples, fewer rows than a kernel block: eager ops
    ("eager", 8, {"query.temporal.dispatch", "query.temporal.wait"}),
    # float32-exact samples, a block of rows or more: the fused kernel
    ("fused", TF.BLOCK_ROWS + 32, {"query.temporal.dispatch", "query.temporal.wait"}),
])
def test_each_temporal_path_has_its_stages(path, series, want, request):
    rng = np.random.default_rng(41)
    if path == "select":
        rows = rng.normal(50.0, 20.0, (series, POINTS))
        assert not TF.f32_exact(rows)
    else:
        rows = rng.integers(0, 101, (series, POINTS)).astype(np.float64)
    fused = request.getfixturevalue("on_the_chips_path") if path == "fused" else None
    got, stages = _evaluate(rows)
    if fused is not None:
        # one kernel call over the rows padded to two blocks, and the
        # range's 31 steps and the window's 6 before them
        assert fused == [(2 * TF.BLOCK_ROWS, 31 + WINDOW - 1)]
    assert {n for n in TEMPORAL if n in stages} == want
    assert "query.temporal" in stages
    assert stages["query.temporal"] >= sum(stages.get(n, 0.0) for n in TEMPORAL)
    assert got.dtype == (np.float64 if path == "select" else np.float32)
    assert np.array_equal(got.astype(np.float64), _oracle(rows))


# --- the benchmark's readers of these stages ---

READERS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "benchmark", "readers")


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(READERS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Ctx:
    def __init__(self, replies) -> None:
        self.window = {"replies": replies}


def _reply(error=None, **stages):
    return {"error": error, "stats": {"stages": {
        k.replace("__", "."): v for k, v in stages.items()}}}


# a panel on the device path, the exact host path, and a lastpoint
# selector with no range function; a failed reply counts for nothing
PANEL = _reply(query__eval=0.030, parse=0.001, fetch=0.015, plan__device_wait=0.010,
               query__temporal=0.012, query__temporal__dispatch=0.002,
               query__temporal__wait=0.009, exec=0.014)
HOST = _reply(query__eval=0.010, parse=0.001, fetch=0.006, query__temporal=0.002,
              query__temporal__select=0.0015, exec=0.003)
SELECTOR = _reply(query__eval=0.005, parse=0.001, fetch=0.0035, exec=0.0005)
FAILED = _reply(error="timeout", query__eval=9.0, query__temporal=9.0,
                query__temporal__wait=9.0)
OLD = _reply(query__eval=0.030, parse=0.001, fetch=0.015, exec=0.014)


@pytest.mark.parametrize("reader,replies,want", [
    ("query_temporal", [PANEL, HOST, SELECTOR, FAILED], 7.0),
    ("query_temporal", [SELECTOR, OLD], None),
    ("query_temporal_wait", [PANEL, HOST, SELECTOR, FAILED], 9.0),
    ("query_temporal_wait", [HOST, SELECTOR], None),
    # 2.0, 1.0 and 0.5 ms of the evaluation that no stage names
    ("query_eval_self", [PANEL, HOST, SELECTOR, FAILED], 1.0),
    # a program without query.temporal: its exec is not self time
    ("query_eval_self", [OLD, SELECTOR], None),
])
def test_temporal_stage_readers(reader, replies, want):
    got = _reader(reader).read(_Ctx(replies), None)
    assert got == (None if want is None else pytest.approx(want))
