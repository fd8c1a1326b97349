"""Fused temporal kernel parity: fused_temporal must agree with the
per-function jnp path for every FUSABLE function (NaN pattern included).

On CPU this exercises the fallback dispatch + the engine wiring; the pallas
path itself compiles for a described v5e in tests/test_tpu_compile.py and is
validated on the chip by chip_smoke.py's kernel-parity phase (1e-4 for 13
functions; stddev/stdvar at ~5e-3 — see TOLERANCE.md)."""

import numpy as np
import pytest

from m3_tpu.query.functions import temporal as T
from m3_tpu.query.functions.temporal_fused import (
    FUSABLE,
    fused_temporal,
    temporal_apply,
)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    vals = rng.normal(50, 5, (96, 64)).astype(np.float32)
    vals[rng.random((96, 64)) < 0.08] = np.nan
    return vals


@pytest.mark.parametrize("name", sorted(FUSABLE))
def test_fused_matches_unfused(name, data):
    got = np.asarray(fused_temporal(data, 5, 10.0, (name,))[0])
    ref = np.asarray(FUSABLE[name](data, 5, 10.0))
    both_nan = np.isnan(got) & np.isnan(ref)
    close = np.abs(got - ref) <= 1e-4 + 1e-4 * np.abs(ref)
    assert np.all(both_nan | close), name


def test_multi_output_order(data):
    r, a = fused_temporal(data, 5, 10.0, ("rate", "avg_over_time"))
    assert np.allclose(
        np.nan_to_num(np.asarray(r)),
        np.nan_to_num(np.asarray(T.rate(data, 5, 10.0))),
        atol=1e-4,
    )
    assert np.allclose(
        np.nan_to_num(np.asarray(a)),
        np.nan_to_num(np.asarray(T.avg_over_time(data, 5))),
        atol=1e-4,
    )


def test_temporal_apply_single(data):
    got = np.asarray(temporal_apply("max_over_time", data, 5, 10.0))
    ref = np.asarray(T.max_over_time(data, 5))
    assert np.array_equal(np.isnan(got), np.isnan(ref))
