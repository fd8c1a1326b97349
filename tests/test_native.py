"""Native C++ codec parity: encode_batch and prescan_batch must be
bit-identical to the Python reference codec."""

import numpy as np
import pytest

from m3_tpu.codec.m3tsz import Encoder, decode, encode_series
from m3_tpu.native import available, encode_batch, prescan_batch
from m3_tpu.ops.chunked import assemble_chunked, decode_chunked, snapshot_stream
from m3_tpu.ops.decode import finalize_decode
from m3_tpu.utils.xtime import Unit

NANOS = 1_000_000_000
T0 = 1_600_000_000 * NANOS

pytestmark = pytest.mark.skipif(not available(), reason="native lib unavailable")


def _series(seed, n, kind="gauge"):
    rng = np.random.default_rng(seed)
    ts = T0 + np.cumsum(rng.integers(1, 30, n)) * NANOS
    if kind == "gauge":
        vals = np.round(rng.normal(100, 30, n), 2)
    elif kind == "float":
        vals = rng.normal(0, 1, n)
    else:
        vals = np.cumsum(rng.integers(0, 1000, n)).astype(np.float64)
    return ts.astype(np.int64), vals


@pytest.mark.parametrize("kind", ["gauge", "float", "counter"])
def test_encode_batch_bit_exact(kind):
    lengths = [1, 5, 64, 133]
    times_all, vals_all = [], []
    for i, n in enumerate(lengths):
        t, v = _series(i, n, kind)
        times_all.append(t)
        vals_all.append(v)
    streams = encode_batch(
        np.concatenate(times_all), np.concatenate(vals_all), np.asarray(lengths, np.int32)
    )
    for i, n in enumerate(lengths):
        want = encode_series(times_all[i].tolist(), vals_all[i].tolist())
        assert streams[i] == want, f"series {i} ({kind}) differs"


def test_encode_batch_mixed_precision_values():
    # values that exercise int->float->int transitions and repeats
    t = T0 + np.arange(20, dtype=np.int64) * NANOS
    v = np.asarray(
        [1.0, 2.0, 2.0, 0.1234567890123, 4.0, 4.0, 1e300, -5.5, 7.0, 7.0] * 2
    )
    [stream] = encode_batch(t, v, np.asarray([20], np.int32))
    assert stream == encode_series(t.tolist(), v.tolist())
    got = decode(stream)
    assert [dp.value for dp in got] == v.tolist()


@pytest.mark.parametrize("k", [4, 32])
def test_prescan_batch_matches_python(k):
    streams = []
    for i, n in enumerate([3, 40, 100]):
        t, v = _series(10 + i, n)
        streams.append(encode_series(t.tolist(), v.tolist()))
    # stream with annotations + time unit changes (prescan must walk them)
    enc = Encoder(T0)
    t = T0
    for j in range(30):
        unit = Unit.SECOND if j % 11 else Unit.MILLISECOND
        t += NANOS if unit == Unit.SECOND else 500_000_000
        enc.encode(t, float(j), unit=unit, annotation=b"meta" if j == 7 else None)
    streams.append(enc.stream())

    native = prescan_batch(streams, k=k)
    for i, s in enumerate(streams):
        want = snapshot_stream(s, k)
        got = native[i]
        assert len(got) == len(want), (i, len(got), len(want))
        for a, b in zip(got, want):
            for key in ("off", "prev_time", "prev_delta", "prev_float_bits",
                        "prev_xor", "int_val", "time_unit", "sig", "mult",
                        "is_float", "span", "total_bits"):
                assert a[key] == b[key], (i, key, a[key], b[key])


def test_native_prescan_device_decode_roundtrip():
    streams = []
    for i in range(6):
        t, v = _series(20 + i, 50 + i * 17)
        streams.append(encode_series(t.tolist(), v.tolist()))
    snaps = prescan_batch(streams, k=16)
    batch = assemble_chunked(streams, snaps, 16)
    ts, vals, valid = finalize_decode(decode_chunked(batch))
    for i, s in enumerate(streams):
        want = decode(s)
        got_t = ts[i][valid[i]]
        assert len(got_t) == len(want)
        assert all(got_t[j] == want[j].timestamp for j in range(len(want)))


def test_pack_windowed_dense_matches_numpy():
    """Native m3agg_* fused densify == numpy window_keys+pack_dense_groups,
    including clamped out-of-range samples (whose in-window offsets exceed
    the resolution and stress the torder downshift) and NaN values (which
    occupy a slot but must be invalid)."""
    from m3_tpu import native
    from m3_tpu.aggregator.kernels import pack_dense_groups, window_keys

    if not native.available():
        pytest.skip("native lib unavailable")

    rng = np.random.default_rng(11)
    g, nw, per = 500, 4, 6
    n = g * nw * per
    nanos = 10**9
    t0 = 1_700_000_000 * nanos
    res = 60 * nanos
    ids = rng.integers(0, g, n).astype(np.int64)
    times = t0 + rng.integers(0, nw * res, n)
    # late stragglers: far past the last window (late-clamp overflow case)
    late = rng.random(n) < 0.01
    times[late] += rng.integers(2, 200, late.sum()) * res
    values = rng.normal(0, 1, n).astype(np.float32)
    values[rng.random(n) < 0.02] = np.nan  # stale markers

    keys, _, order = window_keys(ids, times, t0, res, nw)
    v1, t1, m1 = pack_dense_groups(keys, values, order, g * nw)
    v2, t2, m2 = native.pack_windowed_dense(ids, times, values, t0, res, nw, g)

    assert v1.shape == v2.shape
    assert np.array_equal(m1, m2)
    assert np.array_equal(np.nan_to_num(v1), np.nan_to_num(v2))
    assert np.array_equal(np.isnan(v1), np.isnan(v2))
    # torder parity wherever a slot is occupied (padding torder is 0 in both)
    occupied = np.arange(v1.shape[1])[None, :] < np.bincount(
        keys, minlength=g * nw
    )[:, None]
    assert np.array_equal(t1[occupied], t2[occupied])


def test_decode_batch_matches_python():
    """Native m3tsz_decode_batch == Python decoder on (t, v, unit),
    including float/int mode switches and unit changes."""
    from m3_tpu.codec.m3tsz import decode as py_decode
    from m3_tpu.native import decode_batch

    streams = []
    rng = np.random.default_rng(3)
    t0 = 1_700_000_000 * 10**9
    # ints, floats, mixed, singletons
    for kind in range(8):
        n = int(rng.integers(1, 200))
        times = t0 + np.cumsum(rng.integers(1, 30, n)) * 10**9
        if kind % 3 == 0:
            vals = rng.integers(0, 1000, n).astype(float)
        elif kind % 3 == 1:
            vals = rng.normal(0, 1e6, n)
        else:
            vals = np.where(rng.random(n) < 0.5, rng.integers(0, 9, n), rng.normal())
        streams.append(encode_series(list(map(int, times)), list(map(float, vals))))
    out = decode_batch(streams)
    for s, (t, v, u) in zip(streams, out):
        dps = py_decode(s)
        assert len(dps) == len(t)
        for d, tt, vv, uu in zip(dps, t, v, u):
            assert d.timestamp == int(tt)
            assert d.value == vv or (np.isnan(d.value) and np.isnan(vv))
            assert int(d.unit) == int(uu)


def test_decode_batch_flags_annotations():
    from m3_tpu.codec.m3tsz import Encoder
    from m3_tpu.native import decode_batch

    t0 = 1_700_000_000 * 10**9
    enc = Encoder(t0)
    enc.encode(t0, 1.0)
    enc.encode(t0 + 10**9, 2.0, annotation=b"meta")
    with_ann = enc.stream()
    plain = encode_series([t0, t0 + 10**9], [1.0, 2.0])
    triples, flags = decode_batch([plain, with_ann], with_flags=True)
    assert list(flags) == [0, 1]
    # annotations don't perturb (t, v) decoding
    assert list(triples[1][0]) == [t0, t0 + 10**9]
    assert list(triples[1][1]) == [1.0, 2.0]


def test_shard_batch_matches_python_hash():
    """Native m3hash_shards == utils/hash murmur3 shard routing for every
    length class (block, 1-3 byte tails, empty)."""
    from m3_tpu.native import shard_batch
    from m3_tpu.utils.hash import shard_for

    rng = np.random.default_rng(21)
    ids = [b"s%d" % i for i in range(2000)]
    ids += [bytes(rng.integers(0, 256, int(n))) for n in rng.integers(0, 40, 500)]
    ids += [b"", b"a", b"ab", b"abc", b"abcd", b"\xff" * 7]
    for num_shards in (1, 3, 64, 4096):
        out = shard_batch(ids, num_shards)
        if out is None:
            pytest.skip("native lib unavailable")
        for sid, got in zip(ids, out.tolist()):
            assert got == shard_for(sid, num_shards), (sid, num_shards)


def _scratch_native_tree(tmp_path):
    """A tree holding only what git commits of the native codec: the
    loader module and native/m3tsz.cc (no .so), so a test can race or
    break the build without touching the library other workers use."""
    import os
    import shutil

    import m3_tpu.native as real

    pkg = tmp_path / "m3_tpu" / "native"
    pkg.mkdir(parents=True)
    shutil.copy(real.__file__, pkg / "__init__.py")
    (tmp_path / "native").mkdir()
    shutil.copy(real._SRC_PATH, tmp_path / "native" / "m3tsz.cc")
    return str(pkg / "__init__.py"), str(tmp_path / "native")


_LOAD_SNIPPET = """
import importlib.util, sys
spec = importlib.util.spec_from_file_location("scratch_native", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
lib = mod.load()
print("LOADED" if lib is not None and lib.m3tsz_prescan is not None else "NONE")
"""


def test_concurrent_first_load_both_get_a_library(tmp_path):
    """Two processes racing load() on a tree without the .so (xdist
    workers on a fresh checkout; chip_smoke.py's dbnode + coordinator):
    the build publishes by os.replace, so neither sees a half-written
    file and both end up with a library."""
    import os
    import subprocess
    import sys

    mod_path, native_dir = _scratch_native_tree(tmp_path)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _LOAD_SNIPPET, mod_path],
            stdout=subprocess.PIPE, text=True,
        )
        for _ in range(3)
    ]
    outs = [p.communicate(timeout=180)[0].strip() for p in procs]
    assert outs == ["LOADED"] * 3, outs
    left = sorted(os.listdir(native_dir))
    assert left == ["libm3tsz.so", "libm3tsz.so.buildinfo", "m3tsz.cc"], left


def test_unloadable_existing_library_is_rebuilt(tmp_path):
    """An existing file that will not dlopen (what a non-atomic build left
    behind) is rebuilt and loaded, not answered with None for the life of
    the process."""
    import os
    import subprocess
    import sys

    mod_path, native_dir = _scratch_native_tree(tmp_path)
    lib_path = os.path.join(native_dir, "libm3tsz.so")
    with open(lib_path, "wb") as f:
        f.write(b"\x7fELF-half-written")
    import m3_tpu.native as real

    with open(lib_path + ".buildinfo", "w") as f:
        f.write(real._cpu_signature())
    out = subprocess.run(
        [sys.executable, "-c", _LOAD_SNIPPET, mod_path],
        capture_output=True, text=True, timeout=180,
    )
    assert out.stdout.strip() == "LOADED", (out.stdout, out.stderr)
    assert os.path.getsize(lib_path) > 10_000
