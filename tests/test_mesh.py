"""Sharded scan-path tests on the 8-device virtual CPU mesh (conftest).

Covers the series-hash data parallelism of the reference (murmur3 shard
routing, sharding/shardset.go:149) mapped onto a jax.sharding.Mesh, and the
psum fan-out reduction of the coordinator query path
(src/query/storage/fanout/storage.go:76).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from m3_tpu.codec.m3tsz import decode
from m3_tpu.ops.chunked import build_chunked, lane_kwargs, tile_chunked
from m3_tpu.parallel.mesh import SHARD_AXIS, series_mesh, series_sharding
from m3_tpu.parallel.scan import (
    chunked_scan_aggregate,
    make_sharded_chunked_scan,
)
from m3_tpu.utils.hash import shard_for
from m3_tpu.utils.synthetic import synthetic_streams

N_DEV = 8


@pytest.fixture(scope="module")
def batch():
    streams = synthetic_streams(8, 64, seed=11)
    return tile_chunked(build_chunked(streams, k=8), 32), streams


def _sharded_out(batch):
    mesh = series_mesh(N_DEV)
    sh = series_sharding(mesh)
    args = lane_kwargs(batch, transform=lambda x: jax.device_put(jnp.asarray(x), sh))
    fn = make_sharded_chunked_scan(mesh, batch.num_series, batch.num_chunks, batch.k)
    return jax.block_until_ready(fn(args))


def test_mesh_has_8_cpu_devices():
    devs = jax.devices()
    assert len(devs) == 8 and devs[0].platform == "cpu"
    mesh = series_mesh(N_DEV)
    assert mesh.devices.shape == (8,) and mesh.axis_names == (SHARD_AXIS,)


def test_sharded_totals_match_single_device(batch):
    batch, _ = batch
    out_sharded = _sharded_out(batch)

    args = lane_kwargs(batch, transform=jnp.asarray)
    out_single = jax.jit(
        lambda a: chunked_scan_aggregate(
            a, s=batch.num_series, c=batch.num_chunks, k=batch.k
        )
    )(args)

    assert int(out_sharded.total_count) == int(out_single.total_count)
    np.testing.assert_allclose(
        float(out_sharded.total_sum), float(out_single.total_sum), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(out_sharded.total_min), float(out_single.total_min), rtol=0
    )
    np.testing.assert_allclose(
        float(out_sharded.total_max), float(out_single.total_max), rtol=0
    )
    np.testing.assert_allclose(
        np.asarray(out_sharded.series_sum),
        np.asarray(out_single.series_sum),
        rtol=1e-6,
    )


def test_sharded_totals_match_cpu_oracle(batch):
    batch, streams = batch
    out = _sharded_out(batch)
    reps = batch.num_series // len(streams)
    decoded = [decode(s) for s in streams]
    expect_count = reps * sum(len(d) for d in decoded)
    expect_sum = reps * sum(dp.value for d in decoded for dp in d)
    assert int(out.total_count) == expect_count
    assert abs(float(out.total_sum) - expect_sum) / max(abs(expect_sum), 1) < 1e-5


def test_sharded_output_layout(batch):
    """Per-series outputs stay sharded over the mesh axis; totals replicated."""
    batch, _ = batch
    out = _sharded_out(batch)
    s_spec = out.series_sum.sharding.spec
    assert s_spec == P(SHARD_AXIS), s_spec
    assert out.total_sum.sharding.is_fully_replicated
    # every device holds exactly S/N series of the per-series outputs
    shard_sizes = {
        d.data.shape[0] for d in out.series_sum.addressable_shards
    }
    assert shard_sizes == {batch.num_series // N_DEV}


def test_murmur3_shard_routing_matches_reference_vectors():
    """DefaultHashFn = murmur3_32(id) % shards (sharding/shardset.go:149).

    Known-answer vectors for murmur3-32 (public test vectors) plus the
    device-placement rule: a series lands on mesh device shard % n_dev when
    shards are laid out round-robin.
    """
    # public murmur3_32 seed-0 vectors
    from m3_tpu.utils.hash import murmur3_32

    assert murmur3_32(b"") == 0
    assert murmur3_32(b"hello") == 0x248BFA47
    assert murmur3_32(b"The quick brown fox jumps over the lazy dog") == 0x2E4FF723

    num_shards = 4096
    ids = [f"m3+series-{i}?tag=val".encode() for i in range(256)]
    shards = [shard_for(b, num_shards) for b in ids]
    assert all(0 <= s < num_shards for s in shards)
    # deterministic + spread out
    assert shards == [shard_for(b, num_shards) for b in ids]
    assert len(set(shards)) > 200


def test_psum_rides_shard_axis():
    """A bare shard_map psum over the mesh equals the global sum — the
    primitive the cross-series totals rely on."""
    from jax import shard_map

    mesh = series_mesh(N_DEV)
    x = jnp.arange(64, dtype=jnp.float32)
    xs = jax.device_put(x, series_sharding(mesh))

    f = shard_map(
        lambda v: jax.lax.psum(jnp.sum(v), SHARD_AXIS)[None],
        mesh=mesh,
        in_specs=(P(SHARD_AXIS),),
        out_specs=P(SHARD_AXIS),
        check_vma=False,
    )
    out = np.asarray(jax.jit(f)(xs))
    np.testing.assert_allclose(out, np.full(N_DEV, x.sum()), rtol=0)


def test_sharded_scan_at_scale_64k_series():
    """Scale evidence beyond the smoke shape: 65,536 series x 240 points
    (8,192 series/device on the 8-way mesh) through the FULL sharded
    chunked scan with psum totals, parity-checked against the per-series
    host oracle. ~15.7M datapoints cross the mesh in one step."""
    streams = synthetic_streams(64, 240, seed=17)
    big = tile_chunked(build_chunked(streams, k=24), 65536)
    mesh = series_mesh(N_DEV)
    sh = series_sharding(mesh)
    args = lane_kwargs(big, transform=lambda x: jax.device_put(jnp.asarray(x), sh))
    fn = make_sharded_chunked_scan(mesh, big.num_series, big.num_chunks, big.k)
    out = jax.block_until_ready(fn(args))

    assert int(out.total_count) == 65536 * 240
    # per-series parity vs the host codec on the unique streams
    from m3_tpu.codec.m3tsz import decode

    per = np.asarray(
        [sum(dp.value for dp in decode(s)) for s in streams], np.float64
    )
    got = np.asarray(out.series_sum[: len(streams)], np.float64)
    np.testing.assert_allclose(got, per, rtol=1e-5)
    # psum total equals the f64 oracle within f32 tree-sum tolerance
    want_total = float(np.sum(np.asarray([per[i % 64] for i in range(65536)])))
    assert float(out.total_sum) == pytest.approx(want_total, rel=1e-4)


@pytest.mark.parametrize("ndev", [3, 5])
def test_sharded_scan_odd_mesh_sizes(ndev):
    """Odd mesh cardinalities (the driver dry-runs N=3): padding series to
    a divisible shard count must not change any result."""
    streams = synthetic_streams(8, 64, seed=23)
    b = tile_chunked(build_chunked(streams, k=8), 120)  # divisible by 3 and 5
    devs = jax.devices()[:ndev]
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devs), (SHARD_AXIS,))
    if b.num_series % ndev:
        pytest.skip("series count not divisible; covered by dryrun padding")
    sh = series_sharding(mesh)
    args = lane_kwargs(b, transform=lambda x: jax.device_put(jnp.asarray(x), sh))
    fn = make_sharded_chunked_scan(mesh, b.num_series, b.num_chunks, b.k)
    out = jax.block_until_ready(fn(args))
    single = chunked_scan_aggregate(
        lane_kwargs(b), s=b.num_series, c=b.num_chunks, k=b.k
    )
    np.testing.assert_allclose(
        np.asarray(out.series_sum), np.asarray(single.series_sum), rtol=1e-6
    )
    assert float(out.total_sum) == pytest.approx(float(single.total_sum), rel=1e-6)
