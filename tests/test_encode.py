"""Seeded bit-exactness properties for the device m3tsz encode kernel
(m3_tpu/ops/encode.py) — the write-path twin of the chunked decoder's
parity suite:

- device encode → host ``ReaderIterator`` decode roundtrips every
  datapoint exactly (int-fast and float-fast lanes);
- device-encoded streams are byte-identical to the host codec's;
- a fileset persisted from device-encoded bytes + packed side rows is
  byte-identical ON DISK to the host-encoded one, including mixed,
  time-unit-change, and annotated fallback lanes in the same block;
- born-resident admission (``admit_block_device``) produces pool state
  bit-identical to the host upload path with ZERO stream upload bytes;
- the end-to-end device-ingest Database matches a host-only baseline
  fileset-for-fileset and read-for-read.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from m3_tpu.cache.block_cache import BlockKey
from m3_tpu.codec.m3tsz import Encoder, ReaderIterator, encode_series
from m3_tpu.ops import encode as dev
from m3_tpu.resident.pool import ResidentOptions, ResidentPool
from m3_tpu.storage.fs import FilesetID, FilesetReader, write_fileset
from m3_tpu.utils.instrument import Registry
from m3_tpu.utils.xtime import Unit

NANOS = 1_000_000_000
BS = 1_700_000_000 * NANOS


def _int_lane(rng, n):
    t = BS + np.cumsum(rng.integers(1, 30, n)) * NANOS
    v = rng.integers(-5000, 5000, n).astype(np.float64)
    return t.astype(np.int64), v


def _float_lane(rng, n):
    t = BS + np.cumsum(rng.integers(1, 30, n)) * NANOS
    v = rng.normal(0, 10, n)
    return t.astype(np.int64), v


def _decode(stream):
    it = ReaderIterator(stream)
    out = []
    while it.next():
        out.append(it.current())
    assert it.err is None or isinstance(it.err, EOFError)
    return out


@pytest.mark.parametrize("seed", [1, 7, 23])
def test_device_encode_host_decode_roundtrip(seed):
    rng = np.random.default_rng(seed)
    lanes = []
    for i in range(8):
        n = int(rng.integers(1, 200))
        lanes.append(_int_lane(rng, n) if i % 2 else _float_lane(rng, n))
    kinds = [
        dev.classify_lane(t, v, np.ones(len(t), np.int8)).kind
        for t, v in lanes
    ]
    assert all(k != dev.KIND_NONE for k in kinds), kinds
    res = dev.encode_lanes(lanes, kinds)
    for (t, v), stream in zip(lanes, res.streams()):
        dps = _decode(stream)
        assert [d.timestamp for d in dps] == [int(x) for x in t]
        got = np.asarray([d.value for d in dps])
        assert np.array_equal(got, v), "values did not roundtrip bit-exactly"


@pytest.mark.parametrize("seed", [3, 13])
def test_device_stream_bytes_match_host_codec(seed):
    rng = np.random.default_rng(seed)
    lanes = []
    for i in range(6):
        n = int(rng.integers(1, 150))
        lanes.append(_int_lane(rng, n) if i % 3 else _float_lane(rng, n))
    kinds = [
        dev.classify_lane(t, v, np.ones(len(t), np.int8)).kind
        for t, v in lanes
    ]
    res = dev.encode_lanes(lanes, kinds)
    for (t, v), stream in zip(lanes, res.streams()):
        host = encode_series([int(x) for x in t], [float(x) for x in v])
        assert stream == host, "device stream diverged from host codec"


def _annotated_stream(t0):
    enc = Encoder(t0)
    enc.encode(t0, 1.5, annotation=b"meta")
    enc.encode(t0 + NANOS, 2.5)
    enc.encode(t0 + 3 * NANOS, 2.5, annotation=b"more")
    return enc.stream()


def _unit_change_stream(t0):
    enc = Encoder(t0)
    enc.encode(t0, 4.0, unit=Unit.SECOND)
    enc.encode(t0 + 2 * NANOS, 5.0, unit=Unit.MILLISECOND)
    enc.encode(t0 + 3 * NANOS, 6.0, unit=Unit.MILLISECOND)
    return enc.stream()


def test_fileset_byte_identity_with_fallback_lanes(tmp_path):
    """One block mixing device-eligible lanes with every fallback class:
    the fileset written from device streams + packed side rows must be
    byte-identical to the all-host one."""
    rng = np.random.default_rng(5)
    lanes = [_int_lane(rng, 40), _float_lane(rng, 70)]
    kinds = [dev.KIND_INT, dev.KIND_FLOAT]
    res = dev.encode_lanes(lanes, kinds)
    streams = res.streams()
    rows = dev.side_rows_for(res, lanes, BS)

    # fallback lanes: mixed int/float values, a time-unit change, an
    # annotated stream — all KIND_NONE for the device classifier
    n = 50
    mt = BS + np.cumsum(rng.integers(1, 20, n)) * NANOS
    mv = np.where(np.arange(n) % 2 == 0, rng.normal(0, 5, n),
                  np.arange(n, dtype=np.float64))
    assert dev.classify_lane(
        mt.astype(np.int64), mv, np.ones(n, np.int8)
    ).kind == dev.KIND_NONE
    mixed = encode_series([int(x) for x in mt], [float(x) for x in mv])
    series_host = {
        b"int": streams[0],
        b"float": streams[1],
        b"mixed": mixed,
        b"unitchange": _unit_change_stream(BS + NANOS),
        b"annotated": _annotated_stream(BS + NANOS),
    }
    fid_h = FilesetID("ns", 0, BS, 0)
    fid_d = FilesetID("ns", 1, BS, 0)
    write_fileset(str(tmp_path), fid_h, series_host, 2 * 3600 * NANOS, 32)
    write_fileset(
        str(tmp_path), fid_d, series_host, 2 * 3600 * NANOS, 32,
        side_rows={b"int": rows[0], b"float": rows[1]},
    )
    base_h = os.path.join(str(tmp_path), "data", "ns", "0")
    base_d = os.path.join(str(tmp_path), "data", "ns", "1")
    names_h, names_d = sorted(os.listdir(base_h)), sorted(os.listdir(base_d))
    assert names_h == names_d
    for name in names_h:
        with open(os.path.join(base_h, name), "rb") as fh:
            hb = fh.read()
        with open(os.path.join(base_d, name), "rb") as fd:
            db = fd.read()
        assert hb == db, f"{name} differs between host and device filesets"
    # and the device lanes decode right back through the fileset reader
    reader = FilesetReader(str(tmp_path), fid_d)
    for sid, (t, v) in ((b"int", lanes[0]), (b"float", lanes[1])):
        dps = _decode(reader.stream(sid))
        assert [d.timestamp for d in dps] == [int(x) for x in t]
        assert np.array_equal(np.asarray([d.value for d in dps]), v)


def test_admit_block_device_bit_identical_zero_upload():
    """Born-resident admission: pool pages + side planes match the host
    upload path exactly, with zero stream-byte upload and the device
    admission counters moving instead."""
    rng = np.random.default_rng(7)
    lanes = []
    for i in range(9):
        n = int(rng.integers(1, 200))
        lanes.append(_int_lane(rng, n) if i % 2 else _float_lane(rng, n))
    kinds = [
        dev.classify_lane(t, v, np.ones(len(t), np.int8)).kind
        for t, v in lanes
    ]
    assert all(k != dev.KIND_NONE for k in kinds)
    opts = ResidentOptions(max_bytes=1 << 22, side_bytes=1 << 20)
    res = dev.encode_lanes(lanes, kinds, k=32, round_words_to=opts.page_words)
    streams = res.streams()
    side = dev.side_rows_for(res, lanes, BS)

    p_host = ResidentPool(opts, registry=Registry("th_"))
    items_h = [(bytes([i]), streams[i], len(lanes[i][0])) for i in range(9)]
    assert p_host.admit_block("ns", 0, BS, 1, items_h, chunk_k=32).complete

    p_dev = ResidentPool(opts, registry=Registry("td_"))
    items_d = [
        (bytes([i]), i, int(res.nbytes[i]), int(res.n_chunks[i]),
         dev.lane_max_span(res, i), side[i])
        for i in range(9)
    ]
    assert p_dev.admit_block_device(
        "ns", 0, BS, 1, res.words, items_d, chunk_k=32
    ).complete

    wh, wd = np.asarray(p_host._words), np.asarray(p_dev._words)
    # the side buffer holds one row per chunk slot: view it page-major
    spc = p_host.options.side_page_chunks
    sh, sd = (
        np.asarray(p._side).reshape(-1, spc, p._side.shape[-1])
        for p in (p_host, p_dev)
    )
    for i in range(9):
        k = BlockKey("ns", 0, bytes([i]), BS, 1)
        eh, ed = p_host.get(k), p_dev.get(k)
        assert (eh.nbytes, eh.num_bits, eh.n_chunks, eh.chunk_k) == (
            ed.nbytes, ed.num_bits, ed.n_chunks, ed.chunk_k
        )
        assert eh.max_span_bits == ed.max_span_bits
        assert np.array_equal(
            np.concatenate([wh[p] for p in eh.pages]),
            np.concatenate([wd[p] for p in ed.pages]),
        ), f"lane {i} page words differ"
        assert np.array_equal(
            np.concatenate([sh[p] for p in eh.side_pages]),
            np.concatenate([sd[p] for p in ed.side_pages]),
        ), f"lane {i} side rows differ"
    assert p_dev.upload_bytes == 0
    assert p_dev.device_admissions == 9
    assert p_dev.ingest_side_stage_bytes > 0
    assert p_host.upload_bytes > 0
    assert p_dev.stats()["device_admissions"] == 9


def test_admit_block_device_mixed_host_fallback_riders():
    """Host-fallback lanes ride the SAME admission batch (the
    completeness marker must cover the union), paying a partial upload."""
    rng = np.random.default_rng(11)
    lanes = [_int_lane(rng, int(rng.integers(5, 120))) for _ in range(5)]
    kinds = [dev.KIND_INT] * 5
    opts = ResidentOptions(max_bytes=1 << 22, side_bytes=1 << 20)
    res = dev.encode_lanes(lanes, kinds, k=32, round_words_to=opts.page_words)
    side = dev.side_rows_for(res, lanes, BS)
    streams = res.streams()
    n = 60
    ht = BS + np.cumsum(rng.integers(1, 30, n)) * NANOS
    hv = np.where(np.arange(n) % 2 == 0, rng.normal(0, 5, n),
                  np.arange(n, dtype=np.float64))
    hstream = encode_series([int(x) for x in ht], [float(x) for x in hv])

    p_host = ResidentPool(opts, registry=Registry("mh_"))
    items_h = [(bytes([i]), streams[i], len(lanes[i][0])) for i in range(5)]
    items_h.append((b"\x05", hstream, n))
    assert p_host.admit_block("ns", 0, BS, 1, items_h, chunk_k=32).complete

    p_dev = ResidentPool(opts, registry=Registry("md_"))
    items_d = [
        (bytes([i]), i, int(res.nbytes[i]), int(res.n_chunks[i]),
         dev.lane_max_span(res, i), side[i])
        for i in range(5)
    ]
    r = p_dev.admit_block_device(
        "ns", 0, BS, 1, res.words, items_d, chunk_k=32,
        host_items=[(b"\x05", hstream, n)],
    )
    assert r.complete and r.admitted == 6
    wh, wd = np.asarray(p_host._words), np.asarray(p_dev._words)
    # the side buffer holds one row per chunk slot: view it page-major
    spc = p_host.options.side_page_chunks
    sh, sd = (
        np.asarray(p._side).reshape(-1, spc, p._side.shape[-1])
        for p in (p_host, p_dev)
    )
    for i in range(6):
        k = BlockKey("ns", 0, bytes([i]), BS, 1)
        eh, ed = p_host.get(k), p_dev.get(k)
        assert eh.nbytes == ed.nbytes and eh.n_chunks == ed.n_chunks
        assert eh.max_span_bits == ed.max_span_bits
        assert np.array_equal(
            np.concatenate([wh[p] for p in eh.pages]),
            np.concatenate([wd[p] for p in ed.pages]),
        ), i
        assert np.array_equal(
            np.concatenate([sh[p] for p in eh.side_pages]),
            np.concatenate([sd[p] for p in ed.side_pages]),
        ), i
    assert 0 < p_dev.upload_bytes < p_host.upload_bytes
    assert p_dev.device_admissions == 5
    assert p_dev.is_complete("ns", 0, BS, 1)


def test_database_device_ingest_end_to_end(tmp_path):
    """Device-ingest Database vs host baseline: every fileset file
    byte-identical on disk, every read identical, and the device path
    admits with fewer upload bytes (only fallback lanes pay)."""
    from m3_tpu.ingest import IngestOptions
    from m3_tpu.storage.database import Database, NamespaceOptions

    bsz = 2 * 3600 * NANOS
    rng = np.random.default_rng(17)
    entries = []
    for s in range(12):
        sid = f"series-{s}".encode()
        n = int(rng.integers(20, 120))
        t0 = bsz + int(rng.integers(0, 100)) * NANOS
        ts = t0 + np.cumsum(rng.integers(1, 30, n)) * NANOS
        if s % 3 == 0:
            vals = rng.integers(-500, 500, n).astype(np.float64)
        elif s % 3 == 1:
            vals = rng.normal(0, 10, n)
        else:
            vals = np.where(rng.random(n) < 0.5, rng.integers(0, 9, n),
                            rng.normal(0, 1, n))
        for t, v in zip(ts.tolist(), vals.tolist()):
            entries.append((sid, int(t), float(v)))

    dbs = {}
    for name, ingest in (("host", False), ("dev", True)):
        db = Database(
            str(tmp_path / name),
            num_shards=4,
            commitlog_enabled=False,
            resident_options=ResidentOptions(enabled=True, max_bytes=1 << 22),
            ingest_options=IngestOptions() if ingest else None,
        )
        db.create_namespace("metrics", NamespaceOptions(block_size_nanos=bsz))
        db.bootstrapped = True
        db.write_batch("metrics", list(entries))
        assert db.flush("metrics", 2 * bsz)
        dbs[name] = db

    for root, _dirs, files in os.walk(str(tmp_path / "host")):
        for f in files:
            hp = os.path.join(root, f)
            dp = hp.replace(str(tmp_path / "host"), str(tmp_path / "dev"), 1)
            with open(hp, "rb") as fh, open(dp, "rb") as fd:
                assert fh.read() == fd.read(), f"fileset file differs: {hp}"
    for s in range(12):
        sid = f"series-{s}".encode()
        a = dbs["host"].read("metrics", sid, 0, 4 * bsz)
        b = dbs["dev"].read("metrics", sid, 0, 4 * bsz)
        assert a == b and a
    sh = dbs["host"].resident_pool.stats()
    sd = dbs["dev"].resident_pool.stats()
    assert sd["device_admissions"] > 0 and sh["device_admissions"] == 0
    assert sd["ingest_side_stage_bytes"] > 0
    assert sd["upload_bytes"] < sh["upload_bytes"]
    assert sd["admissions"] == sh["admissions"]
    shard = next(
        s for s in dbs["dev"].namespaces["metrics"].shards if s.ingest
    )
    assert shard.ingest.stats()["appends"] > 0
    for db in dbs.values():
        db.close()
