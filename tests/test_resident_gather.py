"""resident/gather._resident_gather against a word-by-word numpy reference.

The reference reads ``pool[page_rows[s, (w0 + j) // w], (w0 + j) % w]`` one
word at a time, lane by lane. Windows, rel, nbits and valid must agree bit
for bit, in both lane orders, at the test pools' 16-word page and the
deployed 512-word page. Whoever rewrites the gather is held to this (a
page-granular one was built and measured in PR 34: PERF.md section 6).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from m3_tpu.ops.sideplane import SIDE_WORDS
from m3_tpu.resident.gather import _resident_gather

C = 5  # chunks a series, at most
SPC = 2  # side slots a side page


def _case(w: int, cw: int, seed: int):
    """A pool of ``w``-word pages and seven series whose chunk windows
    start where the cut has an edge: word 0, word w - 1 (a page's last
    word), the first word of a page, inside the stream's last page so the
    window runs into the trailing zero pages, and wherever the seed puts
    the rest. Page ids are scattered and descending; series 5 has fewer
    chunks than C (invalid lanes), series 6 is padding (page row 0, no
    chunks)."""
    rng = np.random.default_rng(seed)
    extra = -(-cw // w) + 1  # trailing zero-page columns, as the plans append
    span = -(-(cw - 1) // w) + 1  # pages a window can touch
    n_real = span + 2
    s = 7
    num_pages = 1 + s * n_real
    pool = rng.integers(0, 1 << 32, (num_pages, w), dtype=np.uint64).astype(np.uint32)
    pool[0] = 0  # the reserved zero page
    ids = rng.permutation(np.arange(1, num_pages))
    page_rows = np.zeros((s, n_real + extra), np.int32)
    n_pages = np.array([n_real, n_real, n_real, n_real, 1, n_real - 1, 0])
    for i in range(s):
        mine = ids[i * n_real: i * n_real + n_pages[i]]
        page_rows[i, : n_pages[i]] = mine
    page_rows[1, :n_real] = np.sort(page_rows[1, :n_real])[::-1]  # descending
    n_chunks = np.array([C, C, C, C, 2, 3, 0], np.int32)
    stream_words = n_pages * w
    w0 = np.zeros((s, C), np.int64)
    rel = rng.integers(0, 32, (s, C))
    for i in range(s):
        hi = max(int(stream_words[i]) - 1, 0)
        w0[i] = np.sort(rng.integers(0, hi + 1, C))
    w0[0] = [0, w - 1, w, 2 * w - 1, stream_words[0] - 1]
    w0[1] = [1, w - 2, w + 1, stream_words[1] - cw, stream_words[1] - 2][:C]
    w0[1] = np.clip(w0[1], 0, stream_words[1] - 1)
    w0[4, :2] = [0, w - 1]
    total_bits = (stream_words * 32 - rng.integers(0, 32, s)).clip(0).astype(np.int32)
    total_bits[6] = 0
    # side planes: word 8 carries the chunk's bit offset << 11; the rest of
    # the row is the decoder state, which this test leaves to the parity
    # tests of tests/test_resident.py
    sl = -(-C // SPC)
    n_side_pages = 1 + s * sl
    side = rng.integers(0, 1 << 32, (n_side_pages * SPC, SIDE_WORDS),
                        dtype=np.uint64).astype(np.uint32)
    side[:SPC] = 0  # side page 0 is the zero page
    side_rows = np.zeros((s, sl), np.int32)
    side_ids = rng.permutation(np.arange(1, n_side_pages))
    for i in range(s):
        if n_chunks[i]:
            side_rows[i] = side_ids[i * sl:(i + 1) * sl]
        for ci in range(n_chunks[i]):
            slot = side_rows[i, ci // SPC] * SPC + ci % SPC
            off = int(w0[i, ci]) * 32 + int(rel[i, ci])
            assert off < 1 << 21
            side[slot, 8] = (side[slot, 8] & np.uint32(0x7FF)) | np.uint32(off << 11)
    block_hi = rng.integers(0, 1 << 10, s).astype(np.uint32)
    block_lo = rng.integers(0, 1 << 32, s, dtype=np.uint64).astype(np.uint32)
    return dict(pool=pool, side=side, page_rows=page_rows, side_rows=side_rows,
                n_chunks=n_chunks, total_bits=total_bits, block_hi=block_hi,
                block_lo=block_lo, w0=w0, rel=rel)


def _reference(case, w: int, cw: int, series_major: bool, pad: int):
    """Word by word, lane by lane."""
    s = case["page_rows"].shape[0]
    n = s * C
    windows = np.zeros((n + pad, cw), np.uint32)
    rel = np.zeros(n + pad, np.int32)
    nbits = np.zeros(n + pad, np.int32)
    valid = np.zeros(n + pad, bool)
    for j in range(n):
        si, ci = (j // C, j % C) if series_major else (j % s, j // s)
        if ci >= case["n_chunks"][si]:
            continue
        valid[j] = True
        w0 = int(case["w0"][si, ci])
        rel[j] = case["rel"][si, ci]
        nbits[j] = min(max(int(case["total_bits"][si]) - w0 * 32, 0), cw * 32)
        for k in range(cw):
            page = case["page_rows"][si, (w0 + k) // w]
            windows[j, k] = case["pool"][page, (w0 + k) % w]
    return windows, rel, nbits, valid


@pytest.mark.parametrize("series_major", [True, False], ids=["series-major", "chunk-major"])
@pytest.mark.parametrize("w,cw", [(16, 15), (16, 80), (512, 15), (512, 80), (512, 513)])
def test_resident_gather_matches_word_by_word_reference(w, cw, series_major):
    for seed in (1, 2):
        case = _case(w, cw, seed)
        pad = 0 if series_major else 3  # the packed layout's tile padding
        s = case["page_rows"].shape[0]
        j = np.arange(s * C + pad)
        if series_major:
            si, ci = j // C, j % C
        else:  # padding lanes: chunk C is never valid
            si, ci = np.where(j < s * C, j % s, 0), np.where(j < s * C, j // s, C)
        planes, windows, rel, nbits, valid = _resident_gather(
            case["pool"], case["side"].reshape(-1), case["page_rows"],
            case["side_rows"], case["n_chunks"], case["total_bits"],
            case["block_hi"], case["block_lo"],
            jnp.asarray(si, jnp.int32), jnp.asarray(ci, jnp.int32), cw, w, SPC,
        )
        want = _reference(case, w, cw, series_major, pad)
        got = (windows, rel, nbits, valid)
        for name, g, x in zip(("windows", "rel", "nbits", "valid"), got, want):
            g = np.asarray(g)
            assert g.shape == x.shape and g.dtype == x.dtype, name
            assert np.array_equal(g, x), (name, seed, np.argwhere(g != x)[:5])
        # the cases hold what they say they hold
        assert valid.sum() == case["n_chunks"].sum()
        assert (np.asarray(planes["off"])[~want[3]] == 0).all()
        spans = (case["w0"] % w + cw - 1) // w
        assert spans.max() >= -(-(cw - 1) // w)  # a window touches every page it can
