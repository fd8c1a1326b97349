"""Ask the chip's compiler before the chip.

Every program of the served path is lowered from SHAPES and compiled by
the installed TPU compiler for one described (not attached) v5e device,
with ``interpret=False`` — what Mosaic or XLA:TPU would refuse on the
machine with the chip (fast-memory limit, unaligned slice, an unsupported
op in a 64-bit-as-u32-pair path, a program that does not fit the device's
memory) is refused here, at no chip time. Nothing runs: a compile that
passes says nothing about results or times.

The topology is described inside a module-scoped fixture, never at import
(on-chip-measurement guide, section 2): only the xdist worker that is
handed this file loads the TPU library, and every compile happens in this
process. Keep these tests in this ONE file.

The one-dispatch plan program (query/plan.py) lowers from shapes without
a live pool: ``_build_program(ast, dims)`` closes over static dimensions
only and takes every runtime value as an argument, so the test hands it a
hand-built AST shape tree and ShapeDtypeStructs.

Code that decides "is this the chip" from ``jax.default_backend()`` sees
the CPU here, so the resident body is steered by monkeypatching
``m3_tpu.device.on_tpu`` — in the test, not through an option.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from m3_tpu.query.functions.temporal_fused import FUSABLE

U32, I32 = jnp.uint32, jnp.int32

# the served decode shape: storage/fs.CHUNK_K records per chunk, a 720-point
# int block's window width, ops/fused.ROWS_DEFAULT rows per grid program
CHUNK_K = 32
WINDOW_WORDS = 29
CHUNKS = 23  # ceil(720 / 32)
RESIDENT_BYTES = 1 << 30
# a TSBS-shaped 65,536-doc index segment: 11 fields, one posting per field
# per doc, ~6.5k hostnames + the small fields' terms, keys up to 20 bytes
N_DOCS = 65_536
N_TERMS = 6_800
KEY_WORDS = 5
N_FIELDS = 11


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory pinned to one described v5e device. The
    persistent compile cache is off around these compiles: an entry written
    for a described device cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    one_chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _pool_shapes(sds):
    from m3_tpu.resident.pool import N_SIDE_PLANES, ResidentOptions

    o = ResidentOptions(max_bytes=RESIDENT_BYTES)
    return o, (
        sds((o.num_pages, o.page_words), U32),
        sds((o.num_side_pages * o.side_page_chunks, N_SIDE_PLANES), U32),
    )


def _plan_rows(sds, o, rows: int, lp: int | None = None):
    # page rows: one data page + the trailing zero-page columns a window
    # may read into; side rows: ceil(CHUNKS / side_page_chunks)
    if lp is None:
        lp = 1 + -(-WINDOW_WORDS // o.page_words) + 1
    sl = -(-CHUNKS // o.side_page_chunks)
    return lp, sl, (
        sds((rows, lp), I32), sds((rows, sl), I32), sds((rows,), I32),
        sds((rows,), I32), sds((rows,), U32), sds((rows,), U32),
    )


def test_lane_aggregates_packed_served_shape(sds):
    from m3_tpu.ops import fused

    tiles = 64
    compiled = fused.lane_aggregates_packed.lower(
        sds((tiles, WINDOW_WORDS, fused.ROWS_DEFAULT, 128), U32),
        sds((tiles, fused.NLANE, fused.ROWS_DEFAULT, 128), U32),
        sds((tiles,), I32),
        n=tiles * fused.ROWS_DEFAULT * 128, k=CHUNK_K, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(FUSABLE))
def test_fused_temporal_kernel(sds, name):
    from m3_tpu.query.functions.temporal_fused import _fused_call

    compiled = _fused_call.lower(
        sds((1024, 720), jnp.float32), funcs=(name,), window=7,
        step_seconds=10.0, t_cols=720,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_resident_assemble_and_decode(sds, monkeypatch):
    """resident/gather.resident_chunked_local_fn — the whole warm-scan
    program (pool + side-plane gathers fused with the packed kernel) at the
    buffers a 1 GiB --resident-bytes allocates. The temp bound is the
    regression guard for the side-plane layout: a [pages, spc, 10] side
    buffer cost a 9.8 GB re-layout temp inside this program."""
    from m3_tpu import device
    from m3_tpu.resident.gather import resident_chunked_local_fn

    monkeypatch.setattr(device, "on_tpu", lambda: True)
    o, pool = _pool_shapes(sds)
    s = 1024
    _, _, rows = _plan_rows(sds, o, s)
    fn = jax.jit(resident_chunked_local_fn(
        CHUNKS, CHUNK_K, WINDOW_WORDS, o.page_words, o.side_page_chunks))
    compiled = fn.lower(*pool, *rows).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < RESIDENT_BYTES // 2


def test_batched_encode_kernel(sds):
    """ops/encode.py at the default ingest plane (--ingest-lanes 1024
    --ingest-slots 1024), words rounded to the pool's page size."""
    from m3_tpu.ops import encode

    t = m = 1024
    kern = encode._build_kernel(t, encode.words_bound(t, 512), encode.CHUNK_K_DEFAULT)
    lane, plane = sds((m,), U32), sds((t, m), U32)
    flag = sds((t, m), jnp.bool_)
    kern.lower(
        lane, lane, sds((t, m), I32), flag, sds((m,), jnp.bool_),
        plane, plane, flag, plane, plane, plane, plane,
    ).compile()


def test_device_index_kernels(sds):
    from m3_tpu.index.device import kernels

    n_words = N_DOCS // 32
    slab = N_DOCS  # one field's postings, already a power of two
    post_idx = sds((N_TERMS, 2), I32)
    post_data = sds((N_FIELDS * N_DOCS + slab,), I32)
    scalar, batch = sds((), I32), sds((8,), I32)
    jax.jit(kernels.match_terms_traced).lower(
        sds((N_TERMS, KEY_WORDS), U32), sds((N_TERMS,), I32), batch, batch,
        sds((8, KEY_WORDS), U32), batch,
    ).compile()
    jax.jit(kernels.bitmap_from_terms_traced, static_argnums=(4, 5)).lower(
        post_idx, post_data, batch, scalar, n_words, slab,
    ).compile()
    jax.jit(kernels.bitmap_from_term_range_traced, static_argnums=(5, 6)).lower(
        post_idx, post_data, scalar, scalar, scalar, n_words, slab,
    ).compile()


def _overlay_args(sds, cap: int, n_src: int, cap_s: int):
    """The overlay's arguments (query/plan._overlay_points): ``n_src``
    shards' ingest planes at the deployment's 1,024 lanes x 1,024 slots,
    their synced counts, the matched lanes a shard and the slot rows."""
    lanes = slots = 1024
    return (tuple(sds((4, lanes, slots), U32) for _ in range(n_src)),
            tuple(sds((lanes,), I32) for _ in range(n_src)),
            sds((n_src, cap_s), I32), sds((cap,), I32))


def _no_plane_relayout(text: str) -> None:
    """No operation of the program lays a shard's ingest planes out anew:
    the overlay gathers whole rows of them (query/plan._overlay_points), and
    a gather along the planes' middle axis had the compiler copy all 16 MiB
    a shard a request."""
    copies = re.findall(r"^\s*(?:ROOT )?%\S+ = u32\[4,1024,1024\]\S* (\w+)\(", text, re.M)
    assert not [op for op in copies if op != "parameter"], copies


def _compile_plan_program(sds, monkeypatch, n_docs: int, cw: int, lane_pages: int,
                          t_grid: int, cap: int | None = None, overlay=None):
    """query/plan._build_program for ``metric{tag="v"}`` (two exact leaves
    ANDed) over an ``n_docs`` segment, one block of CHUNKS chunks whose
    widest lane spans ``cw`` window words and ``lane_pages`` pool pages,
    onto a ``t_grid``-step grid, decoding ``cap`` matched-series slots (the
    whole segment where not given), with the open block's overlay where
    ``overlay`` gives its (sources, cap_s, width): the compiled program,
    held to the device's memory and to no gather over a [cap, t_pts] plane
    of decoded points."""
    from m3_tpu import device
    from m3_tpu.query import plan

    # the chip's program: on it the decode is the Pallas point kernel,
    # elsewhere the lax.scan (query/plan._build_program asks device.on_tpu)
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    plan._build_program.cache_clear()
    o, pool = _pool_shapes(sds)
    n_words = n_docs // 32
    slab = n_docs
    ast = ("and", (("terms", 0, 1, 0, slab), ("terms", 1, 1, slab, slab)), ())
    lp = lane_pages + -(-cw // o.page_words) + 1
    _, sl, tables = _plan_rows(sds, o, n_docs + 1, lp)
    cap = n_docs if cap is None else cap
    dims = (n_words, n_docs, cap, 1, CHUNKS, CHUNK_K, cw, lp, sl,
            o.page_words, o.side_page_chunks, t_grid)
    leaves = sds((2,), I32)
    odims = extra = None
    if overlay is not None:
        n_src, cap_s, width = overlay
        odims = (cap, n_src, cap_s, width, t_grid)
        extra = {"overlay": _overlay_args(sds, cap, n_src, cap_s)}
    compiled = plan._build_program(ast, dims, odims).lower(
        sds((N_TERMS, KEY_WORDS), U32), sds((N_TERMS,), I32),
        sds((N_TERMS, 2), I32), sds((N_FIELDS * n_docs + slab,), I32),
        sds((n_words,), U32),
        sds((2, KEY_WORDS), U32), leaves, leaves, leaves,
        sds((1,), I32), sds((1,), I32),
        *pool, *tables,
        sds((2 * t_grid + 6,), U32),
        **(extra or {}),
    ).compile()
    plan._build_program.cache_clear()  # nothing later meets the chip's program
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 12 << 30
    # no gather reads a [cap, t_pts] plane of decoded points, in either
    # layout or flattened. HLO prints a gather's operands by name, so the
    # shapes come from the lines that define them.
    text = compiled.as_text()
    elems = {
        name: math.prod(int(d) for d in shape.split(",") if d)
        for name, shape in re.findall(
            r"^\s*(?:ROOT )?(%\S+) = \w+\[([\d,]*)\]", text, re.M
        )
    }
    operands = re.findall(r"= \S+ gather\((%[^,\s)]+)", text)
    assert operands, "the pool's page gather at least is expected"
    points = cap * CHUNKS * CHUNK_K
    assert not [name for name in operands if elems[name] == points]
    # and ONE gather yields a word per window slot: the pool's. The page id
    # of each word is two ids a lane and a select (resident/gather.py
    # _resident_gather), not a second gather over [lanes, cw]
    slots = cap * CHUNKS * cw
    per_word = re.findall(r"^\s*(?:ROOT )?(%\S+) = \w+\[[\d,]*\]\S* gather\(", text, re.M)
    assert len([name for name in per_word if elems[name] == slots]) == 1
    return compiled


@pytest.mark.parametrize("t_grid", [128, 1024])
def test_one_dispatch_plan_program(sds, monkeypatch, t_grid):
    """A 16,384-doc segment of int lanes onto a window query's 128-step
    grid and a read-back's 1,024. The byte bound is what forbids stage 5's
    [cap, t_grid, t_pts] cube in memory (ONE u32 plane of it is 6.2 GB at
    128 steps), the gather check the per-element loop it replaced
    (functions/temporal.py, "window index machinery")."""
    _compile_plan_program(sds, monkeypatch, 16_384, WINDOW_WORDS, 1, t_grid)


@pytest.mark.parametrize("t_grid", [128, 1024])
def test_plan_program_at_the_devops_cell_dimensions(sds, monkeypatch, t_grid):
    """``devops.haystack`` (BENCHMARK.json): 4,040 series pad to a cap of
    4,064, 23 chunks, and the float64 lanes (5.3 KB a block: three 2 KiB
    pages) set every lane's window: 73-76 words by the seed, which the plan
    rounds to 80 (``plan._bucket_window_words``)."""
    from m3_tpu.query.plan import _bucket_window_words

    assert {_bucket_window_words(cw) for cw in (73, 74, 75, 76)} == {80}
    _compile_plan_program(sds, monkeypatch, 4_064, 80, 3, t_grid)


@pytest.mark.parametrize("n_docs,cap,cw,lane_pages", [
    (4_064, 64, 80, 3),   # devops.haystack: 40 of 4,040 series match
    (4_000, 512, 15, 1),  # cpu-only.haystack: 400 of 4,000
    (4_064, 8, 80, 3),    # the floor: one series matches
])
def test_plan_program_at_the_capacity_of_what_matched(sds, monkeypatch, n_docs, cap, cw,
                                                      lane_pages):
    """The two query cells' programs since the decode capacity follows the
    matched count (``Planner._build``: its power-of-two bucket), and the
    smallest there is. The whole-segment cases above are what a full match
    still compiles."""
    compiled = _compile_plan_program(sds, monkeypatch, n_docs, cw, lane_pages, 128, cap=cap)
    # the decode is one operation (the point kernel), not a scan's loop
    assert "tpu_custom_call" in compiled.as_text()


def test_plan_program_with_the_open_block_overlay(sds, monkeypatch):
    """``cpu-only.dashboard-now``'s panel (BENCHMARK.json): the
    ``cpu-only.haystack`` program (512 slots of 4,000 series, 15-word
    windows) with the open block's rows of eight shards' ingest planes
    (about 50 matched lanes a shard: 64, and 185 ticks: 256 slots)
    appended before stage 5."""
    compiled = _compile_plan_program(sds, monkeypatch, 4_000, 15, 1, 128, cap=512,
                                     overlay=(8, 64, 256))
    assert "tpu_custom_call" in compiled.as_text()
    _no_plane_relayout(compiled.as_text())


def test_overlay_program_alone(sds):
    """``cpu-only.dashboard-now``'s lastpoint: a range wholly inside the
    open block, the overlay's rows alone onto a 31-step grid."""
    from m3_tpu.query import plan

    cap, n_src, cap_s, width, t_grid = 512, 8, 64, 256, 32
    compiled = plan._build_overlay_program((cap, n_src, cap_s, width, t_grid)).lower(
        sds((2 * t_grid + 6,), U32), _overlay_args(sds, cap, n_src, cap_s),
    ).compile()
    plan._build_overlay_program.cache_clear()  # nothing later meets the chip's program
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30
    _no_plane_relayout(compiled.as_text())

