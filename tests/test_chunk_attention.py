"""ISSUE 46: a prompt chunk's attention walks the LIVE runs of its row's
table (``ops.paged_cache.paged_chunk_attention``: an online softmax over
runs of ``CHUNK_RUN_PAGES`` pages) and never scores the slot's whole
length.

The yardstick is the path it replaced, kept here: gather row 0's WHOLE
table (``paged_chunk_rows``), one dense attention under the position
mask. The cases cover every family that calls it: equal widths in
groups (Qwen2, Laguna), keys wider than values and a sink over a ring
(MiMo-V2's window layers), a group of one (Olmo-Hybrid), a window over a
whole table (Qwen2's ``max_window_layers``), at a table a quarter, half
and wholly live.

Tolerance: float32 on both sides; what separates them is the order of
the softmax's sums (one pass against a run at a time): 2e-5 on results
of magnitude 1 leaves a factor of 20 over the 1e-6 read here.

ISSUE 48: where ``chunk_attn_route`` says "kernel" the same call is ONE
Pallas kernel over tiles of the chunk's queries
(``ops.pallas.ragged_paged_attention.chunk_paged_attention_pallas``),
pinned here in the interpreter against both references it replaces, the
walk and the dense gather, at tiles of 8 queries and key blocks of one
lane tile (128 keys: 32 pages of 4; a block is always whole lane tiles,
as on the chip) over tables of several blocks, so that a chunk of 16 is
several tiles over several blocks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.ops.pallas as pallas
from paddle_tpu.ops import paged_cache
from paddle_tpu.ops.attention import dense_attention
from paddle_tpu.ops.paged_cache import (PagedKV, _table_positions,
                                        chunk_attention_positions,
                                        chunk_attn_route,
                                        paged_chunk_attention,
                                        paged_chunk_attention_walk,
                                        paged_chunk_rows)
from paddle_tpu.ops.pallas import ragged_paged_attention as rpa

B, CHUNK = 4, 16


def dense_gather(q, pk, positions, window=None, sink=None):
    """The parent's path: the row's whole table, one masked attention."""
    ks = paged_chunk_rows(pk)
    vs = paged_chunk_rows(pk, pk.vp)
    kpos = _table_positions(pk, pk.seq_lens[:1] - 1) if pk.ring \
        else jnp.arange(ks.shape[1])[None, :]
    qpos = positions[0][:, None]
    keep = kpos <= qpos
    if pk.ring:
        keep &= kpos >= 0
    if window is not None:
        keep &= qpos - kpos < window
    return dense_attention(q, ks, vs, attn_mask=keep[None, None], sink=sink)


def cached_row(rng, M, cached, kvh, dk, dv, ring):
    """Row 0's table and pools with ``cached`` tokens written in order
    (a ring's pages written round), the rest of the pool NOISE: what a
    query must not see is there to be seen."""
    pages = 3 * M + 1
    kp = rng.normal(size=(pages, B, kvh * dk)).astype(np.float32)
    vp = rng.normal(size=(pages, B, kvh * dv)).astype(np.float32)
    table = 1 + rng.permutation(pages - 1)[:M]
    k = rng.normal(size=(cached, kvh * dk)).astype(np.float32)
    v = rng.normal(size=(cached, kvh * dv)).astype(np.float32)
    for t in range(cached):
        page = table[(t // B) % M if ring else t // B]
        kp[page, t % B], vp[page, t % B] = k[t], v[t]
    return PagedKV(jnp.asarray(kp), jnp.asarray(vp),
                   jnp.asarray(table[None], jnp.int32),
                   jnp.asarray([cached], jnp.int32), kvh, ring, "chunk")


CASES = {
    # kvh, group, dk, dv, window, ring, sink
    "groups-of-3": (2, 3, 16, 16, None, False, False),
    "group-of-1": (4, 1, 16, 16, None, False, False),
    "wide-keys-sink": (1, 4, 24, 16, None, False, True),
    "ring-sink-wide-keys": (2, 2, 24, 16, 12, True, True),
    "ring-groups-of-5": (2, 5, 16, 16, 12, True, False),
    "window-whole-table": (2, 2, 16, 16, 24, False, False),
}


@pytest.mark.parametrize("live", ["quarter", "half", "whole"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_walk_agrees_with_the_dense_gather(monkeypatch, case, live):
    kvh, g, dk, dv, window, ring, has_sink = CASES[case]
    monkeypatch.setattr(paged_cache, "CHUNK_RUN_PAGES", 4)
    paged_chunk_attention.clear_cache()
    rng = np.random.default_rng(len(case) + len(live))
    # a ring holds window + chunk + one page; a whole table 48 pages
    M = (-(-window // B) + CHUNK // B + 1) if ring else 48
    slot = 48 * B
    cached = {"quarter": slot // 4, "half": slot // 2 - 3,
              "whole": slot}[live]
    n = CHUNK if live != "half" else CHUNK - 3      # a chunk with pads
    pk = cached_row(rng, M, cached, kvh, dk, dv, ring)
    start = cached - n
    positions = jnp.asarray(start + np.arange(CHUNK))[None]
    q = jnp.asarray(rng.normal(size=(1, CHUNK, kvh * g, dk)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(kvh * g,)), jnp.float32) \
        if has_sink else None
    got = paged_chunk_attention_walk(q, pk, positions, window, sink)
    want = dense_gather(q, pk, positions, window=window, sink=sink)
    assert got.shape == (1, CHUNK, kvh * g, dv)
    # the chunk's live queries; a pad's result is dropped by the engine
    np.testing.assert_allclose(got[0, :n], want[0, :n], atol=2e-5)
    assert np.all(np.isfinite(np.asarray(got)))
    assert float(jnp.abs(want[0, :n]).max()) > 0.1
    paged_chunk_attention.clear_cache()


def test_no_score_is_as_long_as_the_slot(monkeypatch):
    """The lowered program of a 448-page table holds no array with the
    slot's 7,168 positions behind the chunk's queries: the widest score
    is [kv heads, group, chunk, one run]."""
    kvh, g, d, M, Bk = 2, 3, 16, 448, 16
    pk = PagedKV(jnp.zeros((M + 1, Bk, kvh * d)),
                 jnp.zeros((M + 1, Bk, kvh * d)),
                 jnp.arange(1, M + 1, dtype=jnp.int32)[None],
                 jnp.asarray([700], jnp.int32), kvh, False, "chunk")
    q = jnp.zeros((1, 32, kvh * g, d))
    pos = jnp.arange(668, 700)[None]
    text = jax.jit(paged_chunk_attention_walk).lower(q, pk, pos).as_text()
    run = paged_cache.CHUNK_RUN_PAGES * Bk
    assert f"{kvh}x{g}x32x{run}xf32" in text
    assert f"x{M * Bk}xf32" not in text and f"x{M * Bk}x{kvh}" not in text


@pytest.mark.parametrize("cached,M,ring,want", [
    (700, 448, False, (1024, 700)),     # two runs of 512 hold 700
    (512, 448, False, (512, 512)),
    (7168, 448, False, (7168, 7168)),
    (40, 128, False, (512, 40)),
    (5000, 97, True, (2048, 1552)),     # a ring: all of its 4 runs
    (100, 97, True, (2048, 100)),
    (300, 16, False, (256, 256)),       # never past the table
])
def test_the_counters_arithmetic(cached, M, ring, want):
    scored, live = chunk_attention_positions(cached, M, 16, ring)
    assert (scored, live) == want
    if not ring:    # within one run of what is live
        assert scored - live < min(M, paged_cache.CHUNK_RUN_PAGES) * 16


# ------------------------------------------------------------ the kernel
# a kernel case's table: 4 blocks of 128 keys; a window of CASES is as
# many PAGES of 16 there, so that a band is a block and more
PAGES, BLOCK, WIDER = 128, 128, 16


@pytest.fixture
def kernel(monkeypatch):
    """The chunk kernel in the interpreter: tiles of 8 queries, key
    blocks of one lane tile (32 pages)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(rpa, "_TILE_QUERIES", 8)
    monkeypatch.setattr(rpa, "_CHUNK_BLOCK_TOKENS", BLOCK)
    for fn in (paged_chunk_attention, rpa._attend_chunk):
        fn.clear_cache()
    yield
    for fn in (paged_chunk_attention, rpa._attend_chunk):
        fn.clear_cache()


def ring_pages(window, chunk=CHUNK):
    """Pages of a ring: the window, a chunk and one page."""
    return -(-window // B) + -(-chunk // B) + 1


def all_three(rng, pk, chunk, n, g, dk, window, sink):
    """(kernel, walk, dense gather) of a chunk of ``chunk`` positions,
    ``n`` of them live, the last at the row's end."""
    start = int(pk.seq_lens[0]) - n
    positions = jnp.asarray(start + np.arange(chunk))[None]
    q = jnp.asarray(rng.normal(size=(1, chunk, pk.heads * g, dk)),
                    jnp.float32)
    assert chunk_attn_route(q, pk.kp, pk.heads) == "kernel"
    return (paged_chunk_attention(q, pk, positions, window=window,
                                  sink=sink),
            paged_chunk_attention_walk(q, pk, positions, window, sink),
            dense_gather(q, pk, positions, window=window, sink=sink))


@pytest.mark.parametrize("live", ["quarter", "half", "whole"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_agrees_with_the_walk_and_the_dense_gather(kernel, case,
                                                              live):
    kvh, g, dk, dv, window, ring, has_sink = CASES[case]
    window = window and window * WIDER
    rng = np.random.default_rng(len(case) + len(live))
    M = ring_pages(window) if ring else PAGES
    slot = PAGES * B
    cached = {"quarter": slot // 4, "half": slot // 2 - 3,
              "whole": slot}[live]
    n = CHUNK if live != "half" else CHUNK - 3      # a chunk with pads
    assert rpa.chunk_tiling(CHUNK, B, M, window) == (8, BLOCK // B)
    pk = cached_row(rng, M, cached, kvh, dk, dv, ring)
    sink = jnp.asarray(rng.normal(size=(kvh * g,)), jnp.float32) \
        if has_sink else None
    got, walk, dense = all_three(rng, pk, CHUNK, n, g, dk, window, sink)
    assert got.shape == (1, CHUNK, kvh * g, dv)
    np.testing.assert_allclose(got[0, :n], walk[0, :n], atol=2e-5)
    np.testing.assert_allclose(got[0, :n], dense[0, :n], atol=2e-5)
    assert np.all(np.isfinite(np.asarray(got)))
    assert float(jnp.abs(dense[0, :n]).max()) > 0.1


# a band of 150 over a ring of 43 pages (172 keys: a block and a third)
# or a whole table of 96 (three blocks)
RING = ring_pages(150)
TILINGS = {
    # kvh, group, window, ring, chunk, live queries, cached, pages
    "chunk-not-a-multiple-of-the-tile": (2, 3, None, False, 20, 20, 381, 96),
    "short-last-chunk-two-tiles-of-pads": (2, 3, None, False, 24, 5, 277,
                                           96),
    "short-last-chunk-of-a-ring": (2, 2, 150, True, 16, 3, 331, RING),
    "last-page-partly-filled": (2, 3, None, False, 16, 16, 297, 96),
    "ring-wraps-inside-a-tile": (2, 2, 150, True, 16, 16, 176, RING),
    "ring-written-round-twice": (2, 2, 150, True, 16, 16, 377, RING),
    "band-edge-inside-a-key-block": (2, 2, 150, False, 16, 16, 333, 96),
    "band-edge-inside-a-ring's-block": (1, 3, 135, True, 16, 16, 300,
                                        ring_pages(135)),
    "tile-behind-a-block's-edge": (2, 3, None, False, 16, 16, 264, 96),
    "tile-across-a-block's-edge": (2, 3, None, False, 16, 16, 260, 96),
    "one-page-cached-behind-the-chunk": (2, 3, None, False, 16, 16, 20, 96),
    "one-page-cached-behind-a-ring's": (2, 2, 150, True, 16, 16, 20, RING),
    "group-of-1": (3, 1, None, False, 16, 16, 300, 96),
    "group-of-6": (2, 6, None, False, 16, 16, 300, 96),
    "group-of-7": (1, 7, None, False, 16, 16, 300, 96),
    "group-of-9-over-a-ring": (2, 9, 150, True, 16, 16, 300, RING),
}


@pytest.mark.parametrize("case", sorted(TILINGS))
def test_what_tiling_adds(kernel, case):
    kvh, g, window, ring, chunk, n, cached, M = TILINGS[case]
    rng = np.random.default_rng(len(case))
    assert rpa.chunk_tiling(chunk, B, M, window) == (8, BLOCK // B)
    pk = cached_row(rng, M, cached, kvh, 16, 16, ring)
    got, walk, dense = all_three(rng, pk, chunk, n, g, 16, window, None)
    assert got.shape == (1, chunk, kvh * g, 16)
    np.testing.assert_allclose(got[0, :n], walk[0, :n], atol=2e-5)
    np.testing.assert_allclose(got[0, :n], dense[0, :n], atol=2e-5)
    # a pad's result is dropped by the engine: anything finite
    assert np.all(np.isfinite(np.asarray(got)))
    assert float(jnp.abs(dense[0, :n]).max()) > 0.1


@pytest.mark.parametrize("B_, M, window, want", [
    (16, 448, None, 32),        # Laguna's full layers: 512 keys
    (16, 97, 512, 16),          # and its ring: half the window
    (16, 25, 128, 8),           # a narrow band: one lane tile
    (16, 5, None, 8),           # a short table: a lane tile covers it
    (8, 16, None, 16),
    (4, 48, None, 64),
    (24, 50, None, 16),         # pages of 24: 384 keys are 3 lane tiles
])
def test_a_compute_block_is_whole_lane_tiles(B_, M, window, want):
    """What ``_fold_tile`` relies on, whatever the page and the table."""
    tq, pps = rpa.chunk_tiling(1024, B_, M, window)
    assert (tq, pps) == (128, want)
    assert pps * B_ % 128 == 0 and pps * B_ <= 512


@pytest.mark.parametrize("case,window,ring,has_sink,M,cached,block", [
    ("whole-table", None, False, False, 96, 333, 256),
    ("whole-table-sink", None, False, True, 96, 380, 256),
    ("ring-sink", 120, True, True, 35, 341, 256),
    ("window-whole-table", 300, False, False, 96, 370, 256),
])
def test_blocks_of_whole_lane_tiles_keep_a_denominator_a_lane(
        kernel, monkeypatch, case, window, ring, has_sink, M, cached, block):
    """At compute blocks of SEVERAL lane tiles (the chip's are two and
    four) the denominator's partial sum a lane adds the block's
    probabilities lane tile on lane tile (``_fold_tile``); a sink's one
    key is in the first lane."""
    monkeypatch.setattr(rpa, "_CHUNK_BLOCK_TOKENS", block)
    kvh, g = 2, 3
    assert rpa.chunk_tiling(CHUNK, B, M, window)[1] * B in (128, block)
    rng = np.random.default_rng(len(case))
    pk = cached_row(rng, M, cached, kvh, 16, 16, ring)
    sink = jnp.asarray(rng.normal(size=(kvh * g,)), jnp.float32) \
        if has_sink else None
    got, walk, dense = all_three(rng, pk, CHUNK, CHUNK - 2, g, 16, window,
                                 sink)
    n = CHUNK - 2
    np.testing.assert_allclose(got[0, :n], walk[0, :n], atol=2e-5)
    np.testing.assert_allclose(got[0, :n], dense[0, :n], atol=2e-5)
    assert np.all(np.isfinite(np.asarray(got)))


def test_the_kernel_rounds_where_the_walk_rounds(kernel):
    """bfloat16 pools and queries: float32 scores and sums, the
    unnormalised probabilities cast to the pool's type for ``p @ V``, as
    the walk's (whose scores are bfloat16 products rounded once more)."""
    rng = np.random.default_rng(48)
    pk = cached_row(rng, 96, 300, 2, 16, 16, False)
    pk = pk._replace(kp=pk.kp.astype(jnp.bfloat16),
                     vp=pk.vp.astype(jnp.bfloat16))
    positions = jnp.asarray(284 + np.arange(16))[None]
    q = jnp.asarray(rng.normal(size=(1, 16, 6, 16)), jnp.bfloat16)
    got = paged_chunk_attention(q, pk, positions)
    walk = paged_chunk_attention_walk(q, pk, positions)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(walk, np.float32), atol=3e-2)


@pytest.mark.parametrize("backend,interpret,dk,want", [
    ("cpu", False, 128, "walk"),        # no kernel runs on the CPU
    ("cpu", True, 24, "kernel"),        # but in the interpreter: any width
    ("tpu", False, 128, "kernel"),
    ("tpu", False, 192, "walk"),        # MiMo-V2's keys
    ("tpu", False, 64, "walk"),
])
def test_shapes_and_the_platform_choose_the_route(monkeypatch, backend,
                                                  interpret, dk, want):
    monkeypatch.setattr(pallas, "tpu_backend", lambda: backend == "tpu")
    if interpret:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    q = jax.ShapeDtypeStruct((1, 256, 8, dk), jnp.bfloat16)
    kp = jax.ShapeDtypeStruct((9, 16, 4 * dk), jnp.bfloat16)
    assert chunk_attn_route(q, kp, 4) == want
    # a group that is not whole: never the kernel
    assert chunk_attn_route(q, kp, 3) == "walk"


@pytest.mark.parametrize("first,chunk,cached,M,window,want", [
    # Laguna's full layer: 8 tiles of 128, blocks of 512 to each tile's end
    (3072, 1024, 4096, 448, None, (3840, 3648)),
    # and its ring: the band behind a tile and the tile, 3 blocks of 256
    (3072, 1024, 4096, 97, 512, (768, 639)),
    # a short last chunk: three tiles have queries, five walk nothing
    (4096, 1024, 4396, 448, None, (1728, 1621)),
    # a window over a whole table starts where the band does
    (1024, 256, 1280, 128, 128, (256, 255)),
    # never past the table
    (256, 256, 300, 16, None, (128, 128)),
])
def test_the_counters_arithmetic_of_the_kernel(first, chunk, cached, M,
                                               window, want):
    ring = window is not None and M < 128
    got = chunk_attention_positions(cached, M, 16, ring,
                                    tiles=(first, chunk), window=window)
    assert got == want
    assert got[0] >= got[1] > 0
