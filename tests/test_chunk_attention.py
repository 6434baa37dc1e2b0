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
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import paged_cache
from paddle_tpu.ops.attention import dense_attention
from paddle_tpu.ops.paged_cache import (PagedKV, _table_positions,
                                        chunk_attention_positions,
                                        paged_chunk_attention,
                                        paged_chunk_rows)

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
    got = paged_chunk_attention(q, pk, positions, window=window, sink=sink)
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
    text = jax.jit(paged_chunk_attention).lower(q, pk, pos).as_text()
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
