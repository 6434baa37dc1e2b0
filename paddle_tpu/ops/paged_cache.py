"""The paged cache below the models: its data format and what a layer
does with it.

``PagedEngine`` (generation/paged.py) owns the pools, the block tables
and the slot state, and hands every cache layer of a model ONE view of
them a call: a ``PagedKV`` (pages of K and V, or of one latent row) or a
``SlotState`` (recurrent state kept by slot). A view carries everything
the engine tells a layer, the kind of call included (``call``), so a
model's ``forward`` takes the views and no word about the engine beside
them. A K/V layer hands its view and its fresh q, k, v to
``write_and_attend``; a mixer with arithmetic of its own (the latent
attention, the delta rule) reads ``view.call`` and uses the writes and
attentions here. ``CacheLayer`` / ``StateLayer`` are what a model
answers ``paged_cache_layers`` with.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .attention import dense_attention, segment_mask
from .delta_rule import chunk_rule_kernel
from .pallas import expert_mlp

__all__ = ["CacheLayer", "StateLayer", "PagedKV", "SlotState",
           "write_and_attend", "paged_decode_write", "paged_prefill_write",
           "paged_chunk_rows", "paged_chunk_attention",
           "chunk_attention_positions",
           "paged_packed_attention", "paged_decode_attention",
           "paged_decode_attention_dense", "paged_latent_attention",
           "paged_latent_attention_dense", "paged_decode_route",
           "state_step_route", "chunk_rule_route", "chunk_experts_route",
           "chunk_attn_route", "paged_chunk_attention_walk"]


class CacheLayer(NamedTuple):
    """What one cache layer of a model is (``paged_cache_layers``, where
    a model's layers differ): ``rows`` the (heads, width) of each pool
    array of a cached token (K and V, whose widths may differ; or one
    latent row), and ``window``: None for a layer that keeps every
    block of a sequence, else the sliding window of a layer that keeps
    only the band its queries still reach (``PagedKV.ring``).
    ``heads``: the query heads that attend over it, where a model's
    layers differ (None: the configuration's ``num_attention_heads``)."""
    rows: tuple
    window: Optional[int] = None
    heads: Optional[int] = None


class StateLayer(NamedTuple):
    """``CacheLayer``'s sibling (``paged_cache_layers``): a layer that
    caches no token's rows but keeps recurrent STATE, which belongs to a
    SLOT and not to a block. ``arrays``: the (shape, dtype) of each
    array ONE slot keeps (a linear-attention layer: its heads' matrix
    state, its convolution's last inputs); the engine holds each as
    ``[max_slots, *shape]`` beside the pools and hands the layer a
    ``SlotState``. Such state does not grow with the context, is
    addressed by no block table, starts from zero with every request
    and cannot be adopted, spilled, shipped or rolled back by block.
    ``rule``: the shapes ONE position gives its prompt chunk's delta
    rule, (q, v, the log-decay), all float32: what decides the path the
    rule takes (``chunk_rule_route``); () where the layer does not say."""
    arrays: tuple
    rule: tuple = ()
    rows = ()           # no pool array of cached tokens
    window = None       # and no band of them


class SlotState(NamedTuple):
    """Per-layer view of a ``StateLayer``'s arrays handed to the model
    where a K/V layer gets a ``PagedKV``.

    arrays: the engine's ``[max_slots, ...]`` arrays of this layer, whole.
    slots: [rows] the slot of each row of the call (a packed call's
    segments; a chunk's one slot; ``max_slots`` for a dead segment,
    whose result is dropped). None in a decode tick: row r is slot r.
    seq_lens: [rows], as ``PagedKV.seq_lens``: a position at or past its
    row's is padding and must change nothing.
    live: decode ticks, [max_slots] bool: the rows that advance; any
    other row's state stays as it is (a free slot, a row mid-prefill,
    a row that finished on the device).
    fresh: prompt calls, [rows] bool: the row starts from ZERO state,
    whatever its slot held; else from the state its slot holds (the
    chunk before it left it there). Only row 0 may carry; the later
    segments of a packed call start at position 0.
    call: the kind of call, as ``PagedKV.call``."""
    arrays: tuple
    slots: Any = None
    seq_lens: Any = None
    live: Any = None
    fresh: Any = None
    call: str = "decode"

    @property
    def pool(self) -> tuple:
        """This layer's arrays, as the engine holds them."""
        return tuple(self.arrays)


class PagedKV(NamedTuple):
    """Per-layer paged cache view handed to the attention modules.

    kp/vp: [P, B, heads*width] physical block pools (this layer's): a
    token's heads side by side in one row, so a page is one contiguous
    ``(B, heads*width)`` slab. The pools are allocated, written, donated
    and kept in this form because it is the one the kernels fetch pages
    in: handing a pool to a kernel moves nothing. A LATENT pool
    (multi-head latent attention) is ``kp`` alone, [P, B, W]: one row a
    token (compressed latent, shared rope key, zero padding to whole
    128-lane tiles) that is key and value to every head; ``vp`` is None.
    block_tables: [R, M] physical block id per (slot, logical block).
    seq_lens: [R] tokens already cached per slot == this step's write
    position. Shared across layers; XLA dedups the copies.
    heads: how many heads share a pool row (the shape no longer says):
    a Python int, static under every transform. ``vp``'s heads may be
    narrower than ``kp``'s (MiMo-V2: keys of 192 columns, values of 128).
    ring: the layer keeps only a BAND of each sequence (a sliding-window
    layer). Its ``block_tables`` [R, Mw] is then a ring over the row's
    logical blocks, logical block b in entry ``b % Mw``, and its pool
    holds ``Mw`` pages a slot and no more; positions still count from
    the sequence's start, and what a ring entry holds is the newest
    logical block written to it (``_table_positions``). Static too.
    call: which of the engine's kinds of call on a model this view was
    made for, static as well. ``"decode"``: T >= 1 positions a row at
    the row's cursor (the plain tick and the speculative verify);
    ``"packed"``: prompts side by side in one row of tokens, each from
    its position 0 (the model's ``segment_ids`` say whose each token
    is); ``"chunk"``: one slot's prompt chunk behind what is cached;
    ``"prompt"``: one slot's whole prompt. ``write_and_attend`` does
    what the call needs of a K/V layer; a mixer of another kind
    branches on it. The engine always says it; a view built by hand for
    the decode writes and attentions is a decode view.
    """
    kp: Any
    vp: Any
    block_tables: Any
    seq_lens: Any
    heads: int = 1
    ring: bool = False
    call: str = "decode"

    @property
    def block_size(self) -> int:
        return self.kp.shape[1]

    @property
    def width(self) -> int:
        """Columns of one (key) head in a pool row."""
        return self.kp.shape[2] // self.heads

    def table_entry(self, logical):
        """The table column of logical block(s) ``logical``."""
        return logical % self.block_tables.shape[1] if self.ring \
            else logical

    @property
    def pool(self) -> tuple:
        """This layer's pool arrays, as the engine holds them."""
        return (self.kp,) if self.vp is None else (self.kp, self.vp)

    def scatter(self, bidx, boff, k, v, sel=None):
        """New rows ``k[sel]`` [..., heads, width] (and ``v[sel]``; ``v``
        None for a latent pool) written at block ``bidx``, offset
        ``boff``. The new rows are flattened to the pool's, never the
        pool split to theirs."""
        def rows(a, pool):
            a = a if sel is None else a[sel]
            return a.reshape(a.shape[:-2] + (-1,)).astype(pool.dtype)
        kp = self.kp.at[bidx, boff].set(rows(k, self.kp))
        if self.vp is None:
            return self._replace(kp=kp)
        vp = self.vp.at[bidx, boff].set(rows(v, self.vp))
        return self._replace(kp=kp, vp=vp)

    def split(self, rows):
        """Rows GATHERED from a pool, [..., heads*width], with their heads
        apart: [..., heads, width] (the width is the gathered pool's)."""
        return rows.reshape(rows.shape[:-1] + (self.heads, -1))


# ``heads``, ``ring`` and ``call`` are structure, not data: a view that
# crosses a transform (jit, remat, scan) keeps them Python values
jax.tree_util.register_pytree_node(
    PagedKV, lambda pk: (pk[:4], pk[4:]),
    lambda aux, leaves: PagedKV(*leaves, *aux))
jax.tree_util.register_pytree_node(
    SlotState, lambda ss: (ss[:5], ss[5:]),
    lambda aux, leaves: SlotState(*leaves, *aux))


def _table_positions(pk: PagedKV, newest):
    """The sequence position of every token slot of each row's table,
    [rows, M*B], given the ``newest`` position [rows] written so far: a
    plain table holds logical block j in entry j; a ring entry j holds
    the newest logical block congruent to j that has been started,
    and reads negative where none has (the caller masks it)."""
    M, B = pk.block_tables.shape[1], pk.block_size
    lb = jnp.arange(M)[None, :]
    if pk.ring:
        top = (newest // B)[:, None]
        lb = top - (top - lb) % M
    pos = lb[:, :, None] * B + jnp.arange(B)[None, None, :]
    return pos.reshape(pos.shape[0], M * B)


def paged_decode_write(pk: PagedKV, k, v=None):
    """Scatter each row's new K/V (k [R, T, kvh, d], written as rows of
    kvh*d columns; ``v`` None for a latent pool, here and in the
    prefill write) into its blocks at positions seq_len ..
    seq_len+T-1. T == 1 is the plain decode tick;
    T > 1 is the speculative verify (ISSUE 7) writing the probe token
    plus T-1 drafts in one scatter. Positions past a row's ALLOCATED
    blocks divert to the garbage block automatically (unallocated table
    entries are 0 — the garbage block id — and logical blocks past M
    are clamped there explicitly), so a row without speculative
    headroom can ride the multi-token program unharmed: its surplus
    writes are garbage-block noise the attention mask never reads."""
    B = pk.block_size
    R, T = k.shape[0], k.shape[1]
    with jax.named_scope("kv_write"):       # obs.TICK_SCOPES
        if T == 1:
            r = jnp.arange(R)
            bidx = pk.block_tables[r, pk.table_entry(pk.seq_lens // B)]
            boff = pk.seq_lens % B
            return pk.scatter(bidx, boff, k, v, sel=(slice(None), 0))
        M = pk.block_tables.shape[1]
        r = jnp.arange(R)[:, None]                           # [R, 1]
        pos = pk.seq_lens[:, None] + jnp.arange(T)[None, :]  # [R, T]
        lb = pos // B
        if pk.ring:         # a ring always has the next entry: the
            bidx = pk.block_tables[r, pk.table_entry(lb)]   # one behind
        else:                                               # the band
            bidx = jnp.where(lb < M,
                             pk.block_tables[r, jnp.clip(lb, 0, M - 1)], 0)
        boff = pos % B
        return pk.scatter(bidx, boff, k, v)


# The prefill write and the two chunk attentions below are jitted and
# inlined, as the decode kernel's wrapper is
# (``ragged_paged_attention._attend``): a program of L layers traces
# each once, not L times (set-up pays tracing on every start), and
# lowers to what it lowered to without
@functools.partial(jax.jit, inline=True, static_argnames=("garbage_block",))
def paged_prefill_write(pk: PagedKV, k, v=None, positions=None,
                        segments=None, garbage_block: int = 0):
    """Scatter a [1, s, kvh, d] prompt's (or prompt chunk's) K/V into
    row 0's blocks; pad positions (>= seq_lens[0]) go to the garbage
    block. ``positions`` [s] are the tokens' GLOBAL positions (default
    0..s-1 — the whole-prompt case); a chunk passes start..start+s-1
    and seq_lens[0] = start + live-chunk-length. ``segments`` [s]
    (a PACKED call: several prompts side by side, each from its
    position 0) names every token's row of the table in place of row
    0; its pads ride behind the last prompt, past that row's length.
    Into a ring go only the positions that no later one of the same
    call overwrites (the last ring's worth before the row's length)."""
    B = pk.block_size
    s = k.shape[1]
    with jax.named_scope("kv_write"):       # obs.TICK_SCOPES
        pos = positions if positions is not None else jnp.arange(s)
        row = segments if segments is not None else 0
        live = pos < pk.seq_lens[row]
        if pk.ring:
            live &= pos >= pk.seq_lens[row] \
                - pk.block_tables.shape[1] * B
        bidx = jnp.where(live,
                         pk.block_tables[row, pk.table_entry(pos // B)],
                         garbage_block)
        boff = pos % B
        return pk.scatter(bidx, boff, k, v, sel=0)


def paged_chunk_rows(pk: PagedKV, pool=None):
    """Row 0's cached rows in order, [1, M*B, heads, width] of ``pool``
    (``kp`` unless given): what a prompt chunk attends over after its
    own rows were written. Only the row's own pages are gathered and
    split into heads."""
    rows = (pk.kp if pool is None else pool)[pk.block_tables[0]]
    return pk.split(rows.reshape(1, -1, rows.shape[-1]))


# a masked score: finite, so that a row of the online softmax that has
# seen no key yet has a maximum to subtract
_MASKED = -1e30


def _window_scope(name: str, ring: bool) -> str:
    """obs.TICK_SCOPES: a band-keeping layer's attention has a scope of
    its own beside the whole-context layers' (``attn_window`` beside
    ``attn``, ``chunk_attn_window`` beside ``chunk_attn``)."""
    return name + "_window" if ring else name


# Pages one step of a prompt chunk's attention gathers and scores: 32
# pages of 16 tokens are 512 positions, [heads, chunk, 512] float32
# scores whatever the slot's length
CHUNK_RUN_PAGES = 32


def chunk_attention_positions(cached: int, table_blocks: int,
                              block_size: int, ring: bool = False,
                              tiles=None, window: Optional[int] = None):
    """(positions scored, positions live) of ONE layer's
    ``paged_chunk_attention`` call whose row holds ``cached`` tokens once
    the chunk's own are written, over a table of ``table_blocks`` pages:
    the host's arithmetic of the paths below (plain ints), for the
    engine's counters.

    The walk (``tiles`` None): a whole-context layer scores the runs of
    pages up to the one that holds the row's last token; a band-keeping
    layer (``ring``) every run of its ring. Live is what of the row the
    table holds.

    The kernel (``tiles`` = (the chunk's first position, its length),
    ``window`` the layer's): every tile of the chunk's queries scores
    the compute blocks from the one its first query's window starts in
    to the one that holds its last query, and what is live to it is the
    span of positions its queries see; both are the mean over the
    chunk's tiles (the key positions a query of the chunk was scored
    against, as the walk's are)."""
    if tiles is not None:
        from .pallas.ragged_paged_attention import chunk_tiling
        first, s = tiles
        tq, pps = chunk_tiling(s, block_size, table_blocks, window)
        n, scored, live = -(-s // tq), 0, 0
        for at in range(first, first + n * tq, tq):
            if at >= cached:    # a tile of pads walks nothing
                continue
            lo = 0 if window is None else max(at - window + 1, 0)
            hi = -(-min(at + tq, cached) // block_size)
            if not ring:
                hi = min(hi, table_blocks)
            blocks = -(-(hi - lo // block_size) // pps)
            scored += blocks * pps * block_size
            live += max(min(at + tq, cached, hi * block_size) - lo, 0)
        return scored // n, live // n
    run = min(table_blocks, CHUNK_RUN_PAGES)
    runs = -(-table_blocks // run)
    live = min(cached, table_blocks * block_size)
    if not ring:
        runs = min(-(-cached // (run * block_size)), runs)
    return runs * run * block_size, live


@functools.partial(jax.jit, inline=True, static_argnames=("window",))
def paged_chunk_attention(q, pk: PagedKV, positions,
                          window: Optional[int] = None, sink=None):
    """Chunked-prefill attention: q [1, s, h, d] chunk queries at global
    positions [1, s] (``positions[0, 0]`` onwards, in order) attend over
    row 0's blocks: the previously cached chunks AND (causally) this
    chunk's own tokens, which ``paged_prefill_write`` scattered in just
    before. Stale or never-written table positions sit beyond every
    query's position (or in unallocated garbage-block slots) and are
    masked by the causal compare. ``sink`` [h] (see ``dense_attention``)
    is the softmax's start. One compiled program whatever is live.

    Where ``chunk_attn_route`` says "kernel", ONE Pallas kernel a layer
    (``ops.pallas.ragged_paged_attention.chunk_paged_attention_pallas``):
    tiles of the chunk's queries, each over the key blocks it can see,
    the scores in VMEM. Else ``paged_chunk_attention_walk``. What
    either lays out around itself (the walk's transposes, the kernel's
    one copy of q) is inside the scope."""
    with jax.named_scope(_window_scope("chunk_attn", pk.ring)):
        if chunk_attn_route(q, pk.kp, pk.heads) == "walk":
            return paged_chunk_attention_walk(q, pk, positions, window,
                                              sink)
        from .pallas.ragged_paged_attention import \
            chunk_paged_attention_pallas
        return chunk_paged_attention_pallas(
            q[0], pk.kp, pk.vp, pk.block_tables[0], positions[0, 0],
            pk.seq_lens[0], pk.heads, window=window, sink=sink,
            ring=pk.ring)[None]


def paged_chunk_attention_walk(q, pk: PagedKV, positions,
                               window: Optional[int] = None, sink=None):
    """``paged_chunk_attention`` by an online softmax over RUNS of
    ``CHUNK_RUN_PAGES`` pages, in XLA ops: each step gathers one run of
    the row's table, scores it ([h, s, run] float32, never the slot's
    length) and folds it into the running maximum, denominator and
    value sum, so the work follows the context that is LIVE behind the
    chunk: the loop ends at the run that holds the row's last token
    (``pk.seq_lens[0]``) and, under a ``window`` over a whole table,
    starts at the first run the chunk's first query still reaches. A
    band-keeping layer (``pk.ring``) walks its ring, the band behind the
    chunk and the chunk, not the row's whole table. It is the path where
    the kernel does not serve (the CPU, key heads of 192 columns) and
    the reference a test or a timing script pins the kernel against, by
    calling it (inside the caller's scope)."""
    B, M = pk.block_size, pk.block_tables.shape[1]
    P = min(M, CHUNK_RUN_PAGES)
    runs, T = -(-M // P), P * B
    s, h = q.shape[1], q.shape[2]
    g = h // pk.heads
    scale = q.shape[-1] ** -0.5
    table = jnp.pad(pk.block_tables[0], (0, runs * P - M))
    kpos = _table_positions(pk, pk.seq_lens[:1] - 1)[0] if pk.ring \
        else jnp.arange(M * B)
    kpos = jnp.pad(kpos, (0, runs * T - M * B), constant_values=-1)
    qpos = positions[0][:, None]                        # [s, 1]
    qg = jnp.moveaxis(q[0].reshape(s, pk.heads, g, -1), 0, 2)

    def run(j, carry):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(table, j * P, P)
        ks = pk.split(pk.kp[pages].reshape(T, -1))      # [T, kvh, d]
        vs = pk.split(pk.vp[pages].reshape(T, -1))
        at = jax.lax.dynamic_slice_in_dim(kpos, j * T, T)[None, :]
        keep = (at <= qpos) & (at >= 0)                 # [s, T]
        if window is not None:
            keep &= qpos - at < window
        sc = jnp.einsum("kgsd,tkd->kgst", qg, ks).astype(jnp.float32) \
            * scale
        sc = jnp.where(keep, sc, _MASKED)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.where(keep, jnp.exp(sc - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "kgst,tkd->kgsd", p.astype(q.dtype), vs,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    shape = (pk.heads, g, s)
    if sink is None:
        m0 = jnp.full(shape, _MASKED, jnp.float32)
        l0 = jnp.zeros(shape, jnp.float32)
    else:       # one more key of that score whose value is zero
        m0 = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(pk.heads, g, 1), shape)
        l0 = jnp.ones(shape, jnp.float32)
    acc0 = jnp.zeros(shape + (pk.vp.shape[2] // pk.heads,), jnp.float32)
    if pk.ring:
        lo, hi = 0, runs
    else:
        hi = jnp.minimum(-(-pk.seq_lens[0] // T), runs)
        lo = 0 if window is None else \
            jnp.clip(positions[0, 0] - window + 1, 0) // T
    _, l, acc = jax.lax.fori_loop(lo, hi, run, (m0, l0, acc0))
    # a query with no key to see (a pad past the band) reads zero
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 2, 0).reshape(1, s, h, -1).astype(q.dtype)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("window", "band"))
def paged_packed_attention(q, k, v, segment_ids,
                           window: Optional[int] = None, sink=None,
                           band: bool = False):
    """Attention of a PACKED prefill call: q/k/v [1, s, h, d] are the
    call's own freshly computed rows, several prompts side by side,
    each from its position 0 with nothing cached behind it, so nothing
    is gathered from a pool. A query sees the keys of its own prompt at
    or before it: prompts are contiguous in the call, so that is causal
    over the call's index within one segment (``segment_ids`` [1, s]),
    and a window counts the same way. Dense, as every chunk's attention
    is: the scores are [h, s, s] over one chunk, a fraction of what the
    gather over a row's whole table scores. ``band`` names the scope of
    a band-keeping layer's call (``_window_scope``)."""
    with jax.named_scope(_window_scope("chunk_attn", band)):
        return dense_attention(q, k, v, causal=True, window=window,
                               attn_mask=segment_mask(segment_ids),
                               sink=sink)


def paged_decode_route(q, kp, kv_heads: int) -> str:
    """Which attention path ``paged_decode_attention`` and
    ``paged_latent_attention`` take for q [R, T, h, d] against pools
    shaped like ``kp`` [P, B, kv_heads*d]: ``"ragged"`` (the Pallas
    kernel that walks each row's own pages) or ``"dense"`` (the XLA
    whole-table gather). Shapes and the platform decide
    (``use_ragged_kernel`` is the one gate) and nothing else does, so a
    caller can ask with the engine's geometry (``PagedEngine.
    decode_route``) and see the choice the traced program made. On the
    chip a pool of one kv head x 64 columns takes the dense gather: its
    page is not a whole 128-lane tile, and the grid-per-row kernel that
    used to serve that one geometry is gone (no model or cell has it)."""
    from .pallas.ragged_paged_attention import use_ragged_kernel
    return "ragged" if use_ragged_kernel(q, kp, kv_heads) else "dense"


def chunk_attn_route(q, kp, kv_heads: int) -> str:
    """Which path ``paged_chunk_attention`` takes for a prompt chunk's
    queries q [1, s, h, d] against pools shaped like ``kp`` [P, B,
    kv_heads*d]: ``"kernel"`` (the Pallas kernel over tiles of the
    queries, each walking the pages it can see) or ``"walk"`` (the
    online softmax over runs of pages in XLA ops).
    ``paged_decode_route``'s sibling: shapes, dtype and the platform
    decide (``use_chunk_kernel`` is the one gate), so the engine can ask
    with its own geometry and count the choice the traced program
    made."""
    from .pallas.ragged_paged_attention import use_chunk_kernel
    return "kernel" if use_chunk_kernel(q, kp, kv_heads) else "walk"


def state_step_route(S) -> str:
    """Which path a linear-attention layer's decode step
    (``ops.delta_rule.delta_state_step``) takes over a stored state
    shaped like ``S`` [R, G, dk, L]: ``"kernel"`` (the Pallas kernel
    that reads each slot's state once) or ``"fusions"`` (the jnp body's
    two passes). ``paged_decode_route``'s sibling: shapes, dtype and the
    platform decide (``use_state_kernel`` is the one gate), so the
    engine can ask with its own arrays' geometry and count the choice
    the traced program made."""
    from .pallas.delta_state import use_state_kernel
    return "kernel" if use_state_kernel(S) else "fusions"


def chunk_rule_route(q, v, g) -> str:
    """Which path a linear-attention layer's prompt chunk
    (``ops.delta_rule.gated_delta_chunk``) takes over q [T, H, dk], v
    [T, H, dv] and a log-decay ``g``: ``"kernel"`` (the Pallas kernel
    that keeps a chunk's intermediates in VMEM; a decay a key channel at
    whole lane tiles) or ``"fusions"`` (the jnp bodies). ``state_step_
    route``'s sibling: it asks what ``gated_delta_chunk`` asks
    (``chunk_rule_kernel``; shapes, dtype and the platform decide)."""
    return "kernel" if chunk_rule_kernel(q, v, g) else "fusions"


def chunk_experts_route(xt, w_gate) -> str:
    """Which path an expert layer's held part
    (``parallel.moe.ExpertShareMLP.routed``) takes for a prompt call's
    positions xt [T, h] over stacked weights ``w_gate`` [n, h, m]:
    ``"grouped"`` (the product over the (position, expert) pairs sorted
    by expert, ``ops.pallas.expert_mlp.grouped_expert_mlp_pallas``),
    ``"hit_list"`` (a call of no more positions than a tick has rows:
    the tick's kernel) or ``"einsums"`` (every position through every
    held expert). It asks what ``routed`` asks (``use_grouped_kernel``,
    ``use_expert_kernel``; shapes and the platform decide)."""
    if expert_mlp.use_grouped_kernel(xt, w_gate):
        return "grouped"
    return "hit_list" if expert_mlp.use_expert_kernel(xt, w_gate) \
        else "einsums"


def _row_positions(pk: PagedKV, T: int, Tk: int):
    """(key positions [1, 1, Tk], query positions [R, T, 1]): query t of
    row r sits at ``seq_lens[r] + t``."""
    return (jnp.arange(Tk)[None, None, :],
            pk.seq_lens[:, None, None] + jnp.arange(T)[None, :, None])


def paged_decode_attention_dense(q, pk: PagedKV,
                                 scale: Optional[float] = None,
                                 window: Optional[int] = None, sink=None):
    """``paged_decode_attention`` by the dense whole-table gather: every
    row gathers all M of its table's pages and masks by position. The
    math is dense_attention's; only the gather and the per-(row,
    position) mask live here. It is the fallback where the kernel does
    not serve (the CPU, odd shapes) and the reference a test or a
    timing script compares the kernel with, by calling it."""
    R, T = q.shape[0], q.shape[1]
    # the heads come apart in the rows GATHERED, not in the pool
    ks = pk.split(pk.kp[pk.block_tables])        # [R, M, B, kvh, d]
    vs = pk.split(pk.vp[pk.block_tables])
    Tk = ks.shape[1] * ks.shape[2]
    ks = ks.reshape((R, Tk) + ks.shape[3:])
    vs = vs.reshape((R, Tk) + vs.shape[3:])
    kpos, qpos = _row_positions(pk, T, Tk)
    keep = kpos <= qpos                                   # [R, T, Tk]
    if pk.ring:     # what each ring entry holds after this step's write
        kpos = _table_positions(pk, pk.seq_lens + T - 1)[:, None, :]
        keep = (kpos <= qpos) & (kpos >= 0)
    if window is not None:
        keep &= kpos > qpos - window
    return dense_attention(q, ks, vs, attn_mask=keep[:, None],
                           scale=scale, sink=sink)


def _attend_ragged(q, pk: PagedKV, vp, scale: float, **kw):
    """The ragged kernel over q [R, T, h, d] (its single-query form
    takes [R, h, d]) and ``pk.kp`` with ``vp`` (None: a latent pool)."""
    from .pallas.ragged_paged_attention import \
        ragged_paged_attention_pallas
    T = q.shape[1]
    out = ragged_paged_attention_pallas(
        q if T > 1 else q[:, 0], pk.kp, vp, pk.block_tables, pk.seq_lens,
        scale, pk.heads, **kw)
    return out if T > 1 else out[:, None]


def paged_decode_attention(q, pk: PagedKV, scale: Optional[float] = None,
                           window: Optional[int] = None, sink=None):
    """q [R, T, h, d] against each row's blocks: query t of row r sits
    at position seq_lens[r] + t and attends tokens 0..seq_lens[r]+t
    (inclusive of the tokens written this step). T == 1 is the plain
    decode tick; T > 1 is the speculative verify's multi-query rows
    (ISSUE 7) — per-position causal masking inside the row.

    The ragged kernel — one step per row, which walks that row's LIVE
    pages, a run of them per compute block, all kv heads at once —
    serves both; where ``paged_decode_route`` says it does not,
    ``paged_decode_attention_dense`` does. The values may be narrower
    than the keys (returns [R, T, h, d_v]); ``sink`` [h] joins each
    head's softmax denominator; a band-keeping layer's ``pk.ring`` table
    is walked as the ring it is, under a scope of its own."""
    with jax.named_scope(_window_scope("attn", pk.ring)):
        if paged_decode_route(q, pk.kp, pk.heads) == "dense":
            return paged_decode_attention_dense(q, pk, scale, window, sink)
        return _attend_ragged(
            q, pk, pk.vp, scale if scale is not None else pk.width ** -0.5,
            window=window, sink=sink, ring=pk.ring)


def write_and_attend(pk: PagedKV, q, k, v, positions, segment_ids=None,
                     window: Optional[int] = None, sink=None):
    """What a K/V layer does with its view in whichever call
    (``pk.call``) it is in: this call's fresh rows k, v [b, s, kvh, d]
    written into the view, then q [b, s, h, d] at ``positions`` [b, s]
    attended. Returns (out [b, s, h, d_v], the view with the rows in).
    ``window`` and ``sink`` are the layer's own; ``segment_ids`` [1, s]
    a packed call's. A decode row attends over its blocks, the new rows
    included; a packed call's and a whole prompt's queries see the
    call's own rows alone (cast to the pool's type, as a later read
    would find them, in the packed call); a chunk's see what its slot
    has cached and the chunk itself."""
    if pk.call == "decode":
        new = paged_decode_write(pk, k, v)
        out = paged_decode_attention(q, new, window=window, sink=sink)
    elif pk.call == "packed":
        new = paged_prefill_write(pk, k, v, positions=positions[0],
                                  segments=segment_ids[0])
        out = paged_packed_attention(
            q, k.astype(pk.kp.dtype), v.astype(pk.vp.dtype), segment_ids,
            window=window, sink=sink, band=pk.ring)
    elif pk.call == "chunk":
        new = paged_prefill_write(pk, k, v, positions=positions[0])
        out = paged_chunk_attention(q, new, positions, window=window,
                                    sink=sink)
    else:                       # "prompt"
        new = paged_prefill_write(pk, k, v)
        out = dense_attention(q, k, v, causal=True, window=window,
                              sink=sink)
    return out, new


def paged_latent_attention_dense(q, pk: PagedKV, v_width: int,
                                 scale: float):
    """``paged_latent_attention`` by the dense whole-table gather:
    fallback and reference, as ``paged_decode_attention_dense``."""
    R, T = q.shape[0], q.shape[1]
    # every head reads the one row: no per-head copy of the keys
    ks = pk.kp[pk.block_tables]                  # [R, M, B, W]
    ks = ks.reshape(R, -1, ks.shape[-1])
    kpos, qpos = _row_positions(pk, T, ks.shape[1])
    scores = jnp.einsum("rthw,rkw->rhtk", q, ks).astype(jnp.float32) \
        * scale
    scores = jnp.where((kpos <= qpos)[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("rhtk,rkv->rthv", probs, ks[..., :v_width])


def paged_latent_attention(q, pk: PagedKV, v_width: int, scale: float):
    """Absorbed latent attention of decode (T == 1) and verify (T > 1)
    rows against a latent pool: q [R, T, h, W] in the pool's own
    columns (the queries folded through ``W_uk``, the roped part, zeros
    over the padding), keys the pool's rows, values their first
    ``v_width`` columns. Returns [R, T, h, v_width], still latent. The
    ragged kernel walks each row's live pages once; the fallback is
    ``paged_latent_attention_dense``."""
    with jax.named_scope("attn"):           # obs.TICK_SCOPES
        if paged_decode_route(q, pk.kp, pk.heads) == "dense":
            return paged_latent_attention_dense(q, pk, v_width, scale)
        return _attend_ragged(q, pk, None, scale, v_width=v_width)

