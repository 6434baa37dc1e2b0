"""Ragged paged-attention Pallas kernel (reference: PAPERS.md "Ragged
Paged Attention" and jax's own `paged_attention` kernel — the kernel
gathers a row's pages itself, a run of them per compute step).

One grid step per ROW; nothing is sized by ``R*M``. Inside a step the
row walks its own block table to its own length:

- ``block_tables`` [R, M] and ``seq_lens`` [R] ride scalar prefetch
  (SMEM). A row's first and last live page come from its length (and the
  sliding window, and ``q_len`` for multi-query rows); the number of
  compute blocks is a ``lax.fori_loop`` trip count read from them. An
  empty slot walks one page, a full table walks M. No schedule is built
  outside the kernel, and rows that share prefix blocks simply name the
  same physical page in their own tables.
- the pools stay in HBM (``pl.ANY``). A compute block is
  ``pages_per_step`` pages (`_pages_per_step`: about 256 tokens, inside a
  VMEM budget); the kernel issues one ``make_async_copy`` per LIVE page
  of K and of V into a double-buffered VMEM scratch and computes block
  ``i`` while block ``i+1`` lands. A page arrives once, as ``(B,
  kvh*d)``, for all kv heads.
- the kv heads are a static inner loop over column slices of that
  buffer. GQA rides the matmul M dim: q is viewed [R, kvh, group (padded
  to 8), d]; the fp32 online softmax (running max, sum, accumulator per
  head) is carried through the loop and written once per row.
- tokens of a block past the row's length (the rest of its last page,
  pages not fetched) are masked by position. Their K may be anything;
  their V meets a zero weight, so it only has to be finite: the V
  scratch is zeroed at the first row and afterwards holds pool data.

Pool layout: the pools ARE [P, B, kvh*d] (``generation/paged.py``
allocates, writes and keeps them so), a page one contiguous ``(B,
kvh*d)`` slab, and the caller says how many kv heads share a row. The
kernel's operand is the pool itself: viewing a [P, B, kvh, d] pool this
way changed its tiling on the chip, a copy of the whole pool a layer
and tick (tests/test_chip_compile.py keeps it gone).

Latent mode (``vp`` None, ``v_width`` given): absorbed multi-head latent
attention. The pool holds ONE row a token, ``[P, B, W]`` (the
compressed latent, the shared rope key, zero padding up to a multiple of
128 lanes), which every query head reads: one kv "head", the query heads
its group. The values are the first ``v_width`` columns of the keys, so
the kernel keeps no V scratch and a page is fetched once.

Three more things a call may say (MiMo-V2's two layer kinds; a call
that says none of them lowers to what it lowered to before):

- the value heads may be narrower than the key heads (``vp`` [P, B,
  kvh*d_v]); a key head of 192 columns, one and a half lane tiles, is
  read as the ALIGNED 256-column span of the page that holds it, and the
  query arrives padded with zeros over the span's other 64 columns (they
  belong to the neighbouring head): no unaligned slice, no padding in
  the pool, 64 columns more in each score product.
- ``sink`` [h]: one learned score a query head that joins the softmax's
  denominator and carries no value. It is the online softmax's START:
  running max ``sink``, sum 1, accumulator 0 is the state after one key
  of that score and a zero value.
- ``ring``: the table is a RING of M pages over the row's logical
  blocks (a layer that keeps only its window's band: logical block b
  lives in ``table[r, b % M]``); positions still count from the
  sequence's start.

A PROMPT CHUNK's queries (``chunk_paged_attention_pallas``, ISSUE 48)
ride the same page walk in a sibling kernel: one grid step per TILE of
the chunk's queries over ONE row's table, each tile from the block its
first query's window starts in to the block of its last query. What a
tile of hundreds of rows a kv head changes is kept beside the shared
parts: the accumulators in VMEM scratch, the mask only where a block
straddles the diagonal, the band's edge or the row's end, the
cross-lane work of the softmax cut to one maximum a block
(``_fold_tile``), and q and the result read and written as they lie.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_enabled as _interpret

NEG_INF = -1e30

# K and V compute blocks, two of each in flight
_VMEM_BUDGET = 4 << 20
_BLOCK_TOKENS = 256


def use_ragged_kernel(q, kp, kv_heads: int) -> bool:
    """Whether this kernel serves q [R, T, h, d] (T == 1 or the
    speculative verify's multi-query rows) against a pool ``kp`` [P, B,
    kv_heads*d]; ``ops/paged_cache.py:paged_decode_route`` sends every
    other shape to the dense gather. The policy of the other kernels: a
    TPU backend, or the interpreter so that CI drives the dispatch glue
    (it takes any shape with whole query-head groups). On the chip, an
    8-sublane-aligned block size; a page that can be fetched as one
    ``(B, kv_heads*d)`` slab, which Mosaic slices from HBM in whole
    128-lane tiles (one kv head of 64 columns cannot); and heads of 128
    or 256 columns, or one head of any whole number of tiles (a latent
    pool: one wide row a token), or an even number of heads of 192 (each
    head then lies inside an aligned 256-column span of the page)."""
    from . import kernels_enabled
    h, d = q.shape[2:]
    if h % kv_heads or not kernels_enabled():
        return False
    if _interpret():
        return True
    if kp.shape[1] % 8 or kp.shape[2] % 128:
        return False
    if d == 192:
        return kv_heads % 2 == 0
    return d % 128 == 0 and (kv_heads == 1 or d in (128, 256))


def _pages_per_step(B: int, width: int, itemsize: int, M: int) -> int:
    """Pages of ``B`` tokens x ``width`` = kvh*d columns in one compute
    block: about ``_BLOCK_TOKENS`` tokens, halved until K and V, double
    buffered, fit ``_VMEM_BUDGET``; never more than a row's table."""
    pps = max(1, _BLOCK_TOKENS // B)
    while pps > 1 and 4 * pps * B * width * itemsize > _VMEM_BUDGET:
        pps //= 2
    return min(pps, M)


def _key_span(h: int, d_k: int, dq: int) -> slice:
    """The columns of a page that kv head ``h``'s query is multiplied
    with: the head's own ``d_k`` where that is whole lane tiles, else
    the aligned ``dq``-column span that contains it."""
    start = h * d_k // 128 * 128 if dq != d_k else h * d_k
    return slice(start, start + dq)


def _for_each_live_page(entry, pools, bufs, sems, slot, first, live,
                        bs, fn):
    """fn(copy) over the K and V copies of the ``live`` pages of one
    compute block whose first page is logical block ``first`` (``entry``
    reads a table entry: the physical page of a logical block); the same
    descriptors start a copy and wait for it. The ONE page walk of the
    decode kernel and the chunk kernel."""
    def page(j, carry):
        phys = entry(first + j)
        dst = pl.ds(pl.multiple_of(j * bs, bs), bs)
        for n, (hbm, buf) in enumerate(zip(pools, bufs)):
            fn(pltpu.make_async_copy(hbm.at[phys], buf.at[slot, dst],
                                     sems.at[slot, n]))
        return carry
    lax.fori_loop(0, live, page, 0)


def _scores(q, k, keep, scale):
    """float32 scores of q [rows, d] against keys k [tc, d], masked
    where ``keep`` [rows, tc] is False (None: every key is seen)."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    return s if keep is None else jnp.where(keep, s, NEG_INF)


def _fold_block(q, k, v, keep, scale, m_prev, l_prev, acc):
    """One compute block folded into a head's online softmax: the
    scores of q against keys k, unnormalised probabilities cast to the
    type of the values v [tc, dv] for ``p @ v``. Returns the new
    (running max, denominator, value sum)."""
    s = _scores(q, k, keep, scale)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    return (m_new,
            alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True),
            acc * alpha + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))


def _softmax_start(sink_ref, h, rows, dv, lanes=1):
    """A head's online softmax before any key: (max, denominator, value
    sum), the first two ``lanes`` wide. With a sink, the state after one
    key whose score is the sink and whose value is zero."""
    acc = jnp.zeros((rows, dv), jnp.float32)
    if sink_ref is None:
        return (jnp.full((rows, lanes), NEG_INF, jnp.float32),
                jnp.zeros((rows, lanes), jnp.float32), acc)
    if lanes == 1:
        return sink_ref[h][:, :1], jnp.ones((rows, 1), jnp.float32), acc
    # (one key: its weight is in the denominator's first lane alone)
    one = lax.broadcasted_iota(jnp.int32, (rows, lanes), 1) == 0
    return sink_ref[h][:, :lanes], one.astype(jnp.float32), acc


def _fold_tile(q, k, v, keep, scale, m_prev, l_prev, acc):
    """``_fold_block`` for a tile of hundreds of rows, where the
    cross-lane work is what the schedule is made of: the running max is
    kept REPLICATED over ``lanes`` = m_prev.shape[1] lanes (so the
    subtraction and ``alpha`` are elementwise, with no broadcast), and
    the denominator stays a partial sum a lane (the block's
    probabilities added lane tile on lane tile) that the caller sums
    across lanes ONCE, at the tile's end. One cross-lane max a block is
    what is left. k is whole lane tiles of keys (``chunk_tiling``)."""
    lanes = m_prev.shape[1]
    s = _scores(q, k, keep, scale)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    ps = [jnp.exp(s[:, j:j + lanes] - m_new)
          for j in range(0, k.shape[0], lanes)]
    a = alpha if acc.shape[1] == lanes else alpha[:, :1]
    return (m_new, alpha * l_prev + functools.reduce(jnp.add, ps),
            acc * a + lax.dot_general(
                jnp.concatenate(ps, axis=1).astype(v.dtype), v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))


def _ragged_kernel(tbl_ref, len_ref, q_ref, *refs, scale, bs, pps, window,
                   group, q_len, v_width, d_k=None, ring=False,
                   sink=False):
    sink_ref = None
    if sink:
        sink_ref, *refs = refs
    if v_width is None:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems = refs
        pools, bufs = (k_hbm, v_hbm), (k_buf, v_buf)
    else:       # latent mode: values are a column prefix of the keys
        k_hbm, o_ref, k_buf, sems = refs
        v_buf = k_buf
        pools, bufs = (k_hbm,), (k_buf,)
    r = pl.program_id(0)
    M = tbl_ref.shape[1]
    kvh, gp, d = q_ref.shape[1:]
    d_k = d if d_k is None else d_k
    dv = o_ref.shape[-1]
    tc = pps * bs
    # query t of the row sits at seq_len + t: live pages cover the LAST
    # query's tokens, a sliding window's front follows the FIRST
    valid = len_ref[r] + 1
    hi = jnp.maximum((valid + q_len - 1 + bs - 1) // bs, 1)
    if not ring:            # a ring's logical blocks run past its pages
        hi = jnp.minimum(hi, M)
    lo = 0 if window is None else jnp.maximum(valid - window, 0) // bs
    n_blocks = (hi - lo + pps - 1) // pps

    @pl.when(r == 0)
    def _finite_v():
        v_buf[...] = jnp.zeros_like(v_buf)

    def for_each_live_page(i, slot, fn):
        first = lo + i * pps
        _for_each_live_page(
            lambda b: tbl_ref[r, b % M if ring else b], pools, bufs, sems,
            slot, first, jnp.minimum(hi - first, pps), bs, fn)

    for_each_live_page(0, 0, lambda c: c.start())

    def block(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            for_each_live_page(i + 1, 1 - slot, lambda c: c.start())

        for_each_live_page(i, slot, lambda c: c.wait())
        k_ids = lax.broadcasted_iota(jnp.int32, (gp, tc), 1) \
            + (lo + i * pps) * bs
        # multi-query rows: the q tile packs q_len positions x `group`
        # query heads, so sublane j belongs to verify position
        # t = j // group and attends causally up to seq_len + t. Padded
        # sublanes see a wider mask; the caller slices them off.
        t_of = lax.broadcasted_iota(jnp.int32, (gp, tc), 0) // group
        keep = k_ids < valid + t_of
        if window is not None:
            keep &= k_ids >= valid + t_of - window
        out = []
        for h, state in enumerate(carry):
            k = k_buf[slot, :, _key_span(h, d_k, d)]     # [tc, d]
            v = v_buf[slot, :, h * dv:(h + 1) * dv] if v_width is None \
                else k[:, :dv]
            out.append(_fold_block(q_ref[0, h], k, v, keep, scale, *state))
        return tuple(out)

    heads = lax.fori_loop(
        0, n_blocks, block,
        tuple(_softmax_start(sink_ref, h, gp, dv) for h in range(kvh)))
    for h, (_, l, acc) in enumerate(heads):
        o_ref[0, h] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def ragged_paged_attention_pallas(q, kp, vp, block_tables, seq_lens,
                                  scale, kv_heads, window=None,
                                  v_width=None, sink=None,
                                  ring: bool = False):
    """q [R, h, d] (single-query decode) OR [R, T, h, d] (multi-query
    speculative verify rows: query t of row r sits at position
    seq_lens[r] + t and attends tokens 0..seq_lens[r]+t); kp/vp
    [P, B, kv_heads*d] physical pools; block_tables [R, M]; seq_lens
    [R]. Returns q's shape. Latent mode: ``vp`` None and ``v_width`` the
    number of leading key columns that are the values; returns
    [..., h, v_width]. ``vp`` may hold narrower heads than ``kp``
    (returns [..., h, d_v]); ``sink`` [h] float32 joins each head's
    softmax denominator; ``ring`` reads ``block_tables`` [R, M] as a ring
    over each row's logical blocks (it needs a ``window`` no longer than
    the ring holds).

    Multi-query rides the same walk: the q tile packs T positions x
    `group` heads into the sublane dim (padded to 8), so each page is
    still read once per row — the verify's extra queries are matmul
    rows, not extra HBM traffic."""
    if (vp is None) != (v_width is not None):
        raise ValueError("latent mode takes vp=None AND v_width")
    if kp.ndim != 3 or kp.shape[2] != kv_heads * q.shape[-1]:
        raise ValueError(f"pool {kp.shape} is not [P, B, {kv_heads} kv "
                         f"heads x {q.shape[-1]} columns]")
    if ring and window is None:
        raise ValueError("a ring table holds a window's band: give window")
    return _attend(q, kp, vp, block_tables, seq_lens, sink,
                   scale=float(scale), kvh=int(kv_heads), window=window,
                   interpret=_interpret(), v_width=v_width, ring=bool(ring))


# jitted so that a program of L layers traces the kernel body once, not
# L times: set-up pays that on every start, warm compile cache or not.
# Inlined into the caller's jaxpr, so the lowered program and the names
# of its ops (obs.TICK_SCOPES) are what they are without the jit.
@functools.partial(jax.jit, inline=True,
                   static_argnames=("scale", "kvh", "window", "interpret",
                                    "v_width", "ring"))
def _attend(q, kp, vp, block_tables, seq_lens, sink=None, *, scale, kvh,
            window, interpret, v_width=None, ring=False):
    multi = q.ndim == 4
    if multi:
        R, T, h, d = q.shape
    else:
        R, h, d = q.shape
        T = 1
    B = kp.shape[1]
    M = block_tables.shape[1]
    latent = v_width is not None
    dv = v_width if latent else vp.shape[2] // kvh
    group = h // kvh
    rows = T * group
    gp = max(8, -(-rows // 8) * 8)
    pps = _pages_per_step(B, kvh * d, kp.dtype.itemsize, M)
    # a key head that is not whole lane tiles is read as the aligned
    # span of the page that holds it (`_key_span`), the query padded to
    # it, where every head lies in such a span (192 columns, an even
    # number of heads). Other odd widths reach here in interpret mode
    # alone (`use_ragged_kernel`) and are sliced as they are.
    dq = d if latent else -(-d // 128) * 128
    front = [hd * d - _key_span(hd, d, dq).start for hd in range(kvh)]
    if dq != d and (max(front) + d > dq
                    or _key_span(kvh - 1, d, dq).stop > kvh * d):
        dq = d
    if multi:
        # [R, T, kvh, group, d] -> [R, kvh, T*group, d]: position-major
        # sublanes so the kernel's t = sublane // group mapping holds
        qg = q.reshape(R, T, kvh, group, d).transpose(0, 2, 1, 3, 4) \
             .reshape(R, kvh, rows, d)
    else:
        qg = q.reshape(R, kvh, group, d)
    if gp != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - rows), (0, 0)))
    if dq != d:
        qg = jnp.stack([jnp.pad(qg[:, hd], ((0, 0), (0, 0),
                                            (f, dq - d - f)))
                        for hd, f in enumerate(front)], axis=1)

    kernel = functools.partial(_ragged_kernel, scale=scale, bs=B, pps=pps,
                               window=window, group=group, q_len=T,
                               v_width=v_width, d_k=d, ring=ring,
                               sink=sink is not None)
    pools = (kp,) if latent else (kp, vp)
    extra, extra_specs = (), []
    if sink is not None:
        # [h] -> a row a sublane of the q tile (position-major, as q),
        # broadcast over one lane tile
        sk = jnp.tile(sink.astype(jnp.float32).reshape(kvh, group), (1, T))
        sk = jnp.pad(sk, ((0, 0), (0, gp - rows)))
        extra = (jnp.broadcast_to(sk[:, :, None], (kvh, gp, 128)),)
        extra_specs = [pl.BlockSpec((kvh, gp, 128),
                                    lambda r, tbl, lens: (0, 0, 0))]
    out = pl.pallas_call(
        kernel,
        name="ragged_paged_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R,),
            in_specs=[pl.BlockSpec((1, kvh, gp, dq),
                                   lambda r, tbl, lens: (r, 0, 0, 0))]
            + extra_specs
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((1, kvh, gp, dv),
                                   lambda r, tbl, lens: (r, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, pps * B, p.shape[2]), p.dtype)
                            for p in pools]
            + [pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((R, kvh, gp, dv), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(seq_lens, jnp.int32),
      qg, *extra, *pools)
    out = out[:, :, :rows, :]
    if not multi:
        return out.reshape(R, h, dv)
    return out.reshape(R, kvh, T, group, dv).transpose(0, 2, 1, 3, 4) \
              .reshape(R, T, h, dv)


# ------------------------------------------------------ a prompt chunk
# Queries of one tile of a prompt chunk (x the group's heads: the rows
# of every product), the keys of one compute block (`chunk_tiling`) and
# the kv heads in one step of the loop over them (`_chunk_kernel.heads`)
_TILE_QUERIES = 128
_CHUNK_BLOCK_TOKENS = 512
_LANES = 128
_HEADS_A_STEP = 4
# what the chunk kernel's call states: q and the result double buffered,
# the float32 accumulators, two K and two V compute blocks and the
# scores of one head in flight (about 30 MiB at Laguna's 72 heads)
_CHUNK_VMEM_LIMIT = 64 << 20


def use_chunk_kernel(q, kp, kv_heads: int) -> bool:
    """Whether ``chunk_paged_attention_pallas`` serves a prompt chunk's
    queries q [1, s, h, d] against a pool ``kp`` [P, B, kv_heads*d];
    ``ops/paged_cache.py:chunk_attn_route`` sends every other shape to
    the walk over runs of pages. ``use_ragged_kernel``'s policy: a TPU
    backend, or the interpreter (any shape with whole query-head
    groups). On the chip: pages of whole sublane tiles, and key heads
    of 128 columns: a key head of 192 (MiMo-V2) stays on the walk."""
    from . import kernels_enabled
    h, d = q.shape[2:]
    if h % kv_heads or not kernels_enabled():
        return False
    if _interpret():
        return True
    return d == 128 and kp.shape[1] % 8 == 0 \
        and kp.shape[2] == kv_heads * d


def chunk_tiling(s: int, B: int, M: int, window=None):
    """(queries a tile, pages a compute block) of the chunk kernel for a
    chunk of ``s`` positions over a table of ``M`` pages of ``B``. A
    block is ``_CHUNK_BLOCK_TOKENS`` keys (the fewer steps of the loop
    over blocks the better, and what a tile scores past its diagonal is
    half a block of thousands); under a ``window`` a tile sees the band
    and itself, and a block of half the window keeps what is scored
    near that: 768 keys for the 639 a tile of 128 sees in a band of
    512. Always whole ``_LANES``-key tiles (``_fold_tile`` keeps a
    denominator a lane), and no more of them than cover the table: what
    of a block lies past the table is masked like what lies past the
    row's end."""
    tq = min(_TILE_QUERIES, -(-s // 8) * 8)
    tc = _CHUNK_BLOCK_TOKENS
    if window is not None:
        tc = min(tc, window // (2 * _LANES) * _LANES)
    unit = _LANES // math.gcd(B, _LANES)    # pages of one lane tile
    return tq, min(max(tc // (unit * B), 1), -(-M // unit)) * unit


def _chunk_kernel(tbl_ref, at_ref, q_ref, *refs, scale, bs, pps, window,
                  kvh, group, ring, sink):
    """One TILE of a prompt chunk's queries (grid step i: positions
    ``at[0] + i*tq ..`` of a row whose length is ``at[1]`` with the chunk
    written) over the key blocks the tile can see: what a multi-query
    row is to ``_ragged_kernel``, with the accumulators of every kv head
    in VMEM scratch (a tile's rows are ``tq * group``, not 8) and the
    position mask only in the blocks that need one. q and the result
    are blocks of the arrays as they lie, [tq, h*d]: a kv head's rows
    are its group's heads one after another, ``tq`` positions each
    (HEAD-major, where the decode kernel's few rows are position-major),
    so nothing is transposed around the call."""
    sink_ref = None
    if sink:
        sink_ref, *refs = refs
    k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref = refs
    i = pl.program_id(0)
    M = tbl_ref.shape[0]
    tq, rows = q_ref.shape[0], acc_ref.shape[1]
    d, dv = q_ref.shape[1] // (kvh * group), acc_ref.shape[2]
    tc = pps * bs
    first_q, end = at_ref[0] + i * tq, at_ref[1]
    # key blocks up to the one that holds the tile's last query (never
    # past the row's end), from the one the first query's window starts
    # in; a tile of pads past the row's end walks nothing
    lo = 0 if window is None \
        else jnp.maximum(first_q - window + 1, 0) // bs
    hi = (jnp.minimum(first_q + tq, end) + bs - 1) // bs
    if not ring:
        hi = jnp.minimum(hi, M)
    hi = jnp.where(first_q < end, jnp.maximum(hi, lo), lo)
    n_blocks = (hi - lo + pps - 1) // pps

    @pl.when(i == 0)
    def _finite():      # a masked key's value meets a zero weight
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    def cols(c, w):     # column block c of width w (whole lane tiles)
        return pl.ds(pl.multiple_of(c * w, 128) if w % 128 == 0 else c * w,
                     w)

    def heads(fn):
        """fn(h) for every kv head, ``_HEADS_A_STEP`` of them a step of
        a loop: a step's heads are straight-line code (the schedule
        hides one head's cross-lane work and result pops under the
        next's products; a head a step left 53% of the peak where eight
        reach 81%), and they are one body unrolled when the kernel is
        LOWERED, so every start traces one head, not ``kvh``."""
        u = max(u for u in range(1, _HEADS_A_STEP + 1) if kvh % u == 0)

        def step(i, carry):
            def one(j, c):
                fn(i * u + j)
                return c
            return lax.fori_loop(0, u, one, carry, unroll=True)
        lax.fori_loop(0, kvh // u, step, 0)

    lanes = m_ref.shape[-1]

    def start(h):
        m_ref[h], l_ref[h], acc_ref[h] = _softmax_start(sink_ref, h, rows,
                                                        dv, lanes)
    heads(start)

    def for_each_live_page(b, slot, fn):
        first = lo + b * pps
        _for_each_live_page(
            lambda p: tbl_ref[p % M if ring else p], (k_hbm, v_hbm),
            (k_buf, v_buf), sems, slot, first,
            jnp.minimum(hi - first, pps), bs, fn)

    for_each_live_page(0, 0, lambda c: c.start())
    # row j of a kv head's rows is position j % tq of the tile
    q_at = first_q + lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % tq

    def fold(b, slot, masked):
        keep = None
        if masked:
            k_at = (lo + b * pps) * bs \
                + lax.broadcasted_iota(jnp.int32, (1, tc), 1)
            keep = k_at <= jnp.minimum(q_at, end - 1)
            if window is not None:
                keep &= k_at > q_at - window

        def head(h):    # its group's query heads one under another
            q = jnp.concatenate([q_ref[:, cols(h * group + g, d)]
                                 for g in range(group)], axis=0)
            m_ref[h], l_ref[h], acc_ref[h] = _fold_tile(
                q, k_buf[slot, :, cols(h, d)], v_buf[slot, :, cols(h, dv)],
                keep, scale, m_ref[h], l_ref[h], acc_ref[h])
        heads(head)

    def block(b, carry):
        slot = b % 2

        @pl.when(b + 1 < n_blocks)
        def _prefetch():
            for_each_live_page(b + 1, 1 - slot, lambda c: c.start())

        for_each_live_page(b, slot, lambda c: c.wait())
        # no mask where every query of the tile sees every key of the
        # block: behind the first query, inside the row, and (a window)
        # not behind the LAST query's band
        first_k = (lo + b * pps) * bs
        seen = first_k + tc <= jnp.minimum(first_q + 1, end)
        if window is not None:
            seen &= first_k > first_q + tq - 1 - window
        lax.cond(seen, lambda: fold(b, slot, False),
                 lambda: fold(b, slot, True))
        return carry

    lax.fori_loop(0, n_blocks, block, 0)

    def finish(h):      # the denominator's lanes summed, once
        l = jnp.sum(l_ref[h], axis=-1, keepdims=True)
        out = (acc_ref[h] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        for g in range(group):
            o_ref[:, cols(h * group + g, dv)] = out[g * tq:(g + 1) * tq]
    heads(finish)


def chunk_paged_attention_pallas(q, kp, vp, table, first, end, kv_heads,
                                 window=None, sink=None,
                                 ring: bool = False):
    """A prompt chunk's attention over its row's pages, the chunk's own
    rows written: q [s, h, d] at positions ``first .. first+s-1`` of the
    row whose pages ``table`` [M] names and whose length is ``end``
    (queries at or past it are pads: they read zero or anything finite);
    kp/vp [P, B, kv_heads*d] / [P, B, kv_heads*d_v]. Returns [s, h,
    d_v]. ``window``, ``sink`` and ``ring`` as the decode kernel's.

    The queries are cut into tiles of ``chunk_tiling`` positions, a grid
    step each: a tile is a multi-query row of the decode kernel (``tq x
    group`` rows a kv head) that walks the same table from its own
    first position: up to the block of its last query, from the block
    its first query's window starts in. Scores, probabilities and the
    float32 sums stay in VMEM; q and the result are read and written
    where they lie."""
    if kp.ndim != 3 or kp.shape[2] != kv_heads * q.shape[-1]:
        raise ValueError(f"pool {kp.shape} is not [P, B, {kv_heads} kv "
                         f"heads x {q.shape[-1]} columns]")
    if ring and window is None:
        raise ValueError("a ring table holds a window's band: give window")
    return _attend_chunk(q, kp, vp, table, first, end, sink,
                         kvh=int(kv_heads), window=window,
                         interpret=_interpret(), ring=bool(ring))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("kvh", "window", "interpret", "ring"))
def _attend_chunk(q, kp, vp, table, first, end, sink=None, *, kvh, window,
                  interpret, ring=False):
    s, h, d = q.shape
    B, M = kp.shape[1], table.shape[0]
    dv = vp.shape[2] // kvh
    group = h // kvh
    tq, pps = chunk_tiling(s, B, M, window)
    tiles, rows = -(-s // tq), tq * group
    # q and the result as they lie, a tile of positions a block
    q2 = jnp.pad(q.reshape(s, h * d), ((0, tiles * tq - s), (0, 0)))
    kernel = functools.partial(_chunk_kernel, scale=d ** -0.5, bs=B,
                               pps=pps, window=window, kvh=kvh,
                               group=group, ring=ring,
                               sink=sink is not None)
    extra, extra_specs = (), []
    if sink is not None:    # a row a sublane of a kv head's rows
        sk = jnp.repeat(sink.astype(jnp.float32).reshape(kvh, group), tq,
                        axis=1)
        extra = (jnp.broadcast_to(sk[:, :, None], (kvh, rows, 128)),)
        extra_specs = [pl.BlockSpec((kvh, rows, 128),
                                    lambda i, tbl, at: (0, 0, 0))]
    out = pl.pallas_call(
        kernel,
        name="chunk_paged_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[pl.BlockSpec((tq, h * d), lambda i, tbl, at: (i, 0))]
            + extra_specs + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec((tq, h * dv), lambda i, tbl, at: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, pps * B, p.shape[2]), p.dtype)
                            for p in (kp, vp)]
            + [pltpu.SemaphoreType.DMA((2, 2)),
               # the running max and the denominator, a lane tile wide
               pltpu.VMEM((kvh, rows, _LANES), jnp.float32),
               pltpu.VMEM((kvh, rows, _LANES), jnp.float32),
               pltpu.VMEM((kvh, rows, dv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles * tq, h * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.asarray(table, jnp.int32),
      jnp.stack([first, end]).astype(jnp.int32), q2, *extra, kp, vp)
    return out[:s].reshape(s, h, dv)
