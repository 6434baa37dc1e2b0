"""Ragged paged-attention Pallas kernel (ISSUE 6 tentpole; reference:
PAPERS.md "Ragged Paged Attention" — ONE kernel over variable-length
requests with no per-request padding in the work schedule).

The grid-per-row kernel (`paged_attention.py`) runs a fixed ``(R, kvh,
M)`` grid: every row pays M grid steps whether it holds 1 live block or
M. Dead steps clamp their index maps (no copy, no compute), but they
still occupy the scalar core and fragment Mosaic's pipeline R times per
kv head. This kernel flattens the work into a single SCHEDULE of (row,
logical block) pairs, packed live-first:

- the schedule is computed from ``seq_lens``/``block_tables`` with jnp
  ops (cumsum + searchsorted over per-row live-block counts) INSIDE the
  caller's jit — in the fused decode tick it is traced once per program
  and XLA CSE-dedups it across layers. No host round-trip per tick.
- schedule capacity ``S`` is static ``R*M`` (every row's table can be
  fully live; a physical-pool bound would under-count when prefix
  caching shares blocks across rows — see ``schedule_capacity``). The
  live work is packed contiguous at the front, so the dead tail is ONE
  run of clamped (copy-free, predicated-off) steps instead of R of
  them.
- grid ``(kvh, S)``; the fp32 accumulator scratch carries the online
  softmax across a row's consecutive schedule steps; `first`/`last`
  steps of each row's run are detected from the prefetched schedule
  (init / finalize). The output index map repeats a row's index across
  its run, so Mosaic flushes each row's output exactly once.
- dead steps (s >= total live) clamp row/block to the last live step:
  the repeated index skips the HBM→VMEM copy and `@pl.when` skips the
  compute, so the tail costs only scalar-core index math.
- GQA rides the matmul M dim exactly like `paged_attention.py`: q is
  viewed [R, kvh, group(padded to 8), d], each KV block is read once
  per KV head. The pool is viewed [P, B, kvh*d] so KV blocks are
  (B, d) with the column block selecting the head — (8, 128)-tilable
  for the gated shapes.

Sliding windows schedule only the in-band blocks per row (the front
clamp moves into the schedule itself instead of the index map).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_enabled as _interpret

NEG_INF = -1e30


def schedule_capacity(R: int, M: int, P: int) -> int:
    """Static schedule length: every row can contribute up to M live
    LOGICAL blocks, so the schedule must hold R*M. A pool-derived bound
    (P-1 allocatable + one write block per row) would be tighter for
    block-constrained configs but is WRONG under prefix caching: shared
    physical blocks count once against the pool yet appear in every
    borrowing row's table, so the sum of logical live blocks can exceed
    any physical-pool bound — a truncated schedule cuts a row's run
    mid-stride and its output block is never finalized (garbage
    attention for that row and every row after it). The dead tail is
    copy-free and predicated off, so the R*M worst case costs only
    scalar-core index math per unused step."""
    del P
    return R * M


def build_schedule(block_tables, seq_lens, S: int, block_size: int,
                   window=None, q_len: int = 1):
    """Flattened live-first schedule. Returns int32 arrays
    (row[S], blk[S], live[S]) where (row, blk) index ``block_tables``
    and live flags steps < total. Dead steps repeat the LAST live step's
    (row, blk) so their block indices never change (copy-free). All jnp
    — traceable inside the decode tick's jit.

    ``q_len`` > 1 (ISSUE 7 multi-query verify rows): each row carries
    q_len query positions seq_len .. seq_len+q_len-1, so live blocks
    must cover the LAST query's window (lens + q_len attendable tokens)
    while a sliding window's front clamp follows the FIRST query."""
    R, M = block_tables.shape
    B = block_size
    lens = jnp.asarray(seq_lens, jnp.int32)
    valid = lens + q_len                              # attendable tokens
    nb = jnp.clip((valid + B - 1) // B, 1, M)         # last live block + 1
    if window is None:
        lo = jnp.zeros((R,), jnp.int32)
    else:
        lo = jnp.maximum(lens + 1 - window, 0) // B   # first in-band block
    cnt = nb - lo                                     # >= 1 per row
    cum = jnp.cumsum(cnt)
    total = cum[-1]
    starts = cum - cnt
    s = jnp.arange(S, dtype=jnp.int32)
    row = jnp.searchsorted(cum, s, side="right").astype(jnp.int32)
    rowc = jnp.clip(row, 0, R - 1)
    blk = lo[rowc] + (s - starts[rowc])
    live = s < total
    li = jnp.clip(total - 1, 0, S - 1)
    row_s = jnp.where(live, rowc, rowc[li])
    blk_s = jnp.where(live, blk, blk[li])
    return row_s, blk_s, live.astype(jnp.int32)


def _ragged_kernel(tbl_ref, len_ref, row_ref, blk_ref, live_ref,
                   q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr, *,
                   scale, bs, S, window, group):
    si = pl.program_id(1)
    r = row_ref[si]
    b = blk_ref[si]
    live = live_ref[si] == 1
    prv = jnp.maximum(si - 1, 0)
    nxt = jnp.minimum(si + 1, S - 1)
    prev_same = (si > 0) & (row_ref[prv] == r) & (live_ref[prv] == 1)
    next_same = (si < S - 1) & (row_ref[nxt] == r) & (live_ref[nxt] == 1)
    first = live & jnp.logical_not(prev_same)
    last = live & jnp.logical_not(next_same)

    @pl.when(first)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    @pl.when(live)
    def _compute():
        valid = len_ref[r] + 1          # tokens [0, seq_len] attendable
        q = q_ref[0, 0, :, :]                        # [gp, d]
        k = k_ref[0, :, :]                           # [bs, d]
        v = v_ref[0, :, :]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        gp = q.shape[0]
        k_ids = lax.broadcasted_iota(jnp.int32, (gp, bs), 1) + b * bs
        # multi-query rows (ISSUE 7): the q tile packs q_len positions x
        # `group` query heads, so sublane j belongs to verify position
        # t = j // group and attends causally up to seq_len + t. Single-
        # query calls have every real sublane at t == 0 — the original
        # mask; padded sublanes see a wider mask but their rows are
        # sliced off by the caller.
        t_of = lax.broadcasted_iota(jnp.int32, (gp, bs), 0) // group
        keep = k_ids < valid + t_of
        if window is not None:
            keep &= k_ids >= valid + t_of - window
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, :1] = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
        acc[:] = acc[:] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, :1] = m_new

    @pl.when(last)
    def _finalize():
        safe_l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0, :, :] = (acc[:] / safe_l).astype(o_ref.dtype)


def ragged_paged_attention_pallas(q, kp, vp, block_tables, seq_lens,
                                  scale, window=None):
    """q [R, h, d] (single-query decode) OR [R, T, h, d] (multi-query
    speculative verify rows, ISSUE 7: query t of row r sits at position
    seq_lens[r] + t and attends tokens 0..seq_lens[r]+t); kp/vp
    [P, B, kvh, d] physical pools; block_tables [R, M]; seq_lens [R].
    Returns q's shape.

    Multi-query rides the SAME (kvh, S) schedule grid: the q tile packs
    T positions x `group` heads into the sublane dim (padded to 8), so
    each KV block is still read once per kv head per row — the verify's
    extra queries are matmul rows, not extra HBM traffic."""
    multi = q.ndim == 4
    if multi:
        R, T, h, d = q.shape
    else:
        R, h, d = q.shape
        T = 1
    P, B, kvh, _ = kp.shape
    M = block_tables.shape[1]
    group = h // kvh
    rows = T * group
    gp = max(8, -(-rows // 8) * 8)
    S = schedule_capacity(R, M, P)

    if multi:
        # [R, T, kvh, group, d] -> [R, kvh, T*group, d]: position-major
        # sublanes so the kernel's t = sublane // group mapping holds
        qg = q.reshape(R, T, kvh, group, d).transpose(0, 2, 1, 3, 4) \
             .reshape(R, kvh, rows, d)
    else:
        qg = q.reshape(R, kvh, group, d)
    if gp != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - rows), (0, 0)))

    tbl = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)
    row_s, blk_s, live = build_schedule(tbl, lens, S, B, window=window,
                                        q_len=T)

    def q_index(ki, si, tbl, lens, row, blk, live):
        return (row[si], ki, 0, 0)

    def kv_index(ki, si, tbl, lens, row, blk, live):
        # dead steps carry the last live step's (row, blk): the repeated
        # physical index skips the copy
        return (tbl[row[si], blk[si]], 0, ki)

    kernel = functools.partial(_ragged_kernel, scale=scale, bs=B, S=S,
                               window=window, group=group)
    with jax.named_scope("kv_layout"):      # obs.TICK_SCOPES
        kc = kp.reshape(P, B, kvh * d)
        vc = vp.reshape(P, B, kvh * d)
    out = pl.pallas_call(
        kernel,
        name="ragged_paged_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(kvh, S),
            in_specs=[
                pl.BlockSpec((1, 1, gp, d), q_index),
                pl.BlockSpec((1, B, d), kv_index),
                pl.BlockSpec((1, B, d), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, gp, d), q_index),
            scratch_shapes=[
                pltpu.VMEM((gp, d), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, kvh, gp, d), q.dtype),
        interpret=_interpret(),
    )(tbl, lens, row_s, blk_s, live, qg, kc, vc)
    out = out[:, :, :rows, :]
    if not multi:
        return out.reshape(R, h, d)
    return out.reshape(R, kvh, T, group, d).transpose(0, 2, 1, 3, 4) \
              .reshape(R, T, h, d)
