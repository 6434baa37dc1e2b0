"""Pallas TPU paged-attention decode kernel (reference: PaddleNLP
block-attention predictor's fused block_multihead_attention kernel;
tiling discipline follows jax's paged_attention_kernel — scalar-prefetched
block tables driving the BlockSpec index map).

The dense fallback in ``generation/paged.py`` gathers the ENTIRE block
table (``kp[block_tables]`` → [R, M, B, kvh, d]) and attends over all
M·B positions every step — O(max_ctx) HBM traffic per row per token
regardless of the actual context. This kernel streams ONLY each row's
live blocks:

- ``block_tables`` [R, M] and ``seq_lens`` [R] ride scalar prefetch
  (SMEM), so the K/V BlockSpec index maps — which run on the scalar core
  ahead of the pipeline — translate (row, logical block) → physical pool
  block per grid step.
- grid (R, kvh, M) with the logical-block dim innermost; the fp32
  accumulator scratch carries the online softmax across a row's blocks.
- steps past a row's live block count are predicated off with
  ``@pl.when`` AND their index map CLAMPS to the last live block: Mosaic
  skips the HBM→VMEM copy when the computed block index repeats, so dead
  blocks cost neither FLOPs nor bandwidth. Sliding windows clamp the
  front the same way.
- GQA rides the matmul M dim: q is viewed [R, kvh, group, d] (group
  padded to the 8-sublane minimum) and each KV block is read once per
  KV head, never per query head.

Pool layout note: the pools are [P, B, kvh*d] (allocated and kept so,
``generation/paged.py``), so the last-two block dims (B, d) satisfy
Mosaic's (8, 128) tiling with the column block selecting the kv head,
the same trick as ``decode_attention.py``, and the pool is the kernel's
operand as it is.
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


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc, m_scr, l_scr, *, scale, bs, nm, gp, window):
    r = pl.program_id(0)
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    valid = len_ref[r] + 1          # tokens [0, seq_len] attendable
    run = ti * bs < valid
    if window is not None:          # skip blocks fully before the band
        run &= (ti + 1) * bs > valid - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0, :, :]                        # [gp, d]
        k = k_ref[0, :, :]                           # [bs, d]
        v = v_ref[0, :, :]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        k_ids = lax.broadcasted_iota(jnp.int32, (gp, bs), 1) + ti * bs
        keep = k_ids < valid
        if window is not None:
            keep &= k_ids >= valid - window
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

    @pl.when(ti == nm - 1)
    def _finalize():
        safe_l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0, :, :] = (acc[:] / safe_l).astype(o_ref.dtype)


def paged_attention_pallas(q, kp, vp, block_tables, seq_lens, scale,
                           kv_heads, window=None):
    """q [R, h, d]; kp/vp [P, B, kv_heads*d] physical pools;
    block_tables [R, M]; seq_lens [R] (position written this step —
    tokens 0..seq_lens[r] attend). Returns [R, h, d]."""
    R, h, d = q.shape
    kvh, B = kv_heads, kp.shape[1]
    if kp.ndim != 3 or kp.shape[2] != kvh * d:
        raise ValueError(f"pool {kp.shape} is not [P, B, {kvh} kv heads "
                         f"x {d} columns]")
    M = block_tables.shape[1]
    group = h // kvh
    gp = max(8, -(-group // 8) * 8)

    qg = q.reshape(R, kvh, group, d)
    if gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))

    tbl = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)

    def kv_index(r, ki, ti, tbl, lens):
        # clamp dead steps to the last live block (and pre-window steps
        # to the first in-band block): a repeated index skips the copy
        valid = lens[r] + 1
        last = jnp.maximum(lax.div(valid + B - 1, B) - 1, 0)
        lo = 0 if window is None else lax.div(
            jnp.maximum(valid - window, 0), B)
        i_eff = jnp.clip(ti, lo, last)
        return (tbl[r, i_eff], 0, ki)

    kernel = functools.partial(_paged_kernel, scale=scale, bs=B, nm=M,
                               gp=gp, window=window)
    out = pl.pallas_call(
        kernel,
        name="paged_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, kvh, M),
            in_specs=[
                pl.BlockSpec((1, 1, gp, d),
                             lambda r, ki, ti, tbl, lens: (r, ki, 0, 0)),
                pl.BlockSpec((1, B, d), kv_index),
                pl.BlockSpec((1, B, d), kv_index),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, gp, d), lambda r, ki, ti, tbl, lens: (r, ki, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((gp, d), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, kvh, gp, d), q.dtype),
        interpret=_interpret(),
    )(tbl, lens, qg, kp, vp)
    return out[:, :, :group, :].reshape(R, h, d)


def use_paged_kernel(q, kp, kv_heads: int) -> bool:
    """Same gating policy as the other kernels: TPU backend (or interpret
    mode so CI drives the dispatch glue), MXU-friendly head_dim, whole
    query-head groups, 8-sublane-aligned block_size. ``s > 1`` (the
    speculative verify's multi-query rows, ISSUE 7) is gated the same
    way — only the ragged kernel serves it; the grid-per-row kernel
    stays single-query (its caller falls back to dense)."""
    from . import interpret_enabled, kernels_enabled
    R, s, h, d = q.shape
    B, kvh = kp.shape[1], kv_heads
    if h % kvh:
        return False
    if not kernels_enabled():
        return False
    if interpret_enabled():
        return True
    if B % 8:
        return False
    if kvh == 1 and d % 128 == 0:
        return True     # incl. a latent pool: one wide row a token
    return d in (64, 128, 256) and (d % 128 == 0 or kvh == 1)
