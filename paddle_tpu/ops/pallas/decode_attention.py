"""Pallas TPU decode attention (reference: PHI
``fusion/gpu/masked_multihead_attention_kernel.cu`` — the single-token
decode kernel; reimagined for TPU).

Autoregressive decode is HBM-bandwidth-bound: each step streams the whole
static KV cache once. The XLA dense path pays h/kv times that traffic for
GQA models because it materializes `jnp.repeat`-ed K/V; this kernel reads
each KV block exactly once per *kv head* and shares it across the whole
query-head group.

Blocking (ISSUE 6 re-block — Mosaic rejected the old rank-4
``(1, bt, kv, d)`` cache blocks with "last two dimensions of your
block shape [must be] divisible by 8 and 128"): every BlockSpec here is
now STRICTLY (8, 128)-tiled, never relying on the equal-to-array-dims
escape hatch that lowering refused for (kv, d) = (4, 64):

- K/V are viewed ``[b, T, kv*d]`` (free reshape — contiguous) and
  blocked ``(1, bt, cw)`` where the column width ``cw`` covers one kv
  head when ``d % 128 == 0`` and a PAIR of heads when ``d == 64`` —
  ``cw`` is always a 128 multiple and ``bt`` always an 8 multiple. The
  same trick ``paged_attention.py`` (since deleted) used passed that
  window's compile check while this kernel's rank-4 spec failed it.
- the grid is ``(b, nc, nt)`` with the KV-length dim innermost so the
  fp32 accumulator scratch carries the online softmax across blocks;
  ``nc = kv / heads_per_block`` column blocks replace the old in-kernel
  loop over ALL kv heads per grid step, cutting per-step VMEM from
  ~1 MB to ``bt*cw`` bytes and giving Mosaic more steps to pipeline
  (the old one-megablock schedule is the prime suspect for the 0.61x-
  of-dense r05 timing).
- when a column block holds ``hpb > 1`` heads, the query block embeds
  each head's ``[gp, d]`` queries into a ``[hpb*gp, cw]`` tile that is
  ZERO outside the head's own columns, so ONE ``[hpb*gp, cw] x [cw,
  bt]`` matmul yields per-head scores with no in-kernel lane slicing
  (zero rows/columns contribute nothing); the host extracts the
  block-diagonal of the ``[hpb*gp, cw]`` output. FLOPs grow by hpb on
  the MXU ops, HBM traffic — the decode bottleneck — is unchanged.
- ``cache_index`` arrives via scalar prefetch: blocks fully past the
  valid length are predicated off with @pl.when (their compute never
  runs), the boundary block masks with an iota compare.

The non-TPU fallback (`ops.attention.decode_attention`) uses the same
grouped einsum layout, so GQA never materializes a repeat on any backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_T = 512


from . import interpret_enabled as _interpret


def pick_block_t(total: int, preferred: int = DEFAULT_BLOCK_T) -> int:
    b = min(preferred, total)
    while b > 128 and total % b:
        b //= 2
    if total % b == 0:
        return b
    # halving can strand on a size that doesn't divide `total` when
    # `preferred` is not a power of two — e.g. the VMEM budget cap's 384
    # rows (cw in (1024,1365]) against T=2048 walks 384->192->96 and
    # never hits a divisor. The dispatch gate guarantees T % 128 == 0,
    # so a 128-row tile is always legal; fall back to it instead of
    # reporting "no tile".
    return 128 if total % 128 == 0 else 0


def decode_block_geometry(T: int, kv: int, d: int,
                          block_t: int = DEFAULT_BLOCK_T):
    """The kernel's blocking decisions, exposed for tests and the
    dispatch gate: returns (hpb, cw, nc, bt) — heads per column block,
    column width, number of column blocks, T tile. ``hpb > 1`` only when
    it makes ``cw`` a 128 multiple (d=64 with an even kv); otherwise one
    head per block."""
    hpb = 1
    if d < 128 and (d * (128 // d)) == 128 and kv % (128 // d) == 0:
        hpb = 128 // d
    cw = hpb * d
    nc = kv // hpb
    # each K/V block is [bt, cw] in VMEM: cap it at ~1 MB so MHA-sized
    # caches stay well inside the ~16 MB/core budget even with Mosaic's
    # double buffering (K + V + fp32 scratch)
    budget_rows = max(128, (1 << 20) // (2 * cw) // 128 * 128)
    bt = pick_block_t(T, min(block_t, budget_rows))
    return hpb, cw, nc, bt


def decode_block_shapes(b: int, T: int, kv: int, d: int, group: int,
                        block_t: int = DEFAULT_BLOCK_T):
    """(block_shape, array_shape) per operand — what `pallas_call` will
    request. Tests assert every pair satisfies the STRICT Mosaic rule
    (last two block dims divisible by (8, 128)) so the r05 lowering
    failure can never regress silently on a CPU-only image."""
    hpb, cw, nc, bt = decode_block_geometry(T, kv, d, block_t)
    gp = max(8, -(-group // 8) * 8)
    gr = hpb * gp
    return [
        ((1, 1, gr, cw), (b, nc, gr, cw)),        # q (zero-embedded)
        ((1, bt, cw), (b, T, kv * d)),            # k cache (folded)
        ((1, bt, cw), (b, T, kv * d)),            # v cache (folded)
        ((1, 1, gr, cw), (b, nc, gr, cw)),        # out
    ]


def _decode_kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr,
                   *, scale, block_t, nt, window=None):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    valid = idx_ref[0] + 1  # positions [0, cache_index] are attendable
    run = ti * block_t < valid
    if window is not None:  # skip blocks fully before the window band
        run &= (ti + 1) * block_t > valid - window

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]                             # [gr, cw]
        k = k_ref[0]                                # [bt, cw]
        v = v_ref[0]
        # q rows are zero outside their own head's columns, so the full-
        # width contraction is each head's dot with its own keys
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        gr = q.shape[0]
        k_ids = lax.broadcasted_iota(jnp.int32, (gr, block_t), 1) \
            + ti * block_t
        keep = k_ids < valid
        if window is not None:  # only the trailing `window` cache slots
            keep &= k_ids >= valid - window
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:, :1] = alpha * l_scr[:, :1] \
            + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, :1] = m_new

    @pl.when(ti == nt - 1)
    def _finalize():
        safe_l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc[:] / safe_l).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, cache_index, scale,
                            block_t: int = DEFAULT_BLOCK_T, window=None):
    """q [b, h, d]; k/v_cache [b, T, kv, d]; cache_index: scalar int (the
    write position of the current token; positions <= it are valid).
    ``window`` keeps only the trailing window cache slots (sliding-window
    decode). Returns [b, h, d]."""
    b, h, d = q.shape
    _, T, kv, _ = k_cache.shape
    group = h // kv
    gp = max(8, -(-group // 8) * 8)  # round UP to 8-sublane alignment
    hpb, cw, nc, bt = decode_block_geometry(T, kv, d, block_t)
    gr = hpb * gp
    assert bt, f"cache length {T} has no 128-multiple tile"
    nt = T // bt

    qg = q.reshape(b, kv, group, d)
    if gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - group), (0, 0)))
    # zero-embed: row block j of a column block carries head (ci*hpb+j)'s
    # queries in columns [j*d, (j+1)*d) and zeros elsewhere
    qv = qg.reshape(b, nc, hpb, gp, d)
    eye = jnp.eye(hpb, dtype=qv.dtype)
    qz = jnp.einsum("bcjgd,jk->bcjgkd", qv, eye).reshape(b, nc, gr, cw)
    kc = k_cache.reshape(b, T, kv * d)
    vc = v_cache.reshape(b, T, kv * d)

    idx = jnp.asarray(cache_index, jnp.int32).reshape(1)
    kernel = functools.partial(_decode_kernel, scale=scale, block_t=bt,
                               nt=nt, window=window)
    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nc, nt),
            in_specs=[
                pl.BlockSpec((1, 1, gr, cw),
                             lambda bi, ci, ti, idx: (bi, ci, 0, 0)),
                pl.BlockSpec((1, bt, cw),
                             lambda bi, ci, ti, idx: (bi, ti, ci)),
                pl.BlockSpec((1, bt, cw),
                             lambda bi, ci, ti, idx: (bi, ti, ci)),
            ],
            out_specs=pl.BlockSpec((1, 1, gr, cw),
                                   lambda bi, ci, ti, idx: (bi, ci, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((gr, cw), jnp.float32),
                pltpu.VMEM((gr, 128), jnp.float32),
                pltpu.VMEM((gr, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, nc, gr, cw), q.dtype),
        interpret=_interpret(),
    )(idx, qz, kc, vc)
    # [b, nc, hpb*gp, hpb*d] -> per-head block diagonal (row group j,
    # column group j) -> [b, kv, gp, d] -> drop group padding
    out = out.reshape(b, nc, hpb, gp, hpb, d)
    out = jnp.einsum("bcjgjd->bcjgd", out).reshape(b, kv, gp, d)
    return out[:, :, :group, :].reshape(b, h, d)
