"""Pallas TPU flash attention (reference: PHI flash_attn kernels,
paddle/phi/kernels/gpu/flash_attn_kernel.cu — reimagined for TPU).

Online-softmax blocked attention, FlashAttention-2 style, forward AND
backward as Pallas kernels:

- forward: grid (bh, q_blocks, kv_blocks), KV innermost so the fp32
  accumulator scratch carries across KV steps of one Q block; saves only
  out + logsumexp.
- backward dq: grid (bh, q_blocks, kv_blocks) — recompute p from (q,k,lse),
  accumulate dq across KV blocks.
- backward dk/dv: grid (bh_kv, kv_blocks, group, q_blocks) — the GQA group
  is an explicit grid dim so all query heads of a group accumulate into one
  (dk, dv) scratch; no materialized head repeat anywhere.

Block sizes: 1024x1024 measured 3.5ms vs XLA-dense 10.3ms on a v5e at
[8,2048,16/8,128] causal (the Llama bench shape); `pick_block` chooses the
largest tile that divides the sequence. Causal blocks strictly above the
diagonal are predicated off with @pl.when (their DMA still lands, compute
is skipped); partially-masked diagonal blocks mask inside the kernel.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30


def _interpret() -> bool:
    """Run the kernels in Pallas interpret mode (CPU testing)."""
    return bool(os.environ.get("PADDLE_TPU_PALLAS_INTERPRET"))


def pick_block(seq: int, preferred: int) -> int:
    """Largest MXU-friendly tile that divides seq; the kernels tile the
    sequence exactly, so a non-dividing block would silently drop the
    tail — fail loudly instead."""
    b = min(preferred, seq)
    while b > 128 and seq % b:
        b //= 2
    if seq % b:
        raise ValueError(
            f"flash attention needs seq divisible by a {{128..{preferred}}} "
            f"tile; got seq={seq} (pad the sequence or use dense_attention)")
    return b


def _scores(q, k, qi, ki, *, scale, causal, block_q, block_k,
            causal_offset, qs=None, ks=None, window=None):
    """q@k^T with the shared bottom-right causal mask — the ONE definition
    of the masking convention, inlined into fwd and both bwd kernels.
    qs [block_q, 128] / ks [1, block_k] (lane/sublane-broadcast segment-id
    tiles, the jax TPU flash layout) additionally mask cross-segment
    pairs — the packed-sequence case."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_ids = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
            + qi * block_q
        k_ids = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) \
            + ki * block_k
        keep = q_ids + causal_offset >= k_ids
        if window is not None:  # sliding window: trailing `window` keys
            keep &= (q_ids + causal_offset) - k_ids < window
        s = jnp.where(keep, s, NEG_INF)
    if qs is not None:
        qs_full = jnp.tile(qs, (1, block_k // 128))   # [block_q, block_k]
        s = jnp.where(qs_full == ks, s, NEG_INF)
    return s


# ----------------------------------------------------------------- forward
def _fwd_kernel(*refs, scale, causal, block_q, block_k, kv_blocks,
                causal_offset, has_seg, window=None):
    """causal_offset = sk - sq: bottom-right-aligned causal mask (matches
    the naive path and the backward), so query i attends keys <= i+offset."""
    if has_seg:
        (q_ref, k_ref, v_ref, qs_ref, ks_ref,
         o_ref, lse_ref, acc, m_scr, l_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr = refs
        qs_ref = ks_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    run = True
    if causal:
        # block [qi] attends kv blocks whose start <= last query's diag pos
        run = ki * block_k <= (qi + 1) * block_q - 1 + causal_offset
        if window is not None:  # ...and whose end reaches the window band
            run &= (ki + 1) * block_k - 1 >= \
                qi * block_q + causal_offset - (window - 1)

    @pl.when(run)
    def _compute():
        s = _scores(q_ref[0, :, :], k_ref[0, :, :], qi, ki, scale=scale,
                    causal=causal, block_q=block_q, block_k=block_k,
                    causal_offset=causal_offset, window=window,
                    qs=qs_ref[0] if has_seg else None,
                    ks=ks_ref[0, :1, :] if has_seg else None)
        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, :, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        safe_l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, :, :] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, :, :] = jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(safe_l), (acc.shape[0], 128))


def _seg_operands(segment_ids, heads):
    """[b, s] int32 -> the jax-TPU-flash layout: q ids broadcast into the
    128-lane dim, kv ids into an 8-sublane dim, so every block is
    (8,128)-tiled. ``heads`` lets the bh-flattened grids index batch as
    bh // heads."""
    seg = jnp.asarray(segment_ids, jnp.int32)
    b, s = seg.shape
    qs = jnp.broadcast_to(seg[:, :, None], (b, s, 128))
    ks = jnp.broadcast_to(seg[:, None, :], (b, 8, s))
    return qs, ks


def _flash_fwd(q, k, v, scale, causal, block_q, block_k,
               segment_ids=None, heads=1, window=None):
    """q: [bh, sq, d]; k/v: [bh_kv, sk, d] with bh % bh_kv == 0."""
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    group = bh // bh_kv
    q_blocks = sq // block_q
    kv_blocks = sk // block_k
    has_seg = segment_ids is not None

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_blocks=kv_blocks, causal_offset=sk - sq,
        has_seg=has_seg, window=window)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0),
                     memory_space=pltpu.VMEM),
    ]
    operands = [q, k, v]
    if has_seg:
        h = heads
        in_specs += [
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b // h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, block_k), lambda b, i, j: (b // h, 0, j),
                         memory_space=pltpu.VMEM),
        ]
        operands += list(_seg_operands(segment_ids, heads))

    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(bh, q_blocks, kv_blocks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_interpret(),
    )(*operands)
    return out, lse[:, :, :1]   # [bh, sq, 1]


# ---------------------------------------------------------------- backward
def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, kv_blocks,
                   causal_offset, has_seg, window=None):
    if has_seg:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dq_ref, acc) = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
         dq_ref, acc) = refs
        qs_ref = ks_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    run = True
    if causal:
        run = ki * block_k <= (qi + 1) * block_q - 1 + causal_offset
        if window is not None:
            run &= (ki + 1) * block_k - 1 >= \
                qi * block_q + causal_offset - (window - 1)

    @pl.when(run)
    def _compute():
        k = k_ref[0, :, :]
        s = _scores(q_ref[0, :, :], k, qi, ki, scale=scale, causal=causal,
                    block_q=block_q, block_k=block_k,
                    causal_offset=causal_offset, window=window,
                    qs=qs_ref[0] if has_seg else None,
                    ks=ks_ref[0, :1, :] if has_seg else None)
        p = jnp.exp(s - lse_ref[0, :, :1])            # exact probs via lse
        dp = jax.lax.dot_general(
            g_ref[0, :, :], v_ref[0, :, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, :1]) * scale
        acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        dq_ref[0, :, :] = acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, group,
                    q_blocks, causal_offset, has_seg, window=None):
    if has_seg:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, qs_ref, ks_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        qs_ref = ks_ref = None
    kj = pl.program_id(1)
    gi = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when((gi == 0) & (qi == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = kj * block_k <= (qi + 1) * block_q - 1 + causal_offset
        if window is not None:
            run &= (kj + 1) * block_k - 1 >= \
                qi * block_q + causal_offset - (window - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, :, :]
        s = _scores(q, k_ref[0, :, :], qi, kj, scale=scale, causal=causal,
                    block_q=block_q, block_k=block_k,
                    causal_offset=causal_offset, window=window,
                    qs=qs_ref[0] if has_seg else None,
                    ks=ks_ref[0, :1, :] if has_seg else None)
        p = jnp.exp(s - lse_ref[0, :, :1])
        g = g_ref[0, :, :]
        # dv += p^T g
        dv_acc[:] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            g, v_ref[0, :, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, :1]) * scale
        # dk += ds^T q
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((gi == group - 1) & (qi == q_blocks - 1))
    def _finalize():
        dk_ref[0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, scale, causal, block_q, block_k,
               segment_ids=None, heads=1, window=None):
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    group = bh // bh_kv
    q_blocks = sq // block_q
    kv_blocks = sk // block_k
    offset = sk - sq
    has_seg = segment_ids is not None

    # delta_i = rowsum(dout * out): cheap XLA reduction, fp32
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                  # [bh, sq, 1]

    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    dq_operands = [q, k, v, g, lse, delta]
    dkv_in_specs = [
        pl.BlockSpec((1, block_q, d),
                     lambda b, j, gidx, i: (b * group + gidx, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, j, gidx, i: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, j, gidx, i: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, d),
                     lambda b, j, gidx, i: (b * group + gidx, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1),
                     lambda b, j, gidx, i: (b * group + gidx, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, 1),
                     lambda b, j, gidx, i: (b * group + gidx, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    dkv_operands = [q, k, v, g, lse, delta]
    if has_seg:
        h, hk = heads, heads // group
        qs3, ks3 = _seg_operands(segment_ids, heads)
        dq_in_specs += [
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b // h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, block_k), lambda b, i, j: (b // h, 0, j),
                         memory_space=pltpu.VMEM),
        ]
        dq_operands += [qs3, ks3]
        dkv_in_specs += [
            pl.BlockSpec((1, block_q, 128),
                         lambda b, j, gidx, i: (b // hk, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, block_k),
                         lambda b, j, gidx, i: (b // hk, 0, j),
                         memory_space=pltpu.VMEM),
        ]
        dkv_operands += [qs3, ks3]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          kv_blocks=kv_blocks, causal_offset=offset,
                          has_seg=has_seg, window=window),
        name="flash_attention_dq",
        grid=(bh, q_blocks, kv_blocks),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
    )(*dq_operands)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, group=group,
                          q_blocks=q_blocks, causal_offset=offset,
                          has_seg=has_seg, window=window),
        name="flash_attention_dkv",
        grid=(bh_kv, kv_blocks, group, q_blocks),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, gidx, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, j, gidx, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh_kv, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh_kv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(*dkv_operands)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, window=None):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        window=window)
    return out


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, window=None):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          window=window)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, window, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, scale, causal, block_q, block_k,
                      window=window)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# -------------------------------------------------- flash with segment ids
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_seg(q, k, v, seg, scale, causal, block_q, block_k, heads,
               window=None):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        segment_ids=seg, heads=heads, window=window)
    return out


def _flash_seg_vjp_fwd(q, k, v, seg, scale, causal, block_q, block_k,
                       heads, window=None):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          segment_ids=seg, heads=heads, window=window)
    return out, (q, k, v, seg, out, lse)


def _flash_seg_vjp_bwd(scale, causal, block_q, block_k, heads, window,
                       res, g):
    q, k, v, seg, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, scale, causal,
                            block_q, block_k, segment_ids=seg, heads=heads,
                            window=window)
    return dq, dk, dv, None  # int segment ids carry no cotangent


_flash_seg.defvjp(_flash_seg_vjp_fwd, _flash_seg_vjp_bwd)


def flash_attention_bshd(query, key, value, causal=False, scale=None,
                         block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                         segment_ids=None, window=None):
    """Flash attention on [batch, seq, heads, head_dim] (paddle layout).
    ``segment_ids`` [b, s] (0 = pad) restricts attention to same-segment
    pairs — packed-sequence training on the flash path. ``window`` (with
    causal) is sliding-window attention: only the trailing ``window``
    keys per query."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    b, sq, h, d = query.shape
    _, sk, hk, _ = key.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = pick_block(sq, block_q)
    block_k = pick_block(sk, block_k)
    q = jnp.swapaxes(query, 1, 2).reshape(b * h, sq, d)
    k = jnp.swapaxes(key, 1, 2).reshape(b * hk, sk, d)
    v = jnp.swapaxes(value, 1, 2).reshape(b * hk, sk, d)
    if segment_ids is not None:
        out = _flash_seg(q, k, v, jnp.asarray(segment_ids, jnp.int32),
                         scale, causal, block_q, block_k, h, window)
    else:
        out = _flash(q, k, v, scale, causal, block_q, block_k, window)
    return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2)


# --------------------------------------------------- flash with exposed lse
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, scale, causal, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return out, lse[:, :, 0]


def _flash_lse_vjp_fwd(q, k, v, scale, causal, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return (out, lse[:, :, 0]), (q, k, v, out, lse)


def _flash_lse_vjp_bwd(scale, causal, block_q, block_k, res, gs):
    """Backward with cotangents for BOTH outputs. d lse_i / d s_ij = p_ij,
    so the lse cotangent folds into delta: ds = p (dp - (delta - g_lse))."""
    q, k, v, out, lse = res
    g_out, g_lse = gs
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g_out, scale, causal,
                            block_q, block_k)
    # lse cotangent: d lse_i / d s_ij = p_ij, so
    # d/dq sum_i g_lse_i lse_i = g_lse_i * p_ij * k_j * scale (and sym. dk)
    dq2, dk2 = _lse_grad_terms(q, k, lse[:, :, 0], g_lse, scale, causal)
    dq = (dq.astype(jnp.float32) + dq2).astype(q.dtype)
    dk = (dk.astype(jnp.float32) + dk2).astype(k.dtype)
    return dq, dk, dv


def _lse_grad_terms(q, k, lse, g_lse, scale, causal):
    """Dense fallback for the lse-cotangent term (used only by ring
    attention's combine, where per-shard sequences are modest)."""
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    group = bh // bh_kv
    kr = jnp.repeat(k, group, axis=0) if group > 1 else k
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse[:, :, None])
    w = p * g_lse[:, :, None] * scale
    dq = jnp.einsum("bqk,bkd->bqd", w, kr.astype(jnp.float32))
    dk = jnp.einsum("bqk,bqd->bkd", w, q.astype(jnp.float32))
    if group > 1:
        dk = dk.reshape(bh_kv, group, sk, d).sum(axis=1)
    return dq, dk


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def flash_attention_with_lse(query, key, value, causal=False, scale=None,
                             block_q=DEFAULT_BLOCK_Q,
                             block_k=DEFAULT_BLOCK_K):
    """[b, s, h, d] flash attention returning (out, lse[b, h, s]) — the
    building block for cross-device softmax merging (ring attention)."""
    b, sq, h, d = query.shape
    _, sk, hk, _ = key.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = pick_block(sq, block_q)
    block_k = pick_block(sk, block_k)
    q = jnp.swapaxes(query, 1, 2).reshape(b * h, sq, d)
    k = jnp.swapaxes(key, 1, 2).reshape(b * hk, sk, d)
    v = jnp.swapaxes(value, 1, 2).reshape(b * hk, sk, d)
    out, lse = _flash_lse(q, k, v, scale, causal, block_q, block_k)
    out = jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2)
    return out, lse.reshape(b, h, sq)
