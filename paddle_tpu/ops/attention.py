"""Attention kernels (reference: PHI fused attention kernels,
paddle/phi/kernels/fusion/*flash_attn*). TPU path: a Pallas flash-attention
kernel (online softmax, blocked over KV) used when shapes tile cleanly onto
the MXU; otherwise an XLA-fused dense path.

The Pallas kernel lands in `paddle_tpu/ops/pallas/flash_attention.py`;
this module is the dispatch layer.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp


def _flash_enabled() -> bool:
    # NOT cached: both terms (env toggles in tests, platform) must be
    # re-read so interpret-mode coverage is real
    if os.environ.get("PADDLE_TPU_DISABLE_FLASH"):
        return False
    # interpret mode counts: CPU tests must be able to exercise every
    # branch that will select the kernel on hardware
    from .pallas import kernels_enabled
    return kernels_enabled()


def use_flash(query, key, attn_mask, dropout_p) -> bool:
    if not _flash_enabled() or attn_mask is not None or dropout_p > 0.0:
        return False
    b, sq, h, d = query.shape
    sk = key.shape[1]
    # kernel tiles: seq multiples of 128, head_dim in {64, 128, 256}
    return sq % 128 == 0 and sk % 128 == 0 and d in (64, 128, 256)


def flash_attention(query, key, value, causal=False, scale=None,
                    segment_ids=None, window=None):
    """[b, s, h, d] flash attention; grouped-query aware. The Pallas kernel
    is TPU-only; on other backends (CPU mesh tests, dryruns) this routes to
    the numerically-identical dense XLA path. ``segment_ids`` [b, s]
    (0 = pad) restricts attention to same-segment pairs (packed
    sequences)."""
    from .pallas import kernels_enabled
    if not kernels_enabled():
        return dense_attention(query, key, value, causal=causal, scale=scale,
                               window=window,
                               attn_mask=segment_mask(segment_ids)
                               if segment_ids is not None else None)
    from .pallas.flash_attention import flash_attention_bshd
    return flash_attention_bshd(query, key, value, causal=causal,
                                scale=scale, segment_ids=segment_ids,
                                window=window)


def segment_mask(segment_ids):
    """[b, s] segment ids -> [b, 1, s, s] same-segment boolean mask with
    pads (seg 0) attending only pads (flash-kernel semantics; combined
    with `causal=` by dense_attention)."""
    seg = jnp.asarray(segment_ids)
    return (seg[:, :, None] == seg[:, None, :])[:, None]


def dense_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                    causal=False, scale=None, dropout_key=None,
                    window=None, sink=None):
    """XLA-fused dense path, [b, s, h, d]; fp32 softmax; GQA-aware.
    Single source of truth for the non-flash math (nn.functional's
    scaled_dot_product_attention fallback routes here). ``window``
    (with causal) keeps only the trailing ``window`` keys per query —
    sliding-window attention (Qwen2/Mistral). ``sink`` [h]: a learned
    score a head that joins its softmax's denominator and carries no
    value (an attention sink: one more key whose value is zero). The
    values may be narrower or wider than the keys."""
    b, sq, h, d = query.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    q = jnp.swapaxes(query, 1, 2)
    k = jnp.swapaxes(key, 1, 2)
    v = jnp.swapaxes(value, 1, 2)
    if k.shape[1] != h:
        rep = h // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sk = k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        if window is not None:
            # bottom-right aligned: query i sits at absolute sk - sq + i
            qpos = jnp.arange(sq)[:, None] + (sk - sq)
            mask = mask & (qpos - jnp.arange(sk)[None, :] < window)
        scores = jnp.where(mask, scores, -jnp.inf)
    elif window is not None:
        raise ValueError("window requires causal=True (sliding-window "
                         "attention is causal)")
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            scores = jnp.where(attn_mask, scores, -jnp.inf)
        else:
            scores = scores + attn_mask.astype(scores.dtype)
    if sink is not None:
        col = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None],
            scores.shape[:-1] + (1,))
        scores = jnp.concatenate([scores, col], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1).astype(query.dtype)
    if sink is not None:
        probs = probs[..., :-1]
    if dropout_p > 0.0 and dropout_key is not None:
        keep = 1.0 - dropout_p
        dmask = jax.random.bernoulli(dropout_key, keep, probs.shape)
        probs = jnp.where(dmask, probs / keep, 0).astype(probs.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return jnp.swapaxes(out, 1, 2)


def naive_attention(query, key, value, causal=False, scale=None):
    return dense_attention(query, key, value, causal=causal, scale=scale)


def use_decode_kernel(q, k_cache) -> bool:
    """Pallas decode kernel wants a TPU backend (or interpret mode, so CI
    exercises the same dispatch glue), MXU-friendly head_dim, a cache
    length with a 128-multiple tile, and a whole number of query heads
    per kv head."""
    from .pallas import interpret_enabled
    b, s, h, d = q.shape
    T, kv = k_cache.shape[1], k_cache.shape[2]
    if s != 1 or h % kv:
        return False
    if not (interpret_enabled() or _flash_enabled()):
        return False
    if interpret_enabled():
        # interpret mode skips Mosaic's tiling checks; any shape the
        # python emulation can run keeps CI coverage of the dispatch glue
        return d in (64, 128, 256) and T % 128 == 0
    # hardware: the kernel's K/V column blocks are [bt, cw] over the
    # folded [b, T, kv*d] view and must be STRICTLY (8, 128)-tiled (the
    # r05 window refused the equal-to-array-dims escape hatch for
    # (kv, d) = (4, 64)). cw is d when d % 128 == 0 and a head PAIR
    # (128) when d == 64 with an even kv; d=64 with odd kv has no
    # 128-multiple column block and takes the grouped-einsum fallback.
    return T % 128 == 0 and (d in (128, 256)
                             or (d == 64 and kv % 2 == 0))


def decode_attention(q, k_cache, v_cache, cache_index, scale=None,
                     window=None):
    """Single-token decode over a static KV cache (reference: PHI
    fusion/gpu/masked_multihead_attention). q [b, 1, h, d];
    k/v_cache [b, T, kv, d]; positions <= cache_index attend.

    Both paths are GQA-native — no `jnp.repeat` of K/V anywhere, so HBM
    traffic is the cache read itself (the decode bottleneck), not
    h/kv copies of it."""
    b, s, h, d = q.shape
    assert s == 1, f"decode_attention is for q_len=1, got {s}"
    kv, T = k_cache.shape[2], k_cache.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    if use_decode_kernel(q, k_cache):
        from .pallas.decode_attention import decode_attention_pallas
        out = decode_attention_pallas(q[:, 0], k_cache, v_cache,
                                      cache_index, scale, window=window)
        return out[:, None]

    # grouped einsum fallback (CPU mesh tests / odd shapes): same layout,
    # XLA contracts per kv head without materializing the repeat
    g = h // kv
    qg = q[:, 0].reshape(b, kv, g, d)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(T)[None, None, None, :]
    mask = kpos <= cache_index
    if window is not None:  # sliding window: only the trailing keys
        mask = mask & (kpos > cache_index - window)
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v_cache)
    return out.reshape(b, 1, h, d)
