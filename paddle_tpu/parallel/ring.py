"""Ring attention — sequence/context parallelism for long sequences
(reference: fleet's sep/context-parallel path in
paddle/distributed/fleet/meta_parallel/, which shards the sequence over
ranks and exchanges KV with NCCL send/recv).

TPU-native: inside `shard_map` over the ``sp`` mesh axis, each device holds
one sequence block of Q/K/V. KV blocks rotate around the ring with
`lax.ppermute` (ICI neighbor exchange — bandwidth-optimal on a TPU torus)
while each device accumulates its Q block's attention with an *online
softmax* (running max + denominator), exactly the flash-attention
recurrence across devices. Causality is enforced per (q-block, kv-block)
pair, so blocks strictly in the future contribute nothing (their compute is
masked; the rotation still happens to keep the schedule static).

Differentiable end-to-end: ppermute has a transpose rule, so `jax.grad`
through ring_attention yields the reverse ring — no hand-written backward.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_scores(q, k, scale):
    """q [b,sq,h,d], k [b,sk,kvh,d] -> scores [b,h,sq,sk] (fp32), GQA-aware."""
    h, kvh = q.shape[2], k.shape[2]
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
    return jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale


def _block_pv(p, v, h):
    kvh = v.shape[2]
    if kvh != h:
        v = jnp.repeat(v, h // kvh, axis=2)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None, segment_ids=None,
                   window: Optional[int] = None):
    """Blockwise ring attention. Call inside shard_map with q/k/v
    [b, s_local, h|kvh, d] sharded on the sequence dim over `axis_name`.
    Returns [b, s_local, h, d] (the local Q block's full attention).

    ``segment_ids`` [b, s_local] (the LOCAL shard of the packed-sequence
    ids, same convention as the flash kernel: attention only within equal
    ids) rotates around the ring alongside K/V, so packed SFT composes
    with context parallelism. ``window`` (requires causal) keeps only the
    trailing ``window`` keys per query — sliding-window attention under
    sp. Positions are global (block index * s_local + offset), so both
    masks are exact across shard boundaries."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True (sliding-window "
                         "attention narrows the causal band)")
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    perm = [(i, (i + 1) % n) for i in range(n)]
    has_seg = segment_ids is not None
    sc0 = (jnp.asarray(segment_ids, jnp.int32) if has_seg
           else jnp.zeros((b, 0), jnp.int32))  # empty: nothing to rotate

    def tick(carry, step):
        o, m, l, kc, vc, sc = carry
        kv_idx = (idx - step) % n  # whose sequence block we currently hold
        s_scores = _block_scores(q, kc, scale)  # [b,h,sq,sk]
        if causal or has_seg:
            qpos = idx * s + jnp.arange(s)[:, None]
            kpos = kv_idx * s + jnp.arange(s)[None, :]
            if causal:
                keep = kpos <= qpos
                if window is not None:
                    keep &= qpos - kpos < window
            else:
                keep = jnp.ones((s, s), bool)
            keep = keep[None, None]                      # [1,1,sq,sk]
            if has_seg:
                keep = keep & (segment_ids[:, None, :, None]
                               == sc[:, None, None, :])  # [b,1,sq,sk]
            s_scores = jnp.where(keep, s_scores, NEG_INF)
        m_new = jnp.maximum(m, s_scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s_scores - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        pv = _block_pv(p.astype(q.dtype), vc, h)  # [b,sq,h,d]
        o_new = o * jnp.swapaxes(alpha, 1, 2)[..., None].astype(o.dtype) \
            + pv.astype(o.dtype)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        if has_seg:
            sc = lax.ppermute(sc, axis_name, perm)
        return (o_new, m_new, l_new, kc, vc, sc), None

    o0 = jnp.zeros((b, s, h, d), jnp.float32)
    m0 = jnp.full((b, h, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    (o, m, l, _, _, _), _ = lax.scan(tick, (o0, m0, l0, k, v, sc0),
                                     jnp.arange(n))
    denom = jnp.swapaxes(l, 1, 2)[..., None]  # [b,sq,h,1]
    return (o / jnp.maximum(denom, 1e-20)).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                      scale: Optional[float] = None, attn_fn=None,
                      segment_ids=None, window: Optional[int] = None):
    """DeepSpeed-Ulysses sequence parallelism (reference: sep_degree path):
    all_to_all trades the sequence shard for a head shard, runs ordinary
    (full-sequence) attention on h/n heads, and trades back. Cheaper than
    ring when heads >= sp degree; requires num_heads % sp == 0.

    ``segment_ids`` is the LOCAL [b, s/n] shard (all-gathered to the full
    sequence, since each device sees every position after the swap);
    ``window`` narrows the causal band (sliding-window attention)."""
    from ..ops.attention import dense_attention, segment_mask
    n = lax.axis_size(axis_name)

    def swap_in(x):   # [b, s/n, h, d] -> [b, s, h/n, d]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def swap_out(x):  # [b, s, h/n, d] -> [b, s/n, h, d]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    kw = {}
    if segment_ids is not None:
        seg_full = lax.all_gather(jnp.asarray(segment_ids, jnp.int32),
                                  axis_name, axis=1, tiled=True)
        kw["attn_mask"] = segment_mask(seg_full)
    if window is not None:
        kw["window"] = window
    if attn_fn is not None and kw:
        # contract: a custom attn_fn must accept (q, k, v, causal=...,
        # **kw) for whichever of attn_mask/window the caller sets here.
        # Fail with the contract spelled out instead of a TypeError from
        # deep inside the wrapped function.
        import inspect
        try:
            sig = inspect.signature(attn_fn)
            has_var_kw = any(p.kind is inspect.Parameter.VAR_KEYWORD
                             for p in sig.parameters.values())
            missing = [k for k in kw if k not in sig.parameters] \
                if not has_var_kw else []
        except (TypeError, ValueError):   # builtins/partials w/o signature
            missing = []
        if missing:
            cause = "/".join(
                n for n, set_ in (("segment_ids", segment_ids is not None),
                                  ("window", window is not None)) if set_)
            raise TypeError(
                f"ulysses_attention: custom attn_fn {attn_fn!r} does not "
                f"accept {missing} — required because {cause} was set. "
                "attn_fn must take (q, k, v, *, causal, attn_mask, "
                "window) like ops.attention.dense_attention.")
    attn_fn = attn_fn or functools.partial(dense_attention, scale=scale)
    kvh = k.shape[2]
    if kvh < n:  # too few KV heads to split: replicate them up to sp degree
        k = jnp.repeat(k, n // math.gcd(n, kvh), axis=2)
        v = jnp.repeat(v, n // math.gcd(n, kvh), axis=2)
    out = attn_fn(swap_in(q), swap_in(k), swap_in(v), causal=causal, **kw)
    return swap_out(out)


def ring_flash_attention(q, k, v, axis_name: str = "sp",
                         causal: bool = False, scale: Optional[float] = None,
                         segment_ids=None, window: Optional[int] = None):
    """Ring attention with the Pallas flash kernel doing each block pair
    (reference semantics identical to `ring_attention`; this is the fast
    path for long sequences on TPU).

    Per-device blocks merge across ring steps by logsumexp reweighting —
    the same recurrence flash uses internally, lifted to the ring level.
    The ring is unrolled in Python (n is static): step 0 is the diagonal
    (causal within the block); later steps are full block attention taken
    only by devices whose block is in the past (`lax.cond` per device).
    Differentiable end-to-end: flash exposes lse with a custom VJP and
    ppermute transposes to the reverse rotation.

    Note: call inside `shard_map(..., check_vma=False)` — pallas_call
    does not yet declare varying-across-mesh info for its outputs.

    ``segment_ids``/``window`` route to the online-softmax block path
    (`ring_attention`): the per-block flash kernel has no cross-shard
    position offset, so the masked variants use the dense block pairs —
    per-device blocks are modest (s/n) and XLA fuses them; the flash
    fast path covers the plain/causal long-context case.
    """
    if segment_ids is not None or window is not None:
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal,
                              scale=scale, segment_ids=segment_ids,
                              window=window)
    from ..ops.pallas.flash_attention import flash_attention_with_lse
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def merge(o, lse, o_i, lse_i):
        # o, o_i are each NORMALIZED softmax outputs of their blocks;
        # reweight by each block's probability mass and renormalize
        m = jnp.maximum(lse, lse_i)
        w = jnp.exp(lse - m)                    # [b,h,s]
        w_i = jnp.exp(lse_i - m)
        wq = jnp.swapaxes(w, 1, 2)[..., None]   # [b,s,h,1]
        wq_i = jnp.swapaxes(w_i, 1, 2)[..., None]
        o_new = (o * wq + o_i.astype(jnp.float32) * wq_i) / (wq + wq_i)
        lse_new = m + jnp.log(w + w_i)
        return o_new, lse_new

    # step 0: own block, causal if requested
    o_i, lse_i = flash_attention_with_lse(q, k, v, causal=causal,
                                          scale=scale)
    o = o_i.astype(jnp.float32)
    lse = lse_i
    kc, vc = k, v
    for step in range(1, n):
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        if causal:
            # kv block is in this device's past iff idx >= step
            def take(q=q, kc=kc, vc=vc, o=o, lse=lse):
                o_b, lse_b = flash_attention_with_lse(q, kc, vc,
                                                      causal=False,
                                                      scale=scale)
                return merge(o, lse, o_b, lse_b)

            def skip(o=o, lse=lse):
                return o, lse

            o, lse = lax.cond(idx >= step, take, skip)
        else:
            o_b, lse_b = flash_attention_with_lse(q, kc, vc, causal=False,
                                                  scale=scale)
            o, lse = merge(o, lse, o_b, lse_b)
    return o.astype(q.dtype)
