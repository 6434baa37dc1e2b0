"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464; with a decay
for every key channel Kimi Delta Attention, arXiv:2510.26692) and the
short causal convolution in front of it: a linear-attention layer's
recurrence in the three forms a served model needs.

Per head, with a state ``S`` in R^{dk x dv}, a decay ``alpha_t`` in
(0, 1], a write strength ``beta_t`` and L2-normalised ``q_t``, ``k_t``::

    S = alpha_t S;  d_t = beta_t (v_t - S^T k_t);  S = S + k_t d_t^T;
    o_t = S^T q_t

``alpha_t`` is one number a head (a log-decay ``g`` of shape [T, H]) or
one a KEY CHANNEL (``g`` [T, H, dk]): row ``c`` of ``S`` times
``alpha_t[c]``. Every form takes either.

- ``gated_delta_scan``: exactly that, position by position
  (``lax.scan``). The definition the other two are held to.
- ``gated_delta_chunk``: the chunkwise-parallel form for a prompt chunk
  (the WY representation of arXiv:2406.06484 with the decay of
  arXiv:2412.06464): inside a sub-chunk of ``sub`` positions the
  corrections ``d_t`` solve one unit-lower-triangular system a head, the
  state moves once a sub-chunk. Another computation of the same
  numbers. It serves a PACKED call too: positions carry a segment
  index, neither state nor decay crosses a segment boundary, and every
  segment's state at its last position comes back. At a decay a key
  channel over whole lane tiles it is ONE Pallas kernel a call
  (``ops/pallas/delta_chunk.py``, ``chunk_rule_kernel``); the jnp form
  below stays the definition that kernel is held to.
- ``delta_state_step``: one decode position over every row's state, in
  the lane-dense form the engine stores it in.

Everything here is float32 and its matrix products are asked for at
``highest`` precision: the state is an accumulator over a whole context.
A position with ``beta`` 0 and ``alpha`` 1 (log-decay 0) changes
nothing: that is how a caller pads.

A channel's decays cannot be taken out of a chunk's pairwise products as
a head's can (``(k_i . k_j) exp(G_i - G_j)``): the chunk form multiplies
``k_i exp(G_i - G_b)`` by ``k_j exp(G_b - G_j)`` a channel, ``G_b`` the
cumulated log-decay at the middle of ``i``'s block of ``_BASE``
positions, and either factor grows with the block's length. It is exact
while ``_BASE * |g|`` stays under float32's 88 (each factor then inside
e^+-40): a channel's log-decay is at least ``CHANNEL_LOG_DECAY_MIN`` a
position (Kimi Delta Attention bounds its own at -5), which the caller
keeps.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["state_lane_heads", "pack_state", "unpack_state",
           "gated_delta_scan", "gated_delta_chunk", "chunk_rule_kernel",
           "delta_state_step",
           "conv_chunk", "conv_step", "l2_normalize"]

_HI = jax.lax.Precision.HIGHEST
SUB_CHUNK = 64          # positions solved as one triangular system
_BASE = 16              # rows inverted by forward substitution
# the least log-decay a position of a CHANNEL decay may carry: a block
# of _BASE of them spans exp(80), half on either side of its reference
CHANNEL_LOG_DECAY_MIN = -5.0


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


# ------------------------------------------------------- the state's layout
def state_lane_heads(heads: int, dv: int) -> int:
    """How many heads lie side by side in one row of a stored state. The
    chip tiles an array's last axis in 128 lanes: a 192-wide value axis
    alone is padded to 256 in memory, a third more bytes to hold and to
    move every tick. The least count of heads whose values fill whole
    lane tiles, where the head count allows it; else 1."""
    n = 128 // math.gcd(dv, 128)
    return n if heads % n == 0 else 1


def pack_state(S, hp: int):
    """[..., H, dk, dv] -> the stored form [..., H/hp, dk, hp*dv]."""
    *lead, H, dk, dv = S.shape
    S = S.reshape(*lead, H // hp, hp, dk, dv)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, H // hp, dk, hp * dv)


def unpack_state(S, hp: int):
    """The stored form [..., G, dk, hp*dv] -> [..., G*hp, dk, dv]."""
    *lead, G, dk, L = S.shape
    S = S.reshape(*lead, G, dk, hp, L // hp)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, G * hp, dk, L // hp)


def _spread(x, hp: int, dv: int):
    """x [R, H, ...] -> [R, H/hp, ..., hp*dv]: along a new last axis of
    ``hp * dv`` lanes, the value of the head that owns the lane. Selects
    over an iota, which fuses into the consumer; a reshape of a
    broadcast is materialised by the chip's compiler."""
    R, H = x.shape[:2]
    g = x.reshape((R, H // hp, hp) + x.shape[2:])
    lane = jax.lax.broadcasted_iota(jnp.int32, (hp * dv,), 0) // dv
    out = g[:, :, 0][..., None]
    for j in range(1, hp):
        out = jnp.where(lane >= j, g[:, :, j][..., None], out)
    return out


def delta_state_step(S, q, k, v, alpha, beta, live):
    """One position of every row. ``S`` [R, G, dk, hp*dv] float32 in the
    stored form; q, k [R, H, dk] (normalised, q scaled); v [R, H, dv];
    beta [R, H]; alpha [R, H], or [R, H, dk] a key channel; ``live`` [R]
    bool: a row that is not live keeps its state. Returns (S, o [R, H,
    dv]).

    The output is ``(alpha S)^T q + (k . q) d``, the new state's read
    without reading it. A state the Pallas kernel tiles
    (``use_state_kernel``: whole lane and sublane tiles, float32, a TPU
    or the interpreter) takes it: ONE read and one write of each row's
    state. Any other takes the body below, the definition the kernel is
    held to, in two passes: one reads the state for ``S^T k`` and
    ``S^T q`` together, one reads it and writes the new one."""
    from .pallas.delta_state import delta_state_step_pallas, use_state_kernel
    if use_state_kernel(S):
        return delta_state_step_pallas(S, q, k, v, alpha, beta, live)
    R, H, dv = v.shape
    hp = H // S.shape[1]
    kx, qx = _spread(k, hp, dv), _spread(q, hp, dv)     # [R, G, dk, L]
    if alpha.ndim == 3:     # a channel: the decayed state is the operand
        Sd = S * _spread(alpha, hp, dv)
        bx = _spread(beta, hp, dv)
        d = bx * (v.reshape(R, H // hp, hp * dv) - jnp.sum(Sd * kx, axis=2))
        o = jnp.sum(Sd * qx, axis=2) \
            + _spread(jnp.sum(q * k, -1), hp, dv) * d
        new = Sd + kx * d[:, :, None, :]
        return (jnp.where(live[:, None, None, None], new, S),
                o.reshape(R, H, dv))
    ax, bx = _spread(alpha, hp, dv), _spread(beta, hp, dv)  # [R, G, L]
    qk = _spread(jnp.sum(q * k, -1), hp, dv)
    vx = v.reshape(R, H // hp, hp * dv)
    rk = jnp.sum(S * kx, axis=2)
    rq = jnp.sum(S * qx, axis=2)
    d = bx * (vx - ax * rk)
    o = ax * rq + qk * d
    new = ax[:, :, None, :] * S + kx * d[:, :, None, :]
    return (jnp.where(live[:, None, None, None], new, S),
            o.reshape(R, H, dv))


# ------------------------------------------------------ position by position
def gated_delta_scan(q, k, v, g, beta, S0):
    """The recurrence itself. q, k [T, H, dk]; v [T, H, dv]; ``g`` [T, H]
    (or [T, H, dk], a key channel) the LOG of the decay; beta [T, H];
    S0 [H, dk, dv]. Returns (o [T, H, dv], S [H, dk, dv])."""
    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt).reshape(gt.shape + (1,) * (3 - gt.ndim)) * S
        d = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt,
                                           precision=_HI))
        S = S + kt[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=_HI)
    S, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return o, S


# --------------------------------------------------------------- chunkwise
def _unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower triangular A [..., n, n], n a
    power-of-two multiple of 16 (or below 16): forward substitution on
    the 16-row diagonal blocks, all at once, then the blocks merged
    pairwise by products (``[[X1, 0], [-X2 A21 X1, X2]]``). No power
    series: its terms grow where keys repeat."""
    n = A.shape[-1]
    b = min(n, _BASE)
    nb = n // b
    lead = A.shape[:-2]
    blocks = A.reshape(lead + (nb, b, nb, b))
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], -3)
    eye = jnp.eye(b, dtype=A.dtype)
    X = jnp.broadcast_to(eye, diag.shape)
    for i in range(1, b):       # row i from the rows above it
        row = eye[i] - jnp.einsum("...m,...mj->...j", diag[..., i, :], X,
                                  precision=_HI)
        X = X.at[..., i, :].set(row)
    size = b
    while size < n:             # X: [..., n/size, size, size]
        cnt = n // size
        X1, X2 = X[..., 0::2, :, :], X[..., 1::2, :, :]
        blk = A.reshape(lead + (cnt, size, cnt, size))
        A21 = jnp.stack([blk[..., 2 * i + 1, :, 2 * i, :]
                         for i in range(cnt // 2)], -3)
        X21 = -jnp.einsum("...ij,...jk,...kl->...il", X2, A21, X1,
                          precision=_HI)
        top = jnp.concatenate([X1, jnp.zeros_like(X1)], -1)
        bot = jnp.concatenate([X21, X2], -1)
        X = jnp.concatenate([top, bot], -2)
        size *= 2
    return X[..., 0, :, :]


def _sub_chunks(q, k, v, g, beta, seg, c: int):
    """The T positions padded to N whole sub-chunks of ``c`` (a padded
    position: beta 0, g 0, its predecessor's segment) and a head's
    sub-chunk made one matrix: q, k [N, H, c, dk]; v [N, H, c, dv]; g
    [N, H, c] (or [N, H, c, dk]); beta [N, H, c]; seg [N, c]."""
    T = q.shape[0]
    N = -(-T // c)
    pad = N * c - T
    if pad:
        def grow(x, fill=0):
            return jnp.concatenate(
                [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)], 0)
        q, k, v, g, beta = grow(q), grow(k), grow(v), grow(g), grow(beta)
        seg = jnp.concatenate([seg, jnp.broadcast_to(seg[-1:], (pad,))])
    # [N, H, c, .]: a head's sub-chunk is one matrix
    hm = lambda x: jnp.swapaxes(x.reshape((N, c) + x.shape[1:]), 1, 2)  # noqa: E731
    q, k, v = hm(q), hm(k), hm(v)
    g = hm(g) if g.ndim == 3 else hm(g[..., None])[..., 0]
    return q, k, v, g, hm(beta[..., None])[..., 0], seg.reshape(N, c)


def chunk_rule_kernel(q, v, g, sub: int = SUB_CHUNK) -> bool:
    """Whether ``gated_delta_chunk`` hands a chunk of these operands to
    the Pallas kernel (``ops/pallas/delta_chunk.py``): a decay a key
    channel that the kernel's own gate takes. A decay a head never asks:
    its path is the fusions below."""
    if len(g.shape) != 3:
        return False
    from .pallas.delta_chunk import use_chunk_kernel
    return use_chunk_kernel(q, v, g, sub)


def gated_delta_chunk(q, k, v, g, beta, S0, seg=None, segments: int = 1,
                      sub: int = SUB_CHUNK):
    """A chunk of T positions at once. q, k [T, H, dk]; v [T, H, dv];
    ``g`` [T, H] log-decay (or [T, H, dk], a key channel, none under
    ``CHANNEL_LOG_DECAY_MIN``); beta [T, H]; ``S0`` [H, dk, dv] the state
    behind position 0 (it belongs to position 0's segment). ``seg`` [T]
    int32 (None: one segment): non-decreasing segment indices below
    ``segments``; a position whose segment differs from its
    predecessor's starts from ZERO state. Returns (o [T, H, dv],
    S [segments, H, dk, dv]: each segment's state at its last position).
    A padded position (beta 0, g 0) belongs to the segment before it and
    leaves that segment's state as it was.

    A decay a key channel at whole lane tiles runs as one Pallas kernel
    (``chunk_rule_kernel``), which skips a sub-chunk of padded positions
    alone and writes zeros for their outputs; everything else as the
    fusions of ``_chunk_fusions``, the definition the kernel is held
    to."""
    if chunk_rule_kernel(q, v, g, sub):
        from .pallas.delta_chunk import gated_delta_chunk_pallas
        if seg is None:
            seg = jnp.zeros((q.shape[0],), jnp.int32)
        return gated_delta_chunk_pallas(q, k, v, g, beta, S0, seg,
                                        segments, sub)
    return _chunk_fusions(q, k, v, g, beta, S0, seg, segments=segments,
                          sub=sub)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("segments", "sub"))
def _chunk_fusions(q, k, v, g, beta, S0, seg=None, segments: int = 1,
                   sub: int = SUB_CHUNK):
    """``gated_delta_chunk`` in jax.numpy."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    if seg is None:
        seg = jnp.zeros((T,), jnp.int32)
    c = sub
    q, k, v, g, beta, seg = _sub_chunks(q, k, v, g, beta, seg, c)
    N = seg.shape[0]
    if g.ndim == 4:
        return _chunk_channel(q, k, v, g, beta, S0, seg, segments, T)
    G = jnp.cumsum(g, -1)                                   # [N, H, c]
    same = (seg[:, :, None] == seg[:, None, :])[:, None]    # [N, 1, c, c]
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    # decay from position j to position i of one segment, 0 elsewhere
    diff = G[..., :, None] - G[..., None, :]
    gam = jnp.exp(jnp.where(same & (i >= j), diff, -jnp.inf))
    kk = jnp.einsum("nhik,nhjk->nhij", k, k, precision=_HI)
    A = jnp.where(i > j, beta[..., None] * kk * gam, 0.0)
    Tm = _unit_lower_inverse(A)
    # the segment of the state that enters each sub-chunk
    prev = jnp.concatenate([seg[:1, 0], seg[:-1, -1]])      # [N]
    carry = (seg == prev[:, None])[:, None]                 # [N, 1, c]
    gin = jnp.where(carry, jnp.exp(G), 0.0)                 # [N, H, c]
    W = jnp.einsum("nhij,nhjk->nhik", Tm,
                   k * (beta * gin)[..., None], precision=_HI)
    U = jnp.einsum("nhij,nhjv->nhiv", Tm, v * beta[..., None],
                   precision=_HI)
    qk = jnp.where(i >= j, jnp.einsum("nhik,nhjk->nhij", q, k,
                                      precision=_HI) * gam, 0.0)
    # to the sub-chunk's end, inside the end's segment
    gout = gam[..., -1, :]                                  # [N, H, c]
    keep = gin[..., -1]                                     # [N, H]

    def step(S, x):
        q_n, k_n, W_n, U_n, qk_n, gin_n, gout_n, keep_n = x
        vn = U_n - jnp.einsum("hik,hkv->hiv", W_n, S, precision=_HI)
        o = jnp.einsum("hik,hkv->hiv", q_n * gin_n[..., None], S,
                       precision=_HI) \
            + jnp.einsum("hij,hjv->hiv", qk_n, vn, precision=_HI)
        S_out = keep_n[:, None, None] * S + jnp.einsum(
            "hjk,hjv->hkv", k_n * gout_n[..., None], vn, precision=_HI)
        return S_out, (o, S, vn)

    S_end, (o, S_in, vn) = jax.lax.scan(
        step, S0, (q, k, W, U, qk, gin, gout, keep))
    o = jnp.swapaxes(o, 1, 2).reshape(N * c, H, dv)[:T]
    if segments == 1:
        return o, S_end[None]
    # a segment that ends inside a sub-chunk: the same sum to its own
    # last position e, from the state that entered that sub-chunk
    flat = seg.reshape(-1)
    sid = jnp.arange(segments)
    e = jnp.max(jnp.where(flat[None, :] == sid[:, None],
                          jnp.arange(N * c)[None, :], 0), -1)   # [S]
    n_e, i_e = e // c, e % c
    G_e = jnp.take_along_axis(G[n_e], i_e[:, None, None], -1)   # [S, H, 1]
    mine = (seg[n_e] == sid[:, None]) & (jnp.arange(c)[None, :]
                                         <= i_e[:, None])       # [S, c]
    w = jnp.exp(jnp.where(mine[:, None], G_e - G[n_e], -jnp.inf))
    from_in = jnp.where(prev[n_e] == sid, 1.0, 0.0)[:, None] \
        * jnp.exp(G_e[..., 0])                                  # [S, H]
    S_seg = from_in[..., None, None] * S_in[n_e] + jnp.einsum(
        "shjk,shjv->shkv", k[n_e] * w[..., None], vn[n_e], precision=_HI)
    return o, S_seg


def _chunk_channel(q, k, v, g, beta, S0, seg, segments: int, T: int):
    """``gated_delta_chunk`` behind its sub-chunking, for a decay a key
    channel (g [N, H, c, dk]): the same sums with every decay a vector
    over ``dk``. Only the pairwise products differ in kind (module
    docstring): row ``i``'s factor and column ``j``'s are both relative
    to the cumulated log-decay ``ref`` at the middle of ``i``'s block of
    ``_BASE`` positions."""
    N, H, c, dk = q.shape
    dv = v.shape[-1]
    b = min(c, _BASE)
    nb = c // b
    G = jnp.cumsum(g, -2)                                   # [N, H, c, dk]
    same = (seg[:, :, None] == seg[:, None, :])[:, None]    # [N, 1, c, c]
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    # a block's reference: the cumulated log-decay at its MIDDLE, so
    # that a row's factor and a column's both stay inside e^+-40 (a
    # factor near e^-80 would lose its low-order part where the chip
    # splits a float32 product into bfloat16 pieces and flushes the
    # smallest to zero)
    ref = G[..., (b - 1) // 2::b, :]                        # [N, H, nb, dk]
    up = jnp.exp(G - jnp.repeat(ref, b, axis=-2))
    # column j as block I's rows see it: relative to the same reference
    # (at most e^40 inside the block, decayed further and further before
    # it), nothing behind the block
    seen = jnp.arange(c)[None, :] < b * (jnp.arange(nb)[:, None] + 1)
    kh = k[:, :, None] * jnp.exp(jnp.where(
        seen[..., None], ref[..., None, :] - G[:, :, None], -jnp.inf))

    def pairs(x):       # sum_c x_i[c] k_j[c] exp(G_i[c] - G_j[c]), j <= i
        return jnp.einsum("nhIik,nhIjk->nhIij",
                          (x * up).reshape(N, H, nb, b, dk), kh,
                          precision=_HI).reshape(N, H, c, c)

    A = jnp.where(same & (i > j), beta[..., None] * pairs(k), 0.0)
    Tm = _unit_lower_inverse(A)
    qk = jnp.where(same & (i >= j), pairs(q), 0.0)
    # the segment of the state that enters each sub-chunk
    prev = jnp.concatenate([seg[:1, 0], seg[:-1, -1]])      # [N]
    carry = (seg == prev[:, None])[:, None, :, None]        # [N, 1, c, 1]
    gin = jnp.where(carry, jnp.exp(G), 0.0)                 # [N, H, c, dk]
    W = jnp.einsum("nhij,nhjk->nhik", Tm, k * beta[..., None] * gin,
                   precision=_HI)
    U = jnp.einsum("nhij,nhjv->nhiv", Tm, v * beta[..., None],
                   precision=_HI)
    # to the sub-chunk's end, inside the end's segment
    kout = k * jnp.where(same[..., -1, :, None],
                         jnp.exp(G[..., -1:, :] - G), 0.0)
    keep = gin[..., -1, :]                                  # [N, H, dk]

    def step(S, x):
        qin_n, kout_n, W_n, U_n, qk_n, keep_n = x
        vn = U_n - jnp.einsum("hik,hkv->hiv", W_n, S, precision=_HI)
        o = jnp.einsum("hik,hkv->hiv", qin_n, S, precision=_HI) \
            + jnp.einsum("hij,hjv->hiv", qk_n, vn, precision=_HI)
        S_out = keep_n[..., None] * S + jnp.einsum(
            "hjk,hjv->hkv", kout_n, vn, precision=_HI)
        return S_out, (o, S, vn)

    S_end, (o, S_in, vn) = jax.lax.scan(
        step, S0, (q * gin, kout, W, U, qk, keep))
    o = jnp.swapaxes(o, 1, 2).reshape(N * c, H, dv)[:T]
    if segments == 1:
        return o, S_end[None]
    # a segment that ends inside a sub-chunk (``gated_delta_chunk``)
    return o, _segment_states(
        seg, segments, lambda n: (k[n], G[n], vn[n], S_in[n]))


def _segment_states(seg, segments: int, at):
    """Each segment's state at its own last position, a decay a key
    channel: the sum ``gated_delta_chunk`` ends on, from the state that
    entered the sub-chunk of that position. seg [N, c]; ``at(n)``: (k,
    the cumulated log-decay G [H, c, dk], vn [H, c, dv], the entering
    state [H, dk, dv]) of sub-chunk ``n``. One segment a turn of a loop
    over the segments that HOLD a position (indices do not decrease, so
    those up to the last position's); the others' states are zeros. A
    packed call has room for as many segments as slots may start in one
    call and mostly carries one: the sums over every segment at once
    moved that many states' worth of operands for nothing."""
    N, c = seg.shape
    flat = seg.reshape(-1)
    prev = jnp.concatenate([seg[:1, 0], seg[:-1, -1]])      # [N]

    def one(s, out):
        e = jnp.max(jnp.where(flat == s, jnp.arange(N * c), 0))
        n_e, i_e = e // c, e % c
        k, G, vn, S_in = at(n_e)
        G_e = jax.lax.dynamic_slice_in_dim(G, i_e, 1, 1)    # [H, 1, dk]
        mine = (seg[n_e] == s) & (jnp.arange(c) <= i_e)     # [c]
        w = jnp.exp(jnp.where(mine[None, :, None], G_e - G, -jnp.inf))
        from_in = jnp.where(prev[n_e] == s, 1.0, 0.0) * jnp.exp(G_e[:, 0])
        S = from_in[..., None] * S_in + jnp.einsum(
            "hjk,hjv->hkv", k * w, vn, precision=_HI)
        return out.at[s].set(S)
    S_in = at(0)[3]
    return jax.lax.fori_loop(
        0, flat[-1] + 1, one, jnp.zeros((segments,) + S_in.shape, S_in.dtype))


# ---------------------------------------------------- the short convolution
def conv_chunk(u, w, tail, seg=None, ends=None):
    """Causal ``taps``-tap convolution a channel over a chunk. u [T, C];
    w [C, taps] (tap ``taps-1`` multiplies the position itself); ``tail``
    [taps-1, C] the inputs behind position 0 (zeros for a fresh
    sequence; they belong to position 0's segment); ``seg`` [T] as in
    ``gated_delta_chunk``: a tap that reaches into another segment reads
    zero. ``ends`` [segments] (None: one segment that fills the chunk):
    each segment's last REAL position in the chunk. Returns (y [T, C] float32 before the activation, the new
    tails [segments, taps-1, C]: the inputs at each segment's last
    ``taps-1`` real positions)."""
    T, C = u.shape
    taps = w.shape[1]
    if seg is None:
        seg = jnp.zeros((T,), jnp.int32)
    if ends is None:
        ends = jnp.full((1,), T - 1, jnp.int32)
    segments = ends.shape[0]
    ext = jnp.concatenate([tail.astype(u.dtype), u], 0)     # [T+taps-1, C]
    seg_ext = jnp.concatenate(
        [jnp.broadcast_to(seg[:1], (taps - 1,)), seg])
    wf = w.astype(jnp.float32)
    y = jnp.zeros((T, C), jnp.float32)
    for j in range(taps):
        ok = (seg_ext[j:j + T] == seg)[:, None]
        y = y + jnp.where(ok, ext[j:j + T].astype(jnp.float32), 0.0) \
            * wf[:, j]
    # ext[e + 1 + j] is the input at position e - (taps-2) + j
    at = ends[:, None] + 1 + jnp.arange(taps - 1)[None, :]  # [S, taps-1]
    own = seg_ext[at] == jnp.arange(segments)[:, None]
    return y, jnp.where(own[..., None], ext[at], 0).astype(tail.dtype)


def conv_step(u, w, tail, live):
    """One position of every row. u [R, C]; tail [R, taps-1, C]; ``live``
    [R] bool. Returns (y [R, C] float32, the shifted tails, a row that
    is not live keeping its own)."""
    win = jnp.concatenate([tail, u[:, None].astype(tail.dtype)], 1)
    y = jnp.einsum("rjc,cj->rc", win.astype(jnp.float32),
                   w.astype(jnp.float32), precision=_HI)
    return y, jnp.where(live[:, None, None], win[:, 1:], tail)
