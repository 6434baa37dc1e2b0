"""A prompt chunk's gated delta rule at a decay a KEY CHANNEL (Kimi Delta
Attention), as ONE Pallas kernel a layer whose intermediates never leave
VMEM (``ops.delta_rule.gated_delta_chunk`` is the caller;
``delta_rule._chunk_channel`` the definition, and the fallback).

The jnp body is dozens of XLA fusions and a ``lax.scan`` a layer, whose
operands (the column factors ``kh`` [N, H, nb, c, dk], ``A``, its
inverse, ``W``, ``U``, ``qk``) all pass through HBM. Here:

- the grid is ``(N,)``: the chunk's sub-chunks of ``c`` positions IN
  ORDER ("arbitrary"), every head inside a step (a loop over pairs of
  heads); the heads' states ride a VMEM scratch from one step to the
  next, TRANSPOSED (``[dv, dk]``: a decay a key channel is then a row
  over the lanes, and every product below is in a form the MXU takes as
  it lies).
- q, k, g, v and the output are read and written as ``[T, H * d]``, a
  head's ``d`` columns side by side: a step's block is its ``c``
  positions' rows, and head ``h``'s sub-chunk the ``d`` lanes from ``h *
  d`` on (a slice at a whole lane tile). That is the layout their
  producers (projections, the convolution) and the consumer (the output
  norm and projection) have in the prompt programs: nothing is
  transposed, re-tiled or spread in front of the kernel or behind it
  (``tests/test_chip_compile.py`` holds the compiled chunk program to
  that). ``beta`` [T, H]: a head's column is picked by a lane mask.
- the two heads of a pair are traced SIDE BY SIDE, a stage of one then
  the same stage of the other (``_side_by_side``): a sub-chunk is a
  chain of a dozen dependent products, and the compiler's schedule
  keeps the order it is handed, so one head's waits are filled with the
  other's work only if they are handed over interleaved.
- a step computes what ``_chunk_channel`` computes for one sub-chunk:
  the cumulated log-decay ``G`` (a product with a triangle of ones), the
  block references at the MIDDLE of each ``_BASE`` rows, the row factors
  ``up`` and the column factors ``kh`` a block, the pairwise products of
  ``k`` and of ``q`` block by block, the unit-lower inverse (forward
  substitution on the ``_BASE``-row diagonal blocks, all at once, then
  the pairwise merges), ``W`` and ``U``, and the recurrence's step
  (``vn``, ``o``, the state). The substitution runs over the diagonal
  blocks lying side by side along the lanes, on the VPU in float32: a
  finished row is taken out of the rows below it, fifteen dependent
  steps of a product and a difference.
- **a sub-chunk that holds no live position is skipped**: every
  position padded (``beta`` 0 and ``g`` 0) leaves the state as it was,
  bit for bit, and writes zeros where nobody reads. Which sub-chunks
  are live is read from ``beta`` and ``g`` in front of the kernel
  (scalar prefetch): a caller pads as it always did.
- a packed call's segments: neither state nor decay crosses a segment
  boundary (the masks ``same``, ``carry``; ``prev`` is prefetched). The
  kernel then also returns each sub-chunk's entering state and ``vn``,
  and ``delta_rule._segment_states`` sums each segment's state at its
  own last position behind it. A one-segment call has neither output.

The mathematics and the precision are ``_chunk_channel``'s: float32
throughout, every matrix product at ``HIGHEST`` (on the chip Mosaic
splits either operand into three bfloat16 pieces and multiplies six
pairs of them, as XLA does), ``_BASE * |g| < 88`` kept by the same
references. Only the order of some sums differs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_enabled as _interpret

_LANES = 128
_BASE = 16              # delta_rule._BASE: rows of a diagonal block
_HEADS_A_TIME = 2       # heads traced side by side, where the count is even
# room beside a step's blocks (two of each in flight) for its products
_VMEM_SPARE = 8 << 20
_HI = jax.lax.Precision.HIGHEST


def use_chunk_kernel(q, v, g, sub: int) -> bool:
    """Whether this kernel serves a chunk rule over q [T, H, dk], v [T,
    H, dv] and the log-decay ``g``; every other keeps
    ``_chunk_channel``'s (or, a decay a head, ``gated_delta_chunk``'s)
    fusions. The policy of the other kernels: a TPU backend, or the
    interpreter so that CI drives the glue. And what Mosaic tiles
    without padding, on either: float32, a decay a key CHANNEL (``g``
    [T, H, dk]), ``dk`` and ``dv`` whole 128-lane tiles, a sub-chunk of
    ``_BASE`` rows times a power of two, no wider than the lanes."""
    from . import kernels_enabled
    if len(g.shape) != 3 or len(q.shape) != 3:
        return False
    if any(x.dtype != jnp.float32 for x in (q, v, g)):
        return False
    dk, dv = q.shape[-1], v.shape[-1]
    nb = sub // _BASE
    return (kernels_enabled() and dk % _LANES == 0 and dv % _LANES == 0
            and sub % _BASE == 0 and nb & (nb - 1) == 0
            and _BASE <= sub <= _LANES)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b^T
_TN = ((0,), (0,))      # a^T @ b


def _side_by_side(stages):
    """Run generators to their ends a ``yield`` at a time, each in turn;
    their return values. A generator yields where it is about to wait
    for a product: traced in this order the others' work stands between
    a product and its first use."""
    out = [None] * len(stages)
    left = list(enumerate(stages))
    while left:
        still = []
        for i, gen in left:
            try:
                next(gen)
                still.append((i, gen))
            except StopIteration as end:
                out[i] = end.value
        left = still
    return out


def _unit_lower_inverse(A, c):
    """``(I + A)^-1`` for the strictly lower triangular ``A`` [c, c], as
    ``delta_rule._unit_lower_inverse`` makes it: forward substitution
    inside the ``_BASE``-row diagonal blocks, then the blocks merged
    pairwise. No power series. A generator (``_side_by_side``)."""
    b, nb = _BASE, c // _BASE
    r = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    l = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # the diagonal blocks side by side: Ad[i, b I + m] = A_I[i, m]
    diag = jnp.where((r // b) == (l // b), A, 0.0)
    Ad = diag[0:b]
    for I in range(1, nb):
        Ad = Ad + diag[b * I:b * (I + 1)]
    row = jax.lax.broadcasted_iota(jnp.int32, (b, c), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
    # X[i, b I + j] = X_I[i, j], every block at once. (I + A) X = I: row
    # m is final once the rows above it have been taken out of it, and
    # is then taken out of every row i below it, A_I[i, m] times. That
    # factor along a block's lanes, for every m, is off the chain of
    # dependent steps: a step is one broadcast, one product, one
    # difference
    cols = []
    for m in range(b - 1):
        col = jnp.broadcast_to(Ad[:, m:m + 1], (b, c))
        for I in range(1, nb):
            col = jnp.where(lane >= b * I, jnp.broadcast_to(
                Ad[:, b * I + m:b * I + m + 1], (b, c)), col)
        cols.append(col)
    X = (row == lane % b).astype(jnp.float32)
    for m in range(b - 1):
        X = X - cols[m] * X[m:m + 1, :]
        yield
    # block diagonal [c, c], then [[X1, 0], [-X2 A21 X1, X2]] a pair
    X = jnp.where((r // b) == (l // b), jnp.concatenate([X] * nb, 0), 0.0)
    size = b
    while size < c:
        below = ((r // size) % 2 == 1) & ((l // size) == (r // size) - 1)
        P = _dot(X, jnp.where(below, A, 0.0), _NN)
        yield
        X = X - _dot(P, X, _NN)
        yield
        size *= 2
    return X


def _sub_chunk(q, k, g, v, beta, segc, segr, prev, St):
    """One sub-chunk of one head. q, k, g [c, dk]; v [c, dv]; beta [c,
    1]; ``segc`` [c, 1] / ``segr`` [1, c] the positions' segments;
    ``prev`` the segment of the state that enters; ``St`` [dv, dk] that
    state transposed. A generator (``_side_by_side``) that returns (o
    [c, dv], the new ``St``, vn [c, dv])."""
    c, dk = q.shape
    b, nb = _BASE, c // _BASE
    r = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    l = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    G = _dot((r >= l).astype(jnp.float32), g, _NN)          # the cumsum
    yield
    refs = [G[b * I + (b - 1) // 2:b * I + (b - 1) // 2 + 1]
            for I in range(nb)]
    ref = jnp.concatenate([jnp.broadcast_to(x, (b, dk)) for x in refs], 0)
    up = jnp.exp(G - ref)
    kb = k * beta
    ku, qu = kb * up, q * up
    pos = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    rows_a, rows_q = [], []
    for I in range(nb):
        # column j as block I's rows see it; nothing behind the block
        kh = k * jnp.exp(jnp.where(pos < b * (I + 1), refs[I] - G, -jnp.inf))
        at = slice(b * I, b * (I + 1))
        p = _dot(jnp.concatenate([ku[at], qu[at]], 0), kh, _NT)  # [2b, c]
        rows_a.append(p[:b])
        rows_q.append(p[b:])
    yield
    same = segc == segr
    A = jnp.where(same & (r > l), jnp.concatenate(rows_a, 0), 0.0)
    qk = jnp.where(same & (r >= l), jnp.concatenate(rows_q, 0), 0.0)
    Tm = yield from _unit_lower_inverse(A, c)
    gin = jnp.where(segc == prev, jnp.exp(G), 0.0)          # [c, dk]
    WU = _dot(Tm, jnp.concatenate([kb * gin, v * beta], 1), _NN)
    yield
    W, U = WU[:, :dk], WU[:, dk:]
    WS = _dot(jnp.concatenate([W, q * gin], 0), St, _NT)    # [2c, dv]
    yield
    vn = U - WS[:c]
    o = WS[c:] + _dot(qk, vn, _NN)
    # to the sub-chunk's end, inside the end's segment
    kout = k * jnp.where(segc == segc[c - 1:c], jnp.exp(G[c - 1:c] - G), 0.0)
    St = gin[c - 1:c] * St + _dot(vn, kout, _TN)
    return o, St, vn


def _chunk_kernel(live_ref, prev_ref, q_ref, k_ref, g_ref, v_ref, b_ref,
                  segc_ref, segr_ref, S0_ref, o_ref, S_ref, *rest, packed):
    if packed:
        Sin_ref, vn_ref, St_ref = rest
    else:
        St_ref, = rest
    n = pl.program_id(0)
    H, dv, dk = St_ref.shape
    c = b_ref.shape[0]
    pair = _HEADS_A_TIME if H % _HEADS_A_TIME == 0 else 1

    def copy_t(dst, src):           # every head's [a, b] as [b, a]
        def one(h, _):
            dst[h] = src[h].T
        jax.lax.fori_loop(0, H, one, None)

    @pl.when(n == 0)
    def _():
        copy_t(St_ref, S0_ref)

    if packed:
        copy_t(Sin_ref, St_ref)

    @pl.when(live_ref[n] != 0)
    def _():
        segc, segr, prev = segc_ref[n], segr_ref[n], prev_ref[n]
        head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (c, H), 1)

        def some(i, _):
            hs = [i * pair + j for j in range(pair)]
            # head h's columns of the block
            at = lambda h, d: pl.ds(pl.multiple_of(h * d, d), d)  # noqa: E731
            outs = _side_by_side([_sub_chunk(
                q_ref[:, at(h, dk)], k_ref[:, at(h, dk)],
                g_ref[:, at(h, dk)], v_ref[:, at(h, dv)],
                jnp.sum(jnp.where(head_of_lane == h, b_ref[...], 0.0), 1,
                        keepdims=True),
                segc, segr, prev, St_ref[h]) for h in hs])
            for h, (o, St, vn) in zip(hs, outs):
                o_ref[:, at(h, dv)] = o
                St_ref[h] = St
                if packed:
                    vn_ref[:, at(h, dv)] = vn
        jax.lax.fori_loop(0, H // pair, some, None)

    @pl.when(live_ref[n] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        if packed:
            vn_ref[...] = jnp.zeros_like(vn_ref)

    @pl.when(n == pl.num_programs(0) - 1)
    def _():
        copy_t(S_ref, St_ref)


def gated_delta_chunk_pallas(q, k, v, g, beta, S0, seg, segments: int,
                             sub: int):
    """`delta_rule.gated_delta_chunk`'s operands at a decay a key
    channel (``use_chunk_kernel``): q, k, g [T, H, dk]; v [T, H, dv];
    beta [T, H]; S0 [H, dk, dv]; seg [T] int32. Returns (o [T, H, dv],
    S [segments, H, dk, dv])."""
    return _chunk(q, k, v, g, beta, S0, seg, segments=segments, sub=sub,
                  interpret=_interpret())


@functools.partial(jax.jit, inline=True,
                   static_argnames=("segments", "sub", "interpret"))
def _chunk(q, k, v, g, beta, S0, seg, *, segments, sub, interpret):
    T, H, dk = q.shape
    dv = v.shape[-1]
    c = sub
    N = -(-T // c)
    if N * c != T:          # a padded position: beta 0, g 0, the last segment
        q, k, v, g, beta = (jnp.pad(x, [(0, N * c - T)] + [(0, 0)] * (x.ndim - 1))
                            for x in (q, k, v, g, beta))
        seg = jnp.pad(seg, (0, N * c - T), mode="edge")
    f32 = jnp.float32
    # the sub-chunks that hold a position that changes anything, and the
    # segment of the state that enters each sub-chunk
    live = (jnp.any(beta.reshape(N, -1) != 0, 1)
            | jnp.any(g.reshape(N, -1) != 0, 1))
    prev = jnp.concatenate([seg[:1], seg.reshape(N, c)[:-1, -1]])
    rows = lambda x: x.reshape(N * c, -1)                   # noqa: E731
    packed = segments > 1
    by_pos = lambda d: pl.BlockSpec((c, H * d), lambda n, *_: (n, 0))  # noqa: E731
    whole = lambda *s: pl.BlockSpec(s, lambda n, *_: (0,) * len(s))  # noqa: E731
    out_specs = [by_pos(dv), whole(H, dk, dv)]
    out_shape = [jax.ShapeDtypeStruct((N * c, H * dv), f32),
                 jax.ShapeDtypeStruct((H, dk, dv), f32)]
    if packed:
        out_specs += [pl.BlockSpec((None, H, dk, dv),
                                   lambda n, *_: (n, 0, 0, 0)), by_pos(dv)]
        out_shape += [jax.ShapeDtypeStruct((N, H, dk, dv), f32),
                      jax.ShapeDtypeStruct((N * c, H * dv), f32)]
    # a step's blocks, two of each in flight, and the states' scratch
    vmem = 4 * H * (2 * c * (3 * dk + (2 + packed) * dv)
                    + (5 + 2 * packed) * dk * dv)
    o, S_end, *more = pl.pallas_call(
        functools.partial(_chunk_kernel, packed=packed),
        name="delta_chunk_channel",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N,),
            in_specs=[by_pos(dk), by_pos(dk), by_pos(dk), by_pos(dv),
                      pl.BlockSpec((c, H), lambda n, *_: (n, 0)),
                      whole(N, c, 1), whole(N, 1, c), whole(H, dk, dv)],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((H, dv, dk), f32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + _VMEM_SPARE),
        interpret=interpret,
    )(live.astype(jnp.int32), prev.astype(jnp.int32),
      rows(q), rows(k), rows(g), rows(v), beta,
      seg.astype(jnp.int32).reshape(N, c, 1),
      seg.astype(jnp.int32).reshape(N, 1, c), S0)
    o = o.reshape(N * c, H, dv)[:T]
    if not packed:
        return o, S_end[None]
    from ..delta_rule import _segment_states
    S_in, vn = more
    at = lambda x, n: jnp.swapaxes(                         # noqa: E731
        x.reshape(N, c, H, -1)[n], 0, 1)                    # [H, c, .]
    return o, _segment_states(
        seg.reshape(N, c), segments,
        lambda n: (at(k, n), jnp.cumsum(at(g, n), -2), at(vn, n), S_in[n]))
