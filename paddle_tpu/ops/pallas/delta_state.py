"""One decode position of the gated delta rule over every slot's stored
state, as one Pallas kernel that reads each state ONCE and writes it
once (``ops.delta_rule.delta_state_step`` is the caller and, in its jnp
body, the definition).

The jnp body is two XLA fusions: a reduce that reads the state for
``S^T k`` and ``S^T q``, an update that reads it again and writes it.
XLA cannot make them one: the update needs the finished column sums of
a whole ``[dk, L]`` block. A kernel that holds a row's block in VMEM
can.

- the grid is ``(R // rows,)``: a step holds ``rows`` slots' whole
  states ``[rows, G, dk, L]`` (``L = hp * dv`` lanes: ``hp`` heads side
  by side, `delta_rule.state_lane_heads`), in and out, double buffered
  by the pipeline. ``rows`` is the most that divides ``R`` and fits
  `_VMEM_STATE`: a step's traffic (4.4 MB a slot at 15 x 96 x 384) is
  far above its fixed cost, which one ``[dk, L]`` block a step would
  not be.
- the state is aliased to the new state (``input_output_aliases``): the
  caller's donated pool array is updated in place, as the fusion's was.
- the small operands ride in the layouts the body reads them in: ``k``
  and ``q`` side by side as ``[R, dk, 2H]`` (``dk`` on sublanes, a head
  a lane), ``v`` as ``[R, G, L]``; ``alpha``, ``beta`` and ``q . k``,
  a number a head, ride scalar prefetch (SMEM) with ``live``. A head's
  value is spread along its ``dv`` lanes inside the kernel: a lane
  broadcast of its column or a splat of its scalar and, in a 128-lane
  tile that two heads share, a select over a lane iota. Nothing the
  size of the state is built beside it.
- a row that is not ``live`` gets its output ``o`` like every row and
  its state copied as it is.
- a decay a KEY CHANNEL (``alpha`` [R, H, dk]; Kimi Delta Attention) is
  a column a head like ``k`` and ``q`` and rides beside them, ``[R, dk,
  3H]``; ``beta`` and ``q . k`` then ride beside ``v``, ``[R, 3G, L]``,
  already spread along their heads' lanes, and only ``live`` is
  prefetched (`_state_kernel_channel`). The decayed tile ``alpha * s`` is
  what the sums and the update read: still one read and one write.

The arithmetic is the jnp body's, in float32 on the VPU: ``rk =
sum_dk(S * kx)``, ``rq = sum_dk(S * qx)``, ``d = beta * (v - alpha *
rk)``, ``o = alpha * rq + (q . k) * d``, ``S' = alpha * S + kx * d``
(a channel decay: ``Sd = ax * S``, ``rk = sum_dk(Sd * kx)``, ``rq =
sum_dk(Sd * qx)``, ``d = beta * (v - rk)``, ``o = rq + (q . k) * d``,
``S' = Sd + kx * d``). Only the order of the ``dk``-term column sums
may differ.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_enabled as _interpret

# the state blocks of one step, in and out, two of each in flight
_VMEM_STATE = 12 << 20
# room for one step's small operands and products beside them
_VMEM_SPARE = 8 << 20
_LANES = 128


def use_state_kernel(S) -> bool:
    """Whether this kernel serves the stored state ``S`` [R, G, dk, L];
    every other state keeps the two fusions. The policy of the other
    kernels: a TPU backend, or the interpreter so that CI drives the
    glue. And what Mosaic tiles without padding, on either: float32,
    ``L`` whole 128-lane tiles and ``dk`` whole 8-row tiles (a head
    count that leaves one head a row with a padded value axis does
    not)."""
    from . import kernels_enabled
    if len(S.shape) != 4 or S.dtype != jnp.float32:
        return False
    dk, L = S.shape[2:]
    return kernels_enabled() and L % _LANES == 0 and dk % 8 == 0


def _rows_per_step(R: int, slot_bytes: int) -> int:
    """Slots in one grid step: the most that divide ``R`` (no ragged
    last block) and whose four buffers fit `_VMEM_STATE`; one at the
    least."""
    rows = max(1, min(R, _VMEM_STATE // (4 * slot_bytes)))
    while R % rows:
        rows -= 1
    return rows


def _spreader(hp, dv):
    """``spread(of_head, g, t)``: lane tile ``t`` of group ``g``: along
    its 128 lanes ``of_head(head)`` (a scalar, or a column [dk, 1]) of
    the head that owns each lane; left as it is where one head owns
    all."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def spread(of_head, g, t):
        first = t * _LANES // dv
        last = (t * _LANES + _LANES - 1) // dv
        out = of_head(g * hp + first)
        for j in range(first + 1, last + 1):
            out = jnp.where(lane >= j * dv - t * _LANES,
                            of_head(g * hp + j), out)
        return out
    return spread


def _state_kernel(live_ref, abc_ref, s_ref, kq_ref, v_ref, new_ref, o_ref,
                  *, hp, dv):
    rows, G, dk, L = s_ref.shape
    H = G * hp
    spread = _spreader(hp, dv)

    for r in range(rows):
        row = pl.program_id(0) * rows + r
        live = live_ref[row] != 0
        kq = kq_ref[r]                                  # [dk, 2H]
        for g in range(G):
            for t in range(L // _LANES):
                at = slice(t * _LANES, (t + 1) * _LANES)
                s = s_ref[r, g, :, at]                  # [dk, 128]
                kx = spread(lambda h: kq[:, h:h + 1], g, t)
                qx = spread(lambda h: kq[:, H + h:H + h + 1], g, t)
                a, b, c = (spread(lambda h, i=i: abc_ref[(row * 3 + i) * H
                                                         + h], g, t)
                           for i in range(3))
                rk = jnp.sum(s * kx, axis=0, keepdims=True)
                rq = jnp.sum(s * qx, axis=0, keepdims=True)
                d = b * (v_ref[r, g:g + 1, at] - a * rk)
                o_ref[r, g:g + 1, at] = a * rq + c * d
                # a select, not a branch: straight-line code lets the
                # scheduler overlap one tile's column sums with the next
                new_ref[r, g, :, at] = jnp.where(live, a * s + kx * d, s)


def _state_kernel_channel(live_ref, s_ref, kqa_ref, vbc_ref, new_ref, o_ref,
                          *, hp, dv):
    """`_state_kernel` for a decay a key channel: ``kqa`` [rows, dk, 3H]
    holds k, q and alpha a head a lane; ``vbc`` [rows, 3G, L] holds v,
    beta and q . k in the state's lanes."""
    rows, G, dk, L = s_ref.shape
    H = G * hp
    spread = _spreader(hp, dv)
    for r in range(rows):
        live = live_ref[pl.program_id(0) * rows + r] != 0
        kqa = kqa_ref[r]                                # [dk, 3H]
        for g in range(G):
            for t in range(L // _LANES):
                at = slice(t * _LANES, (t + 1) * _LANES)
                s = s_ref[r, g, :, at]                  # [dk, 128]
                kx, qx, ax = (spread(lambda h, i=i: kqa[:, i * H + h:
                                                        i * H + h + 1], g, t)
                              for i in range(3))
                sd = ax * s
                rk = jnp.sum(sd * kx, axis=0, keepdims=True)
                rq = jnp.sum(sd * qx, axis=0, keepdims=True)
                d = vbc_ref[r, G + g:G + g + 1, at] \
                    * (vbc_ref[r, g:g + 1, at] - rk)
                o_ref[r, g:g + 1, at] = rq \
                    + vbc_ref[r, 2 * G + g:2 * G + g + 1, at] * d
                new_ref[r, g, :, at] = jnp.where(live, sd + kx * d, s)


def delta_state_step_pallas(S, q, k, v, alpha, beta, live):
    """`delta_rule.delta_state_step`'s operands and results: ``S`` [R, G,
    dk, hp*dv] float32; q, k [R, H, dk]; v [R, H, dv]; beta [R, H];
    alpha [R, H] or, a key channel, [R, H, dk]; ``live`` [R] bool.
    Returns (S, o [R, H, dv])."""
    step = _step_channel if alpha.ndim == 3 else _step
    return step(S, q, k, v, alpha, beta, live, interpret=_interpret())


# jitted and inlined as the other kernels' wrappers are: a program of L
# state layers traces the body once, and the ops keep the caller's names
@functools.partial(jax.jit, inline=True, static_argnames=("interpret",))
def _step(S, q, k, v, alpha, beta, live, *, interpret):
    R, G, dk, L = S.shape
    H, dv = v.shape[1:]
    hp = H // G
    rows = _rows_per_step(R, G * dk * L * 4)
    f32 = jnp.float32
    kq = jnp.concatenate([k, q], 1).astype(f32).swapaxes(1, 2)  # [R,dk,2H]
    abc = jnp.stack([alpha, beta, jnp.sum(q * k, -1)], 1) \
        .astype(f32).reshape(-1)                            # [R * 3 * H]
    by_rows = lambda *tail: pl.BlockSpec(                   # noqa: E731
        (rows,) + tail, lambda i, live, abc: (i,) + (0,) * len(tail))
    new, o = pl.pallas_call(
        functools.partial(_state_kernel, hp=hp, dv=dv),
        name="delta_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R // rows,),
            in_specs=[by_rows(G, dk, L), by_rows(dk, 2 * H),
                      by_rows(G, L)],
            out_specs=[by_rows(G, dk, L), by_rows(G, L)]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, f32),
                   jax.ShapeDtypeStruct((R, G, L), f32)],
        # operands 0 and 1 are the prefetched scalars
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=4 * rows * G * dk * L * 4 + _VMEM_SPARE),
        interpret=interpret,
    )(live.astype(jnp.int32), abc, S, kq, v.astype(f32).reshape(R, G, L))
    return new, o.reshape(R, H, dv)


@functools.partial(jax.jit, inline=True, static_argnames=("interpret",))
def _step_channel(S, q, k, v, alpha, beta, live, *, interpret):
    R, G, dk, L = S.shape
    H, dv = v.shape[1:]
    hp = H // G
    rows = _rows_per_step(R, G * dk * L * 4)
    f32 = jnp.float32
    kqa = jnp.concatenate([k, q, alpha], 1).astype(f32).swapaxes(1, 2)
    in_lanes = lambda x: jnp.repeat(                        # noqa: E731
        x.astype(f32), dv, axis=-1).reshape(R, G, L)
    vbc = jnp.concatenate([v.astype(f32).reshape(R, G, L), in_lanes(beta),
                           in_lanes(jnp.sum(q * k, -1))], 1)  # [R, 3G, L]
    by_rows = lambda *tail: pl.BlockSpec(                   # noqa: E731
        (rows,) + tail, lambda i, live: (i,) + (0,) * len(tail))
    new, o = pl.pallas_call(
        functools.partial(_state_kernel_channel, hp=hp, dv=dv),
        name="delta_state_step_channel",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R // rows,),
            in_specs=[by_rows(G, dk, L), by_rows(dk, 3 * H),
                      by_rows(3 * G, L)],
            out_specs=[by_rows(G, dk, L), by_rows(G, L)]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, f32),
                   jax.ShapeDtypeStruct((R, G, L), f32)],
        # operand 0 is the prefetched ``live``
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=4 * rows * G * dk * L * 4 + _VMEM_SPARE),
        interpret=interpret,
    )(live.astype(jnp.int32), S, kqa, vbc)
    return new, o.reshape(R, H, dv)
