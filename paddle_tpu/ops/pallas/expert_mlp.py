"""The held experts' SwiGLU of a forward of FEW tokens, as one Pallas
kernel that streams the weights of the experts HIT and of no other
(``parallel.moe.ExpertShareMLP.routed`` is the caller and, in its
einsums, the definition).

A decode tick's 64 rows choose 8 to 12 of a router's 256 to 768 columns
each: of the 16 experts a rank holds, 10 to 14 get a token, and the
layer's time is the read of their weights. The einsums over the stacked
weights read all 16 and multiply the idle ones by a gate of 0.0.

- the caller builds, with ``jnp`` inside its jit, the LIST of experts
  hit (`hit_list`): hit experts first, their count, the tail padded
  with the last hit expert. List and count ride scalar prefetch (SMEM).
- the grid is ``(n, m // tm)``: a step is one ``tm``-column slice of one
  expert's intermediate width. The three weight blocks' index maps read
  the list; past the count they return the block of the LAST worked
  step, so the pipeline fetches nothing more, and ``pl.when`` skips the
  body. With no expert hit the one block the pipeline fetches first is
  not multiplied, and the output is exactly 0.
- ``xt`` [T, h], the gates [n, T, 1] and a float32 accumulator [T, h]
  stay in VMEM across the grid; the weight blocks ([h, tm], [h, tm],
  [tm, h]) are double buffered by the pipeline. ``tm`` is the widest
  slice whose six buffers fit `_VMEM_WEIGHTS`.
- the operands ARE the parameters, ``[n, h, m]``, ``[n, h, m]``, ``[n,
  m, h]``, in the layout the program holds them in: no reshape, no
  transpose, no view (a view of a pool once changed its tiling on the
  chip and cost a copy of it every tick: PERF.md section 6, PR 27).

The arithmetic is the einsums': bf16 products accumulated in float32,
``silu(g) * u`` in the input's precision, the gate applied in float32
before the cast, one float32 accumulator over experts and columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_enabled as _interpret

# the rows above which a forward keeps the einsums: at 256 positions no
# held expert is idle and the arithmetic no longer hides under the read
# (tests/chip_experts_timing.py; PERF.md section 5, "The kernels alone")
MAX_TOKENS = 128
# the three weight blocks, two of each in flight
_VMEM_WEIGHTS = 48 << 20
# room for one step's products beside what a call keeps there
_VMEM_SPARE = 8 << 20


def use_expert_kernel(xt, w_gate) -> bool:
    """Whether this kernel serves tokens ``xt`` [T, h] over stacked
    weights ``w_gate`` [n, h, m]; every other forward keeps the einsums.
    The policy of the other kernels: a TPU backend, or the interpreter
    so that CI drives the glue (it takes any widths). On the chip, ``h``
    and ``m`` whole 128-lane tiles and ``T`` a multiple of 8. And FEW
    tokens, on either: ``T`` at most ``MAX_TOKENS``."""
    from . import kernels_enabled
    T, h = xt.shape
    if T > MAX_TOKENS or not kernels_enabled():
        return False
    if _interpret():
        return True
    return h % 128 == 0 and w_gate.shape[2] % 128 == 0 and T % 8 == 0


def hit_list(hit):
    """hit [n] bool -> (list [n] int32, count [] int32): the experts hit
    in their order, then the last of them repeated; all 0 when none is."""
    n = hit.shape[0]
    index = jnp.arange(n, dtype=jnp.int32)
    rank = jnp.cumsum(hit, dtype=jnp.int32) - 1
    place = hit[:, None] & (rank[:, None] == index[None, :])   # [e, place]
    order = jnp.sum(jnp.where(place, index[:, None], 0), axis=0)
    count = jnp.sum(hit, dtype=jnp.int32)
    last = jnp.max(jnp.where(hit, index, 0))
    return jnp.where(index < count, order, last).astype(jnp.int32), count


def _column_tile(h: int, m: int, itemsize: int) -> int:
    """Columns of the intermediate width in one step: the most 128-lane
    tiles that divide ``m`` and fit `_VMEM_WEIGHTS`; all of ``m`` where
    it is not whole tiles (the interpreter's widths)."""
    if m % 128:
        return m
    tm = m
    while tm > 128 and (6 * h * tm * itemsize > _VMEM_WEIGHTS or m % tm):
        tm -= 128
    return tm


def _expert_kernel(list_ref, count_ref, x_ref, w_ref, wg_ref, wu_ref,
                   wd_ref, o_ref, acc_ref):
    e, j = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (j == 0))
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(e < count_ref[0])
    def _work():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0],
                    preferred_element_type=jnp.float32).astype(x.dtype)
        u = jnp.dot(x, wu_ref[0],
                    preferred_element_type=jnp.float32).astype(x.dtype)
        g = g.astype(jnp.float32)
        a = (g * jax.nn.sigmoid(g)).astype(x.dtype).astype(jnp.float32) \
            * u.astype(jnp.float32)
        a = a.astype(x.dtype).astype(jnp.float32) * w_ref[0]
        acc_ref[...] += jnp.dot(a.astype(x.dtype), wd_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when((e == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def expert_share_mlp_pallas(xt, w, order, count, w_gate, w_up, w_down):
    """xt [T, h]; w [T, n] float32, token t's gate on held expert e (0.0
    where it did not choose it); ``order``, ``count`` of `hit_list`;
    the stacked weights [n, h, m], [n, h, m], [n, m, h]. Returns [T, h]
    in xt's dtype: the sum over the listed experts of
    ``((silu(xt @ w_gate[e]) * (xt @ w_up[e])) * w[:, e]) @ w_down[e]``."""
    return _experts(xt, w, order, count, w_gate, w_up, w_down,
                    interpret=_interpret())


# jitted and inlined as the ragged kernel's wrapper is: a program of L
# expert layers traces the body once, and the ops keep the caller's names
@functools.partial(jax.jit, inline=True, static_argnames=("interpret",))
def _experts(xt, w, order, count, w_gate, w_up, w_down, *, interpret):
    T, h = xt.shape
    n, _, m = w_gate.shape
    tm = _column_tile(h, m, w_gate.dtype.itemsize)
    steps = m // tm
    # the pipeline's two buffers of each weight block, of xt and of the
    # output, and the accumulator: more than the compiler allows a
    # kernel unasked
    vmem = (6 * h * tm * w_gate.dtype.itemsize
            + 4 * T * h * xt.dtype.itemsize + 4 * T * h + _VMEM_SPARE)

    def weights(column_axis):
        def index(e, j, order, count):
            idle = e >= count[0]
            block = [order[e], 0, 0]
            # past the count: the last worked step's block, nothing moves
            block[column_axis] = jnp.where(idle, steps - 1, j)
            return tuple(block)
        return index

    whole = lambda e, j, order, count: (0, 0)              # noqa: E731
    return pl.pallas_call(
        _expert_kernel,
        name="expert_share_mlp",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, steps),
            in_specs=[
                pl.BlockSpec((T, h), whole),
                pl.BlockSpec((1, T, 1),
                             lambda e, j, order, count: (order[e], 0, 0)),
                pl.BlockSpec((1, h, tm), weights(2)),
                pl.BlockSpec((1, h, tm), weights(2)),
                pl.BlockSpec((1, tm, h), weights(1))],
            out_specs=pl.BlockSpec((T, h), whole),
            scratch_shapes=[pltpu.VMEM((T, h), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, h), xt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
    )(order, count.reshape(1), xt, w.T[:, :, None], w_gate, w_up, w_down)
