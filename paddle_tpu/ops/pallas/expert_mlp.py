"""The held experts' SwiGLU as Pallas kernels that stream the weights of
the experts HIT and of no other (``parallel.moe.ExpertShareMLP.routed``
is the caller and, in its einsums, the definition): one for a forward
of FEW tokens (a tick's rows), below, and one for a forward of many (a
prompt call's positions), the grouped product at the end of this file.

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

# the rows up to which a forward puts EVERY row through every expert hit
# (`expert_share_mlp`); a forward of more sorts its (position, expert)
# pairs by expert and multiplies those alone (`grouped_expert_mlp`):
# of a chunk's 1,024 positions 40 choose each held expert
# (tests/chip_experts_timing.py; PERF.md section 5, "The kernels alone")
MAX_TOKENS = 128
# rows of the sorted pairs in one step of the grouped product
ROW_TILE = 128
# its float32 result [positions, h], two buffers of it: a forward of more
# positions than fit is multiplied block of positions by block
_VMEM_RESULT = 32 << 20
# the three weight blocks, two of each in flight
_VMEM_WEIGHTS = 48 << 20
# room for one step's products beside what a call keeps there
_VMEM_SPARE = 8 << 20


def _kernels_take(xt, w_gate) -> bool:
    """The policy of the other kernels: a TPU backend, or the
    interpreter so that CI drives the glue (it takes any widths). On the
    chip, ``h`` and ``m`` whole 128-lane tiles and ``T`` a multiple of
    8."""
    from . import kernels_enabled
    T, h = xt.shape
    if not kernels_enabled():
        return False
    if _interpret():
        return True
    return h % 128 == 0 and w_gate.shape[2] % 128 == 0 and T % 8 == 0


def use_expert_kernel(xt, w_gate) -> bool:
    """Whether `expert_share_mlp` serves tokens ``xt`` [T, h] over
    stacked weights ``w_gate`` [n, h, m]: FEW tokens, ``T`` at most
    ``MAX_TOKENS``, where the kernels run at all (`_kernels_take`)."""
    return xt.shape[0] <= MAX_TOKENS and _kernels_take(xt, w_gate)


def use_grouped_kernel(xt, w_gate) -> bool:
    """Whether `grouped_expert_mlp` serves them: every forward of MORE
    tokens where the kernels run. What neither kernel takes keeps the
    einsums."""
    return xt.shape[0] > MAX_TOKENS and _kernels_take(xt, w_gate)


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


# ------------------------------------------------ a forward of many tokens
#
# A prompt call's 1,024 positions choose 10 of a router's 256 columns
# each; 16 are held, so 640 (position, held expert) pairs are chosen
# where the einsums multiply all 16,384. The pairs, sorted by expert:
#
# - the caller's jit sorts the choices by held expert (one ``lax.sort``
#   of [T * k] keys, the gates riding along; a choice of an expert not
#   held, or of a zero column, sorts behind all pairs and makes none)
#   and gathers the pairs' rows of ``xt`` into a buffer of a STATIC row
#   count, ``2 T`` to a whole `ROW_TILE`.
# - a grid step is one VISIT: the rows of one row tile that belong to
#   one expert (an expert's rows need not start on a tile, so a tile is
#   visited once for each expert that has rows in it; the visits of a
#   buffer are at most its tiles + n - 1) times one column slice of that
#   expert. The visits' tile, expert and row range and each buffer
#   row's position ride scalar prefetch; past the count the index maps
#   stand still and ``pl.when`` skips the body, as above. An expert no
#   pair chose is never visited and its weights are never fetched.
# - a visit multiplies the whole tile through its expert (rows of other
#   experts too: their products are dropped) and adds ITS rows to their
#   positions of a float32 [T, h] result that stays in VMEM across the
#   grid and is cast once by the caller: a position's pairs are summed
#   in float32, as the einsums' contraction over experts sums them.
# - nothing is dropped at any routing: the sorted pairs are consumed in
#   passes of the buffer under a loop whose trip count is the call's own
#   (``ceil(pairs / rows)``: one in every cell's traffic, none where no
#   pair was routed here, ``min(k, n) / 2`` at the worst). A forward of
#   more positions than `_VMEM_RESULT` holds results of (1,280 at a
#   hidden size of 3,072) is such a product a block of positions.
# - the weight operands are the parameters as the program holds them.


def _grouped_kernel(tile_ref, expert_ref, lo_ref, hi_ref, count_ref,
                    pos_ref, x_ref, g_ref, wg_ref, wu_ref, wd_ref, o_ref,
                    y_ref, *, rows):
    v, j = pl.program_id(0), pl.program_id(1)
    steps = pl.num_programs(1)

    @pl.when((v == 0) & (j == 0))
    def _start():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(v < count_ref[0])
    def _work():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0],
                    preferred_element_type=jnp.float32).astype(x.dtype)
        u = jnp.dot(x, wu_ref[0],
                    preferred_element_type=jnp.float32).astype(x.dtype)
        g = g.astype(jnp.float32)
        a = (g * jax.nn.sigmoid(g)).astype(x.dtype).astype(jnp.float32) \
            * u.astype(jnp.float32)
        a = a.astype(x.dtype).astype(jnp.float32) * g_ref[...]
        y = jnp.dot(a.astype(x.dtype), wd_ref[0],
                    preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _first():
            y_ref[...] = y

        @pl.when(j > 0)
        def _more():
            y_ref[...] += y

        @pl.when(j == steps - 1)
        def _combine():
            base = tile_ref[v] * rows

            def row(r, carry):
                p = pos_ref[base + r]
                o_ref[pl.ds(p, 1), :] += y_ref[pl.ds(r, 1), :]
                return carry
            jax.lax.fori_loop(lo_ref[v], hi_ref[v], row, 0)


def _visits(starts, ends, base, rows, tile, visits):
    """The visits of the buffer that holds sorted pairs ``base`` to
    ``base + rows``, from the experts' ranges of the sorted pairs
    (``starts``, ``ends`` [n]): (tile, expert, first row, end row) of
    each of the ``visits`` slots and how many are real; the slots past
    the count repeat the last real visit with no row."""
    n = starts.shape[0]
    a = jnp.clip(starts - base, 0, rows)        # the experts' rows here
    b = jnp.clip(ends - base, 0, rows)
    each = jnp.where(b > a, (b - 1) // tile - a // tile + 1, 0)
    upto = jnp.cumsum(each)
    count = upto[-1]
    slot = jnp.arange(visits, dtype=jnp.int32)
    v = jnp.minimum(slot, jnp.maximum(count - 1, 0))
    expert = jnp.minimum(
        jnp.sum(upto[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        n - 1)
    mine = expert[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :]
    pick = lambda x: jnp.sum(jnp.where(mine, x[None, :], 0),  # noqa: E731
                             axis=1)
    a, b = pick(a), pick(b)
    t = a // tile + v - (pick(upto) - pick(each))
    real = slot < count
    lo = jnp.where(real, jnp.maximum(a, t * tile) - t * tile, 0)
    hi = jnp.where(real, jnp.minimum(b, (t + 1) * tile) - t * tile, 0)
    return t, expert, lo, hi, count


def grouped_expert_mlp_pallas(xt, ids, gates, first_expert, w_gate, w_up,
                              w_down):
    """xt [T, h]; ``ids``, ``gates`` [T, k] of ``ExpertShareMLP.route``;
    the stacked weights [n, h, m], [n, h, m], [n, m, h] of experts
    ``first_expert`` onwards. Returns [T, h] in xt's dtype: the sum over
    each position's chosen experts THAT ARE HELD of ``((silu(xt @
    w_gate[e]) * (xt @ w_up[e])) * gate) @ w_down[e]``."""
    one = functools.partial(_grouped, first_expert=first_expert,
                            interpret=_interpret())
    T, h = xt.shape
    block = max(_VMEM_RESULT // (8 * h) // ROW_TILE, 1) * ROW_TILE
    if T <= block:
        return one(xt, ids, gates, w_gate, w_up, w_down)
    # blocks of positions, the last filled up with positions that chose
    # no expert at all
    fill = -T % block
    xt, ids, gates = (
        jnp.pad(a, ((0, fill), (0, 0)), constant_values=v).reshape(
            -1, block, a.shape[1])
        for a, v in ((xt, 0), (ids, -1), (gates, 0)))
    return jax.lax.map(lambda a: one(*a, w_gate, w_up, w_down),
                       (xt, ids, gates)).reshape(-1, h)[:T]


@functools.partial(jax.jit, inline=True,
                   static_argnames=("first_expert", "interpret"))
def _grouped(xt, ids, gates, w_gate, w_up, w_down, *, first_expert,
             interpret):
    T, h = xt.shape
    n, _, m = w_gate.shape
    k = ids.shape[1]
    choices = T * k
    tile = ROW_TILE
    rows = -(-2 * T // tile) * tile         # of the buffer: a pass
    visits = rows // tile + n - 1
    # the choices sorted by held expert, a choice's own index the low
    # part of its key: its position is read back off the key
    local = ids.reshape(-1).astype(jnp.int32) - first_expert
    held = (local >= 0) & (local < n)
    index = jnp.arange(choices, dtype=jnp.int32)
    key, gate = jax.lax.sort(
        (jnp.where(held, local, n) * choices + index,
         gates.reshape(-1).astype(jnp.float32)), num_keys=1,
        is_stable=False)         # the keys differ
    ends = jnp.cumsum(jnp.sum(
        held[:, None] & (local[:, None] == jnp.arange(n)[None, :]),
        axis=0, dtype=jnp.int32))
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    pairs = ends[-1]
    room = -(-choices // rows) * rows - choices     # a last pass is whole
    key, gate = jnp.pad(key, (0, room)), jnp.pad(gate, (0, room))

    tm = _column_tile(h, m, w_gate.dtype.itemsize)
    steps = m // tm
    # the pipeline's two buffers of each weight block, of a tile of rows
    # and of the float32 result, and a tile's products
    vmem = (6 * h * tm * w_gate.dtype.itemsize
            + 2 * tile * h * xt.dtype.itemsize + 8 * T * h
            + 4 * tile * h + _VMEM_SPARE)

    def weights(column_axis):
        def index_map(v, j, t, e, lo, hi, count, pos):
            block = [e[v], 0, 0]
            # past the count: the last worked step's block, nothing moves
            block[column_axis] = jnp.where(v >= count[0], steps - 1, j)
            return tuple(block)
        return index_map

    rows_of = lambda v, j, t, *_: (t[v], 0)                 # noqa: E731
    call = pl.pallas_call(
        functools.partial(_grouped_kernel, rows=tile),
        name="grouped_expert_mlp",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(visits, steps),
            in_specs=[
                pl.BlockSpec((tile, h), rows_of),
                pl.BlockSpec((tile, 1), rows_of),
                pl.BlockSpec((1, h, tm), weights(2)),
                pl.BlockSpec((1, h, tm), weights(2)),
                pl.BlockSpec((1, tm, h), weights(1))],
            out_specs=pl.BlockSpec((T, h), lambda v, j, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((tile, h), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret)

    def one_pass(p, total):
        base = p * rows
        mine = base + jnp.arange(rows, dtype=jnp.int32) < pairs
        at = jax.lax.dynamic_slice(key, (base,), (rows,))
        pos = jnp.where(mine, jax.lax.div(jax.lax.rem(at, choices), k), 0)
        g = jnp.where(mine, jax.lax.dynamic_slice(gate, (base,), (rows,)),
                      0.0)
        t, e, lo, hi, count = _visits(starts, ends, base, rows, tile,
                                      visits)
        return total + call(
            t, e, lo, hi, count.reshape(1), pos,
            xt.at[pos].get(mode="promise_in_bounds"), g[:, None],
            w_gate, w_up, w_down)

    total = jax.lax.fori_loop(0, jax.lax.div(pairs + rows - 1, rows),
                              one_pass, jnp.zeros((T, h), jnp.float32))
    return total.astype(xt.dtype)
