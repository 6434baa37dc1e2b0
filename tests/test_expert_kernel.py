"""ISSUE 33: a forward of few tokens reads only the held experts that a
row of it chose; ISSUE 47: a forward of many multiplies only the
(position, held expert) pairs the router chose, sorted by expert
(``ops/pallas/expert_mlp.py``, called by
``parallel.moe.ExpertShareMLP.routed``), under the interpreter on the CPU.

- THE SAME SUM: the kernel against ``routed``'s einsums over all held
  experts, whichever experts the rows hit (all, some, one, none), at the
  three expert configurations' ratios of hidden to expert width, beside
  a shared expert and zero-compute columns.
- NOTHING OF AN IDLE EXPERT IS READ INTO THE RESULT: its weights set to
  NaN leave the output finite (the einsums multiply them by a gate of 0.0
  and would not).
- the gate adapts on what it sees: few tokens take the tick's kernel, a
  chunk's positions the grouped product, a process without kernels the
  einsums.
- THE GROUPED PRODUCT IS THE SAME SUM at every routing: no pair routed
  here, every pair on one expert, uniform choice, every position on as
  many held experts as it can choose (past the row budget of a pass:
  nothing is dropped), beside a shared expert and zero columns; an idle
  expert's NaN never reaches the result.
- ``moe_experts_read`` counts the experts whose weights a forward read.
- on a tiny engine of each family the greedy streams are the einsums',
  every tick reads exactly the experts hit, and the tick program holds
  no product over the stacked weights; with chunks of more positions
  than a tick has rows the streams are still the einsums' through
  prompts of several chunks, the engine counts every expert layer of
  every prompt call as grouped, and neither chunk program holds a
  product over the stacks.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.ops.pallas import expert_mlp
from paddle_tpu.parallel.moe import (SERVING_COUNTERS, ExpertShareMLP,
                                     collect_counts)

E, K, FIRST, HELD = 16, 4, 4, 6          # experts 4..9 of 16 are held
UNHELD = [e for e in range(E) if not FIRST <= e < FIRST + HELD]
HITS = {"all": [0, 1, 2, 3, 4, 5], "some": [1, 2, 5], "one": [3],
        "none": []}


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


@contextlib.contextmanager
def gate_shut():
    """``routed`` keeps its einsums whatever it is given: the path the
    kernels are compared with (the attention kernels still run)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(expert_mlp, "use_expert_kernel", lambda *_: False)
        mp.setattr(expert_mlp, "use_grouped_kernel", lambda *_: False)
        yield


def share(h, m, seed=0, dtype=jnp.float32, **kw):
    pt.seed(seed)
    layer = ExpertShareMLP(h, m, E, K, FIRST, HELD, **kw)
    rs = np.random.RandomState(seed)
    for name in ("w_gate", "w_up", "w_down"):
        setattr(layer, name, jnp.asarray(
            rs.randn(*getattr(layer, name).shape) * 0.3, dtype))
    return layer


def choices(T, hit, seed=0):
    """ids [T, K], gates [T, K]: every token chooses up to K of the held
    experts ``hit`` (indices into the share) in rotation and fills up
    with experts that are not held; each of ``hit`` gets a token."""
    rs = np.random.RandomState(seed)
    ids = np.empty((T, K), np.int32)
    for t in range(T):
        for c in range(K):
            ids[t, c] = FIRST + hit[(t * K + c) % len(hit)] \
                if c < len(hit) else UNHELD[(t + c) % len(UNHELD)]
    return jnp.asarray(ids), jnp.asarray(rs.rand(T, K) + 0.1, jnp.float32)


def by_einsums(layer, x, ids, gates):
    with gate_shut():
        return np.asarray(layer.routed(x, ids, gates), np.float32)


# ------------------------------------------------------------- the same sum
@pytest.mark.parametrize("hit", list(HITS))
@pytest.mark.parametrize("h,m", [(448, 128), (384, 128), (256, 128)],
                         ids=["h3.5m", "h3m", "h2m"])
def test_the_kernel_is_the_einsums_whatever_is_hit(kernels, h, m, hit):
    """Hidden sizes 3.5, 3 and 2 expert widths, as GigaChat3.1's 7168,
    LongCat-Flash's 6144 and MiMo-V2.5's 4096 over 2048."""
    layer = share(h, m)
    x = jnp.asarray(np.random.RandomState(1).randn(16, h), jnp.float32)
    ids, gates = choices(16, HITS[hit])
    assert expert_mlp.use_expert_kernel(x, layer.w_gate)
    got = np.asarray(layer.routed(x, ids, gates))
    want = by_einsums(layer, x, ids, gates)
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * np.abs(want).max())
    if hit == "none":
        assert not got.any()            # exactly 0: nothing was multiplied
    else:
        assert np.abs(got).max() > 1.0


@pytest.mark.parametrize("hit", list(HITS))
def test_idle_experts_weights_never_reach_the_result(kernels, hit):
    """NaN in every weight of the experts no row chose: the einsums
    would multiply them by 0.0 into NaN; the kernel does not read them
    into anything. With NO expert hit the one block the pipeline fetches
    first is not multiplied either."""
    h, m = 256, 128
    clean = share(h, m)
    layer = share(h, m)
    idle = np.array([e not in HITS[hit] for e in range(HELD)])
    for name in ("w_gate", "w_up", "w_down"):
        w = np.asarray(getattr(layer, name)).copy()
        w[idle] = np.nan
        setattr(layer, name, jnp.asarray(w))
    x = jnp.asarray(np.random.RandomState(2).randn(8, h), jnp.float32)
    ids, gates = choices(8, HITS[hit])
    got = np.asarray(layer.routed(x, ids, gates))
    assert np.isfinite(got).all()
    want = by_einsums(clean, x, ids, gates)
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * max(np.abs(want).max(), 1.0))
    if idle.any():
        assert np.isnan(by_einsums(layer, x, ids, gates)).any()


@pytest.mark.parametrize("hit", ["all", "some"])
def test_bfloat16_rounds_where_the_einsums_round(kernels, hit):
    """bf16 in, bf16 out, float32 accumulation: against the float32
    layer the kernel is no farther off than the einsums are."""
    h, m = 256, 128
    exact, layer = share(h, m), share(h, m, dtype=jnp.bfloat16)
    x = jnp.asarray(np.random.RandomState(3).randn(16, h) * 0.5,
                    jnp.bfloat16)
    ids, gates = choices(16, HITS[hit])
    for name in ("w_gate", "w_up", "w_down"):       # the rounded weights
        setattr(exact, name, getattr(layer, name).astype(jnp.float32))
    want = by_einsums(exact, x.astype(jnp.float32), ids, gates)
    got = layer.routed(x, ids, gates)
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got, np.float32) - want).max()
    ref = np.abs(by_einsums(layer, x, ids, gates) - want).max()
    assert err <= 1.5 * ref + 1e-3 * np.abs(want).max(), (err, ref)


@pytest.mark.parametrize("beside", ["shared", "zero", "both"])
def test_a_shared_expert_and_zero_columns_beside_the_kernel(kernels, beside):
    """``shared_out`` and ``zero_out`` are untouched: the whole layer's
    forward (router, kernel, identity part, shared expert) is the
    einsums' forward."""
    kw = {}
    if beside in ("shared", "both"):
        kw["num_shared_experts"] = 1
    if beside in ("zero", "both"):
        kw["zero_experts"] = 4
    layer = share(256, 128, **kw)
    rs = np.random.RandomState(4)
    layer.gate = jnp.asarray(rs.randn(*layer.gate.shape), jnp.float32)
    x = jnp.asarray(rs.randn(4, 3, 256), jnp.float32)
    got = np.asarray(layer(x))
    with gate_shut():
        want = np.asarray(layer(x))
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * np.abs(want).max())
    ids, _ = layer.route(x.reshape(-1, 256))
    assert (np.asarray(ids) >= FIRST).any()     # the share got tokens
    if "zero_experts" in kw:
        assert (np.asarray(ids) >= E).any()     # and a zero column did


# --------------------------------------------------------------- the list
@pytest.mark.parametrize("hit", [
    [1] * 8, [0] * 8, [1, 0, 0, 1, 0, 1, 1, 0], [0, 0, 0, 0, 0, 0, 0, 1],
    [1, 0, 0, 0, 0, 0, 0, 0]],
    ids=["all", "none", "mixed", "last", "first"])
def test_the_list_holds_the_hit_first_and_repeats_the_last(hit):
    order, count = jax.jit(expert_mlp.hit_list)(jnp.asarray(hit, bool))
    mine = [e for e, on in enumerate(hit) if on]
    assert int(count) == len(mine) and order.dtype == jnp.int32
    assert order.tolist() == mine + [mine[-1] if mine else 0] \
        * (len(hit) - len(mine))


@pytest.mark.parametrize("h,m,itemsize,tm", [
    (7168, 2048, 2, expert_mlp._column_tile(7168, 2048, 2)),
    (6144, 2048, 2, expert_mlp._column_tile(6144, 2048, 2)),
    (4096, 2048, 2, expert_mlp._column_tile(4096, 2048, 2)),
    (256, 128, 4, 128),         # one tile is the least
    (64, 32, 4, 32),            # not whole tiles: the interpreter's widths
])
def test_the_column_tile_divides_the_width_inside_the_budget(h, m, itemsize,
                                                             tm):
    assert expert_mlp._column_tile(h, m, itemsize) == tm and m % tm == 0
    if m % 128 == 0:
        assert tm % 128 == 0
        assert tm == 128 or 6 * h * tm * itemsize <= expert_mlp._VMEM_WEIGHTS


# --------------------------------------------------------------- the gate
@pytest.mark.parametrize("T,takes", [
    (8, "expert_share_mlp"), (64, "expert_share_mlp"),
    (128, "expert_share_mlp"), (136, "grouped_expert_mlp"),
    (256, "grouped_expert_mlp")])
def test_few_tokens_take_the_kernel_and_a_chunk_the_einsums(kernels, T,
                                                            takes):
    """A tick's 64 rows (and a verify tick's 128) against a chunk call's
    256 positions: the rule reads the static token count and nothing
    else. Since ISSUE 47 the chunk takes the grouped product where it
    kept the einsums (the test keeps its name): neither holds a product
    over the stacks."""
    layer = share(64, 32)
    x = jnp.zeros((T, 64), jnp.float32)
    few = takes == "expert_share_mlp"
    assert expert_mlp.use_expert_kernel(x, layer.w_gate) is few
    assert expert_mlp.use_grouped_kernel(x, layer.w_gate) is not few
    ids, gates = choices(T, HITS["some"])
    jaxpr = jax.make_jaxpr(layer.routed)(x, ids, gates)
    assert _kernel_names(jaxpr.jaxpr) == [takes]
    assert not _stacked_products(jaxpr.jaxpr, layer)


def test_without_kernels_the_einsums_run(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    layer = share(64, 32)
    for T in (8, 256):
        x = jnp.zeros((T, 64), jnp.float32)
        assert not expert_mlp.use_expert_kernel(x, layer.w_gate)
        assert not expert_mlp.use_grouped_kernel(x, layer.w_gate)
        jaxpr = jax.make_jaxpr(layer.routed)(x, *choices(T, HITS["all"]))
        assert not _kernel_names(jaxpr.jaxpr)
        assert len(_stacked_products(jaxpr.jaxpr, layer)) == 3


def test_on_the_chip_the_widths_are_whole_tiles(monkeypatch):
    """What Mosaic can slice: ``h`` and ``m`` whole 128-lane tiles and
    rows a multiple of 8; any other shape keeps the einsums there."""
    import paddle_tpu.ops.pallas as pallas
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(pallas, "tpu_backend", lambda: True)
    w = jax.ShapeDtypeStruct
    ok = expert_mlp.use_expert_kernel
    assert ok(w((64, 4096), jnp.bfloat16), w((16, 4096, 2048), jnp.bfloat16))
    assert not ok(w((64, 4000), jnp.bfloat16),
                  w((16, 4000, 2048), jnp.bfloat16))
    assert not ok(w((64, 4096), jnp.bfloat16),
                  w((16, 4096, 200), jnp.bfloat16))
    assert not ok(w((60, 4096), jnp.bfloat16),
                  w((16, 4096, 2048), jnp.bfloat16))
    assert not ok(w((256, 4096), jnp.bfloat16),
                  w((16, 4096, 2048), jnp.bfloat16))
    many = expert_mlp.use_grouped_kernel
    assert many(w((256, 4096), jnp.bfloat16),
                w((16, 4096, 2048), jnp.bfloat16))
    assert many(w((1024, 3072), jnp.bfloat16),
                w((16, 3072, 1024), jnp.bfloat16))
    assert not many(w((64, 4096), jnp.bfloat16),
                    w((16, 4096, 2048), jnp.bfloat16))
    assert not many(w((256, 4000), jnp.bfloat16),
                    w((16, 4000, 2048), jnp.bfloat16))
    assert not many(w((256, 4096), jnp.bfloat16),
                    w((16, 4096, 200), jnp.bfloat16))
    assert not many(w((252, 4096), jnp.bfloat16),
                    w((16, 4096, 2048), jnp.bfloat16))


# ----------------------------------------------------- the grouped product
MANY = 136      # a pass holds 384 rows of sorted pairs: three row tiles
ROUTINGS = ["none", "one", "uniform", "most"]


def routing(kind, T=MANY, seed=0):
    """ids [T, K], gates [T, K] of a forward of many tokens. ``none``: no
    choice falls on a held expert; ``one``: every token's first choice
    is the SAME held expert, the others not held (T pairs, two tiles of
    one expert); ``uniform``: K of the E columns without repeat (about
    T K HELD / E pairs over all held experts, which start anywhere in a
    tile); ``most``: every token chooses min(K, HELD) held experts (T K
    pairs, more than a pass's rows: a second pass)."""
    rs = np.random.RandomState(seed)
    gates = jnp.asarray(rs.rand(T, K) + 0.1, jnp.float32)
    if kind == "uniform":
        ids = np.stack([rs.permutation(E)[:K] for _ in range(T)])
    elif kind == "most":
        ids = FIRST + (np.arange(T)[:, None] + np.arange(K)) % HELD
    else:
        ids = np.asarray(UNHELD)[(np.arange(T)[:, None] + np.arange(K))
                                 % len(UNHELD)]
        if kind == "one":
            ids[:, 0] = FIRST + 3
    return jnp.asarray(ids, jnp.int32), gates


def _pairs(ids):
    ids = np.asarray(ids)
    return int(((ids >= FIRST) & (ids < FIRST + HELD)).sum())


@pytest.mark.parametrize("kind", ROUTINGS)
@pytest.mark.parametrize("h,m", [(384, 128), (256, 128)],
                         ids=["h3m", "h2m"])
def test_the_grouped_product_is_the_einsums_at_every_routing(kernels, h, m,
                                                             kind):
    """Hidden sizes 3 and 2 expert widths (Laguna's 3072 over 1024,
    MiMo's 4096 over 2048), float32 to 1e-5 of the largest result."""
    layer = share(h, m)
    x = jnp.asarray(np.random.RandomState(1).randn(MANY, h), jnp.float32)
    ids, gates = routing(kind)
    assert expert_mlp.use_grouped_kernel(x, layer.w_gate)
    pairs = {"none": 0, "one": MANY, "most": MANY * min(K, HELD)}
    assert _pairs(ids) == pairs.get(kind, _pairs(ids)) and (
        kind != "uniform" or MANY < _pairs(ids) < 2 * MANY)
    got = np.asarray(jax.jit(layer.routed)(x, ids, gates))
    want = by_einsums(layer, x, ids, gates)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())
    if kind == "none":
        assert not got.any()            # exactly 0: no pass ran
    else:
        assert np.abs(got).max() > 1.0


@pytest.mark.parametrize("T", [256, 1024])
def test_no_pair_is_dropped_past_the_row_budget(kernels, T):
    """Every position on min(K, HELD) held experts is twice the rows of a
    pass (2 T): the loop takes two passes and the sum is the einsums'.
    With ALL of them on two experts, each expert's rows cross passes."""
    layer = share(128, 128)
    x = jnp.asarray(np.random.RandomState(5).randn(T, 128), jnp.float32)
    ids, gates = routing("most", T)
    two = jnp.where(ids % 2 == 0, FIRST, FIRST + HELD - 1)
    for chosen in (ids, two):
        assert _pairs(chosen) == 4 * T
        got = np.asarray(jax.jit(layer.routed)(x, chosen, gates))
        want = by_einsums(layer, x, chosen, gates)
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("T", [512, 600])
def test_a_forward_of_more_positions_than_the_result_holds_goes_in_blocks(
        kernels, monkeypatch, T):
    """The float32 result of 256 positions is all that fits: 512 are two
    blocks, 600 three with the last filled up by positions that chose
    nothing; each block sorts and multiplies its own pairs."""
    h = 128
    monkeypatch.setattr(expert_mlp, "_VMEM_RESULT", 8 * h * 256)
    layer = share(h, 128)
    x = jnp.asarray(np.random.RandomState(6).randn(T, h), jnp.float32)
    ids, gates = routing("uniform", T)
    jaxpr = jax.make_jaxpr(layer.routed)(x, ids, gates)
    assert _kernel_names(jaxpr.jaxpr) == ["grouped_expert_mlp"]
    assert not _stacked_products(jaxpr.jaxpr, layer)
    got = np.asarray(jax.jit(layer.routed)(x, ids, gates))
    want = by_einsums(layer, x, ids, gates)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("kind", ["uniform", "most"])
def test_the_grouped_product_rounds_where_the_einsums_round(kernels, kind):
    """bf16 in, bf16 out, a position's pairs summed in float32 and cast
    once: against the float32 layer the grouped product is no farther
    off than the einsums are."""
    h, m = 256, 128
    exact, layer = share(h, m), share(h, m, dtype=jnp.bfloat16)
    x = jnp.asarray(np.random.RandomState(3).randn(MANY, h) * 0.5,
                    jnp.bfloat16)
    ids, gates = routing(kind)
    for name in ("w_gate", "w_up", "w_down"):       # the rounded weights
        setattr(exact, name, getattr(layer, name).astype(jnp.float32))
    want = by_einsums(exact, x.astype(jnp.float32), ids, gates)
    got = jax.jit(layer.routed)(x, ids, gates)
    assert got.dtype == jnp.bfloat16
    err = np.abs(np.asarray(got, np.float32) - want).max()
    ref = np.abs(by_einsums(layer, x, ids, gates) - want).max()
    assert err <= 1.5 * ref + 1e-3 * np.abs(want).max(), (err, ref)


@pytest.mark.parametrize("hit", ["some", "one", "none"])
def test_idle_experts_weights_never_reach_the_grouped_result(kernels, hit):
    """NaN in every weight of the experts no position chose: no visit
    names them, no block of theirs is fetched or multiplied."""
    h, m = 256, 128
    clean, layer = share(h, m), share(h, m)
    idle = np.array([e not in HITS[hit] for e in range(HELD)])
    for name in ("w_gate", "w_up", "w_down"):
        w = np.asarray(getattr(layer, name)).copy()
        w[idle] = np.nan
        setattr(layer, name, jnp.asarray(w))
    x = jnp.asarray(np.random.RandomState(2).randn(MANY, h), jnp.float32)
    ids, gates = choices(MANY, HITS[hit])
    got = np.asarray(jax.jit(layer.routed)(x, ids, gates))
    assert np.isfinite(got).all()
    want = by_einsums(clean, x, ids, gates)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * max(np.abs(want).max(), 1.0))
    assert np.isnan(by_einsums(layer, x, ids, gates)).any()


@pytest.mark.parametrize("beside", ["shared", "zero", "both"])
def test_a_shared_expert_and_zero_columns_beside_the_grouped_product(
        kernels, beside):
    """The whole layer's forward over 160 positions (router, grouped
    product, identity part, shared expert) is the einsums' forward."""
    kw = {}
    if beside in ("shared", "both"):
        kw["num_shared_experts"] = 1
    if beside in ("zero", "both"):
        kw["zero_experts"] = 4
    layer = share(256, 128, **kw)
    rs = np.random.RandomState(4)
    layer.gate = jnp.asarray(rs.randn(*layer.gate.shape), jnp.float32)
    x = jnp.asarray(rs.randn(4, 40, 256), jnp.float32)
    jaxpr = jax.make_jaxpr(layer.__call__)(x)
    assert _kernel_names(jaxpr.jaxpr) == ["grouped_expert_mlp"]
    got = np.asarray(layer(x))
    with gate_shut():
        want = np.asarray(layer(x))
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())
    ids, _ = layer.route(x.reshape(-1, 256))
    assert (np.asarray(ids) >= FIRST).any()     # the share got tokens
    if "zero_experts" in kw:
        assert (np.asarray(ids) >= E).any()     # and a zero column did


@pytest.mark.parametrize("counts,base", [
    ([0, 0, 0, 0], 0), ([5, 0, 300, 7], 0), ([5, 0, 300, 7], 256),
    ([128, 128, 0, 1], 0), ([700, 0, 0, 0], 512), ([1, 1, 1, 1], 0),
    ([0, 0, 0, 256], 0), ([130, 126, 1, 255], 256)])
def test_the_visits_cover_each_experts_rows_of_each_tile_once(counts, base):
    """``_visits`` against a walk over the sorted pairs: every (tile,
    expert) that shares a row, in order, with that expert's rows of the
    tile; the idle slots repeat the last visit and hold no row."""
    rows, tile = 256, 128
    slots = rows // tile + len(counts) - 1
    ends = np.cumsum(counts)
    t, e, lo, hi, count = jax.jit(
        lambda s, f: expert_mlp._visits(s, f, base, rows, tile, slots))(
        jnp.asarray(ends - counts, jnp.int32), jnp.asarray(ends, jnp.int32))
    owner = np.repeat(np.arange(len(counts)), counts)[base:base + rows]
    want = []
    for tile_i in range(rows // tile):
        mine = owner[tile_i * tile:(tile_i + 1) * tile]
        for expert in sorted(set(mine.tolist())):
            at = np.flatnonzero(mine == expert)
            want.append((tile_i, expert, at[0], at[-1] + 1))
    assert int(count) == len(want) <= slots
    got = list(zip(*(np.asarray(v).tolist() for v in (t, e, lo, hi))))
    assert got[:len(want)] == want
    assert all(g[2] == g[3] == 0 and (not want or g[:2] == want[-1][:2])
               for g in got[len(want):])


# ------------------------------------------------------------ the counter
@pytest.mark.parametrize("path", ["kernel", "einsums"])
@pytest.mark.parametrize("hit", list(HITS))
def test_experts_read_counts_what_the_forward_read(kernels, path, hit):
    """The kernel reads the experts hit by ANY row, live or not (rows
    that are not live still choose); the einsums read all held."""
    layer = share(64, 32)
    T = 8
    x = jnp.asarray(np.random.RandomState(5).randn(T, 64), jnp.float32)
    ids, gates = choices(T, HITS[hit])
    live = jnp.arange(T) % 2 == 0

    @jax.jit
    def run(x, ids, gates, live):
        with collect_counts(live) as box:
            layer.routed(x, ids, gates)
            layer.routed(x, ids, gates)         # two layers of one tick
        return box.total
    with gate_shut() if path == "einsums" else contextlib.nullcontext():
        counts = dict(zip(SERVING_COUNTERS,
                          np.asarray(run(x, ids, gates, live)).tolist()))
    mine = np.asarray(ids)[np.asarray(live)]
    mine = mine[(mine >= FIRST) & (mine < FIRST + HELD)]
    assert counts["moe_layer_ticks"] == 2
    assert counts["moe_local_assignments"] == 2 * mine.size
    assert counts["moe_experts_hit"] == 2 * len(set(mine.tolist()))
    assert counts["moe_experts_read"] == 2 * (
        len(HITS[hit]) if path == "kernel" else HELD)


# ------------------------------------------------------------- the engines
def _kernel_names(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                out += _kernel_names(getattr(inner, "jaxpr", inner))
    return out


def _stacked_products(jaxpr, *layers):
    """The ``dot_general`` equations of a jaxpr, nested ones included,
    that take a whole stack of expert weights of ``layers``."""
    stacks = {tuple(getattr(layer, name).shape) for layer in layers
              for name in ("w_gate", "w_up", "w_down")}
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                tuple(v.aval.shape) in stacks for v in eqn.invars):
            out.append(eqn)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                out += _stacked_products(getattr(inner, "jaxpr", inner),
                                         *layers)
    return out


def deepseek():
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                               deepseek_v2_tiny)
    return DeepseekV2ForCausalLM(deepseek_v2_tiny(
        num_hidden_layers=3, num_experts=16, num_experts_per_tok=4,
        n_group=4, topk_group=2, scoring="sigmoid",
        group_score_mode="top2_sum", norm_topk_prob=True,
        routed_scaling_factor=2.5, first_expert=4, experts_held=4))


def longcat():
    from paddle_tpu.models.longcat_flash import (LongcatFlashForCausalLM,
                                                 longcat_flash_tiny)
    return LongcatFlashForCausalLM(longcat_flash_tiny(
        num_hidden_layers=2, experts_held=4))


def mimo():
    from paddle_tpu.models.mimo_v2 import MiMoV2ForCausalLM, mimo_v2_tiny
    return MiMoV2ForCausalLM(mimo_v2_tiny(experts_held=4))


FAMILIES = {"deepseek": deepseek, "longcat": longcat, "mimo": mimo}


def expert_layers(model):
    return [layer for _, layer in model.named_sublayers()
            if isinstance(layer, ExpertShareMLP)]


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    """(model, its served streams on the einsums' path and the counters
    of their first ticks)."""
    pt.seed(0)
    model = FAMILIES[request.param]()
    with pytest.MonkeyPatch.context() as mp, gate_shut():
        mp.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        served = serve(model)
    return model, served


def serve(model):
    eng = PagedEngine(model, max_slots=4, num_blocks=64, block_size=8,
                      max_blocks_per_seq=16, chunk_prefill_tokens=16)
    rng = np.random.default_rng(7)
    for i, n in enumerate((9, 12, 5, 13)):     # a chunk each
        eng.submit(i, rng.integers(1, 200, n).tolist(), max_new_tokens=10)
    full = None
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
        if eng.stats["decode_steps"] == 6:
            # the ticks drained so far (one is still in flight): every
            # row of each was live, which the tick that runs ahead of
            # the last finishes is not
            full = dict(eng.stats)
    out = eng.results
    return [out[i] for i in range(4)], [eng.logprobs[i] for i in range(4)], \
        full


def test_greedy_streams_are_the_einsums_streams(family, kernels):
    model, (tokens, lps, before) = family
    got, got_lps, stats = serve(model)
    assert got == tokens
    for a, b in zip(got_lps, lps):
        np.testing.assert_allclose(a, b, atol=2e-5)
    # four requests fill the four slots: every row of these ticks is
    # live, so what was read is what was hit
    ticks = stats["moe_layer_ticks"] // len(expert_layers(model))
    assert stats["active_slot_steps"] == 4 * ticks > 0
    assert stats["moe_local_assignments"] == before["moe_local_assignments"]
    assert stats["moe_experts_read"] == stats["moe_experts_hit"] > 0
    assert stats["moe_experts_hit"] == before["moe_experts_hit"]
    # the einsums read every held expert of every layer and tick
    assert before["moe_experts_read"] == 4 * before["moe_layer_ticks"]
    assert stats["moe_experts_read"] < before["moe_experts_read"]


@pytest.mark.parametrize("program", ["_fused_tick", "_fused_tick_greedy"])
def test_the_tick_program_holds_no_product_over_the_stacks(family, kernels,
                                                           program):
    model, _ = family
    eng = PagedEngine(model, max_slots=4, num_blocks=64, block_size=8,
                      max_blocks_per_seq=16, chunk_prefill_tokens=16)
    eng._refresh_dev()          # the tick's device state, nothing run
    fn = getattr(eng, program)
    jaxpr = jax.jit(lambda *a: fn(*a)).trace(
        eng.params, eng.pools, eng.seen, eng._dev).jaxpr.jaxpr
    layers = expert_layers(model)
    assert layers and not _stacked_products(jaxpr, *layers)
    assert _kernel_names(jaxpr).count("expert_share_mlp") == len(layers)


# ------------------------------- prompt chunks of more than a tick's rows
def ling():
    from paddle_tpu.models.ling_hybrid import (LingHybridForCausalLM,
                                               ling_hybrid_tiny)
    return LingHybridForCausalLM(ling_hybrid_tiny(experts_held=4))


def laguna():
    from paddle_tpu.models.laguna import LagunaForCausalLM, laguna_tiny
    return LagunaForCausalLM(laguna_tiny(experts_held=4))


CHUNKED = dict(FAMILIES, ling=ling, laguna=laguna)
CHUNK = 136         # positions a prompt call: more than a tick's rows
PROMPTS = (150, 9, 200, 137)        # two chunks, a packed one, two, two


def chunked_engine(model):
    return PagedEngine(model, max_slots=4, num_blocks=128, block_size=8,
                       max_blocks_per_seq=28, chunk_prefill_tokens=CHUNK)


def serve_chunks(model):
    eng = chunked_engine(model)
    rng = np.random.default_rng(11)
    for i, n in enumerate(PROMPTS):
        eng.submit(i, rng.integers(1, 200, n).tolist(), max_new_tokens=5)
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
    return [eng.results[i] for i in range(4)], \
        [eng.logprobs[i] for i in range(4)], dict(eng.stats)


@pytest.fixture(scope="module", params=list(CHUNKED))
def chunked(request):
    """(model, its served streams with the einsums in every program)."""
    pt.seed(0)
    model = CHUNKED[request.param]()
    with pytest.MonkeyPatch.context() as mp, gate_shut():
        mp.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        served = serve_chunks(model)
    return model, served


def test_streams_through_several_chunks_are_the_einsums_streams(chunked,
                                                                kernels):
    model, (tokens, lps, before) = chunked
    got, got_lps, stats = serve_chunks(model)
    assert got == tokens
    for a, b in zip(got_lps, lps):
        np.testing.assert_allclose(a, b, atol=2e-5)
    # every expert layer of every prompt call took the grouped product
    layers = len(expert_layers(model))
    calls = stats["prefill_chunks"]
    assert calls >= 5 and stats["prefill_segments"] >= 7
    assert stats["chunk_experts_layer_calls"] == layers * calls
    assert stats["chunk_experts_grouped_calls"] == layers * calls
    assert before["chunk_experts_layer_calls"] == layers * calls
    assert before["chunk_experts_grouped_calls"] == 0


def _chunk_args(eng, program):
    from paddle_tpu.generation import paged
    if program == "_chunk_prefill_packed":
        words = 3 * CHUNK + eng._pack_segments * (eng.M + paged._SEG_WORDS)
        return (eng.params, eng.pools, eng.seen,
                jnp.zeros((words,), jnp.int32)), {}
    return ((eng.params, eng.pools, jnp.zeros((eng.M,), jnp.int32),
             jnp.zeros((1, CHUNK), jnp.int32), np.int32(0),
             np.int32(CHUNK), jnp.zeros((2,), jnp.uint32), np.float32(0.8),
             np.int32(20), np.float32(0.95), np.float32(1.1), eng.seen[0],
             np.int32(0)),
            {"bucket": CHUNK})


@pytest.mark.parametrize("program", ["_chunk_prefill",
                                     "_chunk_prefill_packed"])
def test_the_chunk_programs_hold_no_product_over_the_stacks(chunked,
                                                            kernels,
                                                            program):
    model, _ = chunked
    eng = chunked_engine(model)
    fn = getattr(eng, program)
    args, kw = _chunk_args(eng, program)
    jaxpr = jax.jit(lambda *a: fn(*a, **kw)).trace(*args).jaxpr.jaxpr
    layers = expert_layers(model)
    assert layers and not _stacked_products(jaxpr, *layers)
    assert _kernel_names(jaxpr).count("grouped_expert_mlp") == len(layers)
    assert "expert_share_mlp" not in _kernel_names(jaxpr)
    with gate_shut():
        jaxpr = jax.jit(lambda *a: fn(*a, **kw)).trace(*args).jaxpr.jaxpr
    assert len(_stacked_products(jaxpr, *layers)) == 3 * len(layers)


def test_an_engine_without_expert_layers_names_no_expert_counter():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    pt.seed(0)
    eng = PagedEngine(LlamaForCausalLM(llama_tiny()), max_slots=2,
                      num_blocks=16, block_size=8, max_blocks_per_seq=4,
                      chunk_prefill_tokens=16)
    assert "chunk_experts_layer_calls" not in eng.stats
    assert "chunk_experts_grouped_calls" not in eng.stats
