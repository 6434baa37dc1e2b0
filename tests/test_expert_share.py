"""ISSUE 26: the expert layer of one expert-parallel rank, for serving
(``parallel.moe.ExpertShareMLP``), at tiny widths in float32.

- THE SHARES ADD UP: for 4 shares of a 16-expert layer, the routed
  parts each share computes, plus the shared expert counted once, equal
  the uncut layer: the layer written out plainly here, the one share
  that holds all 16, and training's ``MoEMLP`` made dropless.
- NOTHING IS DROPPED: with every token sent to one expert the share
  still matches the plain layer, where the capacity dispatch does not.
- the counters a serving tick reads count what they say.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.parallel.moe import (SERVING_COUNTERS, ExpertShareMLP,
                                     MoEMLP, collect_counts)

H, M, E, K, T = 32, 16, 16, 4, 24
ROUTERS = {
    "softmax": dict(scoring="softmax", norm_topk_prob=False),
    "sigmoid-groups-bias": dict(scoring="sigmoid", n_group=4, topk_group=2,
                                group_score_mode="top2_sum",
                                norm_topk_prob=True,
                                routed_scaling_factor=2.5),
}


def whole(router, seed=0, **kw):
    pt.seed(seed)
    layer = ExpertShareMLP(H, M, E, K, 0, E, num_shared_experts=1,
                           **dict(ROUTERS[router], **kw))
    rs = np.random.RandomState(seed)
    layer.gate = jnp.asarray(rs.randn(H, E), jnp.float32)
    if ROUTERS[router]["scoring"] == "sigmoid":
        layer.expert_bias = jnp.asarray(0.3 * rs.randn(E), jnp.float32)
    return layer


def share(full, first, held, router):
    """The rank that holds experts first .. first+held-1 of ``full``."""
    pt.seed(1)
    part = ExpertShareMLP(H, M, E, K, first, held, num_shared_experts=1,
                          **ROUTERS[router])
    state = dict(full.state_dict())
    for k in ("w_gate", "w_up", "w_down"):
        state[k] = state[k][first:first + held]
    part.set_state_dict(state)
    return part


def plain(layer, x, router):
    """The uncut layer written out token by token, in numpy float64."""
    r = ROUTERS[router]
    p = {k: np.asarray(v, np.float64) for k, v in layer.state_dict().items()}
    silu = lambda a: a / (1 + np.exp(-a))                   # noqa: E731
    x = np.asarray(x, np.float64)
    logits = x @ p["gate"]
    if r["scoring"] == "sigmoid":
        scores = 1 / (1 + np.exp(-logits))
    else:
        z = np.exp(logits - logits.max(-1, keepdims=True))
        scores = z / z.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for t in range(len(x)):
        choice = scores[t] + p["expert_bias"]
        G = r.get("n_group", 1)
        if G > 1:
            g = choice.reshape(G, E // G)
            best = np.argsort(-np.sort(g, -1)[:, -2:].sum(-1))[
                :r["topk_group"]]
            ok = np.repeat(np.isin(np.arange(G), best), E // G)
            choice = np.where(ok, choice, -np.inf)
        chosen = np.argsort(-choice)[:K]
        gates = scores[t, chosen]
        if r["norm_topk_prob"]:
            gates = gates / gates.sum()
        gates = gates * r.get("routed_scaling_factor", 1.0)
        for e, g in zip(chosen, gates):
            out[t] += g * (silu(x[t] @ p["w_gate"][e])
                           * (x[t] @ p["w_up"][e])) @ p["w_down"][e]
        out[t] += (silu(x[t] @ p["shared_gate_proj"])
                   * (x[t] @ p["shared_up_proj"])) @ p["shared_down_proj"]
    return out


@pytest.mark.parametrize("router", list(ROUTERS))
def test_the_shares_add_up(router):
    full = whole(router)
    x = jnp.asarray(np.random.RandomState(2).randn(T, H), jnp.float32)
    want = plain(full, x, router)
    np.testing.assert_allclose(full(x), want, atol=2e-5)
    total = np.asarray(full.shared_out(x), np.float64)      # counted once
    for first in range(0, E, 4):
        part = share(full, first, 4, router)
        ids, gates = part.route(x)
        total += np.asarray(part.routed(x, ids, gates), np.float64)
    np.testing.assert_allclose(total, want, atol=2e-5)
    # training's capacity dispatch, with room for every token, is the
    # same layer: what serving leaves alone
    pt.seed(3)
    train = MoEMLP(H, M, E, top_k=K, capacity_factor=E / K,
                   num_shared_experts=1, **ROUTERS[router])
    train.set_state_dict(full.state_dict())
    np.testing.assert_allclose(train(x), want, atol=2e-5)


@pytest.mark.parametrize("router", list(ROUTERS))
def test_the_shares_add_up_on_the_kernels_path(router, monkeypatch):
    """ISSUE 33: 24 tokens are few, so under the interpreter each share
    reads only the experts its tokens hit; summed over the ranks, plus
    the shared expert once, it is still the whole layer."""
    from paddle_tpu.ops.pallas.expert_mlp import use_expert_kernel
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    full = whole(router)
    x = jnp.asarray(np.random.RandomState(2).randn(T, H), jnp.float32)
    assert use_expert_kernel(x, full.w_gate)
    want = plain(full, x, router)
    np.testing.assert_allclose(full(x), want, atol=2e-5)
    total = np.asarray(full.shared_out(x), np.float64)      # counted once
    for first in range(0, E, 4):
        part = share(full, first, 4, router)
        ids, gates = part.route(x)
        total += np.asarray(part.routed(x, ids, gates), np.float64)
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_nothing_is_dropped_whatever_the_imbalance():
    full = whole("sigmoid-groups-bias")
    bias = np.asarray(full.expert_bias).copy()
    bias[5] = 100.0                     # every token's first choice
    full.expert_bias = jnp.asarray(bias)
    x = jnp.asarray(np.random.RandomState(4).randn(T, H), jnp.float32)
    ids, _ = full.route(x)
    assert np.all(np.any(np.asarray(ids) == 5, axis=-1))
    want = plain(full, x, "sigmoid-groups-bias")
    part = share(full, 4, 4, "sigmoid-groups-bias")
    pids, pg = part.route(x)
    rest = np.zeros_like(want)
    for first in (0, 8, 12):
        other = share(full, first, 4, "sigmoid-groups-bias")
        rest += np.asarray(other.routed(x, *other.route(x)), np.float64)
    got = (np.asarray(part.routed(x, pids, pg), np.float64) + rest
           + np.asarray(full.shared_out(x), np.float64))
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the capacity dispatch at its default factor does drop here
    pt.seed(3)
    train = MoEMLP(H, M, E, top_k=K, num_shared_experts=1,
                   **ROUTERS["sigmoid-groups-bias"])
    train.set_state_dict(full.state_dict())
    assert np.abs(np.asarray(train(x)) - want).max() > 1e-2


def test_a_share_outside_the_layer_is_refused():
    with pytest.raises(ValueError):
        ExpertShareMLP(H, M, E, K, 14, 4)


def test_the_counters_count_live_rows_only(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    full = whole("softmax")
    part = share(full, 4, 4, "softmax")
    x = jnp.asarray(np.random.RandomState(5).randn(6, 2, H), jnp.float32)
    live = np.array([1, 0, 1, 1, 0, 0], bool)

    @jax.jit
    def run(x, live):
        with collect_counts(live) as box:
            part(x)
            part(x)                     # two layers of one tick
        return box.total

    ticks, assigned, hit, read = np.asarray(run(x, jnp.asarray(live)))
    ids = np.asarray(part.route(x.reshape(-1, H))[0]).reshape(6, 2, K)
    mine = (ids >= 4) & (ids < 8) & live[:, None, None]
    assert SERVING_COUNTERS == ("moe_layer_ticks", "moe_local_assignments",
                                "moe_experts_hit", "moe_experts_read")
    assert ticks == 2 and assigned == 2 * mine.sum()
    assert hit == 2 * len(set(ids[mine]))
    assert read == 2 * 4        # the einsums read every held expert
    with collect_counts(jnp.asarray(live)) as box:
        pass
    assert box.total is None            # no expert layer: nothing rides


# ---- ISSUE 41: a 512-wide router in 8 groups, 16 shares of 32 experts
WIDE = dict(scoring="sigmoid", n_group=8, topk_group=4,
            group_score_mode="top2_sum", norm_topk_prob=True,
            routed_scaling_factor=2.5)
EW, KW = 512, 8


def _wide(first=0, held=EW, seed=0, **kw):
    pt.seed(seed)
    layer = ExpertShareMLP(H, M, EW, KW, first, held, num_shared_experts=1,
                           **dict(WIDE, **kw))
    rs = np.random.RandomState(7)
    layer.gate = jnp.asarray(rs.randn(H, EW), jnp.float32)
    layer.expert_bias = jnp.asarray(0.01 * rs.randn(EW), jnp.float32)
    return layer


def _wide_share(full, first, held, **kw):
    part = _wide(first, held, seed=1, **kw)
    state = dict(full.state_dict())
    for k in ("w_gate", "w_up", "w_down"):
        state[k] = state[k][first:first + held]
    part.set_state_dict(state)
    return part


@pytest.mark.parametrize("kernel", [False, True], ids=["einsums", "kernel"])
def test_sixteen_shares_of_a_512_wide_router_add_up(kernel, monkeypatch):
    """Ling-3.0-flash's expert layer cut as its configuration cuts it:
    512 columns in 8 groups of 64, 4 groups and 8 experts a token, 16
    ranks of 32 experts (half a group each). The 16 routed parts and the
    shared expert ONCE are the uncut layer; a rank whose group a token
    did not choose adds exactly nothing for it."""
    if kernel:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    full = _wide()
    x = jnp.asarray(np.random.RandomState(2).randn(T, H), jnp.float32)
    want = np.asarray(full(x), np.float64)
    ids = np.asarray(full.route(x)[0])
    assert ids.shape == (T, KW)
    # group-limited: a token's 8 experts lie in 4 of the 8 groups
    assert all(len(set(row // 64)) <= 4 for row in ids)
    total = np.asarray(full.shared_out(x), np.float64)      # counted once
    for first in range(0, EW, 32):
        part = _wide_share(full, first, 32)
        pids, gates = part.route(x)
        assert np.array_equal(np.asarray(pids), ids)
        out = np.asarray(part.routed(x, pids, gates), np.float64)
        away = ~np.any((ids >= first) & (ids < first + 32), axis=-1)
        assert away.any() and np.all(out[away] == 0)
        total += out
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_rows_routed_here_counts_live_rows_with_a_held_choice(monkeypatch):
    """``count_rows_routed`` adds ``moe_rows_routed_here`` behind the
    four counters: the live rows of which at least one choice is held,
    a layer. A layer that is not asked counts four numbers as ever."""
    from paddle_tpu.parallel.moe import ROUTED_COUNTERS
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    assert ROUTED_COUNTERS == ("moe_rows_routed_here",)
    full = _wide()
    x = jnp.asarray(np.random.RandomState(5).randn(12, 1, H), jnp.float32)
    live = np.arange(12) % 3 != 1
    part = _wide_share(full, 0, 32, count_rows_routed=True)
    quiet = _wide_share(full, 0, 32)

    def run(layer):
        with collect_counts(jnp.asarray(live)) as box:
            layer(x)
            layer(x)
        return np.asarray(box.total)

    counted = run(part)
    ids = np.asarray(part.route(x.reshape(-1, H))[0])
    here = np.any(ids < 32, axis=-1) & live
    assert 0 < here.sum() < live.sum()
    assert counted.tolist() == run(quiet).tolist() + [2 * here.sum()]
    assert counted[1] >= counted[4]     # assignments >= rows routed here
