"""ISSUE 38: Olmo-Hybrid's Gated-DeltaNet linear-attention layers beside
full-attention ones, served through state that belongs to a SLOT.

Contracts pinned here at ``olmo_hybrid_tiny`` widths in float32 (three
linear layers, one full; 8 linear heads, keys 8 and values 16 wide, 4
taps; 4 attention heads of 16, a query group of one), each against the
benchmark's plain reference (``benchmarks/models/olmo_hybrid.py``: the
recurrence a scan over positions) on its own seeded weights, comparing
LOGITS and streamed logprobs:

- THE RECURRENCE: the chunkwise-parallel form and the decode step against
  the position-by-position scan, at lengths that are no multiple of the
  sub-chunk, with ``beta`` across (0, 2), decays near 0 and near 1,
  repeated keys, a carried state, segments packed side by side.
- FULL FORWARD: every logit of every position; a dropped factor 2 of
  ``beta``, a dropped tap, a decay pinned at 1 and another norm
  epsilon each fail the comparison.
- THE ENGINE: chunked prefill then decode through ``PagedEngine``; a
  packed call of three prompts equals three calls; a slot reused after a
  finish, a preemption and ``hard_reset`` starts from zero; dead rows'
  state untouched; run-ahead streams equal drain-first ones; each
  refusal raises.

Tolerances: both sides are float32 (the reference at ``highest``
precision, which the CPU gives the program too): 1e-4 on logits and
logprobs leaves a factor of 50 over the 2e-6 read here; the departures
move 3e-3 to 0.3.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.ops.paged_cache import (CacheLayer, SlotState, StateLayer,
                                        state_step_route)
from paddle_tpu.ops import delta_rule

TOL = 1e-4
BENCH = {
    "model": "olmo_hybrid", "dtype": "float32", "state_dtype": "float32",
    "attention_bias": False, "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 8, "linear_num_value_heads": 8,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
}


@pytest.fixture(scope="module")
def ref():
    from benchmarks.harness import cell
    return cell.load_model(BENCH)


@pytest.fixture(scope="module")
def model(ref):
    return ref.build(BENCH, 11, jax.devices()[0])


def _engine(model, **kw):
    kw.setdefault("chunk_prefill_tokens", 16)
    return PagedEngine(model, max_slots=kw.pop("max_slots", 3),
                       num_blocks=96, block_size=4, max_blocks_per_seq=24,
                       **kw)


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


def _lp_error(ref, model, prompt, tokens, lps, config=BENCH):
    """Streamed logprobs and tokens against the reference's logits of
    prompt + tokens: (largest |logprob difference|, tokens all the
    reference's first choice)."""
    rows = ref.reference_rows(dict(model.functional()[1]), config,
                              [prompt + tokens], [len(prompt)], [tokens],
                              vocab_block=256)[0]
    return (float(np.abs(np.asarray(lps) - (rows["at"] - rows["lse"])).max()),
            bool((rows["best_token"] == np.asarray(tokens)).all()))


# ------------------------------------------------------------ the recurrence
def _inputs(T, H=3, dk=8, dv=16, seed=0, near=None):
    """q, k normalised; beta across (0, 2); log-decays from almost 0 to
    -8 (``near``: 0 pins them at 1, 1 lets them vanish)."""
    rng = np.random.default_rng(seed)
    q = delta_rule.l2_normalize(jnp.asarray(
        rng.normal(size=(T, H, dk)), jnp.float32)) * dk ** -0.5
    k = delta_rule.l2_normalize(jnp.asarray(
        rng.normal(size=(T, H, dk)), jnp.float32))
    v = jnp.asarray(rng.normal(size=(T, H, dv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.01, 1.99, (T, H)), jnp.float32)
    g = -jnp.asarray(np.exp(rng.uniform(-9, 2.1, (T, H))), jnp.float32)
    if near == 0:
        g = g * 1e-4
    if near == 1:
        g = g - 5.0
    S0 = jnp.asarray(rng.normal(size=(H, dk, dv)), jnp.float32)
    return q, k, v, g, beta, S0


@pytest.mark.parametrize("T,sub,near", [
    (50, 64, None), (64, 64, None), (100, 64, None), (37, 16, None),
    (131, 32, None), (70, 64, 0), (70, 64, 1), (1, 64, None)])
def test_the_chunkwise_form_is_the_scan(T, sub, near):
    q, k, v, g, beta, S0 = _inputs(T, near=near)
    o_ref, S_ref = delta_rule.gated_delta_scan(q, k, v, g, beta, S0)
    o, S = delta_rule.gated_delta_chunk(q, k, v, g, beta, S0, sub=sub)
    assert np.abs(o - o_ref).max() < 1e-5
    assert np.abs(S[0] - S_ref).max() < 1e-5


def test_repeated_keys_do_not_blow_the_solve_up():
    """The same key at every position with beta near 2: the triangular
    system's off-diagonal is all 2s, where a power series of it reaches
    1e6 before it cancels; forward substitution stays exact."""
    q, k, v, g, beta, S0 = _inputs(64)
    k = jnp.broadcast_to(k[:1], k.shape)
    beta = jnp.full_like(beta, 1.99)
    g = jnp.zeros_like(g)
    o_ref, S_ref = delta_rule.gated_delta_scan(q, k, v, g, beta, S0)
    o, S = delta_rule.gated_delta_chunk(q, k, v, g, beta, S0)
    assert np.abs(o - o_ref).max() < 1e-4 * max(1, np.abs(o_ref).max())
    assert np.abs(S[0] - S_ref).max() < 1e-4 * max(1, np.abs(S_ref).max())


def test_segments_neither_share_state_nor_decay():
    """Three prompts side by side and padding behind the last: each
    segment's outputs and final state are those of the segment alone
    from zero, the first's from the carried state."""
    lens, T = (23, 5, 41), 80
    q, k, v, g, beta, S0 = _inputs(T, seed=3)
    seg = jnp.asarray(np.repeat([0, 1, 2, 2], lens + (T - sum(lens),)))
    real = jnp.arange(T) < sum(lens)
    g = jnp.where(real[:, None], g, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0)
    o, S = delta_rule.gated_delta_chunk(q, k, v, g, beta, S0, seg,
                                        segments=4, sub=16)
    at = 0
    for s, n in enumerate(lens):
        sl = slice(at, at + n)
        o_ref, S_ref = delta_rule.gated_delta_scan(
            q[sl], k[sl], v[sl], g[sl], beta[sl],
            S0 if s == 0 else jnp.zeros_like(S0))
        assert np.abs(o[sl] - o_ref).max() < 1e-5
        assert np.abs(S[s] - S_ref).max() < 1e-5
        at += n


@pytest.mark.parametrize("H,dv,hp", [(8, 16, 8), (6, 16, 1), (30, 192, 2),
                                     (4, 64, 2)])
def test_the_decode_step_is_one_position_of_the_scan(H, dv, hp):
    """In the stored form (``hp`` heads side by side in a row, whole
    lane tiles where the head count allows), a dead row untouched."""
    assert delta_rule.state_lane_heads(H, dv) == hp
    R, dk = 3, 8
    rows = [_inputs(1, H, dk, dv, seed=r) for r in range(R)]
    q, k, v, g, beta, S0 = (jnp.stack([r[i] for r in rows])
                            for i in range(6))
    live = jnp.asarray([True, False, True])
    S, o = delta_rule.delta_state_step(
        delta_rule.pack_state(S0, hp), q[:, 0], k[:, 0], v[:, 0],
        jnp.exp(g[:, 0]), beta[:, 0], live)
    S = delta_rule.unpack_state(S, hp)
    for r in range(R):
        o_ref, S_ref = delta_rule.gated_delta_scan(*rows[r])
        assert np.abs(o[r] - o_ref[0]).max() < 1e-5
        assert np.abs(S[r] - (S_ref if live[r] else S0[r])).max() < 1e-5


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("R,H,dk,dv,live,fit,taken", [
    (4, 8, 8, 16, "all", None, True),   # the tiny twin: 8 heads a row
    (4, 8, 8, 16, "some", None, True),
    (1, 8, 8, 16, "all", None, True),   # one row
    (4, 4, 16, 64, "some", 2, True),    # two grid steps of two slots
    (5, 4, 16, 64, "some", 2, True),    # rows no multiple of 2: of one
    (3, 30, 96, 192, "some", 1, True),  # the cell's heads: a slot a step
    (3, 2, 8, 128, "some", None, True),     # one head a row, whole tiles
    (3, 6, 8, 16, "some", None, False),     # one head a row, 16 lanes
    (3, 8, 12, 16, "some", None, False),    # keys of no whole sublane tile
])
def test_the_state_kernel_is_the_jnp_body_and_the_scan(
        kernels, monkeypatch, R, H, dk, dv, live, fit, taken):
    """ISSUE 39: the one-pass Pallas kernel (the interpreter here) over
    three positions, each against the jnp body of ``delta_state_step``
    (the gate held shut) from the same state and against the scan; a
    row that is not live keeps its state bit for bit; a geometry the
    gate refuses takes the body (no kernel call in the jaxpr) and reads
    the same numbers. ``fit``: the slots whose four buffers the
    kernel's VMEM budget holds (None: the budget as it is, which holds
    every row of these small states in one grid step)."""
    from paddle_tpu.ops.pallas import delta_state
    hp = delta_rule.state_lane_heads(H, dv)
    slot = H * dk * dv * 4
    if fit:
        monkeypatch.setattr(delta_state, "_VMEM_STATE", 4 * fit * slot)
    assert delta_state._rows_per_step(R, slot) \
        == (R if fit is None else fit if R % fit == 0 else 1)
    T = 3
    rows = [_inputs(T, H, dk, dv, seed=10 + r) for r in range(R)]
    q, k, v, g, beta, S0 = (jnp.stack([r[i] for r in rows])
                            for i in range(6))
    alive = jnp.ones((R,), bool) if live == "all" \
        else jnp.arange(R) % 3 != 1
    S = delta_rule.pack_state(S0, hp)
    assert delta_state.use_state_kernel(S) == taken
    step = lambda *a: delta_rule.delta_state_step(*a)       # noqa: E731
    jaxpr = str(jax.make_jaxpr(step)(S, q[:, 0], k[:, 0], v[:, 0],
                                     jnp.exp(g[:, 0]), beta[:, 0], alive))
    assert ("pallas_call" in jaxpr) == taken
    for t in range(T):
        a = (q[:, t], k[:, t], v[:, t], jnp.exp(g[:, t]), beta[:, t], alive)
        S_new, o = jax.jit(step)(S, *a)
        with monkeypatch.context() as m:
            m.setattr(delta_state, "use_state_kernel", lambda _S: False)
            S_body, o_body = jax.jit(lambda *a: step(*a))(S, *a)
        assert np.abs(S_new - S_body).max() < 1e-6
        assert np.abs(o - o_body).max() < 1e-6
        dead = ~np.asarray(alive)
        assert np.array_equal(np.asarray(S_new)[dead], np.asarray(S)[dead])
        S = S_new
    S = delta_rule.unpack_state(S, hp)
    for r in range(R):
        o_ref, S_ref = delta_rule.gated_delta_scan(*rows[r])
        if alive[r]:
            assert np.abs(S[r] - S_ref).max() < 1e-5
            assert np.abs(o[r] - o_ref[-1]).max() < 1e-5
        else:
            assert np.array_equal(S[r], S0[r])


def test_the_convolution_carries_its_tail_and_keeps_segments_apart():
    rng = np.random.default_rng(0)
    T, C = 30, 12
    u = jnp.asarray(rng.normal(size=(T, C)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(C, 4)), jnp.float32)

    def plain(x, tail):
        ext = np.concatenate([tail, x])
        return sum(ext[j:j + len(x)] * np.asarray(w)[:, j] for j in range(4))
    zero = np.zeros((3, C), np.float32)
    # one sequence in two chunks: the second reads the first's tail
    y1, t1 = delta_rule.conv_chunk(u[:17], w, jnp.asarray(zero))
    y2, t2 = delta_rule.conv_chunk(u[17:], w, t1[0])
    whole = plain(np.asarray(u), zero)
    assert np.abs(np.concatenate([y1, y2]) - whole).max() < 1e-5
    assert np.array_equal(t2[0], u[-3:])
    # a chunk whose last 5 positions are padding leaves the tail at its
    # last real position; a segment of 2 tokens keeps a zero in front
    seg = jnp.asarray([0] * 10 + [1] * 2 + [2] * 18)
    ends = jnp.asarray([9, 11, 24])
    y, tails = delta_rule.conv_chunk(u, w, jnp.asarray(zero), seg, ends)
    assert np.abs(y[:10] - plain(np.asarray(u[:10]), zero)).max() < 1e-5
    assert np.abs(y[10:12] - plain(np.asarray(u[10:12]), zero)).max() < 1e-5
    assert np.abs(y[12:] - plain(np.asarray(u[12:]), zero)).max() < 1e-5
    assert np.array_equal(tails[0], u[7:10])
    assert np.array_equal(tails[1], np.concatenate([zero[:1], u[10:12]]))
    assert np.array_equal(tails[2], u[22:25])
    # one position a row, a dead row's tail kept
    tail = jnp.asarray(rng.normal(size=(2, 3, C)), jnp.float32)
    y, new = delta_rule.conv_step(u[:2], w, tail, jnp.asarray([True, False]))
    assert np.abs(y[0] - plain(np.asarray(u[:1]), np.asarray(tail[0]))
                  ).max() < 1e-5
    assert np.array_equal(new[0], jnp.concatenate([tail[0, 1:], u[:1]]))
    assert np.array_equal(new[1], tail[1])


# ------------------------------------------------------------ full forward
def test_every_logit_of_the_no_cache_forward(ref, model):
    fn, params = model.functional()
    ids = np.asarray(_prompts([83, 83], seed=4))
    logits = np.asarray(fn(params, jnp.asarray(ids)))
    for r in range(2):
        rows = ref.reference_rows(dict(params), BENCH, [ids[r].tolist()],
                                  [1], [ids[r, 1:].tolist()],
                                  vocab_block=256)[0]
        lse = np.log(np.exp(logits[r, :-1]).sum(-1))
        assert np.abs(logits[r, :-1].max(-1) - rows["best"]).max() < TOL
        assert np.abs(lse - rows["lse"]).max() < TOL
        at = np.take_along_axis(logits[r, :-1], ids[r, 1:, None], -1)[:, 0]
        assert np.abs(at - rows["at"]).max() < TOL


@pytest.mark.parametrize("told", ["beta", "tap", "decay", "eps"])
def test_a_dropped_term_fails_the_same_comparison(ref, model, told):
    """The reference told of a model one term of the linear layer away
    from the served one."""
    fn, params = model.functional()
    ids = _prompts([61], seed=5)[0]
    logits = np.asarray(fn(params, jnp.asarray([ids])))[0, :-1]
    cfg, w = dict(BENCH), dict(params)
    if told == "beta":
        cfg["linear_allow_neg_eigval"] = False
    elif told == "eps":
        cfg["rms_norm_eps"] = 1e-2
    elif told == "tap":
        w = {k: v.at[:, 0].set(0) if k.endswith(".conv_weight") else v
             for k, v in w.items()}
    elif told == "decay":
        w = {k: jnp.full_like(v, -30.0) if k.endswith(".A_log") else v
             for k, v in w.items()}
    rows = ref.reference_rows(w, cfg, [ids], [1], [ids[1:]],
                              vocab_block=256)[0]
    assert np.abs(logits.max(-1) - rows["best"]).max() > 20 * TOL


# --------------------------------------------------------------- the engine
def test_the_model_says_what_each_layer_keeps(model):
    layers = model.paged_cache_layers()
    assert [type(x) for x in layers] == [StateLayer] * 3 + [CacheLayer]
    assert layers[0].arrays == (((1, 8, 128), jnp.float32),
                                ((3, 256), jnp.float32))
    assert layers[3] == CacheLayer(((4, 16), (4, 16)))
    eng = _engine(model)
    assert [tuple(a.shape for a in p) for p in eng.pools] == \
        [((3, 1, 8, 128), (3, 3, 256))] * 3 \
        + [((96, 4, 64), (96, 4, 64)), ((2,),)]
    assert eng.pools[0][0].dtype == jnp.float32
    jax.block_until_ready(eng.pools)
    assert all(k in eng.stats for k in (
        "state_layer_ticks", "state_rows_updated", "state_resets",
        "state_carries"))
    assert "state_resets" in eng.health()


@pytest.mark.parametrize("mode", ["chunked", "whole", "host"])
def test_prefill_then_decode_against_the_reference(ref, model, mode):
    """Prompts of one to four chunks (none a multiple of the chunk), six
    requests over three slots so that every slot is reused."""
    kw = {"chunked": {}, "whole": {"chunk_prefill_tokens": None},
          "host": {"fused_tick": False}}[mode]
    eng = _engine(model, **kw)
    prompts = _prompts([5, 37, 16, 23, 9, 61])
    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new_tokens=8)
    res = eng.run()
    for i, p in enumerate(prompts):
        err, same = _lp_error(ref, model, p, res[i], eng.logprobs[i])
        assert err < TOL and same, (i, err)
    st = eng.stats
    if mode == "chunked":
        # chunks of 16: 1 + 3 + 1 + 2 + 1 + 4 calls, six from zero
        assert (st["state_resets"], st["state_carries"]) == (6, 6)
        assert st["state_layer_ticks"] == 3 * st["decode_steps"]
        # every state layer of every tick by the one route (the suite
        # runs with the interpreter on or off by what was collected)
        assert st["state_kernel_ticks"] == st["state_layer_ticks"] * (
            state_step_route(eng.pools[0][0]) == "kernel")
        assert st["state_rows_updated"] == 3 * 6 * 7
        assert st["runahead_ticks"] > 0
    elif mode == "whole":
        assert (st["state_resets"], st["state_carries"]) == (6, 0)


@pytest.mark.parametrize("route", ["kernel", "fusions"])
def test_the_engine_counts_the_ticks_that_took_the_state_kernel(
        ref, model, monkeypatch, route):
    """ISSUE 39: under the interpreter the tiny twin's state (one row of
    8 x 128 a slot: whole tiles) takes the kernel in every state layer
    of every tick; without it (and without a TPU) the jnp body's two
    fusions serve. The engine says which (``state_kernel_ticks``), and
    the stream is the reference's either way."""
    if route == "kernel":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    eng = _engine(model)
    assert state_step_route(eng.pools[0][0]) == route
    prompts = _prompts([5, 37, 16, 23], seed=12)
    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new_tokens=8)
    res = eng.run()
    for i, p in enumerate(prompts):
        err, same = _lp_error(ref, model, p, res[i], eng.logprobs[i])
        assert err < TOL and same, (i, err)
    st = eng.stats
    assert st["state_layer_ticks"] == 3 * st["decode_steps"] > 0
    assert st["state_kernel_ticks"] == \
        (st["state_layer_ticks"] if route == "kernel" else 0)
    assert "state_kernel_ticks" in eng.health()


def test_a_packed_call_of_three_prompts_equals_three_calls(ref, model):
    prompts = _prompts([7, 4, 5], seed=7)       # 16 positions hold all
    together = _engine(model)
    for i, p in enumerate(prompts):
        together.submit(i, p, max_new_tokens=6)
    together.run()
    assert together.stats["prefill_segments"] == 3
    assert together.stats["prefill_chunks"] == 1
    for i, p in enumerate(prompts):
        alone = _engine(model)
        alone.submit(i, p, max_new_tokens=6)
        alone.run()
        assert alone.results[i] == together.results[i]
        assert np.abs(np.asarray(alone.logprobs[i])
                      - np.asarray(together.logprobs[i])).max() < 2e-5
        err, same = _lp_error(ref, model, p, together.results[i],
                              together.logprobs[i])
        assert err < TOL and same


def _poison(eng):
    """Every state array filled with NaN: a slot that did not start
    from zero would stream them."""
    eng.pools = [tuple(jnp.full_like(a, jnp.nan) for a in p)
                 if isinstance(layer, StateLayer) else p
                 for layer, p in zip(eng._layout, eng.pools)] \
        + eng.pools[len(eng._layout):]


@pytest.mark.parametrize("how", ["finish", "hard_reset", "poison"])
def test_a_reused_slot_starts_from_zero(ref, model, how):
    eng = _engine(model, max_slots=1)
    first, second = _prompts([21, 37], seed=9)
    eng.submit("a", first, max_new_tokens=5)
    eng.run()
    if how == "hard_reset":
        eng.hard_reset()
        assert all(float(jnp.abs(a).max()) == 0 for p in eng.pools[:3]
                   for a in p)
    elif how == "poison":
        _poison(eng)
    eng.submit("b", second, max_new_tokens=5)
    eng.run()
    err, same = _lp_error(ref, model, second, eng.results["b"],
                          eng.logprobs["b"])
    assert err < TOL and same


def test_a_preempted_request_restarts_from_zero(ref, model):
    """Two requests over a pool that holds one and a half: the younger
    is requeued with its tokens folded into its prompt and prefilled
    again from position 0; its stream is the uninterrupted one."""
    prompts = _prompts([30, 30], seed=11)
    tight = PagedEngine(model, max_slots=2, num_blocks=20, block_size=4,
                        max_blocks_per_seq=24, chunk_prefill_tokens=16)
    for i, p in enumerate(prompts):
        tight.submit(i, p, max_new_tokens=24)
    tight.run()
    assert tight.stats["preemptions"] > 0
    for i, p in enumerate(prompts):
        err, same = _lp_error(ref, model, p, tight.results[i],
                              tight.logprobs[i])
        assert err < TOL and same


def test_dead_rows_state_is_untouched(model):
    """A decode tick updates live rows only: slot 1 stays free and slot
    2 sits mid-prefill while slot 0 decodes; their arrays are bitwise
    what they were."""
    eng = _engine(model)
    eng.submit("a", _prompts([9])[0], max_new_tokens=12)
    for _ in range(3):
        eng.step()
    eng._drain_pending()
    assert eng.slots[0] is not None and eng.slots[0].tokens
    _poison(eng)            # slot 0's stream is lost; the others tell
    before = [[np.asarray(a) for a in p] for p in eng.pools[:3]]
    eng._decode_fused([0])
    eng._drain_pending()
    for p, old in zip(eng.pools[:3], before):
        for a, b in zip(p, old):
            assert np.array_equal(np.asarray(a)[1:], b[1:], equal_nan=True)


def test_run_ahead_streams_equal_drain_first(model):
    """A full house runs ahead (tick N+1 dispatched before N is
    drained, no host read of the state); the same requests one at a
    time never do."""
    prompts = _prompts([19, 33, 8], seed=13)
    full = _engine(model)
    one = _engine(model, max_slots=4)       # a free slot: drain first
    for i, p in enumerate(prompts):
        full.submit(i, p, max_new_tokens=12)
        one.submit(i, p, max_new_tokens=12)
    full.run()
    one.run()
    assert full.stats["runahead_ticks"] > 0
    assert one.stats["runahead_ticks"] == 0
    for i in range(len(prompts)):
        assert one.results[i] == full.results[i]
        assert np.abs(np.asarray(one.logprobs[i])
                      - np.asarray(full.logprobs[i])).max() < 2e-5


def test_decode_route_asks_the_kv_layers(model, monkeypatch):
    from paddle_tpu.generation import paged
    asked = []

    def route(q, kp, kv_heads):
        asked.append((q.shape, kp.shape, kv_heads))
        return "ragged"
    monkeypatch.setattr(paged, "paged_decode_route", route)
    assert _engine(model).decode_route() == "ragged"
    assert asked == [((3, 1, 4, 16), (96, 4, 64), 4)]


@pytest.mark.parametrize("what", ["prefix", "spec", "spill", "export"])
def test_what_cannot_hold_over_state_layers_raises(model, what):
    if what == "prefix":
        with pytest.raises(ValueError, match="recurrent state"):
            _engine(model, enable_prefix_cache=True)
    elif what == "spec":
        with pytest.raises(ValueError, match="recurrent state"):
            _engine(model, spec_tokens=2)
    elif what == "spill":
        with pytest.raises(ValueError, match="recurrent state"):
            _engine(model).attach_spill(object())
        _engine(model).attach_spill(None)       # detaching is nothing
    else:
        with pytest.raises(ValueError, match="recurrent state"):
            _engine(model)._spill_geometry()


def test_the_view_is_a_pytree_and_carries_its_arrays():
    view = SlotState((jnp.zeros((2, 3)), jnp.ones((2, 4))),
                     seq_lens=jnp.arange(2))
    again = jax.jit(lambda v: v._replace(arrays=tuple(
        a + 1 for a in v.arrays)))(view)
    assert isinstance(again, SlotState) and again.slots is None
    assert float(again.pool[0].sum()) == 6.0
