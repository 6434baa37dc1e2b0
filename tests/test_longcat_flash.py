"""ISSUE 30: LongCat-Flash's shortcut-connected double layer, served.

Contracts pinned here at ``longcat_flash_tiny`` widths in float32, each
against the benchmark's plain reference (``benchmarks/models/
longcat_flash.py``) on its own seeded weights, comparing LOGITS:

- FULL FORWARD: every logit of every position; switching either
  ``mla_scale_*`` factor off in the program fails the same comparison.
- THE CACHE: chunked prefill, then decode, through ``PagedEngine``'s
  TWO latent pools a layer (a prompt longer than a chunk, decode past a
  block boundary, rows of different lengths), on the dense gather and
  through the interpreted kernel.
- THE SHARES ADD UP: 8 experts + 4 zero columns as 4 shares of 2: the
  shares' routed parts plus the identity part ONCE are the reference's
  uncut layer.
- ZERO-COMPUTE EXPERTS: a token whose choices are all zero columns gets
  ``sum g_e * h0`` and touches no expert; gates are the softmax scores
  times the scaling, not renormalised; the counters count live rows.

Tolerances: both sides are float32 (the reference at ``highest``
precision, which the CPU gives the program too), so what separates them
is the order of sums: 1e-4 on logits of magnitude 0.7 leaves a factor
of 300 over the 3e-7 read here and is a five-hundredth of what a
dropped scale factor moves (0.056 and 0.074).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.parallel.moe import (SERVING_COUNTERS, ZERO_COUNTERS,
                                     ExpertShareMLP, collect_counts)

TOL = 1e-4
# the benchmark's configuration keys for the tiny model: experts 2-5 of
# 8 held, 4 zero columns, 3 choices a token
BENCH = {
    "model": "longcat_flash", "dtype": "float32", "attention_bias": False,
    "vocab_size": 256, "hidden_size": 64, "ffn_hidden_size": 128,
    "expert_ffn_hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "n_routed_experts": 4, "n_routed_experts_published": 8,
    "first_expert": 2, "zero_expert_num": 4, "moe_topk": 3,
    "routed_scaling_factor": 6, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
}
WIDTH = 12                      # router columns: 8 experts + 4 zero


@pytest.fixture(scope="module")
def ref():
    from benchmarks.harness import cell
    return cell.load_model(BENCH)


def seeded(ref, **flags):
    """The program's model on the reference's seeded weights, with a
    selection bias wide enough to change the choice at these widths
    (softmax scores over 12 columns lie near 0.08), and the attentions'
    projections times 6: drawn at 0.02 over a hidden state of 64 they
    give a tenth of what they give over the published 6144, scores near
    0 and an attention so flat that nothing in it could be told."""
    model = ref.build(dict(BENCH, **flags), 11, jax.devices()[0])
    for i, layer in enumerate(model.model.layers):
        layer.moe.expert_bias = 0.05 * jax.random.normal(
            jax.random.PRNGKey(i), (WIDTH,))
    model.set_state_dict({k: 6.0 * v for k, v in model.state_dict().items()
                          if ".self_attn." in k and k.endswith("proj.weight")},
                         strict=False)
    return model


@pytest.fixture(scope="module")
def model(ref):
    return seeded(ref)


def prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


def reference_logits(ref, params, seqs):
    """For each sequence, every position's logits sorted falling (the
    reference hands back the ``top`` best: all 256 here) and the logit
    of the token that follows."""
    rows = ref.reference_rows(params, BENCH, seqs, [1] * len(seqs),
                              [s[1:] for s in seqs], top=256)
    return [(r["top"], r["at"]) for r in rows]


def program_logits(model, seqs):
    fn, params = model.functional()
    ids = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for r, s in enumerate(seqs):        # padded behind: causal
        ids[r, :len(s)] = s
    logits = np.asarray(jax.jit(fn)(params, jnp.asarray(ids)))
    out = []
    for r, s in enumerate(seqs):
        rows = logits[r, :len(s) - 1]
        out.append((-np.sort(-rows, -1),
                    rows[np.arange(len(s) - 1), s[1:]]))
    return out


def test_the_full_forward_agrees_with_the_reference(ref, model):
    seqs = prompts(0, (23, 9))
    want = reference_logits(ref, model.functional()[1], seqs)
    for (top, at), (wtop, wat) in zip(program_logits(model, seqs), want):
        np.testing.assert_allclose(top, wtop, atol=TOL)
        np.testing.assert_allclose(at, wat, atol=TOL)
    assert np.abs(want[0][0]).max() > 0.5       # logits of magnitude 1


@pytest.mark.parametrize("flag", ["mla_scale_q_lora", "mla_scale_kv_lora"])
def test_either_scale_factor_switched_off_fails_the_comparison(ref, model,
                                                               flag):
    seqs = prompts(0, (23,))
    (_, want), = reference_logits(ref, model.functional()[1], seqs)
    (_, got), = program_logits(seeded(ref, **{flag: False}), seqs)
    assert np.abs(got - want).max() > 100 * TOL


def test_generate_over_two_static_caches_a_layer_is_the_full_forwards(
        model):
    """``generate()`` decodes in the absorbed form over ``init_kv_caches``
    (two latent caches a layer): its greedy tokens are the teacher-forced
    full forward's own."""
    ids = jnp.asarray([prompts(5, (11,))[0]])
    out = np.asarray(model.generate(ids, max_new_tokens=6, temperature=0.0))
    assert out.shape == (1, 17) and len(model.init_kv_caches(1, 8)) == 4
    fn, params = model.functional()
    logits = np.asarray(jax.jit(fn)(params, jnp.asarray(out)))
    assert logits[0, 10:16].argmax(-1).tolist() == out[0, 11:].tolist()


# ------------------------------------------------------------- the engine
def engine(model, **kw):
    base = dict(max_slots=4, num_blocks=64, block_size=8,
                max_blocks_per_seq=16, chunk_prefill_tokens=16,
                enable_prefix_cache=True)
    base.update(kw)
    return PagedEngine(model, **base)


def serve(eng, ps, n):
    for i, p in enumerate(ps):
        eng.submit(i, p, max_new_tokens=n)
    out = eng.run()
    return [{"prompt": p, "tokens": out[i], "lps": eng.logprobs[i]}
            for i, p in enumerate(ps)]


@pytest.mark.parametrize("route", ["dense", "ragged"])
def test_prefill_then_decode_through_two_pools_a_layer(ref, model, route,
                                                       monkeypatch):
    """A 37-token prompt is three chunks of 16; 12 served tokens take
    the 5-token row from block 0 into block 2 of 8; the reference reads
    the logprob the engine streamed and the logit of each served token
    beside its own best."""
    from benchmarks.harness import verify
    if route == "ragged":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    else:       # without the interpreter no kernel runs here
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    eng = engine(model)
    assert eng.decode_route() == route
    # two cache layers a layer, each ONE latent row of 32 + 8 columns
    assert [tuple(p.shape for p in layer) for layer in eng.pools] == \
        [((64, 8, 128),)] * 4
    sample = serve(eng, prompts(1, (37, 5)), n=12)
    nums = verify.numbers(ref, eng.params, BENCH, sample)
    assert nums["finite"] and nums["tokens"] == 24
    assert nums["argmax_gap_max"] < TOL and nums["logprob_rms"] < TOL
    # the tick's counters: 2 expert layers a tick, 3 choices a live row
    st = eng.stats
    assert st["moe_layer_ticks"] == 2 * st["decode_steps"]
    assert st["moe_live_choices"] == 2 * 3 * st["active_slot_steps"]
    assert 0 < st["moe_zero_choices"] < st["moe_live_choices"]
    assert st["moe_local_assignments"] + st["moe_zero_choices"] \
        <= st["moe_live_choices"]


def test_a_full_house_runs_ahead_over_both_pools(ref, model):
    """Four rows fill the four slots: each tick is dispatched with its
    predecessor undrained (ISSUE 29) and the second attention's pool is
    written under the lag like the first's."""
    from benchmarks.harness import verify
    eng = engine(model)
    sample = serve(eng, prompts(2, (9, 9, 9, 9)), n=12)
    assert eng.stats["runahead_ticks"] >= 8
    nums = verify.numbers(ref, eng.params, BENCH, sample)
    assert nums["argmax_gap_max"] < TOL and nums["logprob_rms"] < TOL


def test_prefix_adoption_covers_both_pools_bit_exactly(model):
    shared = prompts(3, (32,))[0]
    tails = prompts(4, (5, 9))
    want, = serve(engine(model), [shared + tails[1]], n=6)
    eng = engine(model)
    serve(eng, [shared + tails[0]], n=6)
    hit0 = eng.stats["prefix_hit_tokens"]
    eng.submit("again", shared + tails[1], max_new_tokens=6)
    out = eng.run()
    assert eng.stats["prefix_hit_tokens"] - hit0 == 32
    assert out["again"] == want["tokens"]
    assert eng.logprobs["again"] == want["lps"]     # bitwise


# -------------------------------------------------------- the expert layer
H, M, E, Z, K, T = 32, 16, 8, 4, 3, 24
CFG = {"moe_topk": K, "routed_scaling_factor": 6.0}


def layer(first, held, seed=0):
    pt.seed(seed)
    return ExpertShareMLP(H, M, E, K, first, held, zero_experts=Z,
                          scoring="softmax", routed_scaling_factor=6.0)


@pytest.fixture(scope="module")
def whole():
    full = layer(0, E)
    rs = np.random.RandomState(0)
    full.gate = jnp.asarray(0.2 * rs.randn(H, E + Z), jnp.float32)
    full.expert_bias = jnp.asarray(0.05 * rs.randn(E + Z), jnp.float32)
    return full, jnp.asarray(rs.randn(T, H), jnp.float32)


def share(full, first, held):
    part = layer(first, held, seed=1)
    state = dict(full.state_dict())
    for k in ("w_gate", "w_up", "w_down"):
        state[k] = state[k][first:first + held]
    part.set_state_dict(state)
    return part


def test_the_shares_and_the_identity_part_once_are_the_uncut_layer(ref,
                                                                   whole):
    full, x = whole
    p = full.state_dict()
    with jax.default_matmul_precision("highest"):
        gates = ref._route(x[None], p["gate"], p["expert_bias"], cfg=CFG)[0]
        want = jnp.sum(gates[:, E:], -1, keepdims=True) * x
        for e in range(E):
            want = want + gates[:, e, None] * ref._swiglu(
                x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], None)
    ids, g = full.route(x)
    got = full.zero_out(x, ids, g)          # every rank alike: once
    for first in range(0, E, 2):
        got = got + share(full, first, 2).routed(x, ids, g)
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_allclose(full(x), want, atol=TOL)
    # both kinds of column were chosen, so both parts were compared
    assert 0 < int(jnp.sum(ids >= E)) < ids.size


def test_gates_are_the_scores_times_the_scaling_not_renormalised(whole):
    full, x = whole
    ids, g = full.route(x)
    scores = jax.nn.softmax(x @ full.gate, axis=-1)
    np.testing.assert_allclose(
        g, 6.0 * jnp.take_along_axis(scores, ids, -1), rtol=1e-5)
    assert float(jnp.max(jnp.sum(g, -1))) < 0.9 * 6.0   # not summing to 6
    # the bias moves the choice and not the gate
    plain = jax.lax.top_k(scores, K)[1]
    assert bool(jnp.any(jnp.sort(ids, -1) != jnp.sort(plain, -1)))


def test_choices_that_all_fall_on_zero_columns_touch_no_expert(
        whole, monkeypatch):
    # no kernel: the einsums read every held expert, hit or not
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    full, x = whole
    part = share(full, 0, E)
    part.expert_bias = full.expert_bias.at[E:].add(10.0)
    ids, g = part.route(x)
    assert bool(jnp.all(ids >= E))
    live = jnp.ones((T,), bool)
    with collect_counts(live) as box:
        out = part(x)
    np.testing.assert_allclose(
        out, jnp.sum(g, -1, keepdims=True) * x, rtol=1e-6)
    assert bool(jnp.all(part.routed(x, ids, g) == 0))
    counts = dict(zip(SERVING_COUNTERS + ZERO_COUNTERS, box.total.tolist()))
    assert counts == {"moe_layer_ticks": 1, "moe_local_assignments": 0,
                      "moe_experts_hit": 0, "moe_experts_read": E,
                      "moe_live_choices": T * K, "moe_zero_choices": T * K}


def test_the_counters_count_live_rows_only(whole):
    full, x = whole
    live = jnp.arange(T) % 3 == 0
    ids, _ = full.route(x)
    with collect_counts(live) as box:
        full(x)
    counts = dict(zip(SERVING_COUNTERS + ZERO_COUNTERS, box.total.tolist()))
    n = int(jnp.sum(live))
    zero = int(jnp.sum((ids >= E) & live[:, None]))
    assert counts["moe_live_choices"] == n * K
    assert counts["moe_zero_choices"] == zero and 0 < zero < n * K
    # one share holds every expert: each live choice is one or the other
    assert counts["moe_local_assignments"] == n * K - zero


def test_a_layer_without_zero_columns_counts_what_it_did(whole):
    """The DeepSeek family's programs keep to `SERVING_COUNTERS`."""
    pt.seed(0)
    plain = ExpertShareMLP(H, M, E, K, 0, E, scoring="softmax")
    with collect_counts(jnp.ones((T,), bool)) as box:
        plain(whole[1])
    assert box.total.shape == (len(SERVING_COUNTERS),)
