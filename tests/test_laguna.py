"""ISSUE 46: Laguna's window and full attention layers in one model, a
query head count a layer kind over the same kv heads, a sigmoid gate a
head, a rotary scheme a kind, and experts beside a shared one, served
through a cache that keeps a window layer's band only.

Contracts pinned here at ``laguna_tiny`` widths in float32 (one full
layer of 6 query heads with a dense FFN, two window layers of 10 with 8
experts top-3 and a shared one; 2 kv heads of 16 columns, so groups of 3
and 5; window 12; YaRN on the first 8 columns of a full layer's heads
with its factor on cos and sin, plain rotary on all 16 in a window
layer), each against the benchmark's plain reference
(``benchmarks/models/laguna.py``: the band a mask) on its own seeded
weights, comparing LOGITS:

- FULL FORWARD: every logit of every position at contexts five windows
  deep; a dropped gate, a band off by one, plain rotary in YaRN's place,
  its factor left out, YaRN over the whole head, one head count for both
  kinds, a sigmoid router, an unscaled routed sum and bfloat16 in
  float32's place each fail the same comparison.
- THE CACHE: chunked prefill, then decode, through ``PagedEngine``: a
  full layer's pool by the allocator's table, a window layer's a RING of
  5 pages a slot that a 70-token prompt wraps three times, the prompt's
  later chunks through the run-walking chunk attention; on the dense
  gather and through the interpreted kernel; on a full house.
- THE KERNEL: interpret mode against a dense ``jax.numpy`` attention at
  the published head size and query groups of 6 and 9, over a whole
  table and over a ring.
- THE SHARES ADD UP, the shared expert counted once.

Tolerances: both sides are float32 (the reference at ``highest``
precision, which the CPU gives the program too), so what separates them
is the order of sums: 1e-4 on logits of magnitude 0.4 leaves a factor of
100 over the 1e-6 read here. What the departures move is asserted at 20
times the tolerance; the smallest read here is bfloat16's 0.004.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.ops.paged_cache import CacheLayer
from paddle_tpu.parallel.moe import ExpertShareMLP

TOL = 1e-4
FULL, SLIDING = "full_attention", "sliding_attention"
# the benchmark's configuration keys for the tiny model: experts 2-5 of
# 8 held, 3 choices a token
BENCH = {
    "model": "laguna", "dtype": "float32", "attention_bias": False,
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 6,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 12,
    "layer_types": [FULL, SLIDING, SLIDING],
    "num_attention_heads_per_layer": [6, 10, 10],
    "mlp_layer_types": ["dense", "sparse", "sparse"],
    "gating": "per-head",
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 8,
               "original_max_position_embeddings": 32, "beta_slow": 1,
               "beta_fast": 32, "attention_factor": 1.4852030263919618,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}},
    "num_experts": 4, "num_experts_published": 8, "first_expert": 2,
    "num_experts_per_tok": 3, "router_score": "softmax",
    "norm_topk_prob": True, "moe_routed_scaling_factor": 2.5,
    "moe_router_logit_softcapping": 0, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
}


def bench(**flags):
    """``BENCH`` with top-level keys replaced; a ``rope_full`` flag
    updates the full layers' rotary group."""
    cfg = copy.deepcopy(BENCH)
    cfg["rope_parameters"][FULL].update(flags.pop("rope_full", {}))
    cfg.update(flags)
    return cfg


@pytest.fixture(scope="module")
def ref():
    from benchmarks.harness import cell
    return cell.load_model(BENCH)


def seeded(ref, **flags):
    """The program's model on the reference's seeded weights, the
    attentions' projections times 6 (drawn at 0.02 over a hidden state
    of 64 they give scores near 0 and an attention so flat that nothing
    in it could be told; the gate's projection too, so that the gates
    leave 0.5), the routers' times 20 (the softmax over 8 then prefers
    some experts) and the routed experts' times 3 (their part of a
    layer's result is then as large as the shared expert's)."""
    model = ref.build(bench(**flags), 11, jax.devices()[0])
    sd = model.state_dict()
    model.set_state_dict(
        {k: 6.0 * v for k, v in sd.items()
         if ".self_attn." in k and k.endswith("proj.weight")}
        | {k: 20.0 * v for k, v in sd.items() if k.endswith("mlp.gate")}
        | {k: 3.0 * v for k, v in sd.items() if ".mlp.w_" in k},
        strict=False)
    return model


@pytest.fixture(scope="module")
def model(ref):
    return seeded(ref)


def prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


def reference_logits(ref, params, seqs, config=BENCH):
    rows = ref.reference_rows(params, config, seqs, [1] * len(seqs),
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
    seqs = prompts(0, (61, 9))          # five windows deep, and under one
    want = reference_logits(ref, model.functional()[1], seqs)
    for (top, at), (wtop, wat) in zip(program_logits(model, seqs), want):
        np.testing.assert_allclose(top, wtop, atol=TOL)
        np.testing.assert_allclose(at, wat, atol=TOL)
    assert np.abs(want[0][0]).max() > 0.3
    sd = model.state_dict()
    # a head count a layer kind over the same two kv heads, a gate a head
    assert [sd[f"model.layers.{i}.self_attn.{p}_proj.weight"].shape[1]
            for i in range(3) for p in "qkg"] == [
        96, 32, 6, 160, 32, 10, 160, 32, 10]
    # no selection bias in this router
    assert all(float(jnp.abs(v).max()) == 0.0
               for k, v in sd.items() if k.endswith("expert_bias"))


@pytest.mark.parametrize("fault", [
    dict(gating=False),                         # a dropped gate
    dict(sliding_window=13),                    # a band off by one
    dict(rope_full=dict(rope_type="default")),  # plain rotary for YaRN
    dict(rope_full=dict(attention_factor=1.0)),     # its factor left out
    dict(rope_full=dict(partial_rotary_factor=1.0)),    # the whole head
    dict(router_score="sigmoid"),               # the other router
    dict(moe_routed_scaling_factor=1.0),        # an unscaled routed sum
    dict(dtype="bfloat16"),                     # the precision below
], ids=lambda f: str(next(iter(f.values())))[:40] + next(iter(f)))
def test_each_departure_fails_the_comparison(ref, model, fault):
    seqs = prompts(0, (61,))
    (_, want), = reference_logits(ref, model.functional()[1], seqs)
    broken = seeded(ref, **fault)
    # the same weights: a model without gates simply lacks those
    broken.set_state_dict(
        {k: v.astype(broken.config.dtype)
         for k, v in model.state_dict().items()
         if k in broken.state_dict()}, strict=False)
    (_, got), = program_logits(broken, seqs)
    assert np.abs(got - want).max() > 20 * TOL


def test_one_head_count_for_both_kinds_is_another_model(ref, model):
    """The window layers at the full layers' 6 heads: other shapes, so
    the comparison cannot even be fed the same weights."""
    same = seeded(ref, num_attention_heads_per_layer=[6, 6, 6])
    a, b = same.state_dict(), model.state_dict()
    assert a["model.layers.1.self_attn.q_proj.weight"].shape == (64, 96)
    assert b["model.layers.1.self_attn.q_proj.weight"].shape == (64, 160)


# ------------------------------------------------------------- the engine
def engine(model, **kw):
    base = dict(max_slots=4, num_blocks=64, block_size=8,
                max_blocks_per_seq=16, chunk_prefill_tokens=16)
    base.update(kw)
    return PagedEngine(model, **base)


def serve(eng, ps, n):
    for i, p in enumerate(ps):
        eng.submit(i, p, max_new_tokens=n)
    out = eng.run()
    return [{"prompt": p, "tokens": out[i], "lps": eng.logprobs[i]}
            for i, p in enumerate(ps)]


@pytest.mark.parametrize("route", ["dense", "ragged"])
def test_prefill_in_chunks_then_decode_through_both_kinds_of_pool(
        ref, model, route, monkeypatch):
    """A 70-token prompt is five chunks of 16, four of them with cached
    context behind them; 20 served tokens take it to 90, over seven
    windows of 12 deep. The window layers' ring holds 5 pages of 8 a
    slot (window 2 + chunk 2 + 1) and is written round more than twice."""
    from benchmarks.harness import verify
    if route == "ragged":
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    else:       # without the interpreter no kernel runs here
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    eng = engine(model)
    assert eng.decode_route() == route
    row = ((2, 16), (2, 16))
    assert eng._layout == [CacheLayer(row, None, 6), CacheLayer(row, 12, 10),
                           CacheLayer(row, 12, 10)]
    # a full layer: the allocator's 64 blocks; a window layer: 4 slots x
    # 5 pages and the garbage block
    assert [tuple(p.shape for p in layer) for layer in eng.pools] == [
        ((64, 8, 32),) * 2, ((21, 8, 32),) * 2, ((21, 8, 32),) * 2]
    sample = serve(eng, prompts(1, (70, 5, 33)), n=20)
    nums = verify.numbers(ref, eng.params, BENCH, sample)
    assert nums["finite"] and nums["tokens"] == 60
    assert nums["argmax_gap_max"] < TOL and nums["logprob_rms"] < TOL
    st = eng.stats
    # the expert layers' counters count as they do for the other families
    assert st["moe_layer_ticks"] == 2 * st["decode_steps"]
    rows = st["active_slot_steps"]
    assert 0 < st["kv_window_tokens"] <= 2 * 12 * rows
    assert st["kv_context_tokens"] > st["kv_window_tokens"]
    # three packed calls (a first chunk each), then the chunks behind
    # them: 70 tokens 4, 33 tokens 2; each scored whole runs of pages
    # (here a table is one run) and no more
    assert st["prefill_chunks"] == 3 + 6
    live, scored = (st["chunk_attn_positions_live"],
                    st["chunk_attn_positions_scored"])
    full = 32 + 48 + 64 + 70 + 32 + 33      # the full layer: all cached
    band = 2 * (32 + 40 + 40 + 40 + 32 + 33)    # a ring holds 40
    if route == "ragged":   # with the interpreter the chunk kernel runs:
        # a chunk is one tile, and live to it is the band its queries
        # see, the window's 11 behind its first and the chunk's own
        band = 2 * (27 + 27 + 27 + 17 + 27 + 12)
    assert live == full + band
    # the walk scores a table's pages, the kernel one block of a whole
    # lane tile of keys (128) whatever the table
    assert scored == 6 * (16 * 8 + 2 * (128 if route == "ragged" else 5 * 8))
    # the K/V layers of the six calls, and the path they took
    assert st["chunk_attn_layer_calls"] == 6 * 3
    assert st["chunk_attn_kernel_calls"] == (18 if route == "ragged" else 0)


def test_a_slot_many_windows_deep_walks_runs_of_pages(ref, model,
                                                      monkeypatch):
    """A 200-token prompt in chunks of 16 at runs of 4 pages (the cell's
    32-page runs at this size): the full layer's later chunks walk 2 to
    7 runs of a 32-page table, the rings 2; then decode to 216, 18
    windows deep."""
    from benchmarks.harness import verify
    from paddle_tpu.ops import paged_cache
    # without the interpreter no kernel runs here: the walk
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(paged_cache, "CHUNK_RUN_PAGES", 4)
    paged_cache.paged_chunk_attention.clear_cache()
    eng = engine(model, max_slots=2, num_blocks=80, max_blocks_per_seq=32)
    sample = serve(eng, prompts(6, (200, 90)), n=16)
    nums = verify.numbers(ref, eng.params, BENCH, sample)
    assert nums["argmax_gap_max"] < TOL and nums["logprob_rms"] < TOL
    st = eng.stats
    # within one run (32 positions) of what is live, a layer and call
    calls = st["prefill_chunks"] - 2        # less the two packed calls
    assert calls == 12 + 5
    over = st["chunk_attn_positions_scored"] - st["chunk_attn_positions_live"]
    assert 0 <= over < calls * 3 * 32
    paged_cache.paged_chunk_attention.clear_cache()


def test_a_full_house_runs_ahead_over_both_kinds_of_pool(ref, model):
    from benchmarks.harness import verify
    eng = engine(model)
    sample = serve(eng, prompts(2, (29, 29, 29, 29)), n=24)
    assert eng.stats["runahead_ticks"] >= 8
    nums = verify.numbers(ref, eng.params, BENCH, sample)
    assert nums["argmax_gap_max"] < TOL and nums["logprob_rms"] < TOL


def test_adoption_and_spill_are_refused_for_band_keeping_layers(model):
    with pytest.raises(ValueError, match="band"):
        engine(model, enable_prefix_cache=True)
    eng = engine(model)
    with pytest.raises(ValueError, match="band"):
        eng.attach_spill(object())
    eng.attach_spill(None)              # detaching is always allowed


# ------------------------------------------------------------- the kernel
def dense_reference(q, k, v, lens, window, scale):
    """q [R, T, h, d] against each row's own k, v [R, L, kvh, d] in
    plain float32 jax.numpy: query t at position lens + t."""
    R, T, h, _ = q.shape
    kvh = k.shape[2]
    qg = q.reshape(R, T, kvh, h // kvh, -1)
    s = jnp.einsum("rtkgd,rlkd->rkgtl", qg, k) * scale
    pos = jnp.arange(k.shape[1])[None, None, :]
    qpos = lens[:, None, None] + jnp.arange(T)[None, :, None]
    keep = pos <= qpos
    if window is not None:
        keep &= qpos - pos < window
    p = jax.nn.softmax(jnp.where(keep[:, None, None], s, -jnp.inf), -1)
    return jnp.einsum("rkgtl,rlkd->rtkgd", p, v).reshape(R, T, h, -1)


@pytest.mark.parametrize("d,kvh,group,window,ring", [
    (16, 2, 3, None, False),        # the tiny full layer
    (16, 2, 5, 12, True),           # the tiny window layer
    (128, 2, 6, None, False),       # published: 48 heads over 8
    (128, 2, 9, 20, True),          # published: 72 heads over 8, a band
])
def test_the_kernel_against_a_dense_attention(monkeypatch, d, kvh, group,
                                              window, ring):
    from paddle_tpu.ops.paged_cache import (PagedKV, paged_decode_attention,
                                            paged_decode_attention_dense,
                                            paged_decode_route)
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    B, R, h = 8, 3, kvh * group
    M = 5 if ring else 12
    L = 12 * B
    rng = np.random.default_rng(d + group)
    k_all = rng.normal(size=(R, L, kvh, d)).astype(np.float32)
    v_all = rng.normal(size=(R, L, kvh, d)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(R, 1, h, d)), jnp.float32)
    lens = np.asarray([0, 37, 85], np.int32)    # an empty row, a wrapped one
    tables = 1 + np.arange(R)[:, None] * M + np.arange(M)[None, :]
    kp = np.zeros((R * M + 1, B, kvh * d), np.float32)
    vp = np.zeros((R * M + 1, B, kvh * d), np.float32)
    for r in range(R):
        for t in range(lens[r] + 1):
            page = tables[r, (t // B) % M if ring else t // B]
            kp[page, t % B] = k_all[r, t].reshape(-1)
            vp[page, t % B] = v_all[r, t].reshape(-1)
    pk = PagedKV(jnp.asarray(kp), jnp.asarray(vp),
                 jnp.asarray(tables, jnp.int32), jnp.asarray(lens), kvh,
                 ring)
    assert paged_decode_route(q, pk.kp, kvh) == "ragged"
    scale = d ** -0.5
    got = paged_decode_attention(q, pk, scale, window=window)
    want = dense_reference(q, jnp.asarray(k_all), jnp.asarray(v_all),
                           jnp.asarray(lens), window, scale)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(
        paged_decode_attention_dense(q, pk, scale, window), want, atol=2e-5)


# -------------------------------------------------------------- the share
def test_the_shares_add_up_to_the_uncut_layer(ref):
    """8 experts as 4 shares of 2 beside one shared expert: the shares'
    ``routed`` parts, and ``shared_out`` ONCE, are the reference's uncut
    layer (router, top-3 of a softmax normalised and times 2.5, every
    expert, the ungated shared expert)."""
    pt.seed(5)
    E = 8
    kw = dict(num_experts=E, top_k=3, scoring="softmax",
              norm_topk_prob=True, routed_scaling_factor=2.5,
              num_shared_experts=1, shared_intermediate_size=32)
    full = ExpertShareMLP(64, 32, first_expert=0, experts_held=E, **kw)
    full.gate = 20.0 * full.gate
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 64))
    p = full.state_dict()
    cfg = dict(BENCH, num_experts_published=E)
    with jax.default_matmul_precision("highest"):
        gates = ref._route(x[None], p["gate"], cfg=cfg)[0]
        shared = ref._swiglu(x, p["shared_gate_proj"], p["shared_up_proj"],
                             p["shared_down_proj"], None)
        want = shared + sum(gates[:, e, None] * ref._swiglu(
            x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], None)
            for e in range(E))
    assert sorted(np.unique(np.sum(np.asarray(gates) > 0, -1))) == [3]
    np.testing.assert_allclose(np.sum(gates, -1), 2.5, atol=1e-5)
    ids, g = full.route(x)
    got = full.shared_out(x)            # what every chip computes alike
    for first in range(0, E, 2):
        part = ExpertShareMLP(64, 32, first_expert=first, experts_held=2,
                              **kw)
        part.set_state_dict(
            {k: (v[first:first + 2] if k.startswith("w_") else v)
             for k, v in p.items()})
        got = got + part.routed(x, ids, g)
        # a share's forward is its routed part and the shared expert
        np.testing.assert_allclose(
            part(x), part.routed(x, ids, g) + full.shared_out(x), atol=TOL)
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_allclose(full(x), want, atol=TOL)
    assert float(jnp.abs(want - shared).max()) > 1e-3
