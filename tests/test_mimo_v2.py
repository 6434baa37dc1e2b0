"""ISSUE 32: MiMo-V2's window and full attention layers in one model,
served through a cache that keeps a window layer's band only.

Contracts pinned here at ``mimo_v2_tiny`` widths in float32 (one full
layer with a dense FFN, two window layers with experts; key heads of 24
columns, value heads of 16, 1 and 2 kv heads, window 12, rotary on the
first 8 columns, a sink a query head), each against the benchmark's
plain reference (``benchmarks/models/mimo_v2.py``: the band a mask over
the whole sequence) on its own seeded weights, comparing LOGITS:

- FULL FORWARD: every logit of every position at contexts five windows
  deep; a dropped sink, a band off by one, a value scale left out, a
  rotary over the whole head, and bfloat16 in float32's place each fail
  the same comparison.
- THE CACHE: chunked prefill, then decode, through ``PagedEngine``: a
  full layer's pool by the allocator's table, a window layer's a RING of
  5 pages a slot that a 70-token prompt wraps three times; on the dense
  gather and through the interpreted kernel; on a full house (ISSUE 29's
  run-ahead) and under speculative ticks.
- THE KERNEL: interpret mode against a dense ``jax.numpy`` attention for
  unequal key and value widths, a sink, a window over a ring table, and
  192-column key heads read as aligned 256-column spans.
- THE SHARES ADD UP; a window layer's pool never holds more than the
  band and a chunk a slot; prefix adoption and spill are refused.

Tolerances: both sides are float32 (the reference at ``highest``
precision, which the CPU gives the program too), so what separates them
is the order of sums: 1e-4 on logits of magnitude 0.45 leaves a factor
of 500 over the 2e-7 read here. What the departures move, read on the
61-token sequence: a dropped sink 0.14, rotary over the whole head
0.094, a band off by one 0.026, one rope base 0.015, the value scale
left out 0.0082, bfloat16 in float32's place 0.0029: each at least 29
times the tolerance (the test asks for 20).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.ops.paged_cache import CacheLayer
from paddle_tpu.parallel.moe import ExpertShareMLP

TOL = 1e-4
# the benchmark's configuration keys for the tiny model: experts 2-5 of
# 8 held, 2 choices a token
BENCH = {
    "model": "mimo_v2", "dtype": "float32", "attention_bias": False,
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "head_dim": 24, "v_head_dim": 16,
    "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
    "sliding_window": 12, "hybrid_layer_pattern": [0, 1, 1],
    "moe_layer_freq": [0, 1, 1], "rope_theta": 1e7, "swa_rope_theta": 1e4,
    "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "n_routed_experts": 4,
    "n_routed_experts_published": 8, "first_expert": 2,
    "num_experts_per_tok": 2, "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": None, "n_group": 1,
    "topk_group": 1, "max_position_embeddings": 256,
    "layernorm_epsilon": 1e-5, "tie_word_embeddings": False,
}


@pytest.fixture(scope="module")
def ref():
    from benchmarks.harness import cell
    return cell.load_model(BENCH)


def seeded(ref, **flags):
    """The program's model on the reference's seeded weights, the
    attentions' projections times 6 (drawn at 0.02 over a hidden state
    of 64 they give scores near 0 and an attention so flat that nothing
    in it could be told) and a selection bias wide enough to change the
    choice at these widths."""
    model = ref.build(dict(BENCH, **flags), 11, jax.devices()[0])
    for i, layer in enumerate(model.model.layers):
        if hasattr(layer.mlp, "expert_bias"):
            layer.mlp.expert_bias = 0.05 * jax.random.normal(
                jax.random.PRNGKey(i), (8,))
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
    # the sinks are drawn, not zero, and only the window layers have one
    sinks = {k: v for k, v in model.state_dict().items() if "sink" in k}
    assert sorted(sinks) == ["model.layers.1.self_attn.sink",
                             "model.layers.2.self_attn.sink"]
    assert all(float(jnp.std(v)) > 0.3 for v in sinks.values())


@pytest.mark.parametrize("fault", [
    dict(add_swa_attention_sink_bias=False),    # a dropped sink
    dict(sliding_window=13),                    # a band off by one
    dict(attention_value_scale=1.0),            # the value scale left out
    dict(partial_rotary_factor=1.0),            # rotary over all 24 columns
    dict(swa_rope_theta=1e7),                   # one base for both kinds
    dict(dtype="bfloat16"),                     # the precision below
], ids=lambda f: next(iter(f)))
def test_each_departure_fails_the_comparison(ref, model, fault):
    seqs = prompts(0, (61,))
    (_, want), = reference_logits(ref, model.functional()[1], seqs)
    broken = seeded(ref, **fault)
    # the same weights: a model without sinks simply lacks those two
    broken.set_state_dict(
        {k: v.astype(broken.config.dtype)
         for k, v in model.state_dict().items()
         if k in broken.state_dict()}, strict=False)
    (_, got), = program_logits(broken, seqs)
    assert np.abs(got - want).max() > 20 * TOL


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
    assert eng._layout == [
        CacheLayer(((1, 24), (1, 16)), None),
        CacheLayer(((2, 24), (2, 16)), 12),
        CacheLayer(((2, 24), (2, 16)), 12)]
    # a full layer: the allocator's 64 blocks; a window layer: 4 slots x
    # 5 pages and the garbage block; K and V of different widths
    assert [tuple(p.shape for p in layer) for layer in eng.pools] == [
        ((64, 8, 24), (64, 8, 16)),
        ((21, 8, 48), (21, 8, 32)), ((21, 8, 48), (21, 8, 32))]
    sample = serve(eng, prompts(1, (70, 5, 33)), n=20)
    nums = verify.numbers(ref, eng.params, BENCH, sample)
    assert nums["finite"] and nums["tokens"] == 60
    assert nums["argmax_gap_max"] < TOL and nums["logprob_rms"] < TOL
    st = eng.stats
    # the expert layers' counters count as they do for the other families
    assert st["moe_layer_ticks"] == 2 * st["decode_steps"]
    # a live row's query sees at most the window in a window layer, and
    # its band is at most 3 pages of 8 (12 positions straddle 2 or 3)
    rows = st["active_slot_steps"]
    assert 0 < st["kv_window_tokens"] <= 2 * 12 * rows
    assert rows <= st["kv_window_blocks"] <= 2 * 3 * rows
    assert st["kv_context_tokens"] > st["kv_window_tokens"]
    # every page a request touched in a window layer was left behind by
    # its end: 2 layers x ceil(tokens cached / 8) pages
    cached = [len(r["prompt"]) + len(r["tokens"]) - 1 for r in sample]
    assert st["kv_window_blocks_released"] == \
        2 * sum(-(-n // 8) for n in cached)
    assert eng.health()["window_blocks_live"] == 0


def test_the_ring_is_reused_and_never_outgrown(model):
    """Requests of 100 tokens through ONE slot, one after another: the
    window layers' pools are 1 x 5 pages + the garbage block whatever is
    served, so the pages the band leaves behind are the pages it writes
    next; what the engine reports live is the band, never more than the
    band and a chunk."""
    eng = engine(model, max_slots=1)
    assert [p[0].shape[0] for p in eng.pools] == [64, 6, 6]
    seen = []
    for i, p in enumerate(prompts(3, (80, 80))):
        eng.submit(i, p, max_new_tokens=20)
        while eng.slots[0] is not None or eng.queue:
            eng.step()
            seen.append(eng.health()["window_blocks_live"])
    assert max(seen) <= 2 * 5 and 0 in seen
    assert eng.stats["kv_window_blocks_released"] == 2 * 2 * 13
    assert len(eng.results[0]) == len(eng.results[1]) == 20


def test_a_full_house_runs_ahead_over_both_kinds_of_pool(ref, model):
    from benchmarks.harness import verify
    eng = engine(model)
    sample = serve(eng, prompts(2, (29, 29, 29, 29)), n=24)
    assert eng.stats["runahead_ticks"] >= 8
    nums = verify.numbers(ref, eng.params, BENCH, sample)
    assert nums["argmax_gap_max"] < TOL and nums["logprob_rms"] < TOL


def test_speculative_ticks_write_ahead_inside_the_ring(ref, model,
                                                       monkeypatch):
    """k + 1 = 4 positions a tick: the ring is window 2 + chunk 2 + 1
    pages, the verify rows' writes land ahead of the band, and a
    repetitive prompt's accepted drafts commit through both pools."""
    from benchmarks.harness import verify
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    eng = engine(model, spec_tokens=3)
    p = prompts(4, (7,))[0] * 6
    sample = serve(eng, [p], n=30)
    assert eng.stats["spec_proposed"] > 0
    nums = verify.numbers(ref, eng.params, BENCH, sample)
    assert nums["argmax_gap_max"] < TOL and nums["logprob_rms"] < 2 * TOL


def test_whole_prompt_prefill_writes_only_what_the_ring_keeps(ref, model):
    """Without chunked prefill the ring is window 2 + 1 + 1 pages: a
    60-token prompt writes its last 32 positions' worth and decode goes
    on from there."""
    from benchmarks.harness import verify
    eng = engine(model, chunk_prefill_tokens=None,
                 prefill_buckets=(32, 64))
    assert eng.pools[1][0].shape[0] == 4 * 4 + 1
    sample = serve(eng, prompts(5, (60, 20)), n=16)
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
def dense_reference(q, k, v, lens, window, sink, scale):
    """q [R, T, h, dk] against each row's own k, v [R, L, kvh, *] in
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
    s = jnp.where(keep[:, None, None], s, -jnp.inf)
    if sink is not None:
        col = jnp.broadcast_to(sink.reshape(1, kvh, h // kvh, 1, 1),
                               s.shape[:-1] + (1,))
        s = jnp.concatenate([s, col], -1)
    p = jax.nn.softmax(s, -1)[..., :k.shape[1]]
    return jnp.einsum("rkgtl,rlkd->rtkgd", p, v).reshape(R, T, h, -1)


@pytest.mark.parametrize("dk,dv,kvh,group,T,window,ring", [
    (24, 16, 2, 2, 1, 12, True),        # the tiny window layer
    (24, 16, 1, 4, 1, None, False),     # the tiny full layer
    (192, 128, 2, 8, 1, 20, True),      # published widths: aligned spans
    (192, 128, 4, 4, 1, None, False),
    (192, 128, 2, 4, 3, 20, True),      # verify rows over a ring
])
def test_the_kernel_against_a_dense_attention(monkeypatch, dk, dv, kvh,
                                              group, T, window, ring):
    from paddle_tpu.ops.paged_cache import PagedKV, paged_decode_attention
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    B, R, h = 8, 3, kvh * group
    M = 5 if ring else 12
    L = 12 * B
    rng = np.random.default_rng(dk + T)
    k_all = rng.normal(size=(R, L, kvh, dk)).astype(np.float32)
    v_all = rng.normal(size=(R, L, kvh, dv)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(R, T, h, dk)), jnp.float32)
    sink = jnp.asarray(rng.normal(size=(h,)), jnp.float32) if ring else None
    lens = np.asarray([0, 37, 85], np.int32)    # an empty row, a wrapped one
    tables = 1 + np.arange(R)[:, None] * M + np.arange(M)[None, :]
    # the cache as serving leaves it: every position up to this step's
    # T rows written in order, a ring's pages written round
    kp = np.zeros((R * M + 1, B, kvh * dk), np.float32)
    vp = np.zeros((R * M + 1, B, kvh * dv), np.float32)
    for r in range(R):
        for t in range(lens[r] + T):
            page = tables[r, (t // B) % M if ring else t // B]
            kp[page, t % B] = k_all[r, t].reshape(-1)
            vp[page, t % B] = v_all[r, t].reshape(-1)
    pk = PagedKV(jnp.asarray(kp), jnp.asarray(vp),
                 jnp.asarray(tables, jnp.int32), jnp.asarray(lens), kvh,
                 ring)
    k_all, v_all, lens = (jnp.asarray(a) for a in (k_all, v_all, lens))
    scale = dk ** -0.5
    got = paged_decode_attention(q, pk, scale, window=window, sink=sink)
    want = dense_reference(q, k_all, v_all, lens, window, sink, scale)
    assert got.shape == (R, T, h, dv)
    np.testing.assert_allclose(got, want, atol=2e-5)


# -------------------------------------------------------------- the share
def test_the_shares_add_up_to_the_uncut_layer(ref):
    """8 experts as 4 shares of 2, no shared expert: the shares'
    ``routed`` parts are the reference's uncut layer."""
    pt.seed(5)
    E = 8
    kw = dict(num_experts=E, top_k=2, scoring="sigmoid",
              norm_topk_prob=True, n_group=1, topk_group=1,
              num_shared_experts=0)
    full = ExpertShareMLP(64, 32, first_expert=0, experts_held=E, **kw)
    full.expert_bias = 0.05 * jax.random.normal(jax.random.PRNGKey(0), (E,))
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 64))
    p = full.state_dict()
    cfg = dict(BENCH, n_routed_experts_published=E)
    with jax.default_matmul_precision("highest"):
        gates = ref._route(x[None], p["gate"], p["expert_bias"], cfg=cfg)[0]
        want = sum(gates[:, e, None] * ref._swiglu(
            x, p["w_gate"][e], p["w_up"][e], p["w_down"][e], None)
            for e in range(E))
    ids, g = full.route(x)
    got = 0.0
    for first in range(0, E, 2):
        part = ExpertShareMLP(64, 32, first_expert=first, experts_held=2,
                              **kw)
        part.set_state_dict(
            {k: (v[first:first + 2] if k.startswith("w_") else v)
             for k, v in p.items()})
        got = got + part.routed(x, ids, g)
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_allclose(full(x), want, atol=TOL)
    assert float(jnp.abs(want).max()) > 1e-3
