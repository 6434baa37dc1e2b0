"""ISSUE 26: DeepSeek-V2/V3's latent attention through ``PagedEngine``.

Contracts pinned here, at ``deepseek_v2_tiny`` widths with the V3
router, ``v_head_dim != qk_nope_head_dim`` and yarn on, in float32:

- LATENT POOL: the engine holds ONE array a layer, one padded row a
  token; whole and chunked prefill then decode through it agree with
  the model's own full forward and with the benchmark's plain reference
  (``benchmarks/models/deepseek_v3.py:reference_rows``).
- the one code path of the pools: prefix adoption is bit-exact, a
  spill / restore round trip of latent blocks is exact.
- ``decode_route()`` is "ragged" under the interpreter, and the streams
  are the dense gather's.
- KERNEL: the ragged kernel's latent mode against the dense gather at
  the published 576 (640 padded) columns, group 64, ragged lengths,
  single-query and multi-query.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                           deepseek_v2_tiny)
from paddle_tpu.serving.kvspill import KVSpillArena

YARN = dict(type="yarn", factor=4.0, original_max_position_embeddings=64,
            beta_fast=32, beta_slow=1, mscale=1.0, mscale_all_dim=1.0)
# the benchmark's configuration keys for the same tiny model
BENCH = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 24,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "first_expert": 4, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "vocab_size": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 10000.0,
    "rope_scaling": dict(YARN, rope_type="yarn"),
}


def tiny_config(**kw):
    return deepseek_v2_tiny(
        num_hidden_layers=3, num_experts=16, num_experts_per_tok=4,
        n_group=4, topk_group=2, scoring="sigmoid",
        group_score_mode="top2_sum", norm_topk_prob=True,
        routed_scaling_factor=2.5, v_head_dim=24, first_expert=4,
        experts_held=4, max_position_embeddings=256, rope_scaling=YARN,
        yarn_mscale_all_in_scale=True, **kw)


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = DeepseekV2ForCausalLM(tiny_config())
    for i, layer in enumerate(m.model.layers[1:]):
        layer.mlp.expert_bias = 0.2 * jax.random.normal(
            jax.random.PRNGKey(i), (16,))
    return m


def engine(model, **kw):
    base = dict(max_slots=4, num_blocks=64, block_size=8,
                max_blocks_per_seq=16, chunk_prefill_tokens=16,
                enable_prefix_cache=True)
    base.update(kw)
    return PagedEngine(model, **base)


def prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


def assert_greedy(model, ps, toks, lps, width=64):
    """Each served stream is the model's own greedy continuation: ONE
    teacher-forced full forward of every prompt + served tokens (padded
    behind, which a causal model does not see)."""
    fn, params = model.functional()
    ids = np.zeros((len(ps), width), np.int32)
    for r, (p, t) in enumerate(zip(ps, toks)):
        ids[r, :len(p) + len(t)] = p + t
    logp = np.asarray(jax.nn.log_softmax(
        jax.jit(fn)(params, jnp.asarray(ids)), axis=-1))
    for r, (p, t, lp) in enumerate(zip(ps, toks, lps)):
        rows = logp[r, len(p) - 1:len(p) - 1 + len(t)]
        assert rows.argmax(-1).tolist() == t
        np.testing.assert_allclose(lp, rows[np.arange(len(t)), t],
                                   atol=2e-5)


def serve(eng, ps, n=6):
    for i, p in enumerate(ps):
        eng.submit(i, p, max_new_tokens=n)
    out = eng.run()
    return [out[i] for i in range(len(ps))], \
        [eng.logprobs[i] for i in range(len(ps))]


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("chunk", [None, 16], ids=["whole", "chunked"])
def test_prefill_then_decode_agrees_with_the_full_forward(model, chunk):
    eng = engine(model, chunk_prefill_tokens=chunk,
                 enable_prefix_cache=chunk is not None)
    assert [tuple(p.shape for p in layer) for layer in eng.pools] == \
        [((64, 8, 128),)] * 3       # 32 + 8 columns in one 128-lane row
    ps = prompts(0, (5, 20, 37))
    toks, lps = serve(eng, ps)
    assert_greedy(model, ps, toks, lps)
    assert eng.stats["moe_layer_ticks"] == 2 * eng.stats["decode_steps"]
    assert 0 < eng.stats["moe_experts_hit"] <= 4 * eng.stats[
        "moe_layer_ticks"]
    assert eng.stats["moe_local_assignments"] >= eng.stats[
        "moe_experts_hit"]


def test_ticks_run_ahead_of_their_drain_count_their_layers_too(model):
    """Four rows fill the four slots, so each tick is dispatched with
    its predecessor undrained (ISSUE 29), over the latent pool as over
    K/V pools: rows of 9 + 12 tokens cross a block boundary under the
    lag, and the experts' counters, cumulative in the ring's spare row
    and differenced at each drain, lose and double nothing."""
    eng = engine(model)
    ps = prompts(4, (9, 9, 9, 9))
    toks, lps = serve(eng, ps, n=12)
    assert_greedy(model, ps, toks, lps)
    assert eng.stats["runahead_ticks"] >= 8
    assert eng.stats["decode_steps"] == 11 and not eng._pending
    assert eng.stats["moe_layer_ticks"] == 2 * eng.stats["decode_steps"]


def test_the_route_is_ragged_and_the_streams_are_the_dense_gathers(
        model, kernels, monkeypatch):
    ps = prompts(1, (9, 33, 18))
    eng = engine(model)
    assert eng.decode_route() == "ragged"
    got = serve(eng, ps)
    # without the interpreter no kernel runs here: the dense gather
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    dense = engine(model)
    assert dense.decode_route() == "dense"
    want = serve(dense, ps)
    assert got[0] == want[0]
    np.testing.assert_allclose(got[1], want[1], atol=2e-5)


def test_the_engine_agrees_with_the_benchmarks_reference(model, kernels):
    from benchmarks.harness import cell, verify
    mod = cell.load_model({"model": "deepseek_v3"})
    eng = engine(model)
    ps = prompts(2, (7, 40))
    toks, lps = serve(eng, ps, n=10)
    sample = [{"prompt": p, "tokens": t, "lps": lp}
              for p, t, lp in zip(ps, toks, lps)]
    nums = verify.numbers(mod, eng.params, BENCH, sample)
    assert nums["finite"] and nums["tokens"] == 20
    assert nums["argmax_gap_max"] < 1e-4 and nums["logprob_rms"] < 1e-4


def test_prefix_adoption_is_bit_exact(model):
    shared = prompts(3, (32,))[0]
    tails = prompts(4, (5, 9))
    cold = engine(model, enable_prefix_cache=True)
    want = serve(cold, [shared + tails[1]])
    eng = engine(model)
    serve(eng, [shared + tails[0]])
    hit0 = eng.stats["prefix_hit_tokens"]
    eng.submit("again", shared + tails[1], max_new_tokens=6)
    out = eng.run()
    assert eng.stats["prefix_hit_tokens"] - hit0 == 32
    assert out["again"] == want[0][0]
    assert eng.logprobs["again"] == want[1][0]      # bitwise


def test_a_spill_and_restore_round_trip_of_latent_blocks(model):
    arena = KVSpillArena(64 << 20, name="latent")
    eng = engine(model, max_slots=2, num_blocks=16, max_blocks_per_seq=8)
    eng.attach_spill(arena)
    assert eng._spill_geometry()[:4] == (3, 8, 1, 128)
    first = prompts(5, (33,))[0]
    want = serve(engine(model), [first], n=4)
    eng.submit("a", first, max_new_tokens=4)
    eng.run()
    for i, p in enumerate(prompts(6, (33,) * 6)):   # flood the pool
        eng.submit(f"f{i}", p, max_new_tokens=4)
    eng.run()
    assert eng.stats["spill_spans"] > 0
    assert bytes.fromhex(eng.prefix_digest(first)) not in eng.prefix_cache
    eng.submit("a2", first, max_new_tokens=4)
    out = eng.run()
    assert eng.stats["spill_restores"] >= 1, eng.stats
    assert eng.stats["spill_restore_failures"] == 0
    assert out["a2"] == want[0][0]
    assert eng.logprobs["a2"] == want[1][0]         # bitwise


def test_a_hard_reset_takes_fresh_latent_pools(model):
    eng = engine(model)
    ps = prompts(7, (12,))
    want = serve(eng, ps)
    old = eng.pools
    eng.hard_reset()
    assert eng.pools is not old and len(eng.pools[0]) == 1
    assert serve(eng, ps) == want


@pytest.mark.parametrize("T", [1, 3], ids=["single-query", "multi-query"])
def test_the_latent_kernel_matches_the_dense_gather(kernels, T):
    """The published widths: 64 heads over one row of 512 + 64 columns
    padded to 640, values the first 512."""
    from paddle_tpu.ops.paged_cache import (PagedKV, paged_latent_attention,
                                            paged_latent_attention_dense)
    R, P, B, M, h, W, dv = 5, 48, 8, 8, 64, 640, 512
    rs = np.random.RandomState(T)
    q = jnp.asarray(rs.randn(R, T, h, W) * 0.2, jnp.float32)
    kp = jnp.asarray(rs.randn(P, B, W), jnp.float32)
    tables = jnp.asarray(1 + rs.permutation(P - 1)[:R * M].reshape(R, M),
                         jnp.int32)
    lens = jnp.asarray([0, 7, 8, 61 - T, 30], jnp.int32)
    pk = PagedKV(kp, None, tables, lens)
    got = paged_latent_attention(q, pk, dv, 192 ** -0.5)
    assert got.shape == (R, T, h, dv)
    want = paged_latent_attention_dense(q, pk, dv, 192 ** -0.5)
    np.testing.assert_allclose(got, want, atol=2e-5)
