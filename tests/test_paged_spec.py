"""ISSUE 7: speculative multi-token ticks — prompt-lookup decoding
inside the PagedEngine fused tick.

Contracts, each against an independent reference:

- STREAM EXACTNESS: a ``spec_tokens=k`` engine must emit the SAME
  streams as the spec-off fused tick. On the lookup stub (logits are a
  pure per-token table read, so the verify's query count cannot
  perturb them) that is pinned BITWISE — tokens AND logprobs — across
  eos / stop-string / budget landing mid-accepted-window, mixed
  spec/sampled/penalized slots, and mid-stream submits. On the real
  tiny llama, verify (q_len=k+1) vs decode (q_len=1) forwards differ
  by float epsilon (pre-existing; documented in test_speculative.py),
  so tokens are pinned exactly on decisive logits and logprobs to
  tight tolerance.
- DISPATCH: spec ticks keep the ISSUE 6 steady-state contract — one
  compiled dispatch, zero host->device mirror uploads — while
  committing MULTIPLE tokens per dispatch on repetitive streams.
- FALLBACK: rows without block headroom, with collapsed accept EMA,
  sampled, or penalized decode 1 token per tick inside the same
  program, with the stream unchanged.
- KERNEL: the ragged kernel's multi-query rows (per-position causal
  masking within a row) match the dense per-position reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.ops.paged_cache import (PagedKV, paged_decode_attention,
                                        paged_decode_write,
                                        write_and_attend)
from paddle_tpu.generation.prompt_lookup import (accept_length,
                                                 propose_ngram,
                                                 propose_ngram_rows)
from paddle_tpu.models import LlamaForCausalLM
from paddle_tpu.models.llama import llama_tiny

from test_decode_kernels import _flat


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = LlamaForCausalLM(llama_tiny())
    # decisive logits (see test_speculative.py): verify vs decode
    # forwards differ by float epsilon; widening every argmax gap 10x
    # keeps token exactness off the seed lottery
    m.lm_head.weight = m.lm_head.weight * 10.0
    return m


# --------------------------------------------------------------- lookup stub
class _StubCfg:
    vocab_size = 64
    num_hidden_layers = 1
    num_key_value_heads = 1
    head_dim = 8
    dtype = jnp.float32


class LookupStub:
    """CausalLM-contract stub whose logits are a pure per-token TABLE
    READ: token t deterministically argmaxes to (t+1) % period with an
    8.0 margin. The paged cache write + attention still run every call
    (so dispatch/upload counters measure the real tick machinery), but
    their output joins the logits with weight 0.0 — logits are
    bitwise-independent of the query count, making fused-spec vs
    spec-off streams comparable BITWISE, logprobs included.

    ``period`` small -> the greedy stream cycles and prompt-lookup
    accepts nearly every draft; period >= prompt+budget -> the stream
    never repeats an n-gram and acceptance is structurally zero."""

    config = _StubCfg()

    def __init__(self, period=7):
        self.period = period

    def functional(self):
        d, V = self.config.head_dim, self.config.vocab_size
        key = jax.random.PRNGKey(0)
        emb = jax.random.normal(key, (V, d))
        table = jax.nn.one_hot((jnp.arange(V) + 1) % self.period,
                               V) * 8.0
        params = dict(emb=emb, table=table)

        def fn(params, tokens, kv_caches=None, positions=None,
               segment_ids=None):
            x = params["emb"][tokens]              # [R, s, d]
            kv = x[:, :, None, :]
            o, pk = write_and_attend(kv_caches[0], kv, kv, kv, positions,
                                     segment_ids)
            o = o[:, :, 0]
            logits = params["table"][tokens] \
                + 0.0 * jnp.sum(o, axis=-1, keepdims=True)
            return logits, [pk]

        return fn, params


def _stub_engine(period=7, **kw):
    base = dict(max_slots=4, num_blocks=64, block_size=64,
                max_blocks_per_seq=4, prefill_buckets=(16,))
    base.update(kw)
    return PagedEngine(LookupStub(period), **base)


def _drain(eng, submits):
    for rid, ids, kw in submits:
        eng.submit(rid, ids, **kw)
    res = eng.run()
    return res, dict(eng.logprobs)


def _cyc(n, start=1, period=7):
    return np.asarray([[(start + i) % period for i in range(n)]])


# --------------------------------------------------- stream bit-identity
class TestSpecStreamBitIdentity:
    def test_greedy_bit_identical_and_fewer_forwards(self):
        """THE tentpole pin: fused-spec tokens AND logprobs equal the
        spec-off fused tick bitwise, while repetitive streams commit
        multiple tokens per forward (fewer decode dispatches)."""
        subs = [
            ("a", _cyc(6), dict(max_new_tokens=30)),
            ("b", _cyc(9, start=3), dict(max_new_tokens=25)),
            ("c", np.asarray([[2, 9, 4]]), dict(max_new_tokens=20)),
        ]
        off = _stub_engine()
        r_off, lp_off = _drain(off, subs)
        on = _stub_engine(spec_tokens=4)
        r_on, lp_on = _drain(on, subs)
        assert r_off == r_on
        assert lp_off == lp_on
        assert on.stats["spec_accepted"] > 0
        # multi-token commits: meaningfully fewer decode dispatches
        assert on.stats["decode_steps"] < off.stats["decode_steps"] / 1.5

    def test_eos_lands_mid_accepted_window(self):
        """eos inside the accepted window: the commit truncates at the
        eos token and the stream equals the spec-off engine's exactly
        (which test_paged.py pins against generate())."""
        subs = [("e", _cyc(8), dict(max_new_tokens=30, eos_token_id=5))]
        r_off, lp_off = _drain(_stub_engine(), subs)
        eng = _stub_engine(spec_tokens=4)
        r_on, lp_on = _drain(eng, subs)
        assert r_off == r_on and lp_off == lp_on
        assert r_on["e"][-1] == 5 and 5 not in r_on["e"][:-1]
        assert eng.stats["spec_accepted"] > 0   # eos truncation was real

    def test_stop_sequence_lands_mid_window(self):
        """Stop matching stays host-side: a stop completing inside the
        accepted window finishes (and trims) the request even though
        the device committed past it."""
        subs = [("s", _cyc(7), dict(max_new_tokens=30,
                                    stop_sequences=[[3, 4]]))]
        r_off, lp_off = _drain(_stub_engine(), subs)
        r_on, lp_on = _drain(_stub_engine(spec_tokens=4), subs)
        assert r_off == r_on and lp_off == lp_on
        assert tuple(r_on["s"][-2:]) != (3, 4)   # trimmed

    def test_budget_exhausts_mid_window(self):
        """max_new_tokens not a multiple of the accept run: the budget
        clamp truncates the window and sets done."""
        for n in (1, 9, 13):
            subs = [("m", _cyc(6), dict(max_new_tokens=n))]
            r_off, lp_off = _drain(_stub_engine(), subs)
            r_on, lp_on = _drain(_stub_engine(spec_tokens=4), subs)
            assert r_off == r_on and lp_off == lp_on
            assert len(r_on["m"]) == n

    def test_mixed_spec_sampled_penalized_slots_one_tick(self):
        """One tick, three slot kinds (ISSUE 11 semantics): a greedy
        spec row (bitwise), a seeded LOW-temperature sampled row
        (rejection-sampled verify — the distribution is preserved, and
        on the stub's decisive 8.0-margin logits at T=0.2 every
        filtered distribution is numerically a point mass, so the
        stream is deterministically the greedy one: the exact-pin the
        acceptance criteria name), and a repetition-penalized greedy
        row (the per-position penalty scan keeps it bitwise WHILE
        drafting — the old engine fell it back to 1-token ticks).
        Every stream stays exact."""
        subs = [
            ("spec", _cyc(8), dict(max_new_tokens=24)),
            ("samp", _cyc(5, start=2),
             dict(max_new_tokens=18, temperature=0.2, top_k=12, seed=3)),
            ("pen", _cyc(6, start=4),
             dict(max_new_tokens=15, repetition_penalty=1.3)),
        ]
        off = _stub_engine()
        r_off, lp_off = _drain(off, subs)
        eng = _stub_engine(spec_tokens=4)
        r_on, lp_on = _drain(eng, subs)
        assert r_off == r_on
        assert lp_off == lp_on
        assert eng.stats["spec_accepted"] > 0
        # the sampled AND penalized rows actually rode the multi-token
        # path: meaningfully fewer decode dispatches overall
        assert eng.stats["decode_steps"] < off.stats["decode_steps"]

    def test_midstream_submit_bit_identical(self):
        """Continuous batching under spec: a submit landing mid-decode
        refreshes mirrors (slot transition) and both the joined and
        running streams stay exact — emission order included."""
        def run(**kw):
            eng = _stub_engine(**kw)
            eng.submit("r0", _cyc(6), max_new_tokens=26)
            out = []
            for n, pair in enumerate(eng.stream()):
                out.append(pair)
                if n == 3:
                    eng.submit("r1", _cyc(9, start=2), max_new_tokens=14)
            return out, dict(eng.results), dict(eng.logprobs)

        so, ro, lo = run()
        ss, rs_, ls = run(spec_tokens=4)
        assert ro == rs_ and lo == ls
        assert sorted(so) == sorted(ss)   # same tokens per request
        # spec commits several tokens per tick, so interleaving may
        # differ — but each request's own emission order must not
        for rid in ro:
            assert [t for r, t in so if r == rid] == \
                [t for r, t in ss if r == rid]

    def test_table_capacity_exhausts_mid_window_1_token_fallback(self):
        """Block exhaustion mid-window: the request's table runs out of
        headroom as it approaches max_blocks_per_seq*block_size, so the
        device-side write-capacity clamp shrinks kprop tick by tick
        down to the plain 1-token tick — stream stays exact to the very
        last token."""
        subs = [("x", _cyc(6), dict(max_new_tokens=10))]
        kw = dict(block_size=8, max_blocks_per_seq=2, num_blocks=16)
        r_off, lp_off = _drain(_stub_engine(**kw), subs)
        eng = _stub_engine(spec_tokens=4, **kw)
        r_on, lp_on = _drain(eng, subs)
        assert r_off == r_on and lp_off == lp_on
        assert len(r_on["x"]) == 10          # filled the table exactly
        assert eng.stats["spec_accepted"] > 0

    def test_chunked_prefill_and_prefix_cache_with_spec(self):
        """Chunked prefill interleaves with spec ticks (mid-prefill
        slots ride the program as inactive rows; every chunk's refresh
        reseeds their committed-stream buffer), and prefix-cache block
        adoption composes (spec writes land at positions >= the
        prompt, never inside shared prefix blocks). Streams bitwise
        exact in both configs."""
        base = dict(block_size=8, max_blocks_per_seq=8, num_blocks=48,
                    chunk_prefill_tokens=8, prefill_buckets=(8,))
        shared = list(range(1, 7)) * 2 + [2, 3]   # 14-token prefix
        subs = [
            ("a", np.asarray([shared + [4, 5]]),
             dict(max_new_tokens=18)),
            ("b", np.asarray([shared + [1, 2]]),
             dict(max_new_tokens=12)),
            ("c", _cyc(11, start=2), dict(max_new_tokens=9)),
        ]
        # prefix_cache=True exercises chunking AND adoption; the
        # cache-off chunked variant rides the slow-tier sweep's budget
        kw = dict(base, enable_prefix_cache=True)
        r_off, lp_off = _drain(_stub_engine(**kw), subs)
        eng = _stub_engine(spec_tokens=4, **kw)
        r_on, lp_on = _drain(eng, subs)
        assert r_off == r_on and lp_off == lp_on
        assert eng.stats["spec_accepted"] > 0

    def test_llama_tokens_exact_logprobs_close(self, model):
        """Real-model twin of the bitwise pins: seeded submit/stop/eos
        mix on the decisive tiny llama — tokens exactly equal, logprobs
        within float-epsilon of the spec-off engine (the q_len=1 vs
        q_len=k+1 accumulation-order difference test_speculative.py
        documents)."""
        def eng(**kw):
            base = dict(max_slots=4, num_blocks=32, block_size=8,
                        max_blocks_per_seq=8, prefill_buckets=(16, 32))
            base.update(kw)
            return PagedEngine(model, **base)

        rs = np.random.RandomState(21)
        subs = [
            ("a", rs.randint(1, 200, (1, 5)), dict(max_new_tokens=18)),
            ("b", rs.randint(1, 200, (1, 9)),
             dict(max_new_tokens=16, stop_sequences=[[7], [3, 5]])),
            ("c", rs.randint(1, 200, (1, 3)),
             dict(max_new_tokens=14, eos_token_id=2)),
            ("d", rs.randint(1, 200, (1, 7)),
             dict(max_new_tokens=10, temperature=0.9, top_k=20,
                  seed=5)),
        ]
        r_off, lp_off = _drain(eng(), subs)
        r_on, lp_on = _drain(eng(spec_tokens=3), subs)
        for key in ("a", "b", "c"):      # greedy rows: tokens exact
            assert r_off[key] == r_on[key]
            np.testing.assert_allclose(lp_on[key], lp_off[key],
                                       atol=1e-4, rtol=1e-4)
        # the sampled row rides the rejection-sampled verify (ISSUE
        # 11): its stream is preserved in DISTRIBUTION, not bitwise
        # (the PRNG consumption pattern differs from 1-token ticks by
        # design — the distribution pins live in test_ring_spec.py).
        # Here: seeded determinism — the same seed through the spec
        # engine twice is bitwise-identical
        r_on2, lp_on2 = _drain(eng(spec_tokens=3), subs)
        assert r_on["d"] == r_on2["d"] and lp_on["d"] == lp_on2["d"]
        assert len(r_on["d"]) == len(r_off["d"])   # budget honored


# ------------------------------------------------------ dispatch contract
class TestSpecDispatchContract:
    def test_one_dispatch_zero_uploads_per_steady_spec_tick(self):
        """The ISSUE 6 steady-state counters survive speculation: N
        spec ticks = N dispatches, 0 mirror uploads — while each tick
        commits MULTIPLE tokens."""
        eng = _stub_engine(spec_tokens=4)
        for i in range(4):
            eng.submit(f"r{i}", _cyc(8), max_new_tokens=60)
        for _ in range(4):       # admit + prefill + first refresh
            eng.step()
        d0, u0 = eng.dispatch_count, eng.h2d_uploads
        t0 = sum(len(s.tokens) for s in eng.slots if s is not None)
        n = 6
        for _ in range(n):
            eng.step()
        toks = sum(len(s.tokens) for s in eng.slots
                   if s is not None) - t0
        assert eng.dispatch_count - d0 == n
        assert eng.h2d_uploads - u0 == 0
        # repetitive stream: well past 1 token per dispatch
        assert toks >= 2 * n * 4

    def test_collapsed_accept_rate_stops_drafting(self):
        """A stream that never repeats an n-gram (period > budget):
        the accept EMA decays below the floor after a handful of ticks
        and drafting stops (probe ticks only) — the clean per-request
        fallback. Stream stays exact throughout."""
        subs = [("r", np.asarray([[1, 2, 3]]),
                 dict(max_new_tokens=36))]
        r_off, lp_off = _drain(_stub_engine(period=60), subs)
        eng = _stub_engine(period=60, spec_tokens=4)
        r_on, lp_on = _drain(eng, subs)
        assert r_off == r_on and lp_off == lp_on
        assert eng.stats["spec_accepted"] == 0
        # ema 1.0 -> floor in ~5 ticks of k drafts, then probes only
        assert 0 < eng.stats["spec_proposed"] <= 24

    def test_counters_health_and_prometheus_pinned(self):
        """spec_proposed_total / spec_accepted_total ride the same
        registry a /metrics scrape exports; health() derives the accept
        rate from those exact objects (PR 4 pattern)."""
        from paddle_tpu.utils import observability as obs
        eng = _stub_engine(spec_tokens=4)
        eng.submit("r", _cyc(8), max_new_tokens=30)
        eng.run()
        snap = eng.stats
        assert snap["spec_proposed"] > 0
        assert 0 < snap["spec_accepted"] <= snap["spec_proposed"]
        h = eng.health()
        assert h["spec_accept_rate"] == round(
            snap["spec_accepted"] / snap["spec_proposed"], 4)
        label = eng._obs_labels["engine"]
        text = obs.registry().prometheus_text()
        for name, key in (("paged_spec_proposed_total", "spec_proposed"),
                          ("paged_spec_accepted_total", "spec_accepted")):
            line = next(ln for ln in text.splitlines()
                        if ln.startswith(name)
                        and f'engine="{label}"' in ln)
            assert float(line.rsplit(" ", 1)[1]) == snap[key]
        # tokens-per-forward histogram observed once per active row tick
        _, tot, cnt = eng._h_tpf.export()
        assert cnt == eng.stats["decode_steps"]
        assert tot == eng.stats["active_slot_steps"]

    def test_spec_requires_fused_tick(self):
        with pytest.raises(ValueError, match="fused_tick"):
            _stub_engine(spec_tokens=2, fused_tick=False)


# ----------------------------------------------- kernel + primitive parity
def _dense_multi_reference(q, kp, vp, tables, lens, window=None):
    """Per-position causal reference for multi-query rows."""
    from paddle_tpu.ops.attention import dense_attention
    R, T = q.shape[0], q.shape[1]
    kvh, d = kp.shape[2], kp.shape[3]
    ks = kp[tables].reshape(R, -1, kvh, d)
    vs = vp[tables].reshape(R, -1, kvh, d)
    kpos = jnp.arange(ks.shape[1])[None, None, :]
    qpos = lens[:, None, None] + jnp.arange(T)[None, :, None]
    keep = kpos <= qpos
    if window is not None:
        keep &= kpos > qpos - window
    return dense_attention(q, ks, vs, attn_mask=keep[:, None])


class TestMultiQueryRagged:
    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")

    @pytest.mark.parametrize("window", [None, 12])
    def test_multi_query_parity(self, window):
        """T=5 verify rows over uneven/boundary seq_lens: each query
        position t attends 0..len+t — exact vs the dense per-position
        reference. The tier-1 representative of the slow sweep."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention_pallas
        rs = np.random.RandomState(7)
        R, P, B, M, kvh, h, d, T = 4, 24, 8, 4, 2, 4, 64, 5
        q = jnp.asarray(rs.randn(R, T, h, d), jnp.float32)
        kp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        vp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        tables = jnp.asarray(
            rs.permutation(np.arange(P))[:R * M].reshape(R, M),
            jnp.int32)
        lens = jnp.asarray([0, B - 1, B, 2 * B + 3], jnp.int32)
        got = ragged_paged_attention_pallas(q, _flat(kp), _flat(vp),
                                            tables, lens, d ** -0.5, kvh,
                                            window=window)
        ref = _dense_multi_reference(q, kp, vp, tables, lens,
                                     window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("kvh,h,d,lens,window", [
        # T=4 verify rows over several compute blocks of 16 pages: a row
        # whose last query crosses into a new page AND a new block
        # (len 253 + 3 = 256), an empty slot, the table's end
        # (len + T = M*B), a two-block row
        (2, 4, 64, [253, 0, 636, 300], None),
        # the 7B's heads: 4 x 7 sublanes at head 128, windowed so the
        # walk starts mid-table and the front follows query 0
        (4, 28, 128, [253, 0, 636, 300], 280),
    ])
    def test_multi_query_blocks_of_pages(self, kvh, h, d, lens, window):
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention_pallas
        rs = np.random.RandomState(13)
        R, B, M, T = len(lens), 16, 40, 4
        P = R * M + 1
        q = jnp.asarray(rs.randn(R, T, h, d), jnp.float32)
        kp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        vp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        tables = jnp.asarray(
            1 + rs.permutation(P - 1)[:R * M].reshape(R, M), jnp.int32)
        lens = jnp.asarray(lens, jnp.int32)
        got = ragged_paged_attention_pallas(q, _flat(kp), _flat(vp),
                                            tables, lens, d ** -0.5, kvh,
                                            window=window)
        ref = _dense_multi_reference(q, kp, vp, tables, lens,
                                     window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_paged_decode_attention_routes_multi_query(self):
        """The dispatch layer: T>1 rows take the ragged kernel too, and
        it agrees with the dense gather."""
        from paddle_tpu.ops.paged_cache import (paged_decode_attention_dense,
                                                paged_decode_route)
        rs = np.random.RandomState(8)
        R, P, B, M, kvh, h, d, T = 3, 16, 16, 4, 2, 4, 64, 3
        pk = PagedKV(jnp.asarray(rs.randn(P, B, kvh * d), jnp.float32),
                     jnp.asarray(rs.randn(P, B, kvh * d), jnp.float32),
                     jnp.asarray(rs.randint(0, P, (R, M)), jnp.int32),
                     jnp.asarray([3, 30, 57], jnp.int32), kvh)
        q = jnp.asarray(rs.randn(R, T, h, d), jnp.float32)
        assert paged_decode_route(q, pk.kp, kvh) == "ragged"
        np.testing.assert_allclose(
            np.asarray(paged_decode_attention(q, pk)),
            np.asarray(paged_decode_attention_dense(q, pk)),
            atol=2e-5, rtol=2e-5)

    @pytest.mark.slow
    @pytest.mark.parametrize("h,kvh,d,T,window",
                             [(8, 4, 64, 3, None), (16, 2, 128, 5, None),
                              (4, 4, 64, 2, 20), (8, 2, 64, 5, 3),
                              (16, 8, 64, 4, None)])
    def test_multi_query_parity_sweep(self, h, kvh, d, T, window):
        """Exhaustive GQA/T/window matrix (sweep-style -> slow tier;
        the boundary-lens case above is the tier-1 representative)."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention_pallas
        rs = np.random.RandomState(9)
        R, P, B, M = 6, 48, 16, 8
        q = jnp.asarray(rs.randn(R, T, h, d), jnp.float32)
        kp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        vp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        tables = jnp.asarray(
            rs.permutation(np.arange(P))[:R * M].reshape(R, M),
            jnp.int32)
        lens = jnp.asarray([0, 15, 16, 63, 100, 120], jnp.int32)
        got = ragged_paged_attention_pallas(q, _flat(kp), _flat(vp),
                                            tables, lens, d ** -0.5, kvh,
                                            window=window)
        ref = _dense_multi_reference(q, kp, vp, tables, lens,
                                     window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestPromptLookupHelpers:
    def test_propose_ngram_most_recent_match(self):
        seq = jnp.asarray([5, 9, 5, 9, 7, 5, 9, 0, 0, 0], jnp.int32)
        # committed = first 7; suffix 2-gram (5, 9) most recently at
        # index 2 (index 5 is the suffix itself) -> continuation seq[4:]
        draft = propose_ngram(seq, jnp.int32(7), 3, 2, fill=-1)
        np.testing.assert_array_equal(np.asarray(draft), [7, 5, 9])
        # no match -> fill
        seq2 = jnp.asarray([1, 2, 3, 4, 5, 0, 0, 0], jnp.int32)
        draft2 = propose_ngram(seq2, jnp.int32(5), 3, 2, fill=-1)
        np.testing.assert_array_equal(np.asarray(draft2), [-1, -1, -1])

    def test_propose_rows_and_accept_length(self):
        seqs = jnp.asarray([[5, 9, 5, 9, 7, 0], [1, 2, 3, 4, 5, 6]],
                           jnp.int32)
        drafts = propose_ngram_rows(seqs, jnp.asarray([4, 6]), 2, 2)
        np.testing.assert_array_equal(np.asarray(drafts),
                                      [[5, 9], [-1, -1]])
        m = accept_length(jnp.asarray([[5, 9], [-1, -1]]),
                          jnp.asarray([[5, 9, 1], [2, 3, 4]]))
        np.testing.assert_array_equal(np.asarray(m), [2, 0])
        # mismatch mid-prefix stops the count
        assert int(accept_length(jnp.asarray([4, 9, 9]),
                                 jnp.asarray([4, 8, 9, 1]))) == 1

    def test_multi_write_diverts_overflow_to_garbage_block(self):
        """Positions past a row's table (or its allocated blocks: table
        entry 0) must scatter into the garbage block, never clamp onto
        a live block."""
        P, B, M, kvh, d = 4, 4, 2, 1, 8
        kp = jnp.zeros((P, B, kvh * d))
        pk = PagedKV(kp, kp, jnp.asarray([[1, 2]], jnp.int32),
                     jnp.asarray([6], jnp.int32), kvh)
        k = jnp.ones((1, 4, kvh, d))           # positions 6..9; cap = 8
        out = paged_decode_write(pk, k, k)
        got = np.asarray(out.kp)
        assert (got[1] == 0).all()             # block 1 untouched
        assert (got[2, 2:] == 1).all()         # positions 6, 7 landed
        assert (got[3] == 0).all()             # never allocated
        assert (got[0, :2] == 1).all()         # 8, 9 -> garbage block

    @pytest.mark.parametrize("writer", ["tick", "verify", "chunk"])
    def test_writers_put_a_row_where_a_heads_apart_scatter_would(
            self, writer):
        """The pool is [P, B, kvh*d]; the new K/V arrive [.., kvh, d].
        Every writer (the tick's T == 1, the verify's T > 1, a prompt
        chunk at its global positions) leaves the bytes a scatter into
        a [P, B, kvh, d] pool leaves: head h of a token in columns
        h*d .. (h+1)*d of its row."""
        from paddle_tpu.ops.paged_cache import paged_prefill_write
        rs = np.random.RandomState(5)
        P, B, M, kvh, d = 9, 4, 4, 3, 8
        old = rs.randn(2, P, B, kvh, d).astype(np.float32)
        tables = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
        lens = np.asarray([5, 10], np.int32)
        T = {"tick": 1, "verify": 3, "chunk": 6}[writer]
        rows = 1 if writer == "chunk" else 2
        new = rs.randn(2, rows, T, kvh, d).astype(np.float32)
        pk = PagedKV(*(jnp.asarray(a.reshape(P, B, kvh * d)) for a in old),
                     jnp.asarray(tables), jnp.asarray(lens), kvh)
        if writer == "chunk":       # tokens 3..8 of row 0, 5 of them live
            pk = pk._replace(seq_lens=jnp.asarray([8, 0], jnp.int32))
            positions = np.arange(3, 3 + T)
            out = paged_prefill_write(pk, *jnp.asarray(new),
                                      positions=jnp.asarray(positions))
            where = [(0, t, int(p)) for t, p in enumerate(positions)
                     if p < 8]
        else:
            out = paged_decode_write(pk, *jnp.asarray(new))
            where = [(r, t, int(lens[r]) + t) for r in range(rows)
                     for t in range(T)]
        want = old.copy()
        for r, t, pos in where:
            want[:, tables[r, pos // B], pos % B] = new[:, r, t]
        got = np.stack([np.asarray(p).reshape(P, B, kvh, d)
                        for p in out.pool])
        assert out.heads == kvh and out.kp.shape == (P, B, kvh * d)
        np.testing.assert_array_equal(got[:, 1:], want[:, 1:])


# --------------------------------------------------------------- slow tier
@pytest.mark.slow
def test_microbench_spec_tokens_per_forward():
    """ISSUE 7 acceptance: >= 2.0 tokens per forward in the paged spec
    tick on a repetitive stub stream (the profiler's
    paged_spec_tokens_per_sec rung measures the same machinery)."""
    eng = _stub_engine(spec_tokens=4, max_slots=4, num_blocks=32,
                       block_size=64, max_blocks_per_seq=4)
    for i in range(4):
        eng.submit(f"r{i}", _cyc(8), max_new_tokens=120)
    res = eng.run()
    toks = sum(len(v) for v in res.values())
    # per-row tokens per forward: identical streams finish in the same
    # tick, so every row was live for all decode_steps forwards
    tpf = (toks - 4) / 4 / max(eng.stats["decode_steps"], 1)
    assert tpf >= 2.0, (toks, eng.stats["decode_steps"])


@pytest.mark.slow
@pytest.mark.parametrize("k,g", [(1, 1), (2, 2), (6, 3), (4, 1)])
def test_spec_param_sweep_bit_identical(k, g):
    """k x ngram sweep: every config stays bitwise exact vs spec-off
    (sweep-style -> slow tier; the k=4/g=2 cases above are the tier-1
    representatives). The k=2 case runs on chunked-prefill engines
    WITHOUT the prefix cache — the chunked variant the tier-1
    composition test leaves to this sweep."""
    subs = [
        ("a", _cyc(8), dict(max_new_tokens=26)),
        ("b", np.asarray([[3, 1, 4, 1]]), dict(max_new_tokens=17)),
        ("c", _cyc(5, start=2),
         dict(max_new_tokens=21, eos_token_id=6)),
    ]
    kw = dict(block_size=8, max_blocks_per_seq=8, num_blocks=48,
              chunk_prefill_tokens=8, prefill_buckets=(8,)) \
        if k == 2 else {}
    r_off, lp_off = _drain(_stub_engine(**kw), subs)
    r_on, lp_on = _drain(_stub_engine(spec_tokens=k, spec_ngram=g,
                                      **kw), subs)
    assert r_off == r_on and lp_off == lp_on
