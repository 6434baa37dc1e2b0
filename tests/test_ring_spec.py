"""ISSUE 11: async token-ring decode pipeline + rejection-sampled
speculative ticks.

Contracts, each against an independent reference:

- RING EXACTNESS: the engine's greedy streams (tokens, logprobs, stop
  trimming), read off the device ring one step behind, are BITWISE
  identical to the host tick's (``fused_tick=False``, the engine's one
  reference, which reads every token back in its tick) — across ring
  wrap-around (streams longer than the ring), stops completing from a
  DRAINED (not live-read) token, run-ahead/spec composition,
  cancel/preempt racing an in-flight dispatch with undrained entries,
  and the rebuild that zeroes the ring's cursors before int32 ends.
- READBACK AMORTIZATION: steady decode issues one dispatch a tick and
  uploads nothing, every dispatch is drained exactly once, a drain
  counts as a blocking readback only when it had to wait (whether it
  does is read on the chip: ``tick_host_share.*``,
  ``device_idle_share.*``), and a step that runs ahead still drains
  one dispatch.
- REJECTION SAMPLING: ``sampling.residual_resample_rows`` preserves
  the per-position distribution exactly (unit: empirical marginal ==
  filtered softmax, whatever the draft), sampled rows ride speculative
  ticks (>= 1.5 tokens/forward on a repetitive sampled stream where
  spec-off is 1.0), decisive logits exact-pin to the greedy stream,
  and a seeded sweep pins spec-on vs spec-off sampled streams equal in
  distribution (behind ``slow``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.generation.prompt_lookup import mask_drafts
from paddle_tpu.generation.sampling import (filter_logits_rows,
                                            fold_in_rows,
                                            residual_resample_rows,
                                            split_key_rows)

from test_paged_spec import LookupStub, _cyc


def _engine(period=7, **kw):
    base = dict(max_slots=4, num_blocks=64, block_size=16,
                max_blocks_per_seq=8, prefill_buckets=(16,))
    base.update(kw)
    return PagedEngine(LookupStub(period), **base)


def _reference(**kw):
    """The host tick: the one reference. It does not speculate;
    greedy streams do not depend on it."""
    kw.pop("spec_tokens", None)
    return _engine(fused_tick=False, **kw)


def _drain(eng, subs):
    for rid, ids, kw in subs:
        eng.submit(rid, ids, **kw)
    res = eng.run()
    return res, dict(eng.logprobs)


GREEDY_SUBS = [
    ("a", _cyc(6), dict(max_new_tokens=30)),
    ("b", _cyc(9, start=3), dict(max_new_tokens=25)),
    ("s", _cyc(7), dict(max_new_tokens=24, stop_sequences=[[3, 4]])),
    ("e", _cyc(8), dict(max_new_tokens=30, eos_token_id=5)),
]


# ------------------------------------------------------------ ring parity
class TestRingParity:
    def test_ring_bitwise_equals_sync_greedy(self):
        """THE ring pin: tokens, logprobs AND stop trimming bitwise
        identical between the ring's drains and the host tick's
        synchronous readback."""
        r_sync, lp_sync = _drain(_reference(), GREEDY_SUBS)
        eng = _engine()
        r_ring, lp_ring = _drain(eng, GREEDY_SUBS)
        assert r_sync == r_ring
        assert lp_sync == lp_ring
        assert tuple(r_ring["s"][-2:]) != (3, 4)     # stop trimmed
        assert eng.ring_drains > 0

    def test_ring_wraparound_long_streams(self):
        """Streams several times the ring's length (16 entries a row,
        70+ tokens a request) wrap it again and again; the drain's
        monotone cursors keep every entry exactly once."""
        subs = [(rid, ids, dict(kw, max_new_tokens=70 + 5 * i))
                for i, (rid, ids, kw) in enumerate(GREEDY_SUBS[:2])]
        r_sync, lp_sync = _drain(_reference(), subs)
        eng = _engine()
        r_ring, lp_ring = _drain(eng, subs)
        assert eng._ring_len == 16
        assert min(len(v) for v in r_ring.values()) >= 4 * eng._ring_len
        assert r_sync == r_ring and lp_sync == lp_ring

    def test_ring_cursor_guard_rebuilds_before_int32_ends(self,
                                                          monkeypatch):
        """The ring's write cursors only grow; past the limit (here a
        few tokens, in service 2**30) the next transition rebuilds the
        device state, which zeroes them. Counted, one rebuild a
        rollover, and the streams are the host tick's."""
        from paddle_tpu.generation import paged
        subs = [(f"r{i}", _cyc(5 + i % 3, start=i),
                 dict(max_new_tokens=6 + 3 * (i % 4),
                      **(dict(temperature=0.8, seed=i) if i % 3 == 0
                         else {})))
                for i in range(10)]
        ref = _drain(_reference(), subs)
        monkeypatch.setattr(paged, "_RING_CURSOR_LIMIT", 8)
        eng = _engine()
        assert _drain(eng, subs) == ref
        assert eng.ring_cursor_rollovers >= 1
        assert eng.stats["ring_cursor_rollovers"] \
            == eng.ring_cursor_rollovers
        assert eng.full_rebuilds == 1 + eng.ring_cursor_rollovers
        assert eng.patches_fused > 0        # between rollovers: staged

    def test_stop_completes_from_drained_token(self):
        """The stop string lands via the DRAIN loop (one step after
        the device committed it): the request finishes, the match is
        trimmed, and the tokens the device kept committing in the
        in-flight dispatch die with the slot release (no surplus
        tokens in the result)."""
        subs = [("s", _cyc(7), dict(max_new_tokens=28,
                                    stop_sequences=[[3, 4]]))]
        r_sync, lp_sync = _drain(_reference(), subs)
        eng = _engine()
        r_ring, lp_ring = _drain(eng, subs)
        assert r_sync == r_ring and lp_sync == lp_ring
        assert tuple(r_ring["s"][-2:]) != (3, 4)

    @pytest.mark.parametrize("kw, ahead", [
        (dict(), True), (dict(spec_tokens=4), False)],
        ids=["run-ahead", "spec"])
    def test_ring_composes_with_run_ahead_and_spec(self, kw, ahead):
        """The four requests fill the four slots. ring + run-ahead: a
        slice is drained while the next tick, dispatched before it,
        runs. ring + spec_tokens: one drain consumes the whole
        multi-token dispatch, and a speculative engine keeps the
        drain-first order (its acceptance mirror is read at the
        drain). Streams stay exact."""
        r_sync, lp_sync = _drain(_reference(), GREEDY_SUBS)
        eng = _engine(**kw)
        r, lp = _drain(eng, GREEDY_SUBS)
        assert r == r_sync and lp == lp_sync
        assert (eng.stats["runahead_ticks"] > 0) == ahead

    def test_stop_and_deadline_rows_run_ahead(self):
        """A row with stop sequences and a deadline that is not due
        does not hold the engine to the drain-first order: the
        run-ahead engages on the one-slot full house while the stream
        (trim included) stays exact."""
        subs = [("s", _cyc(7), dict(max_new_tokens=24,
                                    stop_sequences=[[3, 4]],
                                    timeout_s=60.0))]
        r_sync, lp_sync = _drain(_reference(max_slots=1), subs)
        eng = _engine(max_slots=1)
        r, lp = _drain(eng, subs)
        assert r == r_sync and lp == lp_sync
        # every decode tick after the first was dispatched with its
        # predecessor undrained, the one past the stop included
        assert eng.stats["runahead_ticks"] \
            == eng.stats["decode_steps"] - 1 > 0

    def test_cancel_races_inflight_dispatch(self):
        """cancel() landing between steps — an undrained dispatch in
        flight — must drain the cancelled SLOT first, then release: no
        token loss on the survivor, no stranded blocks, the cancelled
        request recorded. Since ISSUE 14 the drain is SCOPED to the
        cancelled row: the survivor's pending entries stay pending for
        the next step()'s normal drain instead of being forced out by
        a sibling's cancel."""
        eng = _engine()
        eng.submit("keep", _cyc(6), max_new_tokens=20)
        eng.submit("kill", _cyc(9, start=3), max_new_tokens=20)
        for _ in range(4):
            eng.step()
        assert len(eng._pending) == 1        # dispatch in flight
        assert eng.cancel("kill")
        # scoped: the survivor's entries are still outstanding
        assert list(eng._pending[0]["rows"].values()) \
            == [s for s in eng.slots if s is not None]
        assert eng.ring_scoped_drains == 1
        assert eng.cancelled["kill"] == "cancelled"
        res = eng.run()
        assert "kill" not in res
        # survivor bitwise vs a solo host-tick run (batch independence)
        r_ref, _ = _drain(_reference(),
                          [("keep", _cyc(6), dict(max_new_tokens=20))])
        assert res["keep"] == r_ref["keep"]
        # every block returned to the pool
        assert len(eng.free_blocks) == eng.P - 1

    def test_preempt_under_pressure_with_ring(self):
        """Block-pool pressure forces a preemption mid-run (a slot
        transition racing the ring): recompute-mode requeue keeps the
        streams exact vs the host tick."""
        kw = dict(max_slots=2, num_blocks=6, block_size=8,
                  max_blocks_per_seq=4, prefill_buckets=(16,))
        subs = [("p", _cyc(8), dict(max_new_tokens=14)),
                ("q", _cyc(11, start=2), dict(max_new_tokens=14))]
        es = _reference(**kw)
        r_sync, lp_sync = _drain(es, subs)
        er = _engine(**kw)
        r_ring, lp_ring = _drain(er, subs)
        assert r_sync == r_ring and lp_sync == lp_ring
        assert er.stats["preemptions"] == es.stats["preemptions"]

    def test_ring_trace_events_carry_drain_lag(self):
        """Engine tick trace events report ring_lag (the dispatch-to-
        drain distance; 1 in steady pipelined state)."""
        events = []
        eng = _engine()
        eng.trace_sink = lambda rid, kind, **f: events.append((rid, kind,
                                                               f))
        eng.submit("t", _cyc(6), max_new_tokens=10)
        eng.run()
        ticks = [f for _, kind, f in events if kind == "tick"]
        assert ticks and all(f.get("ring_lag") == 1 for f in ticks)


# ----------------------------------------------------- readback amortization
class TestReadbackAmortization:
    def test_steady_ring_ticks_no_blocking_d2h(self):
        """ISSUE 11 acceptance, the part a CPU can pin: N steady ticks
        are N dispatches and no upload, every dispatch is drained, and
        the only blocking readbacks counted are drains that found
        their data not ready. How many do is a matter of timing, read
        on the chip (``tick_host_share.*``, ``device_idle_share.*``),
        not asserted on a shared CPU."""
        # block_size=64: the 26-step window never crosses a block
        # boundary, so no growth transition perturbs the counters
        eng = _engine(block_size=64, max_blocks_per_seq=2)
        for i in range(4):
            eng.submit(f"r{i}", _cyc(6), max_new_tokens=100)
        for _ in range(6):
            eng.step()
        d0, u0, r0 = eng.dispatch_count, eng.h2d_uploads, eng.ring_drains
        for _ in range(20):
            eng.step()
        assert eng.dispatch_count - d0 == 20
        assert eng.h2d_uploads - u0 == 0
        assert eng.ring_drains - r0 >= 20
        assert eng.d2h_syncs == eng.ring_blocking_drains

    def test_run_ahead_one_drain_per_tick(self):
        """The pins above restated for the order of a full house
        (dispatch first, drain after): over N run-ahead steps N
        dispatches, N drains (each dispatch drained exactly once, one
        left outstanding), no upload, and the only blocking readbacks
        counted are drains that had to wait."""
        # block_size=64: no block boundary, so no growth patch uploads
        eng = _engine(block_size=64, max_blocks_per_seq=2)
        for i in range(4):
            eng.submit(f"r{i}", _cyc(6), max_new_tokens=100)
        for _ in range(4):
            eng.step()
        d0, r0 = eng.stats["decode_steps"], eng.ring_drains
        a0, u0 = eng.stats["runahead_ticks"], eng.h2d_uploads
        for _ in range(10):
            eng.step()
        assert eng.stats["decode_steps"] - d0 == 10
        assert eng.stats["runahead_ticks"] - a0 == 10
        assert eng.ring_drains - r0 == 10
        assert eng.h2d_uploads - u0 == 0
        assert len(eng._pending) == 1
        assert eng.d2h_syncs == eng.ring_blocking_drains


# ------------------------------------------------- rejection sampling unit
class TestResidualResample:
    def _empirical(self, logits, draft, temps, tks, tps, n=4000):
        keys = jax.vmap(jax.random.key_data)(
            jax.random.split(jax.random.PRNGKey(0), n))

        @jax.jit
        def one(k):
            t, a, lp = residual_resample_rows(
                logits[None], jnp.asarray([draft], jnp.int32), k[None],
                jnp.asarray([temps], jnp.float32),
                jnp.asarray([tks], jnp.int32),
                jnp.asarray([tps], jnp.float32))
            return t[0], a[0]
        toks, accs = jax.vmap(one)(keys)
        return np.asarray(toks), np.asarray(accs)

    def test_marginal_preserved_whatever_the_draft(self):
        """The Leviathan residual rule with a one-hot draft: the
        emitted marginal equals the filtered softmax EXACTLY in
        expectation — empirically within sampling noise, for a good,
        a bad, and a missing (-1) draft."""
        logits = jnp.asarray([2.0, 1.0, 0.0, -1.0, 0.5])
        p = np.asarray(jax.nn.softmax(logits))
        for draft in (0, 3, -1):
            toks, accs = self._empirical(logits, draft, 1.0, 0, 1.0)
            freq = np.bincount(toks, minlength=5) / len(toks)
            np.testing.assert_allclose(freq, p, atol=0.03)
            if draft >= 0:
                # accept rate == p(draft)
                np.testing.assert_allclose(accs.mean(), p[draft],
                                           atol=0.03)
            else:
                assert not accs.any()

    def test_filtered_draft_never_accepted(self):
        """A draft outside the top-k set has p=0 under the filtered
        distribution: always rejected, never emitted."""
        logits = jnp.asarray([3.0, 2.0, 1.0, 0.0, -1.0])
        toks, accs = self._empirical(logits, 4, 1.0, 2, 1.0, n=800)
        assert not accs.any()
        assert not (toks == 4).any()
        assert set(np.unique(toks)) <= {0, 1}     # top-2 only

    def test_greedy_rows_bitwise_rule(self):
        """temperature <= 0: token is the raw argmax; accepted iff the
        draft equals it — the spec tick's greedy prefix rule."""
        logits = jnp.asarray([[0.0, 5.0, 1.0], [4.0, 0.0, 1.0]])
        keys = jnp.zeros((2, 2), jnp.uint32)
        t, a, lp = residual_resample_rows(
            logits, jnp.asarray([1, 1], jnp.int32), keys,
            jnp.zeros((2,)), jnp.zeros((2,), jnp.int32), jnp.ones((2,)))
        assert t.tolist() == [1, 0]
        assert a.tolist() == [True, False]
        want = jax.nn.log_softmax(logits, axis=-1)[
            jnp.arange(2), jnp.asarray([1, 0])]
        np.testing.assert_allclose(lp, want, rtol=1e-6)

    def test_helpers_roundtrip(self):
        """split/fold helpers give distinct per-position subkeys and a
        carry matching sample_token_rows' split discipline; the filter
        helper matches the classic processors on a row."""
        keys = jnp.asarray([[1, 2], [3, 4]], jnp.uint32)
        carry, sub = split_key_rows(keys)
        assert carry.shape == sub.shape == (2, 2)
        assert not np.array_equal(np.asarray(carry), np.asarray(sub))
        k0 = fold_in_rows(sub, 0)
        k1 = fold_in_rows(sub, 1)
        assert not np.array_equal(np.asarray(k0), np.asarray(k1))
        lt = filter_logits_rows(jnp.asarray([[1., 2., 3., 4.]]),
                                jnp.asarray([1.0]),
                                jnp.asarray([2], jnp.int32),
                                jnp.asarray([1.0]))
        assert (np.asarray(lt[0, :2]) < -1e29).all()
        np.testing.assert_allclose(np.asarray(lt[0, 2:]), [3.0, 4.0])

    def test_mask_drafts_gates_past_cap(self):
        drafts = jnp.asarray([[5, 6, 7], [8, 9, 1]])
        out = np.asarray(mask_drafts(drafts, jnp.asarray([2, 0])))
        assert out.tolist() == [[5, 6, -1], [-1, -1, -1]]


# ------------------------------------------------- sampled speculative e2e
class TestSampledSpec:
    def test_sampled_spec_multi_token_on_repetitive_stream(self):
        """ISSUE 11 acceptance: a repetitive SAMPLED stream (decisive
        stub logits, T=0.5) commits >= 1.5 tokens/forward under
        spec_tokens=4 where the spec-off engine is exactly 1.0."""
        sub = [("x", _cyc(8),
                dict(max_new_tokens=40, temperature=0.5, seed=11))]
        off = _engine()
        r_off, _ = _drain(off, sub)
        tpf_off = len(r_off["x"]) / off.stats["decode_steps"]
        on = _engine(spec_tokens=4)
        r_on, _ = _drain(on, sub)
        tpf_on = len(r_on["x"]) / on.stats["decode_steps"]
        assert abs(tpf_off - 1.0) < 0.1
        assert tpf_on >= 1.5, tpf_on
        assert on.stats["spec_accepted"] > 0

    def test_decisive_logits_exact_pin(self):
        """On the stub's 8.0-margin logits at low temperature every
        filtered distribution is numerically a point mass: the
        rejection-sampled spec stream equals the spec-off sampled
        stream (which equals greedy) EXACTLY — the acceptance
        criteria's exact-pin."""
        sub = [("x", _cyc(6),
                dict(max_new_tokens=24, temperature=0.25, seed=5)),
               ("g", _cyc(9, start=3), dict(max_new_tokens=20))]
        r_off, lp_off = _drain(_engine(), sub)
        eng = _engine(spec_tokens=4)
        r_on, lp_on = _drain(eng, sub)
        assert r_off == r_on and lp_off == lp_on
        assert eng.stats["spec_accepted"] > 0

    def test_sampled_spec_seeded_reproducible(self):
        """Same seeds through the rejection-sampled engine twice:
        bitwise identical (per-request PRNG streams are deterministic
        even though they differ from the 1-token tick's)."""
        sub = [("x", _cyc(5, start=2),
                dict(max_new_tokens=18, temperature=0.9, top_k=12,
                     seed=3))]
        r1, lp1 = _drain(_engine(spec_tokens=4), sub)
        r2, lp2 = _drain(_engine(spec_tokens=4), sub)
        assert r1 == r2 and lp1 == lp2

    def test_penalized_sampled_row_composes(self):
        """Penalty + sampling + spec in one row: runs, respects the
        budget, reproducible — the composition the old engine refused
        (penalized rows fell back to 1-token ticks)."""
        sub = [("x", _cyc(6),
                dict(max_new_tokens=16, temperature=0.4, seed=2,
                     repetition_penalty=1.3))]
        r1, _ = _drain(_engine(spec_tokens=4), sub)
        r2, _ = _drain(_engine(spec_tokens=4), sub)
        assert r1 == r2 and len(r1["x"]) == 16

    def test_ngram_sampled_batch_path(self):
        """The shared primitive through the batch path
        (ngram_speculative_generate): greedy default is unchanged and
        exact; sampled is seeded-reproducible and seed-sensitive."""
        from paddle_tpu.generation import ngram_speculative_generate
        stub = LookupStub(7)

        class _Gen:
            """CausalLM-ish adapter over the lookup stub for the batch
            path: dense causal attention is irrelevant (logits are a
            table read), so kv caches are a no-op passthrough. The
            table is SOFTENED (margin ~1.5, genuinely stochastic at
            T=0.9) so seed sensitivity is observable."""
            config = stub.config

            def functional(self):
                _, params = stub.functional()
                params = dict(params,
                              table=params["table"] / 8.0 * 1.5)

                def fn(p, tokens, kv_caches=None, cache_index=0):
                    return p["table"][tokens], kv_caches
                return fn, params

            def init_kv_caches(self, b, total):
                return []

        m = _Gen()
        ids = jnp.asarray(_cyc(8))
        out_g, st = ngram_speculative_generate(
            m, ids, max_new_tokens=12, return_stats=True)
        assert st["tokens_per_forward"] >= 2.0   # repetitive: accepts
        o1 = ngram_speculative_generate(
            m, ids, max_new_tokens=12, temperature=0.9,
            key=jax.random.PRNGKey(3))
        o2 = ngram_speculative_generate(
            m, ids, max_new_tokens=12, temperature=0.9,
            key=jax.random.PRNGKey(3))
        o3 = ngram_speculative_generate(
            m, ids, max_new_tokens=12, temperature=0.9,
            key=jax.random.PRNGKey(9))
        assert np.array_equal(np.asarray(o1), np.asarray(o2))
        assert not np.array_equal(np.asarray(o1), np.asarray(o3))

    @pytest.mark.slow
    def test_sampled_spec_distribution_parity_sweep(self):
        """ISSUE 11 acceptance (statistical pin): over a seeded sweep
        on a SOFT-logit stub (margin 4.0: the table successor carries
        ~0.46 probability, the rest ~uniform — genuinely stochastic),
        spec-on sampled streams match spec-off in distribution. The
        discriminating statistic is the TABLE-FOLLOW RATE — the
        fraction of transitions t -> (t+1) % period, pooled over
        positions and streams: prompt-lookup drafts are EXACTLY those
        successor tokens, so any accept bias (the classic rejection-
        sampling bug: accepting drafts too eagerly) inflates it far
        beyond binomial noise (sigma ~= 0.019 at N=720 pairs; a naive
        always-accept drives it toward 1.0). The per-seed prefill
        token is also pinned EQUAL (same path both engines)."""

        class SoftStub(LookupStub):
            def functional(self):
                fn, params = super().functional()
                params = dict(params, table=params["table"] / 8.0 * 4.0)
                return fn, params

        def stream_tokens(spec, seed):
            base = dict(max_slots=4, num_blocks=64, block_size=16,
                        max_blocks_per_seq=8, prefill_buckets=(16,))
            if spec:
                base["spec_tokens"] = 3
            eng = PagedEngine(SoftStub(5), **base)
            eng.submit("x", _cyc(6, period=5),
                       max_new_tokens=4, temperature=1.0, seed=seed)
            return eng.run()["x"]

        N = 240
        follow = {}
        for spec in (False, True):
            first, pairs, hits = [], 0, 0
            for s in range(N):
                toks = stream_tokens(spec, 1000 + s)
                first.append(toks[0])
                for a, b in zip(toks, toks[1:]):
                    pairs += 1
                    hits += int(b == (a + 1) % 5)
            follow[spec] = (hits / pairs, first)
        # identical prefill path: first tokens equal seed by seed
        assert follow[True][1] == follow[False][1]
        diff = abs(follow[True][0] - follow[False][0])
        assert diff < 0.07, (follow[True][0], follow[False][0])


# ------------------------------------------------------ tier-budget audit
class TestMarkerBudget:
    def test_audit_durations_flags_over_budget_calls(self):
        """ISSUE 11 satellite: the marker audit's durations parser
        enforces per-test wall-clock ceilings — default budget for
        unlisted tests, the named BUDGETS row for its pattern, and
        only `call` rows count (setup/teardown are shared fixture
        costs)."""
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "marker_audit", os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "tools", "marker_audit.py"))
        ma = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ma)
        lines = [
            "  30.01s call     tests/test_foo.py::test_huge",
            "  3.50s call     tests/test_foo.py::test_ok",
            # budgeted file: 13s is over DEFAULT but under its 16s row
            "  13.00s call     tests/test_hf_interop.py::test_conv",
            # setup rows never count
            "  40.00s setup    tests/test_foo.py::test_fixture_heavy",
            "============ 9 failed, 716 passed ============",
        ]
        bad = ma.audit_durations(lines)
        assert len(bad) == 1 and "test_huge" in bad[0]
        assert any(
            abs(s - 13.0) < 1e-9 and "test_conv" in n
            for s, n in ma._parse_durations(lines))
