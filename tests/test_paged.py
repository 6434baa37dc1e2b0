"""Paged-KV continuous batching (VERDICT r3 item 3): exactness vs
generate(), mid-decode admission, block recycling, and the throughput
win over whole-batch serving."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.models import LlamaForCausalLM
from paddle_tpu.models.llama import llama_tiny


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    return LlamaForCausalLM(llama_tiny())


def _engine(model, **kw):
    base = dict(max_slots=4, num_blocks=32, block_size=8,
                max_blocks_per_seq=8, prefill_buckets=(16, 32))
    base.update(kw)
    return PagedEngine(model, **base)


def _greedy_new(model, ids, n, eos=None):
    out = model.generate(jnp.asarray(ids), max_new_tokens=n,
                         temperature=0.0, eos_token_id=eos)
    return np.asarray(out)[0, ids.shape[1]:]


class TestPagedExactness:
    def test_mixed_length_stream_matches_generate(self, model):
        """Six mixed-length requests through 4 slots: every output equals
        that request's own greedy decode."""
        eng = _engine(model)
        rs = np.random.RandomState(0)
        prompts = {f"r{i}": rs.randint(1, 256, (1, rs.randint(4, 14)))
                   for i in range(6)}
        for rid, ids in prompts.items():
            eng.submit(rid, ids, max_new_tokens=12)
        out = eng.run()
        for rid, ids in prompts.items():
            np.testing.assert_array_equal(
                np.asarray(out[rid]), _greedy_new(model, ids, 12),
                err_msg=rid)

    def test_admission_mid_decode(self, model):
        """A request submitted AFTER decoding started is admitted into a
        recycled slot and still decodes exactly — the capability the
        bucketed Predictor lacks."""
        eng = _engine(model, max_slots=2)
        rs = np.random.RandomState(1)
        a = rs.randint(1, 256, (1, 6))
        b = rs.randint(1, 256, (1, 10))
        eng.submit("a", a, max_new_tokens=16)
        eng.submit("b", b, max_new_tokens=16)
        for _ in range(5):
            eng.step()
        c = rs.randint(1, 256, (1, 5))
        eng.submit("c", c, max_new_tokens=6)  # lands mid-stream
        out = eng.run()
        assert set(out) == {"a", "b", "c"}
        for rid, ids, n in (("a", a, 16), ("b", b, 16), ("c", c, 6)):
            np.testing.assert_array_equal(
                np.asarray(out[rid]), _greedy_new(model, ids, n),
                err_msg=rid)

    def test_eos_frees_slot_early(self, model):
        eng = _engine(model)
        rs = np.random.RandomState(2)
        ids = rs.randint(1, 256, (1, 8))
        ref = _greedy_new(model, ids, 24, eos=7)
        ref = ref[:np.argmax(ref == 7) + 1] if (ref == 7).any() else ref
        eng.submit("x", ids, max_new_tokens=24, eos_token_id=7)
        out = eng.run()
        np.testing.assert_array_equal(np.asarray(out["x"]), ref)

    def test_sliding_window_model(self):
        pt.seed(3)
        m = LlamaForCausalLM(llama_tiny(sliding_window=8))
        eng = _engine(m)
        ids = np.random.RandomState(3).randint(1, 256, (1, 12))
        eng.submit("w", ids, max_new_tokens=10)
        out = eng.run()
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      _greedy_new(m, ids, 10))


class TestPagedScheduling:
    def test_blocks_recycle(self, model):
        eng = _engine(model)
        n_free0 = len(eng.free_blocks)
        rs = np.random.RandomState(4)
        for i in range(5):
            eng.submit(i, rs.randint(1, 256, (1, 9)), max_new_tokens=10)
        eng.run()
        assert len(eng.free_blocks) == n_free0
        assert all(s is None for s in eng.slots)

    def test_throughput_beats_whole_batch(self, model):
        """One long + seven short requests: continuous batching recycles
        short slots while the long one runs. The whole-batch bucketed
        path pays (rows x max_new per batch); paged pays only the
        active slot-steps."""
        eng = _engine(model)
        rs = np.random.RandomState(5)
        long_ids = rs.randint(1, 256, (1, 8))
        eng.submit("long", long_ids, max_new_tokens=48)
        shorts = {}
        for i in range(7):
            shorts[f"s{i}"] = rs.randint(1, 256, (1, 6))
            eng.submit(f"s{i}", shorts[f"s{i}"], max_new_tokens=8)
        out = eng.run()
        np.testing.assert_array_equal(np.asarray(out["long"]),
                                      _greedy_new(model, long_ids, 48))
        # whole-batch serving with 4-slot batches: [long + 3 short]
        # runs 48 steps x 4 rows, [4 short] runs 8 x 4 rows
        whole_batch_row_steps = 48 * 4 + 8 * 4
        assert eng.stats["active_slot_steps"] < whole_batch_row_steps, \
            eng.stats
        # and the useful work is most of what was computed
        useful = 48 + 7 * 8
        assert eng.stats["active_slot_steps"] <= useful + 8, eng.stats

    def test_oversized_request_rejected(self, model):
        eng = _engine(model)
        with pytest.raises(ValueError, match="max_blocks_per_seq"):
            eng.submit("big", np.ones((1, 60), np.int32),
                       max_new_tokens=32)


class TestPreemption:
    def test_preemption_keeps_outputs_exact(self, model):
        """A pool too small for all requests at once: the youngest slot
        is preempted (recompute mode — emitted tokens fold into the
        requeued prompt) and every output still equals greedy."""
        eng = _engine(model, max_slots=3, num_blocks=7, block_size=8,
                      max_blocks_per_seq=6)
        rs = np.random.RandomState(6)
        prompts = {f"p{i}": rs.randint(1, 256, (1, 7)) for i in range(3)}
        for rid, ids in prompts.items():
            eng.submit(rid, ids, max_new_tokens=24)
        out = eng.run()
        assert eng.stats["preemptions"] > 0, eng.stats
        for rid, ids in prompts.items():
            np.testing.assert_array_equal(
                np.asarray(out[rid]), _greedy_new(model, ids, 24),
                err_msg=rid)
        assert len(eng.free_blocks) == 6  # all recycled (block 0 reserved)


def test_predictor_serve_stream(model):
    """inference.Predictor exposes the continuous-batching path."""
    from paddle_tpu.inference import Config, Predictor
    pred = Predictor(model, Config())
    rs = np.random.RandomState(7)
    reqs = {f"q{i}": rs.randint(1, 256, (1, 6 + i)) for i in range(3)}
    out = pred.serve_stream(reqs, max_new_tokens=8, max_slots=2,
                            num_blocks=16, block_size=8,
                            max_blocks_per_seq=4, prefill_buckets=(16,))
    for rid, ids in reqs.items():
        np.testing.assert_array_equal(np.asarray(out[rid]),
                                      _greedy_new(model, ids, 8),
                                      err_msg=rid)
    assert pred.last_serve_stats["prefills"] == 3


def test_predictor_serve_stream_reuses_engine(model):
    from paddle_tpu.inference import Config, Predictor
    pred = Predictor(model, Config())
    assert pred.last_serve_stats == {}
    kw = dict(max_slots=2, num_blocks=16, block_size=8,
              max_blocks_per_seq=4, prefill_buckets=(16,))
    rs = np.random.RandomState(8)
    a = {f"a{i}": rs.randint(1, 256, (1, 6)) for i in range(2)}
    b = {f"b{i}": rs.randint(1, 256, (1, 9)) for i in range(2)}
    out_a = pred.serve_stream(a, max_new_tokens=6, **kw)
    eng = next(iter(pred._paged_engines.values()))
    out_b = pred.serve_stream(b, max_new_tokens=6, **kw)
    assert len(pred._paged_engines) == 1  # same engine, no recompile
    for reqs, out in ((a, out_a), (b, out_b)):
        for rid, ids in reqs.items():
            np.testing.assert_array_equal(np.asarray(out[rid]),
                                          _greedy_new(model, ids, 6),
                                          err_msg=rid)


class TestPagedSampling:
    """VERDICT-r4 missing #3: per-row sampling + logprobs inside the one
    jitted decode_step."""

    def test_mixed_greedy_and_sampled_stream(self, model):
        """temp=0 rows stay bit-exact vs generate() while SHARING the
        batch with sampled rows; sampled rows are seed-reproducible."""
        rs = np.random.RandomState(7)
        prompts = {f"g{i}": rs.randint(1, 256, (1, rs.randint(4, 12)))
                   for i in range(2)}
        sampled_p = {f"s{i}": rs.randint(1, 256, (1, rs.randint(4, 12)))
                     for i in range(2)}

        def run_engine():
            eng = _engine(model)
            for rid, ids in prompts.items():
                eng.submit(rid, ids, max_new_tokens=10)
            for rid, ids in sampled_p.items():
                eng.submit(rid, ids, max_new_tokens=10, temperature=0.9,
                           top_k=40, top_p=0.95, seed=int(rid[1:]) + 123)
            out = eng.run()
            return eng, out

        eng1, out1 = run_engine()
        for rid, ids in prompts.items():
            np.testing.assert_array_equal(
                np.asarray(out1[rid]), _greedy_new(model, ids, 10),
                err_msg=rid)
        # sampled rows: reproducible across a fresh engine run
        eng2, out2 = run_engine()
        for rid in sampled_p:
            assert out1[rid] == out2[rid], rid
        # logprobs: one per emitted token, finite, <= 0
        for rid in list(prompts) + list(sampled_p):
            lps = eng1.logprobs[rid]
            assert len(lps) == len(out1[rid])
            assert all(np.isfinite(v) and v <= 0.0 for v in lps)

    def test_sampled_differs_by_seed_and_matches_distribution(self, model):
        rs = np.random.RandomState(8)
        ids = rs.randint(1, 256, (1, 6))
        outs = []
        for seed in (0, 1):
            eng = _engine(model)
            eng.submit("x", ids, max_new_tokens=12, temperature=1.0,
                       seed=seed)
            outs.append(tuple(eng.run()["x"]))
        assert outs[0] != outs[1]  # different streams actually sample

    def test_sampled_survives_preemption(self, model):
        """The carried PRNG key must make a preempted SAMPLED request
        resume its stream exactly: same output as an uncontended run."""
        rs = np.random.RandomState(9)
        ids = rs.randint(1, 256, (1, 6))
        solo = _engine(model)
        solo.submit("v", ids, max_new_tokens=30, temperature=0.8,
                    seed=42)
        want = solo.run()["v"]
        # tiny pool forces preemption of the younger request mid-stream
        eng = _engine(model, max_slots=2, num_blocks=7,
                      max_blocks_per_seq=6)
        eng.submit("a", rs.randint(1, 256, (1, 6)), max_new_tokens=30)
        eng.submit("v", ids, max_new_tokens=30, temperature=0.8, seed=42)
        out = eng.run()
        assert eng.stats["preemptions"] >= 1
        assert out["v"] == want


class TestChunkedPrefill:
    """VERDICT-r4 missing/weak: chunked prefill + multi-admission."""

    def test_chunked_exactness_vs_generate(self, model):
        """Prompts spanning several chunks (chunk=8 tokens) must decode
        exactly like generate() — the chunk attention sees earlier
        chunks through the block table."""
        eng = _engine(model, chunk_prefill_tokens=8)
        rs = np.random.RandomState(11)
        prompts = {f"c{i}": rs.randint(1, 256, (1, n))
                   for i, n in enumerate([3, 8, 17, 30])}
        for rid, ids in prompts.items():
            eng.submit(rid, ids, max_new_tokens=10)
        out = eng.run()
        assert eng.stats["prefill_chunks"] >= 1 + 1 + 3 + 4
        for rid, ids in prompts.items():
            np.testing.assert_array_equal(
                np.asarray(out[rid]), _greedy_new(model, ids, 10),
                err_msg=rid)

    def test_chunked_sampled_reproducible(self, model):
        """A sampled request must emit the SAME stream whether its
        prompt prefilled whole or in chunks (one split per token)."""
        rs = np.random.RandomState(12)
        ids = rs.randint(1, 256, (1, 20))
        outs = []
        for chunk in (None, 8):
            eng = _engine(model, chunk_prefill_tokens=chunk)
            eng.submit("s", ids, max_new_tokens=12, temperature=0.9,
                       top_p=0.9, seed=5)
            outs.append(tuple(eng.run()["s"]))
        assert outs[0] == outs[1]

    def test_multi_admission_single_step(self, model):
        """One step() admits EVERY queued request that fits, not one."""
        eng = _engine(model, max_slots=4)
        rs = np.random.RandomState(13)
        for i in range(4):
            eng.submit(f"m{i}", rs.randint(1, 256, (1, 5)),
                       max_new_tokens=4)
        eng.step()
        assert sum(s is not None for s in eng.slots) == 4
        assert not eng.queue

    def test_long_prompt_does_not_stall_decode(self, model):
        """The scheduling property behind chunked prefill: while a long
        prompt enters chunk-by-chunk, the already-active slot keeps
        emitting one token per tick."""
        eng = _engine(model, max_slots=2, chunk_prefill_tokens=8,
                      num_blocks=32, max_blocks_per_seq=8,
                      prefill_buckets=(16, 32, 64))
        rs = np.random.RandomState(14)
        short = rs.randint(1, 256, (1, 4))
        long_p = rs.randint(1, 256, (1, 48))       # 6 chunks of 8
        eng.submit("short", short, max_new_tokens=30)
        eng.step()                                  # short becomes active
        n0 = len(eng.slots[0].tokens)
        eng.submit("long", long_p, max_new_tokens=4)
        ticks = 0
        while any(s is not None and s.request_id == "long"
                  and s.prefill_pos < 48 for s in eng.slots) or \
                any(r.request_id == "long" for r in eng.queue):
            eng.step()
            ticks += 1
            if ticks > 20:
                break
        # during the >= 6 prefill ticks, short emitted a token per tick
        shorts = eng.results.get("short") or eng.slots[
            [i for i, s in enumerate(eng.slots)
             if s and s.request_id == "short"][0]].tokens
        assert len(shorts) - n0 >= 6
        out = eng.run()
        np.testing.assert_array_equal(np.asarray(out["short"]),
                                      _greedy_new(model, short, 30))
        np.testing.assert_array_equal(np.asarray(out["long"]),
                                      _greedy_new(model, long_p, 4))

    def test_preempted_mid_prefill_key_is_authoritative(self, model):
        """Review r5: the requeued request must carry req.key (untouched
        during chunk prefill), not self.keys[slot] — which every decode
        tick garbage-advances for mid-prefill rows."""
        eng = _engine(model, chunk_prefill_tokens=8)
        rs = np.random.RandomState(15)
        eng.submit("g", rs.randint(1, 256, (1, 4)), max_new_tokens=20)
        eng.submit("s", rs.randint(1, 256, (1, 30)), max_new_tokens=8,
                   temperature=0.9, seed=77)
        eng.step()   # admits both; s is mid-prefill (30 > 8)
        sid = [i for i, s in enumerate(eng.slots)
               if s and s.request_id == "s"][0]
        assert eng.slots[sid].prefill_pos < 30
        want_key = eng.slots[sid].key.copy()
        eng.keys[sid] ^= 0xDEAD          # simulate decode-tick drift
        gid = [i for i, s in enumerate(eng.slots)
               if s and s.request_id == "g"][0]
        assert eng._preempt_youngest(exclude=gid)
        assert eng.queue and eng.queue[0].request_id == "s"
        np.testing.assert_array_equal(eng.queue[0].key, want_key)


class TestPrefixCaching:
    """Automatic prefix caching (round 5): shared prompt prefixes reuse
    physical blocks and skip prefill compute, quantized to the chunk
    grid so reuse is bit-exact; blocks outlive their owner in an LRU
    pool and are evicted under pressure."""

    def _engine(self, model, **kw):
        base = dict(max_slots=4, num_blocks=32, block_size=8,
                    max_blocks_per_seq=8, prefill_buckets=(16, 32),
                    chunk_prefill_tokens=16, enable_prefix_cache=True)
        base.update(kw)
        return PagedEngine(model, **base)

    def test_requires_chunked_prefill(self, model):
        with pytest.raises(ValueError, match="chunk_prefill_tokens"):
            PagedEngine(model, enable_prefix_cache=True)

    def test_shared_prefix_skips_chunks_and_stays_exact(self, model):
        """Second request with the same 32-token system prefix: fewer
        prefill chunks, identical output to its own greedy decode."""
        rs = np.random.RandomState(40)
        sys_prompt = rs.randint(1, 256, 32).tolist()
        a = np.asarray([sys_prompt + rs.randint(1, 256, 5).tolist()])
        b = np.asarray([sys_prompt + rs.randint(1, 256, 7).tolist()])
        eng = self._engine(model)
        eng.submit("a", a, max_new_tokens=8)
        eng.run()
        chunks_a = eng.stats["prefill_chunks"]
        eng.submit("b", b, max_new_tokens=8)
        out = eng.run()
        chunks_b = eng.stats["prefill_chunks"] - chunks_a
        # 32 shared tokens = 2 chunks of 16 skipped for b
        assert eng.stats["prefix_hit_tokens"] == 32, eng.stats
        assert chunks_b < chunks_a, (chunks_a, chunks_b)
        np.testing.assert_array_equal(np.asarray(out["b"]),
                                      _greedy_new(model, b, 8))
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      _greedy_new(model, a, 8))

    def test_blocks_survive_owner_and_accounting_drains(self, model):
        """Donor finishes BEFORE the borrower submits: its prefix blocks
        park in cached_free and are still adopted; at drain every
        non-garbage block is either free or parked (no leaks)."""
        rs = np.random.RandomState(41)
        pref = rs.randint(1, 256, 32).tolist()
        eng = self._engine(model)
        eng.submit("a", np.asarray([pref + [7]]), max_new_tokens=4)
        eng.run()
        assert len(eng.cached_free) > 0          # parked, not freed
        eng.submit("b", np.asarray([pref + [9, 9]]), max_new_tokens=4)
        out = eng.run()
        assert eng.stats["prefix_adopted_blocks"] >= 4   # 32 tok / B=8
        np.testing.assert_array_equal(
            np.asarray(out["b"]),
            _greedy_new(model, np.asarray([pref + [9, 9]]), 4))
        assert not eng.block_refs                # no live owners
        assert len(eng.free_blocks) + len(eng.cached_free) == eng.P - 1

    def test_eviction_under_pressure(self, model):
        """A stream of DISTINCT long prompts through a small pool: parked
        blocks must be evicted for new requests, never crashing, and
        every output stays exact."""
        rs = np.random.RandomState(42)
        eng = self._engine(model, num_blocks=16, max_slots=2)
        prompts = {f"r{i}": np.asarray([rs.randint(1, 256, 33)])
                   for i in range(5)}
        for rid, ids in prompts.items():
            eng.submit(rid, ids, max_new_tokens=4)
        out = eng.run()
        for rid, ids in prompts.items():
            np.testing.assert_array_equal(
                np.asarray(out[rid]), _greedy_new(model, ids, 4),
                err_msg=rid)
        assert len(eng.free_blocks) + len(eng.cached_free) == eng.P - 1

    def test_sampled_borrower_reproducible(self, model):
        """Prefix sharing must not perturb a sampled request's PRNG
        stream: same seed twice -> same tokens, with a donor's blocks
        adopted both times."""
        rs = np.random.RandomState(43)
        pref = rs.randint(1, 256, 32).tolist()
        ids = np.asarray([pref + [5, 6]])
        outs = []
        for _ in range(2):
            eng = self._engine(model)
            eng.submit("donor", np.asarray([pref + [1]]), max_new_tokens=2)
            eng.run()
            eng.submit("s", ids, max_new_tokens=10, temperature=0.9,
                       top_p=0.9, seed=123)
            outs.append(eng.run()["s"])
            assert eng.stats["prefix_hit_tokens"] >= 32
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      np.asarray(outs[1]))

    def test_no_false_sharing(self, model):
        """Prompts differing in token 0 must not hit the cache."""
        rs = np.random.RandomState(44)
        base = rs.randint(1, 256, 33)
        other = base.copy()
        other[0] = base[0] % 255 + 1
        eng = self._engine(model)
        eng.submit("a", np.asarray([base]), max_new_tokens=4)
        eng.run()
        eng.submit("b", np.asarray([other]), max_new_tokens=4)
        out = eng.run()
        assert eng.stats["prefix_hit_tokens"] == 0
        np.testing.assert_array_equal(
            np.asarray(out["b"]), _greedy_new(model, np.asarray([other]), 4))

    def test_preempted_request_rehits_prefix(self, model):
        """Recompute-mode preemption becomes cheap: the victim's
        re-prefill adopts its own still-registered prefix blocks."""
        rs = np.random.RandomState(45)
        eng = self._engine(model, num_blocks=14, max_slots=2,
                           max_blocks_per_seq=8)
        a = np.asarray([rs.randint(1, 256, 17)])
        b = np.asarray([rs.randint(1, 256, 17)])
        eng.submit("a", a, max_new_tokens=24)
        eng.submit("b", b, max_new_tokens=24)
        out = eng.run()
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      _greedy_new(model, a, 24))
        np.testing.assert_array_equal(np.asarray(out["b"]),
                                      _greedy_new(model, b, 24))


class TestStopSequences:
    """Token-id stop sequences (round 5): the generated stream ends the
    moment it ends with one; the match is trimmed (vLLM semantics)."""

    def test_stop_truncates_exactly(self, model):
        """Expected output = this request's own greedy stream cut at the
        first occurrence of the stop sequence."""
        eng = _engine(model)
        rs = np.random.RandomState(50)
        ids = rs.randint(1, 256, (1, 8))
        full = _greedy_new(model, ids, 24).tolist()
        # choose a 2-gram that actually occurs mid-stream as the stop
        stop = None
        for i in range(2, len(full) - 2):
            stop = (full[i], full[i + 1])
            break
        eng.submit("s", ids, max_new_tokens=24, stop_sequences=[stop])
        out = eng.run()["s"]
        # reference: scan the greedy stream for the first suffix match
        want = []
        for t in full:
            want.append(t)
            if len(want) >= 2 and tuple(want[-2:]) == stop:
                want = want[:-2]
                break
        assert list(out) == want, (out, want, stop)
        assert len(eng.logprobs["s"]) == len(want)

    def test_no_match_runs_to_budget(self, model):
        eng = _engine(model)
        rs = np.random.RandomState(51)
        ids = rs.randint(1, 256, (1, 8))
        eng.submit("s", ids, max_new_tokens=12,
                   stop_sequences=[(999, 999)])  # out-of-vocab: no match
        out = eng.run()["s"]
        np.testing.assert_array_equal(np.asarray(out),
                                      _greedy_new(model, ids, 12))

    def test_empty_stop_sequence_rejected(self, model):
        eng = _engine(model)
        with pytest.raises(ValueError, match="empty stop"):
            eng.submit("s", np.asarray([[1, 2, 3]]), max_new_tokens=4,
                       stop_sequences=[[]])

    def test_stop_on_final_budgeted_token_still_trims(self, model):
        """Review r5: a stop completing exactly on the last budgeted
        token must be trimmed the same as mid-stream."""
        eng = _engine(model)
        rs = np.random.RandomState(52)
        ids = rs.randint(1, 256, (1, 8))
        full = _greedy_new(model, ids, 24).tolist()
        # pick the FIRST occurrence of some adjacent pair and set the
        # budget so the match completes exactly on the last allowed token
        stop = (full[2], full[3])
        first_end = next(i + 1 for i in range(1, len(full))
                         if (full[i - 1], full[i]) == stop)
        eng.submit("s", ids, max_new_tokens=first_end,
                   stop_sequences=[stop])
        out = eng.run()["s"]
        assert list(out) == full[:first_end - 2], (out, stop, first_end)


class TestEngineRepetitionPenalty:
    """Per-request repetition_penalty in the serving engine (round 5):
    matches generate()'s penalty token-for-token; rows at 1.0 stay
    bit-exact argmax."""

    def test_greedy_penalty_matches_generate(self, model):
        eng = _engine(model)
        rs = np.random.RandomState(60)
        ids = rs.randint(1, 256, (1, 8))
        eng.submit("p", ids, max_new_tokens=16, repetition_penalty=1.5)
        eng.submit("g", rs.randint(1, 256, (1, 6)), max_new_tokens=16)
        out = eng.run()
        want = model.generate(jnp.asarray(ids), max_new_tokens=16,
                              temperature=0.0, repetition_penalty=1.5)
        np.testing.assert_array_equal(np.asarray(out["p"]),
                                      np.asarray(want)[0, 8:])
        # and the penalty changed something vs the raw greedy stream
        assert list(out["p"]) != _greedy_new(model, ids, 16).tolist()

    def test_chunked_prefill_penalty_exact(self, model):
        """The seen mask accumulates across prompt chunks (and the
        prefix-cache seeding path) and still matches generate()."""
        eng = _engine(model, chunk_prefill_tokens=8,
                      enable_prefix_cache=True, max_blocks_per_seq=8)
        rs = np.random.RandomState(61)
        pref = rs.randint(1, 256, 16).tolist()
        a = np.asarray([pref + rs.randint(1, 256, 3).tolist()])
        b = np.asarray([pref + rs.randint(1, 256, 5).tolist()])
        eng.submit("a", a, max_new_tokens=10, repetition_penalty=1.4)
        eng.run()
        eng.submit("b", b, max_new_tokens=10, repetition_penalty=1.4)
        out = eng.run()
        assert eng.stats["prefix_hit_tokens"] > 0   # b reused a's chunks
        for rid, ids in (("a", a), ("b", b)):
            want = model.generate(jnp.asarray(ids), max_new_tokens=10,
                                  temperature=0.0,
                                  repetition_penalty=1.4)
            np.testing.assert_array_equal(
                np.asarray(eng.results[rid]),
                np.asarray(want)[0, ids.shape[1]:], err_msg=rid)

    def test_penalty_survives_preemption(self, model):
        """Recompute-mode preemption rebuilds the seen mask from
        prompt+emitted — penalized decode stays exact."""
        eng = _engine(model, max_slots=3, num_blocks=7, block_size=8,
                      max_blocks_per_seq=6)
        rs = np.random.RandomState(62)
        prompts = {f"p{i}": rs.randint(1, 256, (1, 7)) for i in range(3)}
        for rid, ids in prompts.items():
            eng.submit(rid, ids, max_new_tokens=20,
                       repetition_penalty=1.3)
        out = eng.run()
        assert eng.stats["preemptions"] > 0, eng.stats
        for rid, ids in prompts.items():
            want = model.generate(jnp.asarray(ids), max_new_tokens=20,
                                  temperature=0.0,
                                  repetition_penalty=1.3)
            np.testing.assert_array_equal(
                np.asarray(out[rid]),
                np.asarray(want)[0, ids.shape[1]:], err_msg=rid)

    def test_invalid_penalty_rejected(self, model):
        eng = _engine(model)
        with pytest.raises(ValueError, match="repetition_penalty"):
            eng.submit("x", np.asarray([[1, 2]]), max_new_tokens=4,
                       repetition_penalty=0.0)

    def test_penalty_exact_while_other_slot_prefills(self, model):
        """Review r5: a decode tick running while another slot is
        mid-chunk-prefill must NOT pollute the prefilling row's seen
        mask with its garbage sampled token."""
        eng = _engine(model, chunk_prefill_tokens=8, max_slots=2,
                      max_blocks_per_seq=8)
        rs = np.random.RandomState(63)
        a = rs.randint(1, 256, (1, 6))      # starts decoding first
        b = rs.randint(1, 256, (1, 40))     # 5 chunks of prefill
        eng.submit("a", a, max_new_tokens=20, repetition_penalty=1.4)
        eng.step(); eng.step()              # a decoding, b queued
        eng.submit("b", b, max_new_tokens=16, repetition_penalty=1.4)
        out = eng.run()                     # b prefills under a's decode
        for rid, ids, n in (("a", a, 20), ("b", b, 16)):
            want = model.generate(jnp.asarray(ids), max_new_tokens=n,
                                  temperature=0.0,
                                  repetition_penalty=1.4)
            np.testing.assert_array_equal(
                np.asarray(out[rid]),
                np.asarray(want)[0, ids.shape[1]:], err_msg=rid)


class TestStreaming:
    """stream(): tokens yielded as they land, exactly matching run()'s
    results per request (stop trims never retract a yielded token)."""

    def test_stream_matches_results(self, model):
        eng = _engine(model)
        rs = np.random.RandomState(70)
        prompts = {f"r{i}": rs.randint(1, 256, (1, rs.randint(4, 12)))
                   for i in range(5)}
        for rid, ids in prompts.items():
            eng.submit(rid, ids, max_new_tokens=10)
        got = {}
        order = []
        for rid, tok in eng.stream():
            got.setdefault(rid, []).append(tok)
            order.append(rid)
        for rid in prompts:
            assert got[rid] == list(eng.results[rid]), rid
            np.testing.assert_array_equal(
                np.asarray(got[rid]),
                _greedy_new(model, prompts[rid], 10), err_msg=rid)
        # genuinely interleaved, not request-by-request
        first_block = order[:len(prompts)]
        assert len(set(first_block)) > 1, order[:10]

    def test_stream_with_stop_never_retracts(self, model):
        eng = _engine(model)
        rs = np.random.RandomState(71)
        ids = rs.randint(1, 256, (1, 8))
        full = _greedy_new(model, ids, 24).tolist()
        stop = (full[4], full[5])
        eng.submit("s", ids, max_new_tokens=24, stop_sequences=[stop])
        got = [t for rid, t in eng.stream()]
        assert got == list(eng.results["s"]), (got, eng.results["s"])

    def test_stream_mid_iteration_submit(self, model):
        eng = _engine(model, max_slots=2)
        rs = np.random.RandomState(72)
        a = rs.randint(1, 256, (1, 6))
        eng.submit("a", a, max_new_tokens=8)
        got = {}
        submitted_b = False
        b = rs.randint(1, 256, (1, 7))
        for rid, tok in eng.stream():
            got.setdefault(rid, []).append(tok)
            if not submitted_b and len(got.get("a", [])) >= 3:
                eng.submit("b", b, max_new_tokens=6)
                submitted_b = True
        np.testing.assert_array_equal(np.asarray(got["a"]),
                                      _greedy_new(model, a, 8))
        np.testing.assert_array_equal(np.asarray(got["b"]),
                                      _greedy_new(model, b, 6))

    def test_stream_on_reused_engine_no_replay(self, model):
        """Review r5: a prior run()'s results must not replay into a
        later stream() on the same engine."""
        eng = _engine(model)
        rs = np.random.RandomState(73)
        a = rs.randint(1, 256, (1, 6))
        eng.submit("a", a, max_new_tokens=6)
        eng.run()
        b = rs.randint(1, 256, (1, 7))
        eng.submit("b", b, max_new_tokens=6)
        got = {}
        for rid, tok in eng.stream():
            got.setdefault(rid, []).append(tok)
        assert set(got) == {"b"}, got.keys()
        np.testing.assert_array_equal(np.asarray(got["b"]),
                                      _greedy_new(model, b, 6))


class TestCacheLayers:
    """ISSUE 32: the model says what each cache layer is. The families
    that answered before get the pools they got (one shape every layer,
    ``num_blocks`` pages, a K/V pair or one latent row); a model whose
    layers differ gets a pool a layer, a band-keeping layer's a ring of
    pages a slot; ``decode_route`` answers for every layer's shapes.
    ISSUE 38: a layer may keep STATE by slot instead (``StateLayer``):
    its arrays are ``[max_slots, ...]`` beside the pools, and the four
    older families get exactly the arrays they got. ISSUE 41: state
    layers beside a LATENT pool (one array a layer) in one model."""

    @staticmethod
    def _built(family):
        from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                                   deepseek_v2_tiny)
        from paddle_tpu.models.longcat_flash import (
            LongcatFlashForCausalLM, longcat_flash_tiny)
        from paddle_tpu.models.mimo_v2 import (MiMoV2ForCausalLM,
                                               mimo_v2_tiny)
        from paddle_tpu.models.olmo_hybrid import (OlmoHybridForCausalLM,
                                                   olmo_hybrid_tiny)
        from paddle_tpu.models.ling_hybrid import (LingHybridForCausalLM,
                                                   ling_hybrid_tiny)
        pt.seed(0)
        return {
            "llama": lambda: LlamaForCausalLM(llama_tiny()),
            "deepseek": lambda: DeepseekV2ForCausalLM(deepseek_v2_tiny()),
            "longcat": lambda: LongcatFlashForCausalLM(
                longcat_flash_tiny(num_hidden_layers=1)),
            "mimo": lambda: MiMoV2ForCausalLM(mimo_v2_tiny()),
            "olmo": lambda: OlmoHybridForCausalLM(olmo_hybrid_tiny()),
            "ling": lambda: LingHybridForCausalLM(ling_hybrid_tiny(
                experts_held=4)),
        }[family]()

    @pytest.mark.parametrize("family,pools", [
        # 2 layers x (K, V) of 2 kv heads x 16 columns
        ("llama", [((32, 8, 32), (32, 8, 32))] * 2),
        # 2 layers x one latent row: 32 + 8 columns padded to 128
        ("deepseek", [((32, 8, 128),)] * 2),
        # one double layer: two latent rows
        ("longcat", [((32, 8, 128),)] * 2),
        # a full layer (1 kv head; keys 24, values 16) by the allocator's
        # pages; two window layers (2 kv heads) by a ring of window 2 +
        # chunk 2 + 1 pages a slot and the garbage block
        ("mimo", [((32, 8, 24), (32, 8, 16)),
                  ((21, 8, 48), (21, 8, 32)), ((21, 8, 48), (21, 8, 32))]),
        # three linear layers: by SLOT, 8 heads' 8 x 16 states side by
        # side in one 128-lane row and the convolution's last 3 inputs;
        # a full layer (4 kv heads of 16) by the allocator's pages; the
        # prompt calls' two counters behind them
        ("olmo", [((4, 1, 8, 128), (4, 3, 256))] * 3
         + [((32, 8, 64), (32, 8, 64)), ((2,),)]),
        # two linear layers by SLOT (8 heads' 16 x 16 states in one
        # 128-lane row, 3 inputs of 3 x 128 channels), then ONE latent
        # row a token (32 + 8 columns padded to 128) by the allocator's
        # pages, then the prompt calls' two counters
        ("ling", [((4, 1, 16, 128), (4, 3, 384))] * 2
         + [((32, 8, 128),), ((2,),)]),
    ])
    def test_each_familys_pools(self, family, pools):
        from paddle_tpu.ops.paged_cache import StateLayer
        eng = _engine(self._built(family), chunk_prefill_tokens=16)
        assert [tuple(p.shape for p in layer) for layer in eng.pools] \
            == pools
        state = [isinstance(layer, StateLayer) for layer in eng._layout]
        assert [layer.window for layer in eng._layout] \
            == ([None, 12, 12] if family == "mimo"
                else [None] * len(eng._layout))
        # the extra tick counters exist only where a band is kept, or
        # state by slot; every other engine holds a pool a layer
        assert ("kv_window_blocks" in eng.stats) == (family == "mimo")
        slots = {"olmo": 3, "ling": 2}.get(family, 0)   # state layers
        assert ("state_resets" in eng.stats) == bool(slots)
        assert state == ([True] * slots + [False] if slots
                         else [False] * len(pools))
        assert len(eng.pools) == len(eng._layout) + bool(slots)
        assert ("moe_rows_routed_here" in eng.stats) == (family == "ling")

    def test_decode_route_answers_for_every_layer(self, monkeypatch):
        """"ragged" only if EVERY cache layer's shapes take the kernel:
        an engine whose window layers did not would serve them by the
        dense gather while its first layer said "ragged"."""
        from paddle_tpu.generation import paged
        eng = _engine(self._built("mimo"), chunk_prefill_tokens=16)
        asked = []

        def route(q, kp, kv_heads):
            asked.append((q.shape[-1], kp.shape, kv_heads))
            return "dense" if kv_heads == 2 else "ragged"
        monkeypatch.setattr(paged, "paged_decode_route", route)
        assert eng.decode_route() == "dense"
        assert asked == [(24, (32, 8, 24), 1), (24, (21, 8, 48), 2),
                         (24, (21, 8, 48), 2)]
        monkeypatch.setattr(paged, "paged_decode_route",
                            lambda q, kp, kv_heads: "ragged")
        assert eng.decode_route() == "ragged"

    def test_a_ring_is_never_longer_than_a_sequence(self):
        """Where the window and a chunk already cover a whole sequence
        the ring is the sequence's own pages and never wraps."""
        eng = _engine(self._built("mimo"), max_blocks_per_seq=4,
                      chunk_prefill_tokens=16)
        assert eng._ring_blocks(12) == 4
        assert eng.pools[1][0].shape[0] == 4 * 4 + 1
