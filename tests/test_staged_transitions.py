"""ISSUE 14 + 19: persistent decode program — slot transitions as
per-slot descriptors staged into the tick program itself.

The served engine (the default) packs each transition into a
descriptor, stages the pending ones into a device-resident queue of
``max_slots`` rows by a plain H2D upload, and the NEXT tick's program
applies them all in one masked batched scatter — one executable, one
dispatch, whether a tick carries 0 or R transitions. What it is
compared with is the host tick (``fused_tick=False``), the engine's one
reference; for a speculative engine, greedy streams against the
non-speculative host tick (tokens bitwise, logprobs to float rounding:
its forward scores k+1 positions at once).

Contracts:

- STREAM PARITY: greedy and seeded-sampled token/logprob streams are
  BITWISE the host tick's, per request, across every transition kind
  — admit, finish, chunked-prefill advance, preempt, cancel, block
  growth. (Tokens leave the fused engine one step behind the device,
  so the interleave BETWEEN requests may differ; each request's own
  stream may not.)
- ONE DISPATCH PER TICK (ISSUE 19 acceptance): steady churn runs N
  ticks in exactly N dispatches and 0 full rebuilds, including an
  R-row synchronized finish wave, which fits the queue.
- WARM ADMIT (ROADMAP 4(b) first rung): ``submit()`` on a warm
  chunked engine claims the slot eagerly and issues ZERO dispatches
  until the next tick.
- SCOPED DRAIN: an out-of-band transition (cancel/expiry) consumes
  only the affected slot's pending ring entries; untouched siblings'
  pending tokens survive and land at the next step()'s normal drain.
- UPLOAD ACCOUNTING: steady churn runs 0 full-state rebuilds; a
  staging upload is ``R * desc_len * 4 + 4`` bytes and a steady tick
  uploads nothing.
- FAILOVER: ``export_resumable()`` descriptors, read off host mirrors
  that advance via drains, hold a prefix of the reference's stream,
  and a resume from them continues it bitwise.
- THE SWITCHES ARE GONE: the constructor refuses the five deleted
  options.
"""
import inspect

import numpy as np
import pytest

from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.generation.stub import TickStubModel


def _cyc(n, start=0):
    return (np.arange(n) % 5 + 1 + start)[None]


def _engine(**kw):
    base = dict(max_slots=4, num_blocks=32, block_size=8,
                max_blocks_per_seq=8, prefill_buckets=(16,))
    base.update(kw)
    return PagedEngine(TickStubModel(), **base)


def _reference(**kw):
    """The host tick: the one reference. It does not speculate, so a
    speculative engine's GREEDY streams are compared with it."""
    kw.pop("spec_tokens", None)
    return _engine(fused_tick=False, **kw)


def _drain(eng, submits):
    for rid, ids, skw in submits:
        eng.submit(rid, ids, **skw)
    res = eng.run()
    return res, dict(eng.logprobs)


# mixed greedy/sampled workload exercising admit, finish, eos, stops
# and block growth (prompts + budgets cross the 8-token block grid)
MIXED_SUBS = [
    ("g", _cyc(6), dict(max_new_tokens=20)),
    ("s", _cyc(8, 2), dict(max_new_tokens=14, temperature=0.8,
                           top_k=20, seed=5)),
    ("st", _cyc(9, 1), dict(max_new_tokens=24, stop_sequences=[[3, 4]])),
    ("e", _cyc(5, 3), dict(max_new_tokens=16, eos_token_id=2)),
]


def _lps_close(a, b):
    """A speculative tick scores a window of k+1 positions in one
    forward, the host tick one position: the same tokens, logprobs
    equal to float rounding."""
    assert a.keys() == b.keys()
    for rid in a:
        np.testing.assert_allclose(a[rid], b[rid], rtol=0, atol=1e-5)


def _per_request(pairs):
    """A ``stream()``'s (request, token) pairs, per request in order."""
    out = {}
    for rid, tok in pairs:
        out.setdefault(rid, []).append(tok)
    return out


class TestDeltaParity:
    def test_transition_matrix_bitwise(self):
        """Admit/finish/growth/stop/eos churn + a mid-run second wave
        (admits into slots whose previous tenants finished): the
        staged-queue engine and the host tick agree on every token and
        every logprob float."""
        def run(make):
            eng = make()
            res, lps = _drain(eng, MIXED_SUBS)
            # second wave: readmits into released rows (the ring
            # cursors continue where the previous tenant stopped)
            res2, lps2 = _drain(eng, [
                ("w1", _cyc(4, 1), dict(max_new_tokens=9)),
                ("w2", _cyc(7, 2), dict(max_new_tokens=11,
                                        temperature=0.6, seed=9)),
            ])
            res.update(res2)
            lps.update(lps2)
            return eng, res, lps

        er, rr, lr = run(_reference)
        ef, rf, lf = run(_engine)
        assert rr == rf
        assert lr == lf
        assert er.full_rebuilds == 0         # the host tick has no state
        # one rebuild, the first; every transition after it rode the
        # staged queue
        assert ef.full_rebuilds == 1
        assert ef.patches_fused > 0

    def test_midstream_admit_interleave_exact(self):
        """A submit() landing mid-decode rides a staged descriptor;
        each request's emission order is the host tick's exactly (the
        interleave between the two is one step apart: the ring)."""
        def run(make):
            eng = make()
            eng.submit("r0", _cyc(6), max_new_tokens=18)
            out = []
            for n, pair in enumerate(eng.stream()):
                out.append(pair)
                if n == 4:
                    eng.submit("r1", _cyc(10, 3), max_new_tokens=12,
                               temperature=0.8, seed=3)
            return out, dict(eng.results), dict(eng.logprobs)

        sr, rr, lr = run(_reference)
        sd, rd, ld = run(_engine)
        assert _per_request(sr) == _per_request(sd) == rd
        assert rr == rd and lr == ld

    def test_chunked_prefill_and_prefix_cache_parity(self):
        """Chunk advances are lens-only patches until the final chunk
        activates the row; prefix-cache adoption (a table-row patch
        pointing at shared physical blocks) stays bitwise too."""
        sys_p = list(range(1, 17))

        def run(make):
            eng = make(max_slots=2, chunk_prefill_tokens=8,
                       enable_prefix_cache=True, prefill_buckets=(8,))
            r1, l1 = _drain(eng, [
                ("x", np.asarray(sys_p + [20, 21])[None],
                 dict(max_new_tokens=10)),
            ])
            # second request adopts x's registered prefix blocks
            r2, l2 = _drain(eng, [
                ("y", np.asarray(sys_p + [30])[None],
                 dict(max_new_tokens=8, temperature=0.5, seed=7)),
            ])
            r1.update(r2)
            l1.update(l2)
            return eng, r1, l1

        er, rr, lr = run(_reference)
        ed, rd, ld = run(_engine)
        assert rr == rd and lr == ld
        assert ed.stats["prefix_hit_tokens"] == \
            er.stats["prefix_hit_tokens"] > 0
        assert ed.full_rebuilds == 1

    def test_preemption_parity(self):
        """Block-pool pressure forces recompute-mode preemption (a
        release patch + a requeue) mid-run; streams match the host
        tick's, sampled victim included."""
        kw = dict(max_slots=2, num_blocks=6, block_size=8,
                  max_blocks_per_seq=4, prefill_buckets=(16,))
        subs = [("p", _cyc(8), dict(max_new_tokens=14)),
                ("q", _cyc(11, 2), dict(max_new_tokens=14,
                                        temperature=0.9, seed=5))]
        er, rr, lr = (lambda e: (e, *_drain(e, subs)))(_reference(**kw))
        ed, rd, ld = (lambda e: (e, *_drain(e, subs)))(_engine(**kw))
        assert rr == rd and lr == ld
        assert er.stats["preemptions"] > 0
        assert ed.stats["preemptions"] > 0

    def test_cancel_race_parity(self):
        """cancel() between steps (a dispatch in flight): the
        survivor's stream matches the host tick's run token for token,
        and the cancel lands identically."""
        def run(make):
            eng = make()
            eng.submit("keep", _cyc(6), max_new_tokens=20)
            eng.submit("kill", _cyc(9, 3), max_new_tokens=20)
            for _ in range(4):
                eng.step()
            assert eng.cancel("kill")
            res = eng.run()
            return eng, res, dict(eng.logprobs)

        er, rr, lr = run(_reference)
        ed, rd, ld = run(_engine)
        assert rr == rd and lr == ld
        assert er.cancelled == ed.cancelled == {"kill": "cancelled"}
        assert len(ed.free_blocks) == ed.P - 1

    def test_spec_greedy_parity(self):
        """Speculative ticks: the descriptor carries the committed-
        token row, accept EMA and probe counter, so greedy spec
        tokens (draft-invariant by the argmax-prefix rule) are the
        host tick's through admit/finish churn."""
        def run(make):
            eng = make(prefill_buckets=(8,), spec_tokens=3)
            res, lps = _drain(eng, [
                ("g", _cyc(6), dict(max_new_tokens=15)),
                ("h", _cyc(8, 2), dict(max_new_tokens=10)),
            ])
            res2, lps2 = _drain(eng, [
                ("i", _cyc(5, 1), dict(max_new_tokens=12))])
            res.update(res2)
            lps.update(lps2)
            return eng, res, lps

        _, rr, lr = run(_reference)
        ef, rf, lf = run(_engine)
        assert rr == rf
        _lps_close(lr, lf)
        assert ef.full_rebuilds == 1 and ef.patches_fused > 0
        assert ef.stats["spec_accepted"] > 0

    @pytest.mark.parametrize("name", [
        "ring_mode", "ring_len", "delta_transitions", "patch_fuse",
        "patch_queue_len", "ticks_per_dispatch"])
    def test_the_mode_switches_are_gone(self, name):
        """The served path has no variants: the constructor has none of
        the five options PR 28 deleted nor the scan PR 29 replaced, and
        refuses each by name."""
        params = inspect.signature(PagedEngine.__init__).parameters
        assert name not in params
        assert len(params) == 17        # self, the model, 15 options
        with pytest.raises(TypeError, match=name):
            _engine(**{name: None})


class TestScopedDrain:
    def test_sibling_pending_tokens_survive(self):
        """A cancel's scoped drain consumes ONLY the cancelled row's
        pending entries; the sibling's in-flight tokens stay pending
        and land at the next step() — none lost, none duplicated."""
        eng = _engine()
        eng.submit("keep", _cyc(6), max_new_tokens=20)
        eng.submit("kill", _cyc(9, 3), max_new_tokens=20)
        for _ in range(4):
            eng.step()
        assert len(eng._pending) == 1
        keep_slot = next(s for s in eng.slots
                         if s is not None and s.request_id == "keep")
        n_keep = len(keep_slot.tokens)
        assert eng.cancel("kill")
        # the survivor's entries were NOT consumed by the cancel
        assert len(eng._pending) == 1
        assert len(keep_slot.tokens) == n_keep
        assert eng.ring_scoped_drains == 1
        res = eng.run()
        ref = _reference()
        ref.submit("keep", _cyc(6), max_new_tokens=20)
        assert res["keep"] == ref.run()["keep"]

    def test_scoped_drain_on_spec_engine(self):
        """The scoped drain's spec branch (per-row kprop/macc counters
        + EMA mirror) composes with a cancel racing an in-flight
        speculative dispatch; the survivor stays bitwise."""
        kw = dict(prefill_buckets=(8,), spec_tokens=3)
        eng = _engine(**kw)
        eng.submit("keep", _cyc(6), max_new_tokens=20)
        eng.submit("kill", _cyc(9, 3), max_new_tokens=20)
        for _ in range(4):
            eng.step()
        assert len(eng._pending) == 1
        assert eng.cancel("kill")
        assert eng.ring_scoped_drains == 1
        res = eng.run()
        ref = _reference(**kw)
        ref.submit("keep", _cyc(6), max_new_tokens=20)
        assert res["keep"] == ref.run()["keep"]

    def test_expire_scopes_to_deadline_slot(self):
        """A running-request deadline expiry on the SUBMIT path (the
        bounded-queue reap, which used to force a global drain) drains
        only the expiring slot: the sibling's pending tokens stay
        pending and its stream is unaffected (bitwise vs a run without
        the expiring tenant, by batch-composition independence)."""
        eng = _engine(max_queue=8)
        eng.submit("keep", _cyc(6), max_new_tokens=16)
        eng.submit("doomed", _cyc(7, 2), max_new_tokens=50)
        for _ in range(4):
            eng.step()
        assert len(eng._pending) == 1
        doomed = next(s for s in eng.slots
                      if s is not None and s.request_id == "doomed")
        doomed.deadline = 0.0      # already past on the monotonic clock
        sc0 = eng.ring_scoped_drains
        # the bounded-queue submit runs _expire against the in-flight
        # dispatch — scoped to the doomed row, sibling left pending
        eng.submit("late", _cyc(4), max_new_tokens=4)
        assert eng.cancelled.get("doomed") == "timeout"
        assert eng.ring_scoped_drains == sc0 + 1
        assert len(eng._pending) == 1
        res = eng.run()
        assert eng.cancelled.get("doomed") == "timeout"
        ref = _reference()
        ref.submit("keep", _cyc(6), max_new_tokens=16)
        assert res["keep"] == ref.run()["keep"]


class TestUploadAccounting:
    def test_zero_rebuilds_steady_churn(self):
        """THE ISSUE 14 acceptance counter: a churny stream (short
        requests, a finish + admit every few ticks) runs ZERO
        full-state rebuilds after the first dispatch — every transition
        rides the staged queue — and the bytes say what an upload
        weighs: each is one whole queue, ``R * desc_len`` int32 and the
        count."""
        eng = _engine()
        eng.submit("w", _cyc(4), max_new_tokens=2)
        eng.run()                       # compile + first rebuild
        fr0, pf0 = eng.full_rebuilds, eng.patches_fused
        u0, b0 = eng.h2d_uploads, eng.h2d_upload_bytes
        for i in range(12):
            eng.submit(i, _cyc(4 + i % 3), max_new_tokens=4)
        eng.run()
        assert eng.full_rebuilds - fr0 == 0
        assert eng.patches_fused - pf0 >= 12    # an admit a request
        uploads = eng.h2d_uploads - u0
        assert uploads > 0
        assert eng.h2d_upload_bytes - b0 == \
            uploads * (eng.R * eng._desc_len * 4 + 4)

    def test_steady_ticks_no_patches_no_bytes(self):
        """Between transitions nothing is uploaded at all: the
        1-dispatch/0-upload steady pins extend to the byte counter and
        the patch counter."""
        eng = _engine(block_size=64, max_blocks_per_seq=2)
        for i in range(4):
            eng.submit(f"r{i}", _cyc(6), max_new_tokens=100)
        for _ in range(6):
            eng.step()
        d0, u0 = eng.dispatch_count, eng.h2d_uploads
        b0, p0 = eng.h2d_upload_bytes, eng.patches_fused
        for _ in range(20):
            eng.step()
        assert eng.dispatch_count - d0 == 20
        assert eng.h2d_uploads - u0 == 0
        assert eng.h2d_upload_bytes - b0 == 0
        assert eng.patches_fused - p0 == 0

    def test_counters_flow_to_stats_health_and_snapshot(self):
        """full_rebuilds / patches_fused / h2d_upload_bytes reach the
        registry-backed stats (and so health() and a /metrics scrape)
        and the debug_snapshot transitions block, equal to the plain
        attributes the tests and tools read."""
        eng = _engine()
        eng.submit("a", _cyc(5), max_new_tokens=6)
        eng.run()
        st = eng.stats
        assert st["full_rebuilds"] == eng.full_rebuilds == 1
        assert "delta_patches" not in st
        assert "patch_queue_overflows" not in st
        assert st["h2d_upload_bytes"] == eng.h2d_upload_bytes > 0
        # the registry twin of dispatch_count (ISSUE 19): every
        # dispatch site counts both, so /metricsz sees what tests pin
        assert st["dispatches"] == eng.dispatch_count > 0
        assert st["patches_fused"] == eng.patches_fused
        assert st["ring_cursor_rollovers"] == 0
        snap = eng.debug_snapshot()["transitions"]
        assert set(snap) == {
            "full_rebuilds", "patches_fused", "ring_cursor_rollovers",
            "pending_patch_rows", "h2d_uploads", "h2d_upload_bytes",
            "dispatches", "dispatches_per_tick"}
        assert snap["full_rebuilds"] == eng.full_rebuilds
        assert snap["patches_fused"] == eng.patches_fused
        assert snap["ring_cursor_rollovers"] == 0
        assert snap["h2d_upload_bytes"] == eng.h2d_upload_bytes
        assert snap["dispatches"] == eng.dispatch_count
        assert snap["dispatches_per_tick"] > 0
        # the final finish's release patch coalesces until the next
        # dispatch would flush it — visible here as the pending row
        assert snap["pending_patch_rows"] == [0]
        h = eng.health()
        assert h["full_rebuilds"] == eng.full_rebuilds
        assert h["dispatches_per_tick"] == pytest.approx(
            eng.dispatch_count / h["decode_steps"], abs=1e-3)


class TestFusedPatchQueue:
    """ISSUE 19 acceptance pins: the staged patch queue makes churn
    cost exactly one dispatch per tick."""

    def test_steady_churn_one_dispatch_per_tick(self):
        """THE acceptance counter: after warmup, N churny ticks
        (staggered finishes, every transition staged) run in EXACTLY N
        dispatches and 0 full rebuilds."""
        eng = _engine()
        for i in range(4):
            # consecutive budgets: once the shortest finishes, some
            # slot transitions on (nearly) every remaining tick
            eng.submit(f"r{i}", _cyc(6), max_new_tokens=5 + i)
        eng.step()       # admits all 4 (prefills) + first tick/rebuild
        assert eng.full_rebuilds == 1
        d0 = eng.dispatch_count
        t0 = eng.stats["decode_steps"]
        eng.run()
        ticks = eng.stats["decode_steps"] - t0
        assert ticks > 0
        assert eng.dispatch_count - d0 == ticks     # N ticks, N dispatches
        assert eng.full_rebuilds == 1               # no churn rebuilds
        assert eng.patches_fused >= 3               # staged waves carried it

    def test_synchronized_wave_single_dispatch(self):
        """R=8 simultaneous finishes — a wave as wide as the queue: a
        row a slot, so it fits — is absorbed by ONE staged upload
        consumed in the next tick's program: the follow-up request
        costs exactly 1 prefill + its ticks."""
        eng = _engine(max_slots=8, num_blocks=64)
        for i in range(8):
            eng.submit(f"w{i}", _cyc(6), max_new_tokens=4)
        eng.run()        # same budgets: all 8 rows finish the same tick
        d0, u0 = eng.dispatch_count, eng.h2d_uploads
        t0 = eng.stats["decode_steps"]
        pf0 = eng.patches_fused
        eng.submit("s", _cyc(5, 1), max_new_tokens=3)
        eng.run()
        ticks = eng.stats["decode_steps"] - t0
        # 1 prefill + N ticks — the 8-row release wave plus s's admit
        # rode one staged queue
        assert eng.dispatch_count - d0 == ticks + 1
        assert eng.full_rebuilds == 1
        # all 8 releases + the admit coalesced into s's slot: >= 8 rows
        assert eng.patches_fused - pf0 >= 8
        # the wave's upload, then one for s's own finish at most
        assert 1 <= eng.h2d_uploads - u0 <= ticks

    def test_warm_admit_is_dispatch_free(self):
        """ROADMAP 4(b) first rung: submit() on a warm (chunked, fused)
        replica claims the slot eagerly and issues ZERO dispatches —
        the admit descriptor rides the staged queue into the tick the
        engine was going to run anyway."""
        kw = dict(chunk_prefill_tokens=8, prefill_buckets=(8,))
        eng = _engine(**kw)
        eng.submit("w", _cyc(4), max_new_tokens=2)
        eng.run()
        d0, u0 = eng.dispatch_count, eng.h2d_uploads
        eng.submit("a", _cyc(6), max_new_tokens=4)
        assert eng.dispatch_count == d0      # zero-flush warm admit
        assert eng.h2d_uploads == u0         # not even a staging upload
        assert any(s is not None and s.request_id == "a"
                   for s in eng.slots)       # ...but the slot is claimed
        assert not eng.queue
        ref = _reference(**kw)
        ref.submit("w", _cyc(4), max_new_tokens=2)
        ref.run()
        ref.submit("a", _cyc(6), max_new_tokens=4)
        assert eng.run()["a"] == ref.run()["a"]


class TestFailoverParity:
    def test_export_resumable_parity_and_bitwise_resume(self):
        """Mirrors advanced by drains export resume descriptors that
        hold a prefix of the host tick's streams (one dispatch is in
        flight: its tokens are not committed), and a resume from them
        continues the stream bitwise (the ISSUE 12/13 failover gate)."""
        def start(make):
            eng = make(max_slots=2)
            eng.submit("r1", _cyc(6), max_new_tokens=30)
            eng.submit("r2", _cyc(7, 1), max_new_tokens=30,
                       temperature=0.7, seed=2)
            return eng

        eng = start(_engine)
        for _ in range(9):
            eng.step()
        exp = eng.export_resumable()
        full = start(_reference).run()
        for rid in ("r1", "r2"):
            n = len(exp[rid]["committed"])
            assert 0 < n < 30 and exp[rid]["remaining"] == 30 - n
            assert exp[rid]["committed"] == full[rid][:n]
        # greedy resume on a fresh engine == the uninterrupted run
        d = exp["r1"]
        fresh = _engine(max_slots=2)
        fresh.submit("r1", np.asarray(d["prompt"])[None],
                     max_new_tokens=d["remaining"],
                     resume_tokens=d["committed"],
                     resume_lps=d["committed_lps"])
        assert fresh.run()["r1"] == full["r1"]


@pytest.mark.slow
class TestDeltaSweep:
    @pytest.mark.parametrize("chunk", [None, 8])
    @pytest.mark.parametrize("spec", [0, 3])
    def test_parity_sweep(self, chunk, spec):
        """Heavy matrix: chunked-prefill x speculative, longer budgets,
        staggered second wave — the served engine vs the host tick,
        bitwise. (Tier-1 keeps the single-combination pins above.)"""
        if spec and chunk:
            kw = dict(chunk_prefill_tokens=chunk, spec_tokens=spec,
                      prefill_buckets=(8,))
        elif chunk:
            kw = dict(chunk_prefill_tokens=chunk, prefill_buckets=(8,))
        elif spec:
            kw = dict(spec_tokens=spec, prefill_buckets=(8,))
        else:
            kw = {}
        # sampled rows join only the non-spec combos: a sampled row
        # under spec is equal to the reference in distribution, not
        # bitwise (rejection sampling consumes its keys differently)
        subs = [(f"r{j}", _cyc(5 + j % 4, j), dict(
            max_new_tokens=10 + 3 * (j % 3),
            **({} if (j % 2 == 0 or spec) else
               dict(temperature=0.7, seed=j, top_k=12))))
            for j in range(6)]

        def run(make):
            eng = make(**kw)
            res, lps = _drain(eng, subs[:4])
            res2, lps2 = _drain(eng, subs[4:])
            res.update(res2)
            lps.update(lps2)
            return res, lps

        (rr, lr), (rf, lf) = run(_reference), run(_engine)
        assert rr == rf
        if spec:
            _lps_close(lr, lf)
        else:
            assert lr == lf
