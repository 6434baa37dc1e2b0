"""ISSUE 29: a full engine keeps two ticks on the chip.

When every slot holds a decoding request and the step has nothing to
decide, ``PagedEngine.step()`` dispatches tick N+1 BEFORE it drains
tick N (``_may_run_ahead``). The contracts, each against the host tick
(``fused_tick=False``, the engine's one reference) or an exact count:

- STREAMS: tokens, logprobs, finish reasons and stop trims are bitwise
  the host tick's, run-ahead engaged or not (a real model, so K/V
  written under the lag is read back by attention).
- THE TRAPS, one case each: a stop matched while the next tick runs; a
  block boundary crossed under the lag (a table-only patch, ``lens`` /
  ``last`` / ``rem`` / ``active`` untouched); pool pressure (the step
  falls back to drain-first and its preemption); eos and budget
  finishes by the device flag and the refill of the slot after them;
  cancel, deadline expiry, ``close``, ``hard_reset`` and
  ``export_resumable`` with TWO dispatches outstanding.
- THE COUNTER: ``runahead_ticks`` equals the decode dispatches made
  with one undrained, and is 0 on an engine with a free slot;
  ``health()`` / ``debug_snapshot()`` say how many are outstanding.
- NO NEW PROGRAM: the run-ahead uses the one tick program a drain-first
  step compiled.

The latent pool's case and the experts' counters are in
test_latent_paged.py; the restated ring pins in test_ring_spec.py.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation import paged
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.models import LlamaForCausalLM
from paddle_tpu.models.llama import llama_tiny


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    return LlamaForCausalLM(llama_tiny())


def _engine(model, **kw):
    base = dict(max_slots=3, num_blocks=40, block_size=8,
                max_blocks_per_seq=8, prefill_buckets=(16, 32))
    base.update(kw)
    return PagedEngine(model, **base)


def _ids(seed, n):
    return np.random.RandomState(seed).randint(1, 200, (1, n))


def _subs(n=3, new=22, **kw):
    """``n`` greedy requests with prompts of 5, 8, 11, ... tokens."""
    return [(f"r{i}", _ids(20 + i, 5 + 3 * i),
             dict(max_new_tokens=new, **kw)) for i in range(n)]


def _serve(eng, subs):
    for rid, ids, kw in subs:
        eng.submit(rid, ids, **kw)
    return eng.run(), dict(eng.logprobs)


def _host(model, subs, **kw):
    return _serve(_engine(model, fused_tick=False, **kw), subs)


def _watch(eng):
    """Record, for every decode dispatch, how many were undrained when
    it was made (1: the step ran ahead)."""
    seen = []
    inner = eng._decode_fused

    def spy(active):
        seen.append(len(eng._pending))
        return inner(active)
    eng._decode_fused = spy
    return seen


def _two_outstanding(eng, subs, steps=6):
    """Fill the house, run ahead, then lose one step's drain: TWO
    dispatches are outstanding when this returns, which a step never
    leaves behind by itself."""
    for rid, ids, kw in subs:
        eng.submit(rid, ids, **kw)
    for _ in range(steps):
        eng.step()
    assert len(eng._pending) == 1 and eng.stats["runahead_ticks"] > 0
    drain, eng._drain_oldest = eng._drain_oldest, lambda: None
    eng.step()
    eng._drain_oldest = drain
    assert len(eng._pending) == 2
    assert eng.health()["outstanding_dispatches"] == 2
    assert eng.debug_snapshot()["ring"]["outstanding"] == 2


# ----------------------------------------------------------- the counter
def test_runahead_ticks_counts_dispatches_made_with_one_undrained(model):
    """Greedy and seeded sampled rows on a full house: the streams are
    the host tick's, and the counter is exactly the dispatches that
    found one undrained (never two: a step drains what it ran ahead
    of)."""
    subs = _subs(new=22)
    subs[1][2].update(temperature=0.8, top_k=30, seed=7)
    subs[2][2].update(temperature=0.7, top_p=0.9, seed=3)
    eng = _engine(model)
    seen = _watch(eng)
    assert _serve(eng, subs) == _host(model, subs)
    assert set(seen) == {0, 1}
    assert eng.stats["runahead_ticks"] == seen.count(1) >= 15
    assert eng.stats["decode_steps"] == len(seen) == 21
    # every budget end is foreseen: no tick ran for a finished row and
    # nothing is left on the device
    assert not eng._pending
    assert eng.health()["outstanding_dispatches"] == 0


def test_a_free_slot_never_runs_ahead(model):
    """Two requests in three slots: an arrival could be admitted at any
    step, so every step drains first."""
    subs = _subs(n=2)
    eng = _engine(model)
    seen = _watch(eng)
    assert _serve(eng, subs) == _host(model, subs)
    assert set(seen) == {0}
    assert eng.stats["runahead_ticks"] == 0


def test_outstanding_dispatches_are_reported(model):
    eng = _engine(model)
    for rid, ids, kw in _subs():
        eng.submit(rid, ids, **kw)
    assert eng.health()["outstanding_dispatches"] == 0
    for _ in range(5):
        eng.step()
    assert eng.health()["outstanding_dispatches"] == 1
    assert eng.debug_snapshot()["ring"]["outstanding"] == 1
    assert eng.stats["runahead_ticks"] == eng.health()["runahead_ticks"]


def test_run_ahead_compiles_no_program_of_its_own(model):
    eng = _engine(model)
    for rid, ids, kw in _subs():
        eng.submit(rid, ids, **kw)
    while not eng._pending:             # the first decode: drain-first
        eng.step()
    assert eng.stats["runahead_ticks"] == 0
    n = eng._tick_greedy_jit._cache_size()
    eng.run()
    assert eng.stats["runahead_ticks"] > 0
    assert eng._tick_greedy_jit._cache_size() == n == 1
    assert eng._tick_jit._cache_size() == 0


# ------------------------------------------------------------- the traps
def test_a_stop_matched_while_the_next_tick_runs(model):
    """The drain of tick N matches r0's stop sequence when tick N+1 is
    already running with r0 active: N+1's token for r0 is never read
    (the drained cursor steps over it), its K/V write dies with the
    blocks, and the slot's next tenant, admitted from the queue, gets
    the stream the host tick gives it."""
    free, _ = _host(model, _subs())
    stop = [free["r0"][9:11]]
    subs = _subs()
    subs[0][2]["stop_sequences"] = stop
    subs.append(("late", _ids(40, 6), dict(max_new_tokens=9)))
    eng = _engine(model)
    seen = _watch(eng)
    for rid, ids, kw in subs:
        eng.submit(rid, ids, **kw)
    while "r0" not in eng.results:
        eng.step()
    # the matching drain was a run-ahead step's: the tick dispatched
    # before it is outstanding, and it served r0
    assert seen[-1] == 1 and len(eng._pending) == 1
    assert 0 in eng._pending[0]["rows"] and eng.slots[0] is None
    got = eng.run(), dict(eng.logprobs)
    assert got == _host(model, subs)
    assert got[0]["r0"] == free["r0"][:9]
    assert len(eng.free_blocks) == eng.P - 1


def test_a_block_boundary_under_lag_patches_the_table_only(model):
    """Rows of 5, 8, 11 prompt tokens and 40 new ones cross five block
    boundaries each (blocks of 8). Under the lag the descriptor of a
    grown row carries its table row and the flag, and no mirror that is
    a tick behind; the block covers the position the DISPATCHED tick
    writes, one past the undrained tick's. The streams (attention reads
    what was written there) are the host tick's."""
    subs = _subs(new=40)
    eng = _engine(model)
    packed = []
    inner = eng._pack_descriptor

    def spy(i, table_only=False):
        d = inner(i, table_only=table_only)
        packed.append((len(eng._pending), table_only, d.copy(),
                       int(eng.seq_lens[i]), len(eng.slots[i].blocks)))
        return d
    eng._pack_descriptor = spy
    assert _serve(eng, subs) == _host(model, subs)
    lagged = [p for p in packed if p[0] == 1]
    assert len(lagged) >= 12            # 3 rows x 4-5 boundaries
    for _, table_only, d, seq_len, blocks in lagged:
        assert table_only and d[6] == paged._DESC_TABLE_ONLY
        assert not d[1:6].any() and not d[7:15].any()
        # the tick being dispatched writes at seq_len + 1
        assert blocks == (seq_len + 1) // 8 + 1
        assert np.count_nonzero(d[15:15 + eng.M]) == blocks


def test_a_table_only_descriptor_touches_the_table_alone(model):
    """The program's half of the same trap: a ``_DESC_TABLE_ONLY`` entry
    with rubbish in every other word changes one table row."""
    eng = _engine(model)
    for rid, ids, kw in _subs():
        eng.submit(rid, ids, **kw)
    for _ in range(4):
        eng.step()
    eng._drain_pending()
    st = dict(eng._dev)
    d = np.full((eng._desc_len,), 77, np.int32)
    d[0], d[6] = 1, paged._DESC_TABLE_ONLY | paged._DESC_KEY_OVERRIDE
    d[15:15 + eng.M] = np.arange(eng.M) + 3
    pq = np.zeros((eng.R, eng._desc_len), np.int32)
    pq[0] = d
    st.update(pq=jnp.asarray(pq), pqn=jnp.int32(1))
    new = eng._apply_patch_queue(st)
    assert np.array_equal(new["tables"][1], d[15:15 + eng.M])
    for k, v in st.items():
        if k in ("pq", "pqn"):
            continue
        got = np.asarray(new[k])
        want = np.asarray(v).copy()
        if k == "tables":
            want[1] = d[15:15 + eng.M]
        assert np.array_equal(got, want), k


def test_pool_pressure_falls_back_to_drain_first(model):
    """Two rows, a pool too small for both to finish: when the block
    the next tick needs cannot be served, the step runs nothing ahead;
    it drains, preempts the youngest and recomputes it, as the host
    tick does. No tick is dispatched ahead on a step that preempts."""
    kw = dict(max_slots=2, num_blocks=6, block_size=8,
              max_blocks_per_seq=4)
    subs = [("p", _ids(31, 8), dict(max_new_tokens=14)),
            ("q", _ids(32, 11), dict(max_new_tokens=14))]
    ref = _engine(model, fused_tick=False, **kw)
    want = _serve(ref, subs)
    eng = _engine(model, **kw)
    seen = _watch(eng)
    for rid, ids, k in subs:
        eng.submit(rid, ids, **k)
    while eng.queue or any(s is not None for s in eng.slots):
        n, pre = len(seen), eng.stats["preemptions"]
        eng.step()
        if eng.stats["preemptions"] > pre:
            assert seen[n:] in ([], [0])
    assert (dict(eng.results), dict(eng.logprobs)) == want
    assert eng.stats["preemptions"] == ref.stats["preemptions"] >= 1
    assert eng.stats["runahead_ticks"] > 0      # and it re-engaged
    assert len(eng.free_blocks) == eng.P - 1


def test_eos_and_budget_finish_by_the_device_flag_and_the_refill(model):
    """r0 ends on an eos the host cannot foresee: the tick dispatched
    ahead runs with r0 finished on the device and advances nothing for
    it. A request submitted right then is admitted into r0's slot at
    ``submit()`` (chunked admission is eager) while that tick is still
    outstanding: its drain must not credit or finish the new tenant.
    r1 ends on its budget, which is foreseen. Every stream is the host
    tick's."""
    kw = dict(chunk_prefill_tokens=8)
    free, _ = _host(model, _subs(), **kw)
    at = next(k for k in range(6, 20)
              if free["r0"][k] not in free["r0"][:k])
    eos = free["r0"][at]
    subs = _subs()
    subs[0][2]["eos_token_id"] = eos
    subs[1][2]["max_new_tokens"] = 12
    refill = ("x", _ids(41, 9), dict(max_new_tokens=10))
    eng = _engine(model, **kw)
    seen = _watch(eng)
    for rid, ids, k in subs:
        eng.submit(rid, ids, **k)
    while "r0" not in eng.results:
        eng.step()
    assert seen[-1] == 1 and 0 in eng._pending[0]["rows"]
    eng.submit(refill[0], refill[1], **refill[2])
    assert eng.slots[0] is not None and eng.slots[0].request_id == "x"
    got = eng.run(), dict(eng.logprobs)
    assert got == _host(model, subs + [refill], **kw)
    assert got[0]["r0"] == free["r0"][:at + 1]
    assert len(got[0]["r1"]) == 12
    assert len(got[0]["x"]) == 10


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_cancel_and_expiry_consume_both_outstanding_dispatches(model, how):
    """An out-of-band release with two dispatches outstanding drains
    the row from BOTH, oldest first (two scoped drains), before its
    blocks go; the siblings' entries stay pending in both and their
    streams are the host tick's."""
    subs = _subs(new=24)
    eng = _engine(model, max_queue=8)
    _two_outstanding(eng, subs)
    kept = [len(s.tokens) for s in eng.slots[:2]]
    doomed = eng.slots[2]
    n0 = len(doomed.tokens)
    if how == "cancel":
        assert eng.cancel("r2")
    else:
        doomed.deadline = 0.0
        eng._expire()
    assert eng.ring_scoped_drains == 2
    assert len(doomed.tokens) == n0 + 2         # both ticks' tokens
    assert eng.cancelled["r2"] == ("cancelled" if how == "cancel"
                                   else "timeout")
    assert [len(s.tokens) for s in eng.slots[:2]] == kept
    assert [sorted(p["rows"]) for p in eng._pending] == [[0, 1]] * 2
    res = eng.run()
    want, _ = _host(model, subs[:2])
    assert {k: res[k] for k in want} == want and "r2" not in res
    assert len(eng.free_blocks) == eng.P - 1


@pytest.mark.parametrize("how", ["close", "hard_reset", "export"])
def test_engine_wide_paths_with_two_outstanding_dispatches(model, how):
    """``close(drain=False)`` drains every outstanding dispatch before
    it aborts; ``hard_reset`` forgets them all; ``export_resumable``
    reads host mirrors only, so the two undrained ticks' tokens are
    simply not in it, and the resumed streams are still the
    uninterrupted ones."""
    subs = _subs(new=24)
    eng = _engine(model)
    _two_outstanding(eng, subs)
    had = [len(s.tokens) for s in eng.slots]
    if how == "close":
        eng.close(drain=False)
        assert not eng._pending
        assert set(eng.cancelled) == {"r0", "r1", "r2"}
        assert len(eng.free_blocks) == eng.P - 1
    elif how == "hard_reset":
        eng.hard_reset()
        assert not eng._pending
        assert eng.health()["outstanding_dispatches"] == 0
        assert _serve(eng, subs) == _host(model, subs)
    else:
        descs = eng.export_resumable()
        assert [len(descs[f"r{i}"]["committed"]) for i in range(3)] == had
        other = _engine(model)
        for rid, d in descs.items():
            other.submit(rid, np.asarray([d["prompt"]]),
                         max_new_tokens=d["remaining"],
                         resume_tokens=d["committed"],
                         resume_lps=d["committed_lps"])
        assert other.run() == _host(model, subs)[0]
