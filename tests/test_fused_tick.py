"""ISSUE 6: ragged paged attention + device-resident fused decode tick.

Three contracts, each pinned against an independent reference:

- STREAM PARITY: the fused tick (run ahead of its drain on a full
  house, ISSUE 29, or drained first) must emit BIT-IDENTICAL
  token/logprob streams to the per-tick host path
  (``fused_tick=False``), which test_paged.py pins against generate().
- DISPATCH: a steady-state fused tick is exactly ONE compiled dispatch
  with ZERO host->device mirror uploads and one token a row, whether
  the step drains first or dispatches first.
- KERNEL PARITY: the ragged kernel (each row walks its own pages, a
  run of them per compute block) matches the dense whole-table gather
  across uneven ``seq_lens`` (single-token rows, block-boundary
  lengths, full tables, windows), and the re-blocked decode kernel's
  BlockSpecs are strictly (8, 128)-tiled at the BENCH_SELF_r05 failing
  shape so the hardware lowering failure cannot regress silently on a
  CPU-only image.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.generation.stub import TickStubModel as StubModel
from paddle_tpu.models import LlamaForCausalLM
from paddle_tpu.models.llama import llama_tiny
from paddle_tpu.ops.paged_cache import PagedKV, paged_decode_attention

from test_decode_kernels import _flat


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    return LlamaForCausalLM(llama_tiny())


def _engine(model, **kw):
    base = dict(max_slots=4, num_blocks=32, block_size=8,
                max_blocks_per_seq=8, prefill_buckets=(16, 32))
    base.update(kw)
    return PagedEngine(model, **base)


# --------------------------------------------------------------- stub model
def _stub_engine(R=8, **kw):
    """An engine over ``generation/stub.py``'s model: negligible model
    compute, so timings and dispatch counts measure the TICK MACHINERY."""
    base = dict(max_slots=R, num_blocks=256, block_size=64,
                max_blocks_per_seq=8, prefill_buckets=(16,))
    base.update(kw)
    return PagedEngine(StubModel(), **base)


# ------------------------------------------------------------ stream parity
def _drain(eng, submits):
    for rid, ids, kw in submits:
        eng.submit(rid, ids, **kw)
    res = eng.run()
    return res, dict(eng.logprobs)


class TestFusedTickParity:
    def test_greedy_stops_and_eos_bit_identical(self, model):
        """Mixed-length greedy batch with stop sequences and an eos
        request: fused and host paths must agree on every token AND
        every logprob float (stop rows force the scan-ineligible,
        single-fused-tick path)."""
        rs = np.random.RandomState(11)
        subs = [
            ("a", rs.randint(1, 200, (1, 5)), dict(max_new_tokens=20)),
            ("b", rs.randint(1, 200, (1, 17)), dict(max_new_tokens=12)),
            ("c", rs.randint(1, 200, (1, 9)),
             dict(max_new_tokens=24, stop_sequences=[[7], [3, 5]])),
            ("d", rs.randint(1, 200, (1, 3)),
             dict(max_new_tokens=16, eos_token_id=2)),
        ]
        r_host, lp_host = _drain(_engine(model, fused_tick=False), subs)
        r_fused, lp_fused = _drain(_engine(model), subs)
        assert r_host == r_fused
        assert lp_host == lp_fused

    def test_sampled_streams_bit_identical(self, model):
        """Seeded sampled rows sharing the batch with greedy rows: the
        fused tick splits keys exactly like the host path, so sampled
        streams match bit-for-bit too."""
        rs = np.random.RandomState(12)
        subs = [
            ("g", rs.randint(1, 200, (1, 6)), dict(max_new_tokens=14)),
            ("s1", rs.randint(1, 200, (1, 8)),
             dict(max_new_tokens=14, temperature=0.9, top_k=20, seed=5)),
            ("s2", rs.randint(1, 200, (1, 12)),
             dict(max_new_tokens=10, temperature=0.7, top_p=0.9,
                  seed=9)),
        ]
        r_host, lp_host = _drain(_engine(model, fused_tick=False), subs)
        r_fused, lp_fused = _drain(_engine(model), subs)
        assert r_host == r_fused
        assert lp_host == lp_fused

    def test_midstream_submit_bit_identical(self, model):
        """A submit() landing mid-decode (the continuous-batching case)
        stages a slot transition; the joined request's stream and the
        already-running streams stay exact. The fused tick drains its
        token ring one step behind the device, so the submit's
        admission tick shifts against the host path's: the pin is
        per-request content and order (batch composition independence
        keeps each stream bitwise)."""
        rs = np.random.RandomState(13)
        first = rs.randint(1, 200, (1, 6))
        late = rs.randint(1, 200, (1, 10))

        def run(**kw):
            eng = _engine(model, **kw)
            eng.submit("r0", first, max_new_tokens=18)
            out = []
            it = eng.stream()
            for n, pair in enumerate(it):
                out.append(pair)
                if n == 4:
                    eng.submit("r1", late, max_new_tokens=12,
                               temperature=0.8, seed=3)
            return out, dict(eng.results), dict(eng.logprobs)

        sh, rh, lh = run(fused_tick=False)
        sr, rr, lr = run()
        assert rh == rr and lh == lr
        for rid in rh:           # per-request emission order exact
            assert [t for r, t in sr if r == rid] == \
                [t for r, t in sh if r == rid]

    def test_run_ahead_full_house_bit_identical(self, model):
        """ISSUE 29: three requests fill a three-slot engine, so once
        all decode every step dispatches the next tick before it
        drains the last (greedy and seeded sampled rows, budgets that
        end on different ticks, block boundaries crossed under the
        lag). Same streams as the host tick, still a dispatch a tick,
        and the run-ahead engaged."""
        rs = np.random.RandomState(14)
        subs = [
            ("a", rs.randint(1, 200, (1, 4)), dict(max_new_tokens=25)),
            ("b", rs.randint(1, 200, (1, 9)),
             dict(max_new_tokens=21, temperature=0.8, seed=2)),
            ("c", rs.randint(1, 200, (1, 14)), dict(max_new_tokens=17)),
        ]
        eng_h = _engine(model, max_slots=3, fused_tick=False)
        r_host, lp_host = _drain(eng_h, subs)
        eng_s = _engine(model, max_slots=3)
        r_ahead, lp_ahead = _drain(eng_s, subs)
        assert r_host == r_ahead
        assert lp_host == lp_ahead
        # the house is full until "c" ends its budget: those ticks ran
        # ahead, each foreseen budget end and the ticks after it did not
        assert 10 <= eng_s.stats["runahead_ticks"] <= 15
        assert eng_s.stats["decode_steps"] == eng_h.stats["decode_steps"]

    def test_run_ahead_with_a_stop_row_stays_exact(self, model):
        """A stop sequence is matched at the drain, when the next tick
        already runs with the row active: that tick's token and K/V
        write die with the slot release. The trimmed result is the
        host tick's, and so is the stream of the row beside it."""
        rs = np.random.RandomState(15)
        ids = rs.randint(1, 200, (1, 7))
        other = ("y", rs.randint(1, 200, (1, 5)), dict(max_new_tokens=20))
        free, _ = _drain(_engine(model, max_slots=2, fused_tick=False),
                         [("x", ids, dict(max_new_tokens=20)), other])
        stop = [free["x"][8:10]]        # matched ten tokens in
        subs = [("x", ids, dict(max_new_tokens=20, stop_sequences=stop)),
                other]
        r_host, lp_host = _drain(
            _engine(model, max_slots=2, fused_tick=False), subs)
        eng = _engine(model, max_slots=2)
        r_ahead, lp_ahead = _drain(eng, subs)
        assert r_host == r_ahead and lp_host == lp_ahead
        assert len(r_ahead["x"]) <= 8 and len(r_ahead["y"]) == 20
        assert eng.stats["runahead_ticks"] >= 6
        assert len(eng.free_blocks) == eng.P - 1


# --------------------------------------------------------- dispatch contract
class TestDispatchContract:
    def test_one_dispatch_zero_uploads_per_steady_tick(self):
        """THE ISSUE 6 acceptance counter: N steady-state fused ticks =
        exactly N compiled dispatches and ZERO host->device mirror
        uploads (the host path re-uploads every mirror every tick).
        ISSUE 14 extends the pin to BYTES: upload events of wildly
        different sizes (a one-row patch vs a full rebuild) were
        indistinguishable in the event counter alone."""
        eng = _stub_engine()
        for i in range(8):
            eng.submit(f"r{i}", np.arange(1, 9)[None],
                       max_new_tokens=120)
        for _ in range(6):       # admit + prefill + first refresh
            eng.step()
        d0, u0 = eng.dispatch_count, eng.h2d_uploads
        b0 = eng.h2d_upload_bytes
        n = 25
        for _ in range(n):
            eng.step()
        assert eng.dispatch_count - d0 == n
        assert eng.h2d_uploads - u0 == 0
        assert eng.h2d_upload_bytes - b0 == 0

        host = _stub_engine(fused_tick=False)
        for i in range(8):
            host.submit(f"r{i}", np.arange(1, 9)[None],
                        max_new_tokens=120)
        for _ in range(6):
            host.step()
        u0, b0 = host.h2d_uploads, host.h2d_upload_bytes
        host.step()
        assert host.h2d_uploads - u0 >= 5   # tables/lens/last/reps/act
        # and the bytes satellite: every per-tick re-upload is weighed
        assert host.h2d_upload_bytes - b0 >= \
            host.block_tables.nbytes + host.seq_lens.nbytes

    def test_run_ahead_keeps_a_dispatch_and_a_token_a_tick(self):
        """A full house runs ahead: each step is still one dispatch and
        hands the host one token a row (the tick before the one it
        dispatched), one dispatch stays outstanding between steps, and
        ``runahead_ticks`` counts those steps."""
        eng = _stub_engine()
        for i in range(8):
            eng.submit(f"r{i}", np.arange(1, 9)[None],
                       max_new_tokens=200)
        for _ in range(4):
            eng.step()
        d0, a0 = eng.dispatch_count, eng.stats["runahead_ticks"]
        tok0 = sum(len(s.tokens) for s in eng.slots if s is not None)
        for _ in range(5):
            eng.step()
            assert len(eng._pending) == 1
        toks = sum(len(s.tokens) for s in eng.slots
                   if s is not None) - tok0
        assert eng.dispatch_count - d0 == 5
        assert eng.stats["runahead_ticks"] - a0 == 5
        assert toks == 5 * 8            # 5 dispatches x 8 rows


# ------------------------------------------------------- ragged kernel parity
def _dense_paged_reference(q, kp, vp, tables, lens, window=None):
    from paddle_tpu.ops.attention import dense_attention
    R = q.shape[0]
    kvh, d = kp.shape[2], kp.shape[3]
    ks = kp[tables].reshape(R, -1, kvh, d)
    vs = vp[tables].reshape(R, -1, kvh, d)
    kpos = jnp.arange(ks.shape[1])[None, :]
    keep = kpos <= lens[:, None]
    if window is not None:
        keep &= kpos > lens[:, None] - window
    return dense_attention(q[:, None], ks, vs,
                           attn_mask=keep[:, None, None, :])[:, 0]


def _kernel_calls(fn, *args):
    """The ``pallas_call`` equations of ``fn(*args)``'s jaxpr, nested
    ones included."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    yield from walk(getattr(inner, "jaxpr", inner))
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


class TestRaggedKernel:
    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")

    @pytest.mark.parametrize("window", [None, 12])
    def test_parity_uneven_and_boundary_lens(self, window):
        """seq_lens 0 (single attendable token), B-1, B (block
        boundary), and a mid-block length — no per-request padding,
        exact vs the dense gather."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention_pallas
        rs = np.random.RandomState(7)
        R, P, B, M, kvh, h, d = 4, 24, 8, 4, 2, 4, 64
        q = jnp.asarray(rs.randn(R, h, d), jnp.float32)
        kp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        vp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        tables = jnp.asarray(
            rs.permutation(np.arange(P))[:R * M].reshape(R, M),
            jnp.int32)
        lens = jnp.asarray([0, B - 1, B, 2 * B + 3], jnp.int32)
        got = ragged_paged_attention_pallas(q, _flat(kp), _flat(vp),
                                            tables, lens, d ** -0.5, kvh,
                                            window=window)
        ref = _dense_paged_reference(q, kp, vp, tables, lens,
                                     window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_engine_routes_through_ragged_kernel(self):
        """paged_decode_attention takes the ragged kernel where it
        serves; the dense gather is a function of its own, and the two
        agree."""
        from paddle_tpu.ops.paged_cache import (paged_decode_attention_dense,
                                                paged_decode_route)
        rs = np.random.RandomState(8)
        R, P, B, M, kvh, h, d = 3, 16, 16, 4, 2, 4, 64
        pk = PagedKV(jnp.asarray(rs.randn(P, B, kvh * d), jnp.float32),
                     jnp.asarray(rs.randn(P, B, kvh * d), jnp.float32),
                     jnp.asarray(rs.randint(0, P, (R, M)), jnp.int32),
                     jnp.asarray([3, 30, 60], jnp.int32), kvh)
        q = jnp.asarray(rs.randn(R, 1, h, d), jnp.float32)
        assert paged_decode_route(q, pk.kp, kvh) == "ragged"
        kernels = _kernel_calls(lambda q: paged_decode_attention(q, pk), q)
        assert [k.params["name"] for k in kernels] \
            == ["ragged_paged_attention"]
        assert not _kernel_calls(
            lambda q: paged_decode_attention_dense(q, pk), q)
        np.testing.assert_allclose(
            np.asarray(paged_decode_attention(q, pk)),
            np.asarray(paged_decode_attention_dense(q, pk)),
            atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("value", ["dense", "grid"])
    def test_no_variable_chooses_the_route(self, model, monkeypatch,
                                           value):
        """The variable that used to pick the kernel when a program was
        traced is not read: shapes and the platform decide."""
        monkeypatch.setenv("PADDLE_TPU_PAGED_ATTN", value)
        assert _engine(model).decode_route() == "ragged"

    @pytest.mark.parametrize("B,M,kvh,h,d,lens,window", [
        # 16 pages a compute block (256 tokens / B 16), 40 a table: rows
        # of 1, 16, 17 and 33 live pages -- a whole block, one page over,
        # two blocks and one page -- and the full table, lens = M*B - 1
        (16, 40, 2, 4, 64, [0, 255, 256, 527, 639], None),
        # an empty slot beside a full one, and only those
        (16, 40, 2, 4, 64, [0, 639, 0, 639], None),
        # a window that starts the walk mid-table: pages 19-39, 0-1, 9-16
        (16, 40, 2, 4, 64, [639, 20, 260], 330),
        # the 1.5B's and the 7B's heads at head 128: kvh 2 x group 6,
        # kvh 4 x group 7 (padded to 8 sublanes)
        (16, 20, 2, 12, 128, [0, 100, 319, 17], None),
        (16, 20, 4, 28, 128, [0, 100, 319, 17], None),
    ])
    def test_parity_blocks_of_pages(self, B, M, kvh, h, d, lens, window):
        """The walk in compute blocks of several pages: live pages that
        are not a multiple of `pages_per_step`, a row at the full table,
        empty slots, the cells' head geometries."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import (
            _pages_per_step, ragged_paged_attention_pallas)
        rs = np.random.RandomState(11)
        R = len(lens)
        P = R * M + 1
        assert _pages_per_step(B, kvh * d, 4, M) == min(256 // B, M) < M
        q = jnp.asarray(rs.randn(R, h, d), jnp.float32)
        kp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        vp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        tables = jnp.asarray(
            1 + rs.permutation(P - 1)[:R * M].reshape(R, M), jnp.int32)
        lens = jnp.asarray(lens, jnp.int32)
        got = ragged_paged_attention_pallas(q, _flat(kp), _flat(vp),
                                            tables, lens, d ** -0.5, kvh,
                                            window=window)
        ref = _dense_paged_reference(q, kp, vp, tables, lens,
                                     window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_dead_pages_never_reach_the_output(self):
        """Pages past a row's length are neither fetched nor trusted:
        NaN in every pool page a row does not hold live tokens in (the
        rest of its table included) leaves the output as it was."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention_pallas
        rs = np.random.RandomState(12)
        R, B, M, kvh, h, d = 3, 16, 40, 2, 4, 64
        P = R * M + 1
        q = jnp.asarray(rs.randn(R, h, d), jnp.float32)
        kp = rs.randn(P, B, kvh, d).astype(np.float32)
        vp = rs.randn(P, B, kvh, d).astype(np.float32)
        tables = 1 + rs.permutation(P - 1)[:R * M].reshape(R, M)
        lens = np.asarray([0, 300, 527])
        ref = _dense_paged_reference(q, jnp.asarray(kp), jnp.asarray(vp),
                                     jnp.asarray(tables, jnp.int32),
                                     jnp.asarray(lens, jnp.int32))
        live = np.zeros(P, bool)
        for r in range(R):
            live[tables[r, :lens[r] // B + 1]] = True
        kp[~live] = np.nan
        vp[~live] = np.nan
        got = ragged_paged_attention_pallas(
            q, _flat(jnp.asarray(kp)), _flat(jnp.asarray(vp)),
            jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32),
            d ** -0.5, kvh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_wrapper_builds_no_schedule(self):
        """Nothing outside the kernel is sized by R*M: the wrapper's
        jaxpr holds no rank-1 array of that length (the old
        `build_schedule` made three per program, by cumsum and
        searchsorted), only the [R, M] table itself."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention_pallas
        R, P, B, M, kvh, h, d = 4, 24, 8, 5, 2, 4, 64
        jaxpr = jax.make_jaxpr(
            lambda q, kp, vp, tbl, lens: ragged_paged_attention_pallas(
                q, kp, vp, tbl, lens, d ** -0.5, kvh))(
            jnp.zeros((R, h, d)), jnp.zeros((P, B, kvh * d)),
            jnp.zeros((P, B, kvh * d)), jnp.zeros((R, M), jnp.int32),
            jnp.zeros((R,), jnp.int32)).jaxpr
        shapes = [v.aval.shape for eqn in jaxpr.eqns
                  for v in list(eqn.invars) + list(eqn.outvars)
                  if hasattr(v.aval, "shape")]
        assert (R, M) in shapes
        assert not [sh for sh in shapes if sh == (R * M,)]
        call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        assert call.params["grid_mapping"].grid == (R,)

    @pytest.mark.parametrize("B,width,itemsize,M,pps", [
        (16, 512, 2, 128, 16),      # qwen2-7b-d16: 256 tokens, 1 MB
        (16, 256, 2, 128, 16),      # qwen2-1.5b
        (8, 128, 4, 4, 4),          # a table shorter than a block
        (16, 2048, 4, 128, 8),      # 8 MB at 16 pages: halved
        (512, 2048, 4, 128, 1),     # one page is the least
    ])
    def test_pages_per_step_follows_shapes(self, B, width, itemsize, M,
                                           pps):
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            _pages_per_step
        assert _pages_per_step(B, width, itemsize, M) == pps

    def test_narrow_pages_take_another_route(self, monkeypatch):
        """On the chip a page is fetched as one (B, kvh*d) slab, which
        Mosaic slices only in whole 128-lane tiles: one kv head of 64
        columns takes the dense gather."""
        from paddle_tpu.ops.paged_cache import paged_decode_route
        from paddle_tpu.ops import pallas
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
        monkeypatch.setattr(pallas, "tpu_backend", lambda: True)
        pool = lambda kvh, d: jnp.zeros((8, 16, kvh * d), jnp.bfloat16)
        q = lambda T, h, d: jnp.zeros((2, T, h, d), jnp.bfloat16)
        assert paged_decode_route(q(1, 28, 128), pool(4, 128), 4) \
            == "ragged"
        assert paged_decode_route(q(3, 28, 128), pool(4, 128), 4) \
            == "ragged"
        assert paged_decode_route(q(1, 8, 64), pool(1, 64), 1) == "dense"
        assert paged_decode_route(q(3, 8, 64), pool(1, 64), 1) == "dense"

    def test_parity_shared_blocks_exceeding_pool_bound(self):
        """Prefix-cache shape: rows share most physical blocks, so the
        total of logical live blocks (16) exceeds any bound derived from
        the pool (P-1+R = 11). Each row walks its own table, so every
        row must match the dense gather (an earlier kernel's schedule
        was cut at such a bound and left rows unfinished)."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention_pallas
        rs = np.random.RandomState(17)
        R, P, B, M, kvh, h, d = 4, 8, 8, 4, 2, 4, 64
        q = jnp.asarray(rs.randn(R, h, d), jnp.float32)
        kp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        vp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        # all rows borrow blocks 1-3 (shared prefix) + own block
        tables = jnp.asarray([[1, 2, 3, 4], [1, 2, 3, 5],
                              [1, 2, 3, 6], [1, 2, 3, 7]], jnp.int32)
        lens = jnp.asarray([4 * B - 2, 3 * B, 4 * B - 1, 3 * B + 5],
                           jnp.int32)
        got = ragged_paged_attention_pallas(q, _flat(kp), _flat(vp),
                                            tables, lens, d ** -0.5, kvh)
        ref = _dense_paged_reference(q, kp, vp, tables, lens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.slow
    @pytest.mark.parametrize("h,kvh,d,window",
                             [(8, 4, 64, None), (16, 2, 128, None),
                              (4, 4, 64, 20), (8, 2, 64, 3),
                              (16, 8, 64, None), (8, 4, 128, 40)])
    def test_parity_sweep(self, h, kvh, d, window):
        """Exhaustive GQA/window matrix over a larger ragged pool
        (sweep-style -> slow tier; the boundary-lens case above is the
        tier-1 representative)."""
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention_pallas
        rs = np.random.RandomState(9)
        R, P, B, M = 6, 48, 16, 8
        q = jnp.asarray(rs.randn(R, h, d), jnp.float32)
        kp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        vp = jnp.asarray(rs.randn(P, B, kvh, d), jnp.float32)
        tables = jnp.asarray(
            rs.permutation(np.arange(P))[:R * M].reshape(R, M),
            jnp.int32)
        lens = jnp.asarray([0, 15, 16, 63, 100, 127], jnp.int32)
        got = ragged_paged_attention_pallas(q, _flat(kp), _flat(vp),
                                            tables, lens, d ** -0.5, kvh,
                                            window=window)
        ref = _dense_paged_reference(q, kp, vp, tables, lens,
                                     window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


# ----------------------------------------------- decode kernel re-block (r05)
class TestDecodeKernelReblock:
    def test_r05_failing_shape_blockspecs_strictly_tiled(self):
        """BENCH_SELF_r05 `decode_kernel` refused to lower: args[2]'s
        block shape wasn't (8, 128)-divisible. Every BlockSpec the
        re-blocked kernel requests — at the r05 bench shape b8 T2048
        h16 kv8 d128 AND the d=64 GQA shape the old kernel relied on
        the equal-dims escape hatch for — must now satisfy the STRICT
        rule, never the escape hatch."""
        from paddle_tpu.ops.pallas.decode_attention import \
            decode_block_shapes
        for (b, T, h, kv, d) in ((8, 2048, 16, 8, 128),
                                 (8, 2048, 8, 4, 64),
                                 (1, 4096, 32, 8, 128),
                                 (2, 256, 24, 2, 64)):
            shapes = decode_block_shapes(b, T, kv, d, group=h // kv)
            for block, arr in shapes:
                assert block[-2] % 8 == 0 and block[-1] % 128 == 0, \
                    (b, T, h, kv, d, block, arr)
                # block must still tile the array it blocks
                assert arr[-2] % block[-2] == 0
                assert arr[-1] % block[-1] == 0

    def test_hardware_gate_excludes_untileable_shapes(self):
        """d=64 with an ODD kv has no 128-multiple column width: the
        hardware gate must route it to the grouped-einsum fallback
        instead of a lowering error (interpret mode still covers it)."""
        from paddle_tpu.ops.pallas.decode_attention import \
            decode_block_geometry
        hpb, cw, nc, bt = decode_block_geometry(2048, 3, 64)
        assert hpb == 1 and cw == 64      # not Mosaic-tilable -> gated
        hpb, cw, nc, bt = decode_block_geometry(2048, 4, 64)
        assert hpb == 2 and cw == 128 and nc == 2
        hpb, cw, nc, bt = decode_block_geometry(2048, 8, 128)
        assert hpb == 1 and cw == 128 and nc == 8

    def test_r05_shape_interpret_parity(self, monkeypatch):
        """Numerics at the failing shape's blocking (b=1 slice — the
        BlockSpecs don't depend on b; the full b8 run is the slow-tier
        twin below)."""
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        self._parity(1, 2048, 16, 8, 128)

    @pytest.mark.slow
    def test_r05_shape_interpret_parity_full_batch(self, monkeypatch):
        """The literal BENCH_SELF_r05 shape: b8 T2048 h16 kv8 d128."""
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        self._parity(8, 2048, 16, 8, 128)

    @staticmethod
    def _parity(b, T, h, kv, d):
        from paddle_tpu.ops.attention import dense_attention
        from paddle_tpu.ops.pallas.decode_attention import \
            decode_attention_pallas
        rs = np.random.RandomState(10)
        q = jnp.asarray(rs.randn(b, h, d), jnp.float32)
        ck = jnp.asarray(rs.randn(b, T, kv, d), jnp.float32)
        cv = jnp.asarray(rs.randn(b, T, kv, d), jnp.float32)
        ci = jnp.int32(T - 48)
        got = decode_attention_pallas(q, ck, cv, ci, d ** -0.5)
        mask = (jnp.arange(T)[None, :] <= ci)[None, None]
        ref = dense_attention(q[:, None], ck, cv, attn_mask=mask)[:, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
