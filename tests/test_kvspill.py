"""ISSUE 17: checksummed host-RAM KV spill tier (KVSpillArena).

Contracts pinned here:

- ARENA: the take-side validation ladder — crc32 mismatch, truncated
  record, geometry skew, capacity refusal — drops the record, counts
  it, and NEVER returns bytes; chain spans dedup into one payload
  record (longest digest) with every shorter span an index alias
  returning the FULL record payload.
- PARITY: greedy streams are bitwise identical (tokens AND logprobs)
  spill-on vs spill-off under eviction pressure — restored KV is
  byte-for-byte what re-prefill would have computed.
- CORRUPTION: a span stored with ``spill_corrupt`` armed (byte flip
  AFTER the crc is banked) is caught by the checksum on the way back;
  the engine falls back to re-prefill and the stream stays bitwise
  the reference — a corrupted span may cost a prefill, never a token.
- WARM RESTART: a fresh engine re-attached to the arena (the
  supervisor-rebuild path) advertises the spilled tier through
  ``has_prefix`` and serves the spilled prefix with
  ``prefix_hit_tokens > 0`` — no re-prefill across the crash.
- CHAOS (slow): the ``serve_loadgen --chaos --spill on`` harness —
  seeded mid-run kills with the shared arena attached — finishes with
  zero corrupted streams, zero checksum surprises, and at least one
  arena restore on a rebuilt replica (``tools/marker_audit.py`` chaos
  patterns).
"""
import asyncio

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.models import LlamaForCausalLM
from paddle_tpu.models.llama import llama_tiny
from paddle_tpu.serving.kvspill import KVSpillArena
from paddle_tpu.utils import faults

from test_gateway import _load_loadgen


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    return LlamaForCausalLM(llama_tiny())


def _engine(model, arena=None, **kw):
    base = dict(max_slots=2, num_blocks=16, block_size=8,
                max_blocks_per_seq=8, prefill_buckets=(16, 32),
                chunk_prefill_tokens=16, enable_prefix_cache=True)
    base.update(kw)
    eng = PagedEngine(model, **base)
    if arena is not None:
        eng.attach_spill(arena)
    return eng


def _greedy_new(model, ids, n):
    import jax.numpy as jnp
    out = model.generate(jnp.asarray(ids), max_new_tokens=n,
                         temperature=0.0)
    return np.asarray(out)[0, ids.shape[1]:]


# ================================================================== arena
GEO = (2, 8, 1, 4, "float32", 16)   # (L, B, kvh, d, dtype, chunk)


def _payload(n_blocks, fill=7.0):
    L, B, kvh, d = GEO[0], GEO[1], GEO[2], GEO[3]
    return np.full((2 * L, n_blocks, B, kvh, d), fill,
                   np.float32).tobytes()


class TestArena:
    def test_spill_take_roundtrip(self):
        arena = KVSpillArena(1 << 20, name="u_rt")
        pay = _payload(2)
        assert arena.spill([(b"d2", (1, 2))],
                           lambda e: pay, GEO, 5) == 1
        assert len(arena) == 1
        assert arena.probe(b"d2") == 16        # 2 blocks x B=8
        assert arena.take(b"d2", GEO) == (pay, 16)
        snap = arena.snapshot()
        assert snap["hits"] == 1 and snap["records"] == 1
        assert snap["occupancy_bytes"] == len(pay)

    def test_chain_dedup_one_gather_aliases_full_payload(self):
        """One D2H per chain: the longest span is the payload record;
        a shorter span in the same call is an index alias whose take
        returns the FULL record bytes + the RECORD's token count (the
        caller slices the leading blocks it needs)."""
        arena = KVSpillArena(1 << 20, name="u_alias")
        pay = _payload(4)
        gathers = []

        def fetch(entry):
            gathers.append(entry)
            return pay
        assert arena.spill([(b"d4", (1, 2, 3, 4)), (b"d2", (1, 2))],
                           fetch, GEO) == 1
        assert gathers == [(1, 2, 3, 4)]       # single gather
        assert arena.probe(b"d2") == 16        # alias advertises OWN span
        assert arena.take(b"d2", GEO) == (pay, 32)  # record's payload
        assert arena.snapshot()["digests"] == 2

    def test_capacity_refusal_and_lru_eviction(self):
        one = len(_payload(2))
        arena = KVSpillArena(2 * one, name="u_cap")
        # can never fit -> refused and counted, nothing stored
        assert arena.spill([(b"big", tuple(range(1, 9)))],
                           lambda e: _payload(8), GEO) == 0
        assert arena.snapshot()["drops"] == 1 and len(arena) == 0
        for i in range(3):                     # 3 spans into a 2-span cap
            arena.spill([(bytes([i]) * 4, (1, 2))],
                        lambda e: _payload(2, fill=float(i)), GEO)
        assert len(arena) == 2
        assert arena.lru_evictions == 1
        assert arena.probe(b"\x00" * 4) is None   # oldest evicted
        assert arena.probe(b"\x02" * 4) == 16

    def test_geometry_skew_drops_record(self):
        arena = KVSpillArena(1 << 20, name="u_geo")
        arena.spill([(b"dg", (1, 2))], lambda e: _payload(2), GEO)
        other = (4,) + GEO[1:]                 # different layer count
        assert arena.take(b"dg", other) is None
        assert arena.snapshot()["drops"] == 1
        assert arena.probe(b"dg") is None      # evicted, not retried

    def test_truncated_record_drops(self):
        arena = KVSpillArena(1 << 20, name="u_trunc")
        arena.spill([(b"dt", (1, 2))], lambda e: _payload(2), GEO)
        rec = arena._records[b"dt"]
        rec.payload = rec.payload[:-4]         # torn host buffer
        assert arena.take(b"dt", GEO) is None
        assert arena.snapshot()["drops"] == 1
        assert arena.probe(b"dt") is None

    def test_corrupt_fault_caught_by_checksum(self):
        """``spill_corrupt`` flips a byte AFTER the crc is banked: the
        probe still advertises the span, but take must catch the rot,
        count it, and evict — bytes never reach the caller."""
        arena = KVSpillArena(1 << 20, name="u_crc")
        with faults.scoped("spill_corrupt"):
            arena.spill([(b"dc", (1, 2))], lambda e: _payload(2), GEO)
        assert arena.probe(b"dc") == 16
        assert arena.take(b"dc", GEO) is None
        snap = arena.snapshot()
        assert snap["checksum_failures"] == 1 and snap["drops"] == 0
        assert arena.probe(b"dc") is None

    def test_drop_fault_refuses_store(self):
        arena = KVSpillArena(1 << 20, name="u_drop")
        with faults.scoped("spill_drop"):
            assert arena.spill([(b"dd", (1, 2))],
                               lambda e: _payload(2), GEO) == 0
        assert arena.snapshot()["drops"] == 1
        assert arena.probe(b"dd") is None

    def test_generation_advances_on_mutation(self):
        arena = KVSpillArena(1 << 20, name="u_gen")
        g0 = arena.generation
        arena.spill([(b"dgn", (1, 2))], lambda e: _payload(2), GEO)
        assert arena.generation > g0           # gossip sees the store
        g1 = arena.generation
        arena.take(b"dgn", (9,) + GEO[1:])     # skew -> eviction
        assert arena.generation > g1           # ...and the eviction


# ================================================================= engine
class TestSpillParity:
    def test_eviction_pressure_bitwise_spill_on_vs_off(self, model):
        """Five distinct 33-token prompts through a 15-block pool:
        spill-on evicts THROUGH the arena, spill-off discards — every
        stream (tokens and logprobs) must be bitwise identical."""
        def run(arena):
            rs = np.random.RandomState(50)
            prompts = {f"r{i}": np.asarray([rs.randint(1, 256, 33)])
                       for i in range(5)}
            eng = _engine(model, arena)
            for rid, ids in prompts.items():
                eng.submit(rid, ids, max_new_tokens=4)
            return eng, eng.run(), prompts
        eng_off, out_off, prompts = run(None)
        eng_on, out_on, _ = run(KVSpillArena(64 << 20, name="parity"))
        for rid in prompts:
            np.testing.assert_array_equal(
                np.asarray(out_on[rid]), np.asarray(out_off[rid]),
                err_msg=rid)
            np.testing.assert_array_equal(
                np.asarray(eng_on.logprobs[rid]),
                np.asarray(eng_off.logprobs[rid]), err_msg=rid)
        assert eng_on.stats["spill_spans"] > 0     # pressure spilled
        assert eng_off.stats["spill_spans"] == 0

    def test_evicted_span_restores_from_arena_and_stays_exact(
            self, model):
        """After a span is evicted D2H, resubmitting its prompt must
        restore it (one H2D scatter, no re-prefill of the span) and
        the stream must equal the model's own greedy decode."""
        arena = KVSpillArena(64 << 20, name="restore")
        eng = _engine(model, arena)
        rs = np.random.RandomState(51)
        first = np.asarray([rs.randint(1, 256, 33)])
        eng.submit("a", first, max_new_tokens=4)
        eng.run()
        for i in range(6):                     # flood the 15-block pool
            eng.submit(f"f{i}",
                       np.asarray([rs.randint(1, 256, 33)]),
                       max_new_tokens=4)
        eng.run()
        digest = eng.prefix_digest(first)
        assert bytes.fromhex(digest) not in eng.prefix_cache
        assert eng.has_prefix(digest)          # spilled tier advertises
        hit0 = eng.stats["prefix_hit_tokens"]
        eng.submit("a2", first, max_new_tokens=4)
        out = eng.run()
        assert eng.stats["spill_restores"] >= 1, eng.stats
        assert eng.stats["prefix_hit_tokens"] > hit0
        np.testing.assert_array_equal(np.asarray(out["a2"]),
                                      _greedy_new(model, first, 4))

    def test_corrupted_span_never_emits_a_token(self, model):
        """Every record stored under ``spill_corrupt`` carries silent
        bit rot. The warm resubmit must catch it at the checksum,
        count a restore failure, fall back to re-prefill, and emit a
        stream bitwise identical to the uncorrupted reference."""
        arena = KVSpillArena(64 << 20, name="corrupt")
        eng = _engine(model, arena)
        rs = np.random.RandomState(52)
        first = np.asarray([rs.randint(1, 256, 33)])
        ref = _greedy_new(model, first, 4)
        eng.submit("a", first, max_new_tokens=4)
        eng.run()
        with faults.scoped("spill_corrupt"):
            for i in range(6):                 # evict a's spans rotten
                eng.submit(f"f{i}",
                           np.asarray([rs.randint(1, 256, 33)]),
                           max_new_tokens=4)
            eng.run()
        digest = eng.prefix_digest(first)
        assert eng.has_prefix(digest)          # still advertised...
        eng.submit("a2", first, max_new_tokens=4)
        out = eng.run()
        np.testing.assert_array_equal(np.asarray(out["a2"]), ref)
        assert eng.stats["spill_restores"] == 0
        assert eng.stats["spill_restore_failures"] >= 1, eng.stats
        assert arena.snapshot()["checksum_failures"] >= 1


class TestWarmRestart:
    def test_rebuild_recovers_warm_from_arena(self, model):
        """The supervisor-rebuild contract: drain-spill on the dying
        engine, then a FRESH engine re-attached to the same arena
        advertises the span, restores it at admission, and serves it
        with prefix-hit tokens — bitwise the original stream."""
        arena = KVSpillArena(64 << 20, name="warm")
        e0 = _engine(model, arena, num_blocks=32)
        rs = np.random.RandomState(53)
        prompt = np.asarray([rs.randint(1, 256, 33)])
        e0.submit("a", prompt, max_new_tokens=4)
        ref = np.asarray(e0.run()["a"])
        lp_ref = np.asarray(e0.logprobs["a"])
        assert e0.spill_parked() > 0           # SIGTERM drain banks
        e1 = _engine(model, arena, num_blocks=32)   # rebuilt replica
        digest = e1.prefix_digest(prompt)
        assert e1.has_prefix(digest)           # warm BEFORE any traffic
        e1.submit("b", prompt, max_new_tokens=4)
        out = e1.run()
        assert e1.stats["spill_restores"] >= 1, e1.stats
        assert e1.stats["prefix_hit_tokens"] >= 16, e1.stats
        np.testing.assert_array_equal(np.asarray(out["b"]), ref)
        np.testing.assert_array_equal(np.asarray(e1.logprobs["b"]),
                                      lp_ref)

    def test_a_record_banked_with_the_heads_apart_restores(self, model):
        """The pools are [P, B, kvh*d]; until PR 27 they were [P, B,
        kvh, d] and a span was packed ``(2L, n, B, kvh, d)``. In host
        order these are the same bytes under the same geometry tuple:
        a record packed the old way from a pool's values is, byte for
        byte, what ``_spill_fetch`` packs, and a fresh engine restores
        it and serves the original stream."""
        e0 = _engine(model, None, num_blocks=32)
        rs = np.random.RandomState(55)
        prompt = np.asarray([rs.randint(1, 256, 33)])
        e0.submit("a", prompt, max_new_tokens=4)
        ref = np.asarray(e0.run()["a"])
        lp_ref = np.asarray(e0.logprobs["a"])
        cfg = model.config
        kvh, d = cfg.num_key_value_heads, cfg.head_dim
        geo = (cfg.num_hidden_layers, 8, kvh, d, str(e0.pools[0][0].dtype),
               16)
        assert e0._spill_geometry() == geo
        key = bytes.fromhex(e0.prefix_digest(prompt))
        entry = e0.prefix_cache[key]
        apart = [np.asarray(p).reshape(32, 8, kvh, d)
                 for layer in e0.pools for p in layer]
        banked = np.stack([p[np.asarray(entry)] for p in apart]).tobytes()
        assert np.stack(apart).any() and kvh > 1
        assert e0._spill_fetch(entry) == banked
        arena = KVSpillArena(64 << 20, name="apart")
        assert arena.put(key, banked, len(entry) * 8, geo)
        e1 = _engine(model, arena, num_blocks=32)
        e1.submit("b", prompt, max_new_tokens=4)
        out = e1.run()
        assert e1.stats["spill_restores"] == 1, e1.stats
        assert e1.stats["prefix_hit_tokens"] >= 16, e1.stats
        np.testing.assert_array_equal(np.asarray(out["b"]), ref)
        np.testing.assert_array_equal(np.asarray(e1.logprobs["b"]),
                                      lp_ref)

    def test_geometry_skew_falls_back_to_prefill(self, model):
        """An arena fed by one block geometry attached to an engine
        with another: the take-side geometry check refuses the
        payload, the restore counts a failure, and the stream is
        still exact via re-prefill."""
        arena = KVSpillArena(64 << 20, name="skew")
        e0 = _engine(model, arena, num_blocks=32)
        rs = np.random.RandomState(54)
        prompt = np.asarray([rs.randint(1, 256, 33)])
        e0.submit("a", prompt, max_new_tokens=4)
        ref = np.asarray(e0.run()["a"])
        assert e0.spill_parked() > 0
        e1 = _engine(model, arena, num_blocks=32, block_size=4,
                     max_blocks_per_seq=16)    # skewed geometry
        e1.submit("b", prompt, max_new_tokens=4)
        out = e1.run()
        np.testing.assert_array_equal(np.asarray(out["b"]), ref)
        assert e1.stats["spill_restores"] == 0
        assert arena.snapshot()["drops"] >= 1


# ================================================================== chaos
def _chaos_spill_ns(**kw):
    import types
    base = dict(requests=400, rate=50.0, share_frac=0.9, sys_tokens=16,
                tail_tokens=24, max_new=16, interactive_frac=0.7,
                ttft_slo_ms=5000.0, timeout_s=60.0, tenants=2,
                replicas=3, policy="prefix", max_queue=256,
                model="stub", seed=0, url=None, out="",
                chaos=True, chaos_kills=3, chaos_mode="kill",
                failover_budget=3, watchdog_timeout_s=0.5,
                goodput_floor=0.95, spill="on", spill_mb=64)
    base.update(kw)
    return types.SimpleNamespace(**base)


@pytest.mark.slow
@pytest.mark.chaos
def test_spill_chaos_kill_replay_clean():
    """The ISSUE 17 acceptance run: 3-replica gateway, 3 seeded
    mid-run SIGKILL-style crashes, the shared host-RAM arena attached.
    Eviction pressure banks spans (the shared sys prefix rides along
    as an alias of its dying descendants), a rebuilt replica
    advertises the spilled tier, restores at least one span, and
    EVERY completed greedy stream replays bitwise — zero corrupted
    streams, zero checksum failures, errors within the budget bound."""
    slg = _load_loadgen()
    rung = asyncio.run(slg.run_loadgen(_chaos_spill_ns()))
    ch = rung["chaos"]
    assert ch["corrupted_streams"] == 0, ch
    assert ch["errors_5xx"] == 0, ch
    assert ch["completed_frac"] >= 0.95, ch
    assert ch["ok"], ch
    arena = rung["kv_spill_arena"]
    assert arena["spans"] > 0, arena           # pressure spilled
    assert arena["checksum_failures"] == 0, arena
    assert rung["kv_spill_restores"] >= 1, rung
    assert rung["kv_spill_restored_tokens"] > 0, rung
