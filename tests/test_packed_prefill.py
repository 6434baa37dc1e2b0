"""ISSUE 31: prompts share a prefill call.

A drain-first step packs the prompts that start at position 0 (nothing
cached behind them) first-fit into calls of at most ``chunk`` positions
and ``_pack_segments`` segments; one executable serves every number of
segments. A chunk with cached context behind it (a continuation, a
prefix adopter) runs alone through the program that gathers the row's
table. Contracts pinned here on the CPU, in float32, at the tiny presets
of the three served families:

- a wave served packed is the same prompts served one a step: greedy
  tokens equal, logprobs and the pools' live rows inside the 2e-5 the
  chunk parity tests of tests/test_latent_paged.py use;
- which program serves what, read off the calls and the counters
  ``prefill_segments`` / ``prefill_chunks``;
- first-fit in admission order, and no prefilling slot left without a
  chunk in a step;
- one trace of the packed program after 1, 2 and ``_pack_segments``
  segments;
- a sampled segment beside greedy neighbours draws what the single-slot
  program draws from the same key;
- the packed program sorts a vocabulary row only under a ``cond``, one
  row at a time: what a call pays for sampling follows its live sampled
  segments.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation import paged
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.generation.sampling import (sample_token_rows,
                                            sample_token_segments)

ATOL = 2e-5
FAMILIES = ("llama", "deepseek", "longcat")


def build(family):
    pt.seed(0)
    if family == "llama":
        from paddle_tpu.models.qwen2 import Qwen2ForCausalLM, qwen2_tiny
        return Qwen2ForCausalLM(qwen2_tiny())
    if family == "deepseek":
        from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                                   deepseek_v2_tiny)
        return DeepseekV2ForCausalLM(deepseek_v2_tiny(
            num_hidden_layers=2, scoring="sigmoid", experts_held=4,
            v_head_dim=24))
    from paddle_tpu.models.longcat_flash import (LongcatFlashForCausalLM,
                                                 longcat_flash_tiny)
    return LongcatFlashForCausalLM(longcat_flash_tiny(experts_held=4))


@pytest.fixture(scope="module", params=FAMILIES)
def family_model(request):
    return build(request.param)


@pytest.fixture(scope="module")
def llama():
    return build("llama")


def engine(model, **kw):
    """chunk 16 over blocks of 4: ``_pack_segments`` is 4."""
    base = dict(max_slots=6, num_blocks=96, block_size=4,
                max_blocks_per_seq=16, chunk_prefill_tokens=16,
                enable_prefix_cache=True)
    base.update(kw)
    return PagedEngine(model, **base)


def prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


def count_calls(eng):
    """Both chunk programs wrapped to count their calls."""
    calls = {"packed": 0, "alone": 0}

    def counting(name, program):
        def call(*a, **kw):
            calls[name] += 1
            return program(*a, **kw)
        return call
    eng._chunk_jit = paged._ChunkPrograms(
        counting("packed", eng._chunk_jit.packed),
        counting("alone", eng._chunk_jit.alone))
    return calls


def live_rows(eng, slot_id):
    """The rows the slot's prompt wrote, every cache layer and pool."""
    req = eng.slots[slot_id]
    n = len(req.prompt)
    return [np.asarray(pool[np.asarray(req.blocks)]).reshape(
        -1, pool.shape[-1])[:n] for layer in eng.pools for pool in layer]


# ------------------------------------------------ packed against one a step
def test_a_packed_wave_is_the_prompts_served_one_a_step(family_model):
    ps = prompts(1, (3, 9, 5, 16, 2, 11))
    packed, single = engine(family_model), engine(family_model)
    for i, p in enumerate(ps):
        packed.submit(i, p, max_new_tokens=12)
    packed.step()
    assert packed.stats["prefill_segments"] == 6
    # first-fit: [3, 9, 2], [5, 11], [16]
    assert packed.stats["prefill_chunks"] == 3
    for i, p in enumerate(ps):
        single.submit(i, p, max_new_tokens=12)
        single.step()
    assert single.stats["prefill_segments"] == 6
    assert single.stats["prefill_chunks"] == 6
    for i in range(len(ps)):
        a, b = ([s.request_id for s in e.slots].index(i)
                for e in (packed, single))
        for x, y in zip(live_rows(packed, a), live_rows(single, b)):
            np.testing.assert_allclose(x, y, atol=ATOL)
    out_p, out_s = packed.run(), single.run()
    for i in range(len(ps)):
        assert out_p[i] == out_s[i]
        np.testing.assert_allclose(packed.logprobs[i], single.logprobs[i],
                                   atol=ATOL)


def test_a_packed_wave_is_the_models_own_greedy_continuation(family_model):
    """Against the model's full forward, not against another engine."""
    ps = prompts(2, (7, 4, 12, 3))
    eng = engine(family_model)
    for i, p in enumerate(ps):
        eng.submit(i, p, max_new_tokens=4)
    out = eng.run()
    assert eng.stats["prefill_chunks"] == 2         # [7, 4, 3], [12]
    fn, params = family_model.functional()
    ids = np.zeros((len(ps), 24), np.int32)
    for r, p in enumerate(ps):
        ids[r, :len(p) + 4] = p + out[r]
    logp = np.asarray(jax.nn.log_softmax(
        jax.jit(fn)(params, jnp.asarray(ids)), axis=-1))
    for r, p in enumerate(ps):
        rows = logp[r, len(p) - 1:len(p) + 3]
        assert rows.argmax(-1).tolist() == out[r]
        np.testing.assert_allclose(eng.logprobs[r],
                                   rows[np.arange(4), out[r]], atol=ATOL)


def test_a_window_counts_inside_its_own_segment():
    """A sliding window over packed prompts is the window over each
    prompt alone: the call's index and the position differ by the same
    offset for a query and its keys."""
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import llama_tiny
    pt.seed(0)
    model = LlamaForCausalLM(llama_tiny(sliding_window=4))
    ps = prompts(3, (7, 8))
    packed, single = engine(model), engine(model)
    for i, p in enumerate(ps):
        packed.submit(i, p, max_new_tokens=4)
    out_p = packed.run()
    assert packed.stats["prefill_chunks"] == 1
    for i, p in enumerate(ps):
        single.submit(i, p, max_new_tokens=4)
        assert single.run()[i] == out_p[i]
        np.testing.assert_allclose(packed.logprobs[i], single.logprobs[i],
                                   atol=ATOL)


# --------------------------------------------------- which program serves what
def test_a_prompt_over_the_chunk_continues_alone(family_model):
    eng = engine(family_model)
    calls = count_calls(eng)
    long, short = prompts(4, (37, 6))
    eng.submit("long", long, max_new_tokens=3)
    eng.submit("short", short, max_new_tokens=3)
    eng.step()
    # the long prompt's first 16 tokens fill a call of one segment
    assert calls == {"packed": 2, "alone": 0}
    assert eng.slots[0].prefill_pos == 16
    eng.step()
    eng.step()
    assert calls == {"packed": 2, "alone": 2}
    assert eng.stats["prefill_chunks"] == eng.stats["prefill_segments"] == 4
    out = eng.run()
    alone = engine(family_model)
    alone.submit("long", long, max_new_tokens=3)
    assert alone.run()["long"] == out["long"]


def test_a_prefix_adopter_takes_the_old_program(family_model):
    eng = engine(family_model)
    calls = count_calls(eng)
    system = prompts(5, (32,))[0]
    a, b = system + [7, 8, 9], system + [11, 12]
    eng.submit("a", a, max_new_tokens=3)
    out = eng.run()
    assert calls == {"packed": 1, "alone": 2}
    eng.submit("b", b, max_new_tokens=3)
    out.update(eng.run())
    assert eng.stats["prefix_hit_tokens"] == 32
    # b starts at position 32: one chunk, alone, nothing packed
    assert calls == {"packed": 1, "alone": 3}
    cold = engine(family_model, enable_prefix_cache=False)
    cold.submit("b", b, max_new_tokens=3)
    assert cold.run()["b"] == out["b"]


def test_segments_over_calls_is_the_pack(llama):
    eng = engine(llama)
    for i, p in enumerate(prompts(6, (4, 4, 4, 4, 20))):
        eng.submit(i, p, max_new_tokens=2)
    eng.run()
    # step 1: [4, 4, 4, 4] and the long prompt's first 16; step 2: its
    # last 4 alone
    assert eng.stats["prefill_segments"] == 6
    assert eng.stats["prefill_chunks"] == 3
    assert eng.stats["prefills"] == 5


def test_every_request_and_chunk_fires_its_trace_event(llama):
    eng = engine(llama)
    events = []
    eng.trace_sink = lambda rid, kind, **f: events.append((rid, kind, f))
    ps = prompts(7, (5, 6, 20))
    for i, p in enumerate(ps):
        eng.submit(i, p, max_new_tokens=2)
    eng.run()
    chunks = [(rid, f["start"], f["tokens"]) for rid, kind, f in events
              if kind == "prefill_chunk"]
    assert sorted(chunks) == [(0, 0, 5), (1, 0, 6), (2, 0, 16), (2, 16, 4)]
    done = [(rid, f["tokens"]) for rid, kind, f in events
            if kind == "prefill_done"]
    assert sorted(done) == [(0, 5), (1, 6), (2, 20)]


# ------------------------------------------------------------- the packing
class _Slot:
    def __init__(self, n, seq):
        self.prompt, self.admit_seq = [1] * n, seq


@pytest.mark.parametrize("lengths, want", [
    # first-fit, not next-fit: the 6 goes back into the first call
    ((10, 9, 6, 5, 7, 1, 1, 1), [[0, 2], [1, 3, 5, 6], [4, 7]]),
    # at most _pack_segments (4) segments a call, however short
    ((1, 1, 1, 1, 1), [[0, 1, 2, 3], [4]]),
    # a prompt over the chunk takes its first 16 tokens, a call alone
    ((40, 3, 16, 2), [[0], [1, 3], [2]]),
    ((), []),
])
def test_first_fit_in_admission_order(llama, lengths, want):
    eng = engine(llama, max_slots=8)
    # slots filled out of admission order: the order is admit_seq's
    order = list(range(len(lengths)))[::-1]
    for slot_id, k in zip(range(len(lengths)), order):
        eng.slots[slot_id] = _Slot(lengths[k], seq=k)
    calls = eng._pack_calls(list(range(len(lengths))))
    by_admission = [[order[i] for i in call] for call in calls]
    assert by_admission == want
    for call in calls:
        assert len(call) <= eng._pack_segments == 4
        assert sum(min(16, len(eng.slots[i].prompt)) for i in call) <= 16


def test_no_prefilling_slot_waits_a_step(llama):
    """Eight prompts of every kind admitted at once: after ONE step each
    has advanced one chunk, as when each had a call of its own."""
    eng = engine(llama, max_slots=8, num_blocks=160)
    system = prompts(8, (16,))[0]
    eng.submit("seed", system + [5], max_new_tokens=2)
    eng.run()
    lengths = (3, 40, 16, 9, 1, 25, 12)
    for i, p in enumerate(prompts(9, lengths)):
        eng.submit(i, p, max_new_tokens=8)
    eng.submit("adopter", system + [6, 7], max_new_tokens=8)
    eng.step()
    for s in eng.slots:
        n = len(s.prompt)
        if s.request_id == "adopter":
            assert s.prefill_pos == n == 18
        else:
            assert s.prefill_pos == min(16, n)
    eng.step()
    for s in eng.slots:
        assert s.prefill_pos == min(32, len(s.prompt))


# ------------------------------------------------------------ one executable
def test_one_trace_for_every_number_of_segments(llama):
    eng = engine(llama)
    sizes = []
    for wave in ((5,), (4, 6), (3, 3, 3, 3)):
        before = eng.stats["prefill_chunks"]
        for i, p in enumerate(prompts(10, wave)):
            eng.submit((wave, i), p, max_new_tokens=2)
        eng.run()
        assert eng.stats["prefill_chunks"] - before == 1
        sizes.append((eng._chunk_jit._cache_size(),
                      eng._chunk_jit.packed._cache_size(),
                      eng._chunk_jit.alone._cache_size()))
    assert sizes == [(1, 1, 0)] * 3
    eng.submit("long", prompts(11, (20,))[0], max_new_tokens=2)
    eng.run()
    assert eng._chunk_jit._cache_size() == 2


# ------------------------------------------------------------------ sampling
SAMPLING = dict(temperature=0.8, top_k=50, top_p=0.95, seed=1234)


def test_a_sampled_segment_draws_the_single_slot_programs_token(llama):
    """Beside greedy neighbours in one call, against ``_chunk_prefill``
    called on the same prompt, key and sampling parameters."""
    ps = prompts(12, (5, 6, 4))
    eng = engine(llama)
    eng.submit("g0", ps[0], max_new_tokens=6)
    eng.submit("s", ps[1], max_new_tokens=6, **SAMPLING)
    eng.submit("g1", ps[2], max_new_tokens=6)
    key = eng.slots[1].key.copy()
    eng.step()
    assert eng.stats["prefill_chunks"] == 1
    sampled = eng.slots[1]
    ref = engine(llama)
    ids = np.zeros((1, 16), np.int32)
    ids[0, :6] = ps[1]
    row = np.zeros((16,), np.int32)
    row[:2] = (1, 2)
    nxt, lp, new_key, *_ = ref._chunk_jit.alone(
        ref.params, ref.pools, jnp.asarray(row), jnp.asarray(ids),
        np.int32(0), np.int32(6), jnp.asarray(key), np.float32(0.8),
        np.int32(50), np.float32(0.95), np.float32(1.0), ref.seen[0],
        bucket=16)
    assert sampled.tokens[0] == int(nxt)
    np.testing.assert_allclose(sampled.lps[0], float(lp), atol=ATOL)
    assert sampled.key.tolist() == np.asarray(new_key).tolist()
    # and the whole streams are the ones each request gets alone
    out = eng.run()
    for rid, p, kw in (("g0", ps[0], {}), ("s", ps[1], SAMPLING),
                       ("g1", ps[2], {})):
        alone = engine(llama)
        alone.submit(rid, p, max_new_tokens=6, **kw)
        assert alone.run()[rid] == out[rid]


def test_sampled_and_penalised_requests_stream_as_when_served_alone(llama):
    """The seen mask a packed call builds and writes back by slot is the
    one the decode ticks go on from."""
    ps = prompts(13, (6, 7, 5, 20))
    kws = (dict(repetition_penalty=1.3), dict(SAMPLING),
           dict(SAMPLING, seed=9, repetition_penalty=1.2),
           dict(repetition_penalty=1.5))
    eng = engine(llama)
    for i, (p, kw) in enumerate(zip(ps, kws)):
        eng.submit(i, p, max_new_tokens=8, **kw)
    out = eng.run()
    for i, (p, kw) in enumerate(zip(ps, kws)):
        alone = engine(llama)
        alone.submit(i, p, max_new_tokens=8, **kw)
        assert alone.run()[i] == out[i]
        np.testing.assert_allclose(eng.logprobs[i], alone.logprobs[i],
                                   atol=ATOL)


def test_sample_token_segments_is_sample_token_rows():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(6, 300)) * 3, jnp.float32)
    keys = jnp.asarray(rng.integers(0, 2 ** 32, (6, 2), dtype=np.uint32))
    temps = jnp.asarray([0.0, 0.8, 1.2, 0.0, 0.5, 0.9], jnp.float32)
    tks = jnp.asarray([0, 50, 0, 5, 3, 0], jnp.int32)
    tps = jnp.asarray([1.0, 0.95, 0.5, 0.9, 1.0, 1.0], jnp.float32)
    want = sample_token_rows(logits, keys, temps, tks, tps)
    got = jax.jit(sample_token_segments)(logits, keys, temps, tks, tps,
                                         jnp.ones((6,), bool))
    for w, g in zip(want, got):
        assert np.asarray(w).tolist() == np.asarray(g).tolist()
    # a dead row draws nothing; the live rows are what they were
    live = jnp.asarray([True, True, False, True, False, True])
    got = jax.jit(sample_token_segments)(logits, keys, temps, tks, tps,
                                         live)
    rows = np.flatnonzero(np.asarray(live))
    assert np.asarray(got[0])[rows].tolist() \
        == np.asarray(want[0])[rows].tolist()


def _sorts(jaxpr, under_cond=False):
    """(operand shape, whether under a cond) of every sort, recursively."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            found.append((eqn.invars[0].aval.shape, under_cond))
        inner = under_cond or eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _sorts(sub, inner)
    return found


def test_the_packed_program_sorts_one_row_at_a_time_under_a_cond(llama):
    eng = engine(llama)
    call, _ = _packed_call(eng, (5, 6))
    jaxpr = jax.make_jaxpr(eng._chunk_prefill_packed)(
        eng.params, eng.pools, eng.seen, call)
    sorts = _sorts(jaxpr.jaxpr)
    vocab = eng.seen.shape[1]
    assert sorts, "the sampled branch is gone"
    for shape, under_cond in sorts:
        assert under_cond, shape
        assert int(np.prod(shape)) <= vocab, shape


def _packed_call(eng, lengths):
    for i, p in enumerate(prompts(14, lengths)):
        eng.submit(i, p, max_new_tokens=2)
    slots = [i for i, s in enumerate(eng.slots) if s is not None]
    call, lives = eng._pack_call(slots)
    return jnp.asarray(call), lives


def test_the_calls_one_upload_lays_out_segments_and_pads(llama):
    eng = engine(llama)
    call, lives = _packed_call(eng, (5, 3))
    call = np.asarray(call)
    ids, seg, pos = call[:48].reshape(3, 16)
    assert lives == [5, 3]
    assert ids[:8].tolist() == eng.slots[0].prompt + eng.slots[1].prompt
    assert not ids[8:].any()
    assert seg.tolist() == [0] * 5 + [1] * 11
    # the pads ride behind the last segment, past its length
    assert pos.tolist() == list(range(5)) + list(range(11))
    sg = call[48:].reshape(4, eng.M + paged._SEG_WORDS)
    assert sg[:, eng.M].tolist() == [5, 3, 0, 0]           # lengths
    assert sg[:, eng.M + 1].tolist() == [0, 1, eng.R, eng.R]   # slots
    assert sg[:2, eng.M + 2].tolist() == [4, 7]            # last live index
    assert sg[0, :2].tolist() == eng.slots[0].blocks
    assert sg[1, :2].tolist() == eng.slots[1].blocks + [0]
