"""ISSUE 44: a prompt chunk's delta rule at a decay a key channel as ONE
Pallas kernel a layer (``ops/pallas/delta_chunk.py``), here through the
interpreter at Ling-3.0-flash's geometry cut in heads only (2-4 heads of
128 x 128, sub-chunks of 64).

- THE KERNEL against the scan (the definition) and against the fusions
  it replaces (``_chunk_fusions``): a whole call and lengths that are no
  multiple of the sub-chunk, a carried state, every log-decay at
  ``CHANNEL_LOG_DECAY_MIN`` and at 0, repeated keys (where a power
  series for the inverse would not converge), a padded tail of a whole
  dead sub-chunk and of part of one (the state behind the skipped
  sub-chunk bit for bit the state in front of it), packed segments one
  of which ends inside a sub-chunk.
- THE GATE: bfloat16, keys 96 wide and a decay a head fall back to the
  fusions and agree with the scan; ``chunk_rule_route`` says what the
  traced program took.
- THE ENGINE: ``chunk_rule_kernel_calls`` equals
  ``chunk_rule_layer_calls`` in a tiny ``ling_hybrid`` engine at a head
  of 128 and is 0 in an ``olmo_hybrid`` engine, whose programs' lowered
  text is the same whether or not the new module can be imported.

Tolerances are ``tests/test_ling_hybrid.py``'s for the chunk form
against the scan: 1e-4 on outputs and states (read here: 4e-6), 1e-5 a
segment.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.ops.paged_cache import chunk_rule_route
from paddle_tpu.ops import delta_rule

TOL = 1e-4
D = 128


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def _inputs(T, H=2, dk=D, dv=D, seed=0, g_all=None, live=None,
            same_keys=False, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    q = delta_rule.l2_normalize(f(T, H, dk)) * dk ** -0.5
    k = delta_rule.l2_normalize(f(1 if same_keys else T, H, dk))
    k = jnp.broadcast_to(k, (T, H, dk))
    v = f(T, H, dv)
    beta = jnp.asarray(rng.uniform(0.9 if same_keys else 0.01, 0.99,
                                   (T, H)), jnp.float32)
    g = -5.0 * jax.nn.sigmoid(3 * f(T, H, dk))
    if g_all is not None:
        g = jnp.full_like(g, g_all)
    if live is not None:            # as the model pads
        real = jnp.arange(T) < live
        g = jnp.where(real[:, None, None], g, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)
    S0 = f(H, dk, dv)
    return tuple(x.astype(dtype) for x in (q, k, v, g, beta, S0))


def _taken(*args, **kw):
    """Whether the traced program of this call holds the kernel."""
    jaxpr = jax.make_jaxpr(
        lambda *a: delta_rule.gated_delta_chunk(*a, **kw))(*args)
    return "pallas_call" in str(jaxpr)


@pytest.mark.parametrize("T,H,g_all,same_keys", [
    (256, 2, None, False), (256, 4, None, False), (200, 2, None, False),
    (130, 3, None, False), (64, 2, None, False), (1, 2, None, False),
    (256, 2, delta_rule.CHANNEL_LOG_DECAY_MIN, False),
    (100, 2, delta_rule.CHANNEL_LOG_DECAY_MIN, False),
    (256, 2, 0.0, False), (70, 2, 0.0, False),
    (256, 2, None, True), (256, 2, 0.0, True)])
def test_the_kernel_is_the_scan_and_the_fusions(kernels, T, H, g_all,
                                                same_keys):
    """From a carried state. With every key of a head the same and beta
    near 1 the triangle's entries are near 1: the terms of a power
    series for its inverse would grow; the substitution does not care."""
    a = _inputs(T, H, g_all=g_all, same_keys=same_keys, seed=T + H)
    assert _taken(*a)
    o_ref, S_ref = delta_rule.gated_delta_scan(*a)
    o, S = delta_rule.gated_delta_chunk(*a)
    o_f, S_f = delta_rule._chunk_fusions(*a)
    assert np.isfinite(o).all() and np.isfinite(S).all()
    assert S.shape == (1, H, D, D) and o.shape == (T, H, D)
    assert np.abs(o - o_ref).max() < TOL
    assert np.abs(S[0] - S_ref).max() < TOL
    assert np.abs(o - o_f).max() < TOL
    assert np.abs(S - S_f).max() < TOL


@pytest.mark.parametrize("live", [192, 150, 64, 1])
def test_a_dead_sub_chunk_is_skipped(kernels, live):
    """256 positions of which ``live`` are real: the sub-chunks behind
    them change nothing, a partly padded one is computed. The state is
    BIT FOR BIT the state of the call cut behind its last live
    sub-chunk, the outputs of the real positions are its outputs, and a
    skipped sub-chunk's are zeros."""
    a = _inputs(256, live=live, seed=live)
    cut = -(-live // 64) * 64
    o, S = delta_rule.gated_delta_chunk(*a)
    o_cut, S_cut = delta_rule.gated_delta_chunk(*(x[:cut] for x in a[:5]),
                                                a[5])
    assert np.array_equal(np.asarray(S), np.asarray(S_cut))
    assert np.array_equal(np.asarray(o[:cut]), np.asarray(o_cut))
    assert not np.asarray(o[cut:]).any()
    o_ref, S_ref = delta_rule.gated_delta_scan(*a)
    assert np.abs(o[:live] - o_ref[:live]).max() < TOL
    assert np.abs(S[0] - S_ref).max() < TOL


def test_nothing_live_leaves_the_state_as_it_came(kernels):
    a = _inputs(128, live=0)
    o, S = delta_rule.gated_delta_chunk(*a)
    assert np.array_equal(np.asarray(S[0]), np.asarray(a[5]))
    assert not np.asarray(o).any()


@pytest.mark.parametrize("lens,T,segments", [
    ((70, 5, 100), 256, 3),     # ends inside sub-chunks 1, 1 and 2
    ((70, 5, 100), 256, 4),     # and a segment that holds nothing
    ((64, 90), 192, 2),         # an end on a sub-chunk's last position
    ((23, 41), 64, 2)])         # two segments in one sub-chunk
def test_packed_segments_neither_share_state_nor_decay(kernels, lens, T,
                                                       segments):
    """Each segment's outputs and state at its own last position are
    the segment's alone from zero, the first's from the carried state;
    padding behind the last."""
    ids = list(range(len(lens))) + [len(lens) - 1]
    seg = jnp.asarray(np.repeat(ids, lens + (T - sum(lens),)), jnp.int32)
    q, k, v, g, beta, S0 = _inputs(T, live=sum(lens), seed=3)
    assert _taken(q, k, v, g, beta, S0, seg, segments=segments)
    o, S = delta_rule.gated_delta_chunk(q, k, v, g, beta, S0, seg,
                                        segments=segments)
    o_f, S_f = delta_rule._chunk_fusions(q, k, v, g, beta, S0, seg,
                                         segments=segments)
    assert S.shape == (segments, 2, D, D)
    assert np.abs(S - S_f).max() < 1e-5
    at = 0
    for s, n in enumerate(lens):
        sl = slice(at, at + n)
        o_ref, S_ref = delta_rule.gated_delta_scan(
            q[sl], k[sl], v[sl], g[sl], beta[sl],
            S0 if s == 0 else jnp.zeros_like(S0))
        assert np.abs(o[sl] - o_ref).max() < 1e-5
        assert np.abs(o[sl] - o_f[sl]).max() < 1e-5
        assert np.abs(S[s] - S_ref).max() < 1e-5
        at += n


# ------------------------------------------------------------------ the gate
@pytest.mark.parametrize("why,kw", [
    ("bfloat16", dict(dtype=jnp.bfloat16)),
    ("keys 96 wide", dict(dk=96)),
    ("values 64 wide", dict(dv=64)),
    ("a decay a head", dict(head=True)),
    ("a sub-chunk of 256", dict(sub=256)),
    ("no interpreter", dict(off=True))])
def test_what_the_gate_refuses_takes_the_fusions(kernels, monkeypatch, why,
                                                 kw):
    kw = dict(kw)
    if kw.pop("off", False):
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET")
    head, sub = kw.pop("head", False), kw.pop("sub", 64)
    q, k, v, g, beta, S0 = _inputs(100, **kw)
    if head:
        g = g[..., 0]
    assert not delta_rule.chunk_rule_kernel(q, v, g, sub)
    assert chunk_rule_route(q, v, g) == "fusions" or sub != 64
    assert not _taken(q, k, v, g, beta, S0, sub=sub)
    if sub != 64 or q.dtype != jnp.float32:
        return      # the fusions' own shapes: tests/test_ling_hybrid.py
    o, S = delta_rule.gated_delta_chunk(q, k, v, g, beta, S0)
    o_ref, S_ref = delta_rule.gated_delta_scan(q, k, v, g, beta, S0)
    assert np.abs(o - o_ref).max() < TOL
    assert np.abs(S[0] - S_ref).max() < TOL


@pytest.mark.parametrize("interpreter", [True, False])
@pytest.mark.parametrize("channel", [True, False], ids=["channel", "head"])
def test_the_route_is_what_the_traced_program_took(monkeypatch, interpreter,
                                                   channel):
    # (another test file sets the variable as it is imported)
    if interpreter:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    q, k, v, g, beta, S0 = _inputs(64)
    if not channel:
        g = g[..., 0]
    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, v, g)]
    route = chunk_rule_route(*shapes)
    assert route == ("kernel" if interpreter and channel else "fusions")
    assert _taken(q, k, v, g, beta, S0) == (route == "kernel")


# ---------------------------------------------------------------- the engine
def _ling(**kw):
    import paddle_tpu as pt
    from paddle_tpu.models.ling_hybrid import (LingHybridForCausalLM,
                                               ling_hybrid_tiny)
    pt.seed(0)
    return LingHybridForCausalLM(ling_hybrid_tiny(
        num_attention_heads=2, num_key_value_heads=2, head_dim=D,
        experts_held=4, **kw))


def _olmo():
    import paddle_tpu as pt
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridForCausalLM,
                                               olmo_hybrid_tiny)
    pt.seed(0)
    return OlmoHybridForCausalLM(olmo_hybrid_tiny())


def _serve(model, lengths=(5, 37, 16), **kw):
    kw.setdefault("chunk_prefill_tokens", 16)
    eng = PagedEngine(model, max_slots=3, num_blocks=96, block_size=4,
                      max_blocks_per_seq=24, **kw)
    rng = np.random.default_rng(1)
    for i, n in enumerate(lengths):
        eng.submit(i, rng.integers(1, 256, n).tolist(), max_new_tokens=4)
    eng.run()
    return eng


@pytest.mark.parametrize("mode", ["chunked", "whole"])
def test_a_ling_engine_counts_its_kernel_calls(monkeypatch, mode):
    """Two state layers a prompt call; under the interpreter every one
    of them takes the kernel, and the tokens are those of the engine
    whose prompt calls took the fusions."""
    kw = {} if mode == "chunked" else {"chunk_prefill_tokens": None}
    model = _ling()
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    plain = _serve(model, **kw)
    st = plain.stats
    calls = st["prefill_chunks"] if mode == "chunked" else st["prefills"]
    assert st["chunk_rule_layer_calls"] == 2 * calls > 0
    assert st["chunk_rule_kernel_calls"] == 0
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    eng = _serve(model, **kw)
    st = eng.stats
    assert st["chunk_rule_kernel_calls"] == st["chunk_rule_layer_calls"] \
        == 2 * calls
    assert "chunk_rule_kernel_calls" in eng.health()
    for i in plain.results:
        assert eng.results[i] == plain.results[i]
        assert np.abs(np.asarray(eng.logprobs[i])
                      - np.asarray(plain.logprobs[i])).max() < TOL


def test_an_olmo_engine_never_takes_the_kernel(kernels):
    """A decay a head: three state layers a prompt call, none on the
    kernel, with the interpreter on."""
    st = _serve(_olmo()).stats
    assert st["chunk_rule_layer_calls"] == 3 * st["prefill_chunks"] > 0
    assert st["chunk_rule_kernel_calls"] == 0


def test_an_engine_without_state_layers_has_neither_counter():
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    pt.seed(0)
    st = _serve(LlamaForCausalLM(llama_tiny()), lengths=(5,)).stats
    assert "chunk_rule_layer_calls" not in st
    assert "chunk_rule_kernel_calls" not in st


@pytest.mark.parametrize("name", ["tick", "tick_greedy", "packed", "alone",
                                  "prefill", "host_greedy"])
def test_olmo_programs_do_not_see_the_new_module(monkeypatch, name):
    """``tools/program_text.py``'s check (PR 41: 46 lines equal to the
    parent's) inside one checkout: Olmo-Hybrid's tick and chunk programs
    lower to the same text with ``ops/pallas/delta_chunk.py`` importable
    and with its import failing, so nothing of it is on their path."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import program_text
    finally:
        sys.path.pop(0)
    lowered = lambda: dict(program_text.programs(_olmo(), 0))[name]  # noqa: E731
    with_it = lowered().as_text()
    monkeypatch.setitem(sys.modules, "paddle_tpu.ops.pallas.delta_chunk",
                        None)
    with pytest.raises(ImportError):
        import paddle_tpu.ops.pallas.delta_chunk  # noqa: F401
    assert lowered().as_text() == with_it
