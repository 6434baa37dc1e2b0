"""ISSUE 24: what the device runs inside a tick has names.

Contracts pinned here, at tiny widths and without compiling anything
(the programs are only traced and lowered):

- SCOPES: every op of ``_fused_tick``, ``_fused_tick_greedy``,
  ``_chunk_prefill`` and ``_chunk_prefill_packed`` that a
  ``jax.named_scope`` covers carries a name
  of ``obs.TICK_SCOPES`` in its ``op_name``; each program uses exactly
  the scopes its stages have, and together they use the whole
  vocabulary, so a scope that is renamed or dropped in the program
  fails here before a device trace loses it.
- KERNEL NAMES: the Pallas kernel of the serving route is a
  ``pallas_call`` with a ``name``: what a profiler trace calls it.
- SCOPES CHANGE NOTHING: a program lowered with ``jax.named_scope``
  patched to a null context HERE has the same histogram of opcodes. The
  program has no switch for this.

The kernel routes are taken in interpret mode: only the tracing of the
dispatch glue matters here.
"""
import collections
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.generation import paged
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.ops import paged_cache
from paddle_tpu.utils import observability as obs

CHUNK = 16
ATTN = {"attn"}             # the decode side; a chunk has its own
# the parts only an expert layer and latent attention have (ISSUE 26),
# and the identity part of a router wider than its experts (ISSUE 30)
MOE_MLA = {"router", "experts", "shared_expert", "absorb", "zero_experts"}
# a band-keeping (sliding-window) layer's attention, beside the
# whole-context layers' (ISSUE 32)
WINDOW = {"attn_window", "chunk_attn_window"}
# a linear-attention layer's own parts (ISSUE 38): the short
# convolutions, the recurrence's two forms, the gated per-head norm
LINEAR = {"conv", "delta_state", "chunk_delta_state", "gate_norm"}
# what only Ling 3.0's layers have (ISSUE 41): the full-rank gate of a
# decay a key channel, a gate a head on latent attention's output
KDA = {"decay_gate", "head_gate"}
# what only Laguna's layers have (ISSUE 46): a gate a query head on a
# K/V attention's result
GQA_GATE = {"attn_gate"}
LLAMA = set(obs.TICK_SCOPES) - MOE_MLA - WINDOW - LINEAR - KDA - GQA_GATE
PROGRAMS = {
    "_fused_tick": LLAMA - {"chunk_attn"},
    "_fused_tick_greedy": LLAMA - {"chunk_attn"},
    "_chunk_prefill": LLAMA - ATTN - {"patch"},
    "_chunk_prefill_packed": LLAMA - ATTN,
}
# DeepSeek-V3's block, both kinds of layer. A chunk attends in the
# expanded form, so it has no `absorb`
DEEPSEEK = {
    "_fused_tick_greedy": set(obs.TICK_SCOPES) - WINDOW - LINEAR - KDA
    - GQA_GATE - {"chunk_attn", "zero_experts"},
    "_chunk_prefill": set(obs.TICK_SCOPES) - WINDOW - LINEAR - KDA
    - GQA_GATE - ATTN - {"patch", "absorb", "zero_experts"},
}
DEEPSEEK["_chunk_prefill_packed"] = DEEPSEEK["_chunk_prefill"] | {"patch"}
# LongCat-Flash's double layer: zero-compute experts, no shared expert
LONGCAT = {k: v - {"shared_expert"} | {"zero_experts"}
           for k, v in DEEPSEEK.items()}
# MiMo-V2's two layer kinds: K/V attention (no `absorb`), full layers
# under `attn` / `chunk_attn`, window layers under the scopes of their
# own, experts without a shared one
MIMO = {
    "_fused_tick_greedy": LLAMA - {"chunk_attn"} | {
        "router", "experts", "attn_window"},
    "_chunk_prefill": LLAMA - ATTN - {"patch"} | {
        "router", "experts", "chunk_attn_window"},
}
MIMO["_chunk_prefill_packed"] = MIMO["_chunk_prefill"] | {"patch"}
# Olmo-Hybrid's two layer kinds: the full layers under `attn` /
# `chunk_attn` as ever, the linear layers' decode step under
# `delta_state` and their chunkwise form under `chunk_delta_state`
HYBRID = {
    "_fused_tick_greedy": LLAMA - {"chunk_attn"} | LINEAR - {
        "chunk_delta_state"},
    "_chunk_prefill": LLAMA - ATTN - {"patch"} | LINEAR - {"delta_state"},
}
HYBRID["_chunk_prefill_packed"] = HYBRID["_chunk_prefill"] | {"patch"}
# Ling 3.0: Kimi-Delta-Attention layers (Olmo-Hybrid's scopes and the
# decay gate's), gated latent layers (DeepSeek's and the head gate's),
# experts with a shared one. A chunk attends in the expanded form
LING = {
    "_fused_tick_greedy": LLAMA - {"chunk_attn"} | KDA | LINEAR - {
        "chunk_delta_state"} | {"router", "experts", "shared_expert",
                                "absorb"},
    "_chunk_prefill": LLAMA - ATTN - {"patch"} | KDA | LINEAR - {
        "delta_state"} | {"router", "experts", "shared_expert"},
}
LING["_chunk_prefill_packed"] = LING["_chunk_prefill"] | {"patch"}
# Laguna: MiMo-V2's two kinds of K/V attention, each head's result gated,
# experts WITH a shared one
LAGUNA = {k: v | GQA_GATE | {"shared_expert"} for k, v in MIMO.items()}


@pytest.fixture(scope="module")
def engine():
    eng = PagedEngine(LlamaForCausalLM(llama_tiny()), max_slots=4,
                      num_blocks=32, block_size=8, max_blocks_per_seq=8,
                      chunk_prefill_tokens=CHUNK)
    eng._refresh_dev()          # the tick's device state, nothing run
    return eng


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


def _args(eng, program):
    if program == "_chunk_prefill_packed":
        words = 3 * CHUNK + eng._pack_segments * (eng.M + paged._SEG_WORDS)
        return (eng.params, eng.pools, eng.seen,
                jnp.zeros((words,), jnp.int32)), {}
    if program != "_chunk_prefill":
        return (eng.params, eng.pools, eng.seen, eng._dev), {}
    return ((eng.params, eng.pools, jnp.zeros((eng.M,), jnp.int32),
             jnp.zeros((1, CHUNK), jnp.int32), np.int32(0),
             np.int32(CHUNK), jnp.zeros((2,), jnp.uint32), np.float32(0.8),
             np.int32(20), np.float32(0.95), np.float32(1.1), eng.seen[0]),
            {"bucket": CHUNK})


def _trace(eng, program):
    """A fresh trace of the program (a new callable, so no cache of an
    earlier trace answers for it)."""
    fn = getattr(eng, program)
    args, kw = _args(eng, program)
    return jax.jit(lambda *a: fn(*a, **kw)).trace(*args)


def _scope(op_name):
    found = [p for p in op_name.split("/") if p in obs.TICK_SCOPES]
    return found[-1] if found else None


@pytest.fixture(scope="module")
def deepseek_engine():
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                               deepseek_v2_tiny)
    cfg = deepseek_v2_tiny(num_hidden_layers=2, num_experts=8,
                           scoring="sigmoid", experts_held=4)
    eng = PagedEngine(DeepseekV2ForCausalLM(cfg), max_slots=4,
                      num_blocks=32, block_size=8, max_blocks_per_seq=8,
                      chunk_prefill_tokens=CHUNK)
    eng._refresh_dev()
    return eng


@pytest.mark.parametrize("program", sorted(DEEPSEEK))
def test_an_expert_and_latent_model_carries_its_scopes(deepseek_engine,
                                                       kernels, program):
    assert deepseek_engine.decode_route() == "ragged"
    _, scopes = _lowered(deepseek_engine, program)
    assert set(scopes) - {None} == DEEPSEEK[program]
    assert scopes[None] < 0.1 * sum(scopes.values()), scopes


@pytest.fixture(scope="module")
def longcat_engine():
    from paddle_tpu.models.longcat_flash import (LongcatFlashForCausalLM,
                                                 longcat_flash_tiny)
    cfg = longcat_flash_tiny(num_hidden_layers=1, experts_held=4)
    eng = PagedEngine(LongcatFlashForCausalLM(cfg), max_slots=4,
                      num_blocks=32, block_size=8, max_blocks_per_seq=8,
                      chunk_prefill_tokens=CHUNK)
    eng._refresh_dev()
    return eng


@pytest.mark.parametrize("program", sorted(LONGCAT))
def test_a_double_layer_carries_its_scopes_and_two_kernels(longcat_engine,
                                                           kernels, program):
    assert longcat_engine.decode_route() == "ragged"
    _, scopes = _lowered(longcat_engine, program)
    assert set(scopes) - {None} == LONGCAT[program]
    assert scopes[None] < 0.1 * sum(scopes.values()), scopes
    if not program.startswith("_chunk_prefill"):
        # one latent kernel an attention, and the held experts' kernel
        # (4 rows are few tokens: ISSUE 33) where their result joins
        names = _kernel_names(_trace(longcat_engine, program).jaxpr.jaxpr)
        assert sorted(names) == ["expert_share_mlp"] \
            + ["ragged_paged_attention"] * 2


@pytest.fixture(scope="module")
def mimo_engine():
    from paddle_tpu.models.mimo_v2 import MiMoV2ForCausalLM, mimo_v2_tiny
    eng = PagedEngine(MiMoV2ForCausalLM(mimo_v2_tiny(experts_held=4)),
                      max_slots=4, num_blocks=32, block_size=8,
                      max_blocks_per_seq=8, chunk_prefill_tokens=CHUNK)
    eng._refresh_dev()
    return eng


@pytest.mark.parametrize("program", sorted(MIMO))
def test_window_and_full_layers_carry_scopes_of_their_own(mimo_engine,
                                                          kernels, program):
    """One full layer and two window layers: the window layers' kernel
    calls (and their chunk attention over the ring) are named apart
    from the full layer's, so a device trace can tell the band's read
    from the whole context's."""
    assert mimo_engine.decode_route() == "ragged"
    _, scopes = _lowered(mimo_engine, program)
    assert set(scopes) - {None} == MIMO[program]
    assert scopes[None] < 0.1 * sum(scopes.values()), scopes
    if not program.startswith("_chunk_prefill"):
        names = _kernel_names(_trace(mimo_engine, program).jaxpr.jaxpr)
        # the dense leading layer has no experts; the two others do
        assert names == ["ragged_paged_attention"] \
            + ["ragged_paged_attention", "expert_share_mlp"] * 2


@pytest.fixture(scope="module")
def laguna_engine():
    from paddle_tpu.models.laguna import LagunaForCausalLM, laguna_tiny
    eng = PagedEngine(LagunaForCausalLM(laguna_tiny(experts_held=4)),
                      max_slots=4, num_blocks=32, block_size=8,
                      max_blocks_per_seq=8, chunk_prefill_tokens=CHUNK)
    eng._refresh_dev()
    return eng


@pytest.mark.parametrize("program", sorted(LAGUNA))
def test_gated_heads_over_shared_kv_heads_carry_their_scopes(
        laguna_engine, kernels, program):
    """One full layer of 6 query heads and two window layers of 10 over
    the same 2 kv heads (ISSUE 46): MiMo-V2's scopes where the work is
    the same, the head gate under a scope of its own, the shared expert
    under the expert families'; a tick's kernel calls are one ragged
    call a layer, at its own query group, and the two expert layers'."""
    assert laguna_engine.decode_route() == "ragged"
    _, scopes = _lowered(laguna_engine, program)
    assert set(scopes) - {None} == LAGUNA[program]
    assert scopes[None] < 0.1 * sum(scopes.values()), scopes
    if not program.startswith("_chunk_prefill"):
        jaxpr = _trace(laguna_engine, program).jaxpr.jaxpr
        assert _kernel_calls(jaxpr) == [
            ("ragged_paged_attention", "attn"),
            ("ragged_paged_attention", "attn_window"),
            ("expert_share_mlp", "experts"),
            ("ragged_paged_attention", "attn_window"),
            ("expert_share_mlp", "experts")]


@pytest.fixture(scope="module")
def hybrid_engine():
    from paddle_tpu.models.olmo_hybrid import (OlmoHybridForCausalLM,
                                               olmo_hybrid_tiny)
    eng = PagedEngine(OlmoHybridForCausalLM(olmo_hybrid_tiny()),
                      max_slots=4, num_blocks=32, block_size=8,
                      max_blocks_per_seq=8, chunk_prefill_tokens=CHUNK)
    eng._refresh_dev()
    return eng


@pytest.mark.parametrize("program", sorted(HYBRID))
def test_linear_attention_layers_carry_scopes_of_their_own(hybrid_engine,
                                                           kernels, program):
    """Three linear layers and one full layer: the recurrence, the
    convolutions and the gated norm are named apart from the full
    layer's attention, so a device trace can tell the state's read and
    write from the pages'; a tick's kernel calls are the three linear
    layers' state steps (ISSUE 39: the tiny twin's state is whole
    tiles), each under ``delta_state`` where the benchmark's readers
    look for its time, and the full layer's attention under ``attn``."""
    assert hybrid_engine.decode_route() == "ragged"
    _, scopes = _lowered(hybrid_engine, program)
    assert set(scopes) - {None} == HYBRID[program]
    assert scopes[None] < 0.1 * sum(scopes.values()), scopes
    # the recurrence is no afterthought of another scope: the chunkwise
    # form by its share of the program's ops (the full layer's chunk
    # kernel is one of them), the decode step by its kernel's call
    if program.startswith("_chunk_prefill"):
        _, scopes = _lowered(hybrid_engine, program, kernel_bodies=False)
        assert scopes["chunk_delta_state"] > scopes["chunk_attn"]
    else:
        jaxpr = _trace(hybrid_engine, program).jaxpr.jaxpr
        assert _kernel_calls(jaxpr) == \
            [("delta_state_step", "delta_state")] * 3 \
            + [("ragged_paged_attention", "attn")]


@pytest.fixture(scope="module")
def ling_engine():
    from paddle_tpu.models.ling_hybrid import (LingHybridForCausalLM,
                                               ling_hybrid_tiny)
    eng = PagedEngine(LingHybridForCausalLM(ling_hybrid_tiny(
        experts_held=4)), max_slots=4, num_blocks=32, block_size=8,
        max_blocks_per_seq=8, chunk_prefill_tokens=CHUNK)
    eng._refresh_dev()
    return eng


@pytest.mark.parametrize("program", sorted(LING))
def test_ling_layers_carry_the_scopes_of_both_kinds(ling_engine, kernels,
                                                    program):
    """Two Kimi-Delta-Attention layers and a gated latent one over a
    dense FFN and two expert layers (ISSUE 41): the scopes that exist
    keep their names where the work is the same, and only the decay
    gate and the head gate are new; a tick's kernel calls are the two
    state steps at a decay a CHANNEL, the latent ragged call and the two
    expert layers' kernels, each under the scope the readers look in."""
    assert ling_engine.decode_route() == "ragged"
    _, scopes = _lowered(ling_engine, program)
    assert set(scopes) - {None} == LING[program]
    assert scopes[None] < 0.1 * sum(scopes.values()), scopes
    if program.startswith("_chunk_prefill"):
        assert scopes["chunk_delta_state"] > scopes["chunk_attn"]
    else:
        jaxpr = _trace(ling_engine, program).jaxpr.jaxpr
        assert _kernel_calls(jaxpr) == [
            ("delta_state_step_channel", "delta_state"),
            ("delta_state_step_channel", "delta_state"),
            ("expert_share_mlp", "experts"),
            ("ragged_paged_attention", "attn"),
            ("expert_share_mlp", "experts")]


@pytest.mark.parametrize("interpreted", [True, False],
                         ids=["interpreter", "no-kernel"])
@pytest.mark.parametrize("name", [
    "engine", "deepseek_engine", "mimo_engine", "laguna_engine",
    "hybrid_engine", "ling_engine"])
def test_the_engine_counts_the_route_the_chunk_program_traced(
        request, monkeypatch, name, interpreted):
    """``chunk_attn_kernel_calls`` (ISSUE 48) is counted on the host from
    ``PagedEngine.chunk_attn_routes``: what it says of each layer is
    what the traced ``_chunk_prefill`` holds, a chunk kernel's call
    under the layer's own scope or none."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as ragged
    if interpreted:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    else:   # (tests/test_flash_segments.py sets it for a whole worker)
        monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    eng = request.getfixturevalue(name)
    jitted = (ragged._attend_chunk, paged_cache.paged_chunk_attention)
    for fn in jitted:       # each keeps what it traced under the other
        fn.clear_cache()
    try:
        calls = _kernel_calls(_trace(eng, "_chunk_prefill").jaxpr.jaxpr)
        routes = eng.chunk_attn_routes()
    finally:
        for fn in jitted:
            fn.clear_cache()
    assert [scope for kernel, scope in calls
            if kernel == "chunk_paged_attention"] == [
        "chunk_attn_window" if layer.window else "chunk_attn"
        for layer, route in zip(eng._layout, routes) if route == "kernel"]
    walked = [r for r in routes if r is not None]
    assert len(routes) == len(eng._layout)
    assert set(walked) <= ({"kernel"} if interpreted else {"walk"})
    # the latent families expand their rows and walk nothing
    assert bool(walked) == (name not in ("deepseek_engine", "ling_engine"))


def test_the_programs_use_the_whole_vocabulary():
    assert set().union(*PROGRAMS.values(), *DEEPSEEK.values(),
                       *LONGCAT.values(), *MIMO.values(),
                       *HYBRID.values(), *LING.values(),
                       *LAGUNA.values()) \
        == set(obs.TICK_SCOPES)
    assert len(set(obs.TICK_SCOPES)) == len(obs.TICK_SCOPES)
    assert not set(obs.TICK_SCOPES) & set(obs.TICK_PHASES
                                          + obs.LOOP_PHASES)


def _kernel_calls(jaxpr, above=""):
    """(the kernel's ``name``, the scope of ``obs.TICK_SCOPES`` it sits
    under) of each ``pallas_call``, in program order."""
    out = []
    for eqn in jaxpr.eqns:
        stack = f"{above}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"], _scope(stack)))
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                out += _kernel_calls(getattr(inner, "jaxpr", inner), stack)
    return out


def _kernel_names(jaxpr):
    return [name for name, _ in _kernel_calls(jaxpr)]


@pytest.mark.parametrize("route, name", [
    ("ragged", "ragged_paged_attention")])
def test_serving_kernels_are_named(engine, kernels, route, name):
    assert engine.decode_route() == route
    names = _kernel_names(_trace(engine, "_fused_tick_greedy").jaxpr.jaxpr)
    layers = engine.model.config.num_hidden_layers
    assert names == [name] * layers


def _lowered(eng, program, kernel_bodies=True):
    """(opcode histogram, how many ops each scope covers; None for the
    ops under no scope) of a fresh trace. Without ``kernel_bodies`` a
    kernel's call is ONE op of its scope: the body the interpreter
    inlines under the call's own name is the interpreter's, not the
    program's."""
    low = _trace(eng, program).lower()
    names = re.findall(r'loc\("(jit\([^"]*)"', low.as_text(debug_info=True))
    call = "/pallas_call"
    bodies = tuple({n[:-len(call)] + "/" for n in names if n.endswith(call)
                    and not n[:-len(call)].endswith(")")})
    if not kernel_bodies:
        names = [n for n in names if n.endswith(call)
                 or not n.startswith(bodies)]
    return (collections.Counter(re.findall(
        r"\b(?:stablehlo|chlo|func)\.[\w.]+", low.as_text())),
        collections.Counter(_scope(n) for n in names))


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_program_ops_carry_their_scopes(engine, kernels, program):
    _, scopes = _lowered(engine, program)
    assert set(scopes) - {None} == PROGRAMS[program]
    # what no scope covers is index plumbing between the stages
    assert scopes[None] < 0.1 * sum(scopes.values()), scopes


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_scopes_do_not_change_the_program(engine, kernels, monkeypatch,
                                          program):
    from paddle_tpu.ops.pallas import ragged_paged_attention as ragged
    scoped, _ = _lowered(engine, program)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    # the kernel's wrapper and the chunk programs' write and attentions
    # are jitted: each keeps the jaxpr it traced with the scopes on, and
    # would keep the one traced here without them
    jitted = (ragged._attend, ragged._attend_chunk,
              paged_cache.paged_prefill_write,
              paged_cache.paged_chunk_attention,
              paged_cache.paged_packed_attention)
    for fn in jitted:
        fn.clear_cache()
    try:
        plain, scopes = _lowered(engine, program)
    finally:
        for fn in jitted:
            fn.clear_cache()
    assert set(scopes) == {None}    # the patch took: no scope was traced
    assert sum(scoped.values()) > 100
    assert plain == scoped
