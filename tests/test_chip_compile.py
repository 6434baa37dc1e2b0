"""The serving path's decode-attention kernel through the chip's own
compiler, at the widths it is served at, with no chip attached.

The interpreter accepts slices, tilings and VMEM sizes that Mosaic
refuses; the TPU compiler is installed here and compiles for a v5e that
is described, not attached (nothing runs: no result, no time). All such
compiles live in THIS file, inside fixtures: one process may hold the
TPU library, and every xdist worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without the chip: the next
    run would warn and compile again."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("R,M,B,kvh,group,d,P,T,window", [
    (8, 128, 16, 4, 7, 128, 2049, 1, None),     # qwen2-7b-d16, a tick
    (8, 128, 16, 4, 7, 128, 2049, 4, None),     # its speculative verify
    (8, 128, 16, 4, 7, 128, 2049, 1, 1024),     # a sliding window
    (32, 128, 16, 2, 6, 128, 4096, 1, None),    # qwen2-1.5b
    (8, 32, 8, 2, 4, 256, 513, 1, None),        # 8-token pages, head 256
])
def test_ragged_kernel_compiles_for_a_v5e(one_chip, no_persistent_cache,
                                          monkeypatch, R, M, B, kvh, group,
                                          d, P, T, window):
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention_pallas
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    h = kvh * group
    q = arr((R, h, d) if T == 1 else (R, T, h, d))
    compiled = jax.jit(
        lambda q, kp, vp, tbl, lens: ragged_paged_attention_pallas(
            q, kp, vp, tbl, lens, d ** -0.5, kvh, window=window)).lower(
        q, arr((P, B, kvh * d)), arr((P, B, kvh * d)),
        arr((R, M), jnp.int32), arr((R,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("R,M,P,kvh,window,ring,T", [
    (64, 128, 8193, 4, None, False, 1),     # a full layer's tick
    (64, 25, 1601, 8, 128, True, 1),        # a window layer's, its ring
    (64, 25, 1601, 8, 128, True, 3),        # and a speculative verify
])
def test_unequal_head_kernel_compiles_for_a_v5e(
        one_chip, no_persistent_cache, monkeypatch, R, M, P, kvh, window,
        ring, T):
    """MiMo-V2's two layer kinds: 64 query heads, key heads of 192
    columns (read as aligned 256-column spans of the page) and value
    heads of 128; a window layer adds its sink and walks its band's
    ring of 25 pages a slot."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention_pallas
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q = arr((R, 64, 192) if T == 1 else (R, T, 64, 192))
    sink = arr((64,), jnp.float32) if ring else None
    compiled = jax.jit(
        lambda q, kp, vp, tbl, lens, sink: ragged_paged_attention_pallas(
            q, kp, vp, tbl, lens, 192 ** -0.5, kvh, window=window,
            sink=sink, ring=ring)).lower(
        q, arr((P, 16, kvh * 192)), arr((P, 16, kvh * 128)),
        arr((R, M), jnp.int32), arr((R,), jnp.int32), sink).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("M,P,group,window,ring", [
    (448, 28673, 6, None, False),   # a full layer's tick: 48 query heads
    (97, 6209, 9, 512, True),       # a window layer's: 72, its ring
    (65, 4161, 9, 512, True),       # the ring at chunks of 512
])
def test_laguna_groups_compile_for_a_v5e(one_chip, no_persistent_cache,
                                         monkeypatch, M, P, group, window,
                                         ring):
    """Laguna-S-2.1's two layer kinds (ISSUE 46): 8 kv heads of 128
    columns under query groups of 6 and of 9 (no power of two), a table
    of 448 pages a row, a band of 512 positions over a ring of 97."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention_pallas
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda q, kp, vp, tbl, lens: ragged_paged_attention_pallas(
            q, kp, vp, tbl, lens, 128 ** -0.5, 8, window=window,
            ring=ring)).lower(
        arr((64, 8 * group, 128)), arr((P, 16, 1024)), arr((P, 16, 1024)),
        arr((64, M), jnp.int32), arr((64,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("M,P,h,kvh,dk,dv,chunk,window,ring,sink", [
    (448, 28673, 48, 8, 128, 128, 1024, None, False, False),   # laguna
    (97, 6209, 72, 8, 128, 128, 1024, 512, True, False),
    (128, 8193, 64, 4, 192, 128, 256, None, False, False),     # mimo
    (25, 1601, 64, 8, 192, 128, 256, 128, True, True),
    (128, 2049, 28, 4, 128, 128, 256, None, False, False),     # qwen2
])
def test_the_chunk_walk_compiles_for_a_v5e_with_no_slot_long_score(
        one_chip, no_persistent_cache, M, P, h, kvh, dk, dv, chunk, window,
        ring, sink):
    """ISSUE 46: a prompt chunk's attention at the cells' shapes holds no
    float32 array with the slot's length behind the chunk's queries: the
    widest score is [kv heads, group, chunk, one run of 32 pages]."""
    from paddle_tpu.ops.paged_cache import (CHUNK_RUN_PAGES, PagedKV,
                                            _window_scope,
                                            paged_chunk_attention_walk)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, kp, vp, tbl, lens, pos, sink):
        pk = PagedKV(kp, vp, tbl, lens, kvh, ring, "chunk")
        with jax.named_scope(_window_scope("chunk_attn", ring)):
            return paged_chunk_attention_walk(q, pk, pos, window, sink)

    text = jax.jit(attend).lower(
        arr((1, chunk, h, dk)), arr((P, 16, kvh * dk)),
        arr((P, 16, kvh * dv)), arr((1, M), jnp.int32),
        arr((1,), jnp.int32), arr((1, chunk), jnp.int32),
        arr((h,), jnp.float32) if sink else None).compile().as_text()
    run = min(M, CHUNK_RUN_PAGES) * 16
    assert f"f32[{kvh},{h // kvh},{chunk},{run}]" in text
    assert "while(" in text or ring
    if M * 16 > run:
        assert f",{M * 16}]" not in text.replace("s32[", "")


@pytest.mark.parametrize("M,P,h,kvh,chunk,window,ring", [
    (448, 28673, 48, 8, 1024, None, False),     # laguna, a full layer
    (97, 6209, 72, 8, 1024, 512, True),         # a window layer, its ring
    (128, 2049, 28, 4, 256, None, False),       # qwen2-7b
    (128, 2049, 30, 30, 256, None, False),      # olmo-hybrid, groups of 1
])
def test_the_chunk_kernel_compiles_for_a_v5e_with_no_score_in_hbm(
        one_chip, no_persistent_cache, monkeypatch, M, P, h, kvh, chunk,
        window, ring):
    """ISSUE 48: where the route is "kernel" a prompt chunk's attention
    at the cells' shapes (heads of 128 columns, pages of 16) is ONE
    Mosaic call a layer: no loop of XLA ops, no float32 array with the
    chunk's queries behind the heads, no copy of a pool."""
    import paddle_tpu.ops.pallas as pallas
    from paddle_tpu.ops.paged_cache import (PagedKV, chunk_attn_route,
                                            paged_chunk_attention)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    # the gate asks jax for its backend, which is the CPU here
    monkeypatch.setattr(pallas, "tpu_backend", lambda: True)
    paged_chunk_attention.clear_cache()     # traced for the walk above

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(q, kp, vp, tbl, lens, pos):
        pk = PagedKV(kp, vp, tbl, lens, kvh, ring, "chunk")
        assert chunk_attn_route(q, kp, kvh) == "kernel"
        return paged_chunk_attention(q, pk, pos, window=window)

    try:
        text = jax.jit(attend).lower(
            arr((1, chunk, h, 128)), arr((P, 16, kvh * 128)),
            arr((P, 16, kvh * 128)), arr((1, M), jnp.int32),
            arr((1,), jnp.int32), arr((1, chunk), jnp.int32)) \
            .compile().as_text()
    finally:
        paged_chunk_attention.clear_cache()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "while(" not in text
    assert f"f32[{kvh},{h // kvh},{chunk}," not in text
    pools = _pool_instructions(text, P)
    assert sorted(op for _, op in pools.values()) == ["parameter"] * 2, pools


@pytest.mark.parametrize("R,T", [(64, 1), (64, 2)])
def test_latent_kernel_compiles_for_a_v5e(one_chip, no_persistent_cache,
                                          monkeypatch, R, T):
    """The latent mode at GigaChat3.1 / DeepSeek-V3's geometry: 64 query
    heads over one 640-column row a token (512 latent + 64 roped + 64
    zeros), values the first 512 columns, 8193 pages of 16."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention_pallas
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q = arr((R, 64, 640) if T == 1 else (R, T, 64, 640))
    compiled = jax.jit(
        lambda q, kp, tbl, lens: ragged_paged_attention_pallas(
            q, kp, None, tbl, lens, 192 ** -0.5, 1, v_width=512)).lower(
        q, arr((8193, 16, 640)), arr((R, 128), jnp.int32),
        arr((R,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("h,T", [
    (7168, 64),     # gigachat3.1-702b-ep16-d6, a tick's 64 rows
    (6144, 64),     # longcat-flash-omni-ep32-d4
    (4096, 64),     # mimo-v2.5-ep16-d7
    (7168, 128),    # the most rows that take the kernel
])
def test_expert_kernel_reads_the_stacked_weights_as_they_lie(
        one_chip, no_persistent_cache, monkeypatch, h, T):
    """One rank's 16 experts of width 2048 through ``ExpertShareMLP``
    at the three expert configurations' hidden sizes: the held experts'
    part compiles to ONE ``tpu_custom_call`` whose weight operands are
    the program's parameters themselves. No copy, transpose or reshape
    of a stack (a "view" of a pool whose tiling differed cost a copy of
    it every tick: PERF.md section 6, PR 27), and no product over one."""
    import re
    import paddle_tpu.ops.pallas as pallas
    from paddle_tpu.parallel.moe import ExpertShareMLP
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    # the gate asks jax for its backend, which is the CPU here
    monkeypatch.setattr(pallas, "tpu_backend", lambda: True)
    n, m = 16, 2048
    layer = ExpertShareMLP.__new__(ExpertShareMLP)      # shapes only
    layer.first_expert, layer.experts_held, layer.zero_experts = 16, n, 0

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def routed(xt, ids, gates, w_gate, w_up, w_down):
        layer.__dict__.update(w_gate=w_gate, w_up=w_up, w_down=w_down)
        return ExpertShareMLP.routed(layer, xt, ids, gates)

    text = jax.jit(routed).lower(
        arr((T, h)), arr((T, 8), jnp.int32), arr((T, 8), jnp.float32),
        arr((n, h, m)), arr((n, h, m)), arr((n, m, h))).compile().as_text()
    stacks = re.findall(
        rf"%(\S+) = \w+\[{n},(?:{h},{m}|{m},{h})\]\S* ([\w-]+)\(", text)
    assert sorted(op for _, op in stacks) == ["parameter"] * 3, stacks
    call, = re.findall(r"custom-call\(([^)]*)\), "
                       r'custom_call_target="tpu_custom_call"', text)
    operands = set(re.findall(r"%([\w.]+)", call))
    assert {name for name, _ in stacks} <= operands


@pytest.mark.parametrize("T,h,m,n,k", [
    (1024, 3072, 1024, 16, 10),     # laguna-s-2.1-ep16-d9, a prompt chunk
    (256, 2560, 768, 32, 8),        # ling-3.0-flash-ep16-d14
    (256, 4096, 2048, 16, 8),       # mimo-v2.5-ep16-d7
    (256, 7168, 2048, 16, 8),       # gigachat3.1-702b-ep16-d6
    (256, 6144, 2048, 16, 12),      # longcat-flash-omni-ep32-d4
])
def test_grouped_product_reads_the_stacked_weights_as_they_lie(
        one_chip, no_persistent_cache, monkeypatch, T, h, m, n, k):
    """A prompt call's positions through ``ExpertShareMLP.routed`` at
    the five expert configurations' widths: the held experts' part
    compiles to ONE ``tpu_custom_call`` inside the loop over passes of
    the sorted pairs, whose weight operands are the program's parameters
    as the loop carries them: no copy, transpose or reshape of a stack,
    and no product over one (the dynamic row update of the float32
    result and the VMEM it keeps are what the interpreter cannot
    refuse)."""
    import re
    import paddle_tpu.ops.pallas as pallas
    from paddle_tpu.parallel.moe import ExpertShareMLP
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    # the gate asks jax for its backend, which is the CPU here
    monkeypatch.setattr(pallas, "tpu_backend", lambda: True)
    layer = ExpertShareMLP.__new__(ExpertShareMLP)      # shapes only
    layer.first_expert, layer.experts_held, layer.zero_experts = 16, n, 0

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def routed(xt, ids, gates, w_gate, w_up, w_down):
        layer.__dict__.update(w_gate=w_gate, w_up=w_up, w_down=w_down)
        return ExpertShareMLP.routed(layer, xt, ids, gates)

    text = jax.jit(routed).lower(
        arr((T, h)), arr((T, k), jnp.int32), arr((T, k), jnp.float32),
        arr((n, h, m)), arr((n, h, m)), arr((n, m, h))).compile().as_text()
    stacks = re.findall(
        rf"%(\S+) = \w+\[{n},(?:{h},{m}|{m},{h})\]\S* ([\w-]+)\(", text)
    assert sorted(op for _, op in stacks) == \
        ["get-tuple-element"] * 3 + ["parameter"] * 3, stacks
    call, = re.findall(r"custom-call\(([^)]*)\), "
                       r'custom_call_target="tpu_custom_call"', text)
    operands = set(re.findall(r"%([\w.\-]+)", call))
    assert {name for name, op in stacks
            if op == "get-tuple-element"} <= operands


_COPIES = ("reshape", "copy", "copy-start", "transpose")


def _pool_instructions(text, P):
    """``{name: (type with layout, opcode)}`` of every instruction of a
    compiled module whose result is shaped like a pool of ``P`` pages."""
    import re
    found = re.findall(
        rf"%(\S+) = (\w+\[{P},16,[\d,]*\]\{{[^}}]*\}}) ([\w-]+)\(", text)
    return {name: (kind, op) for name, kind, op in found}


@pytest.mark.parametrize("P,R,h,kvh,d,T,v_width", [
    (2049, 8, 28, 4, 128, 1, None),     # qwen2-7b-d16, a tick
    (2049, 8, 28, 4, 128, 4, None),     # its speculative verify
    (8193, 64, 64, 1, 640, 1, 512),     # gigachat3.1's latent row
])
def test_write_and_attend_compile_with_no_pool_sized_copy(
        one_chip, no_persistent_cache, monkeypatch, P, R, h, kvh, d, T,
        v_width):
    """One layer's part of a tick, ``paged_decode_write`` and the decode
    attention over DONATED pools: the pool the program is handed is the
    pool the write updates in place and the kernel reads. A [P, B, kvh,
    d] pool viewed [P, B, kvh*d] for the kernel changed its tiling
    (T(4,128) -> T(8,128)): a copy of every pool in every tick, a fifth
    of the Qwen cells' tick (PERF.md section 6, PR 27)."""
    import re
    import paddle_tpu.ops.pallas as pallas
    from paddle_tpu.ops.paged_cache import (PagedKV, paged_decode_attention,
                                            paged_decode_write,
                                            paged_latent_attention)
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    # the route asks jax for its backend, which is the CPU here
    monkeypatch.setattr(pallas, "tpu_backend", lambda: True)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    latent = v_width is not None

    def layer(pools, tbl, lens, q, new):
        pk = PagedKV(pools[0], None if latent else pools[1], tbl, lens,
                     kvh)
        pk = paged_decode_write(pk, *new)
        out = paged_latent_attention(q, pk, v_width, 192 ** -0.5) \
            if latent else paged_decode_attention(q, pk)
        return out, pk.pool

    n = 1 if latent else 2
    text = jax.jit(layer, donate_argnums=0).lower(
        (arr((P, 16, kvh * d)),) * n, arr((R, 128), jnp.int32),
        arr((R,), jnp.int32), arr((R, T, h, d)),
        (arr((R, T, kvh, d)),) * n).compile().as_text()

    pools = _pool_instructions(text, P)
    copies = {name: op for name, (_, op) in pools.items() if op in _COPIES}
    assert not copies, f"pool-sized copies: {copies}"
    params = {kind for kind, op in pools.values() if op == "parameter"}
    call = re.search(r"custom-call\(([^)]*)\), "
                     r'custom_call_target="tpu_custom_call"', text)
    assert call, "no tpu_custom_call in the compiled module"
    read = {pools[name.strip().lstrip("%")][0]
            for name in call.group(1).split(",")
            if name.strip().lstrip("%") in pools}
    assert read and read == params and len(params) == 1, (read, params)


@pytest.mark.parametrize("R,H,dk,dv,channel", [
    (32, 30, 96, 192, False),       # olmo-hybrid-7b-d16
    (128, 32, 128, 128, True),      # ling-3.0-flash-ep16-d14
], ids=["olmo", "ling"])
@pytest.mark.parametrize("route", ["fusions", "kernel"])
def test_the_state_step_compiles_lane_dense_for_a_v5e(one_chip,
                                                      no_persistent_cache,
                                                      monkeypatch, route,
                                                      R, H, dk, dv, channel):
    """ISSUE 38: a linear-attention layer's decode step at the hybrid
    cell's shapes (32 rows, 30 heads, keys 96, values 192). The state is
    stored two heads to a row (384 = 3 lane tiles): a 192-wide last axis
    alone is padded to 256 in the chip's memory, a third more to hold
    and to move. ISSUE 41: and at Ling's (128 rows, 32 heads of 128 x
    128, a head a lane tile) with a decay a key CHANNEL, a column beside
    k and q. Nothing the size of the state is materialised beside
    it, by either route, and the donated state is updated in place.
    ``fusions`` (the jnp body, the gate held shut): two passes, one read
    for ``S^T k`` and ``S^T q``, one read and write. ``kernel`` (ISSUE
    39, what the gate chooses on the chip): ONE custom call that reads
    the state and writes it, aliased to its operand, and no fusion and
    no copy of the state's shape."""
    import re
    import paddle_tpu.ops.pallas as pallas
    from paddle_tpu.ops import delta_rule
    from paddle_tpu.ops.pallas import delta_state
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    hp = delta_rule.state_lane_heads(H, dv)
    assert hp == (1 if channel else 2)

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    S = arr((R, H // hp, dk, hp * dv))
    if route == "kernel":
        monkeypatch.setattr(pallas, "tpu_backend", lambda: True)
        assert delta_state.use_state_kernel(S)
        # whole slots a grid step: 4.4 (4.2) MB of traffic each, 5 us at
        # the chip's bandwidth against the microsecond a step costs
        assert delta_state._rows_per_step(R, S.size * 4 // R) \
            * 2 * S.size * 4 // R >= 4 << 20
    else:
        monkeypatch.setattr(delta_state, "use_state_kernel",
                            lambda S: False)
    compiled = jax.jit(lambda *a: delta_rule.delta_state_step(*a),
                       donate_argnums=(0,)).lower(
        S, arr((R, H, dk)), arr((R, H, dk)), arr((R, H, dv)),
        arr((R, H, dk) if channel else (R, H)), arr((R, H)),
        arr((R,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    state = R * H * dk * dv * 4
    # the state as it is, unpadded, updated in place, no scratch copy
    assert state <= mem.argument_size_in_bytes < 1.05 * state
    assert mem.alias_size_in_bytes == state
    assert mem.temp_size_in_bytes < 0.05 * state
    text = compiled.as_text()
    shape = f"f32[{R},{H // hp},{dk},{hp * dv}]"
    # inside the entry computation: the parameter and ONE op that writes
    # the new state (broadcasts inside fusions are not arrays)
    entry = text[text.index("ENTRY"):]
    fusions = len(re.findall(re.escape(shape) + r"\S* fusion\(", entry))
    calls = len(re.findall(re.escape(shape) + r"[^=]* custom-call\(",
                           entry))
    assert (fusions, calls) == ((1, 0) if route == "fusions" else (0, 1))
    assert ("tpu_custom_call" in text) == (route == "kernel")
    assert not re.findall(re.escape(shape) + r"\S* (?:copy|transpose)\(",
                          entry)


@pytest.mark.parametrize("T,segments", [(256, 1), (256, 16), (200, 3)])
def test_the_chunk_rule_kernel_compiles_for_a_v5e(one_chip,
                                                  no_persistent_cache,
                                                  monkeypatch, T, segments):
    """ISSUE 44: a prompt chunk's delta rule at a decay a key channel at
    Ling's shapes (32 heads of 128 x 128, a call of 256 positions): one
    custom call, for the one segment of a chunk with context behind it
    and for the sixteen a packed call has room for (the kernel then also
    returns the sub-chunks' entering states), and no temporary of the
    fusions' sizes (``kh`` alone was 64 MB a layer)."""
    import paddle_tpu.ops.pallas as pallas
    from paddle_tpu.ops import delta_rule
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(pallas, "tpu_backend", lambda: True)
    H, d = 32, 128

    def arr(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q, g = arr((T, H, d)), arr((T, H, d))
    assert delta_rule.chunk_rule_kernel(q, q, g)
    compiled = jax.jit(lambda *a: delta_rule.gated_delta_chunk(
        *a, segments=segments)).lower(
        q, q, q, g, arr((T, H)), arr((H, d, d)),
        arr((T,), jnp.int32)).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 48 << 20


@pytest.mark.parametrize("R,M,P", [(32, 128, 3873)])
def test_a_query_group_of_one_compiles_for_a_v5e(one_chip,
                                                 no_persistent_cache,
                                                 monkeypatch, R, M, P):
    """ISSUE 38: the hybrid cell's full layers: 30 kv heads of 128 with
    ONE query head each, pages 3,840 columns wide."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention_pallas, use_ragged_kernel)
    import paddle_tpu.ops.pallas as pallas
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(pallas, "tpu_backend", lambda: True)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    q, kp = arr((R, 30, 128)), arr((P, 16, 30 * 128))
    assert use_ragged_kernel(arr((R, 1, 30, 128)), kp, 30)
    compiled = jax.jit(
        lambda q, kp, vp, tbl, lens: ragged_paged_attention_pallas(
            q, kp, vp, tbl, lens, 128 ** -0.5, 30)).lower(
        q, kp, kp, arr((R, M), jnp.int32), arr((R,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["tick", "chunk"])
def test_lings_tick_and_chunk_compile_for_a_v5e(one_chip,
                                                no_persistent_cache,
                                                monkeypatch, program):
    """ISSUE 41: the family's forward in a decode tick's form (128 rows,
    a position each) and in a prompt chunk's (one slot, 256 positions)
    at the benchmark configuration's published widths, cut to three
    layers that hold every kind (two Kimi-Delta-Attention layers over
    dense FFNs, a gated latent layer over the 32 held experts of a
    512-wide router): slot state beside a latent pool in one program. A
    tick calls the channel-decay state kernel twice, the ragged kernel's
    latent mode and the expert kernel once each; a chunk calls the
    chunk-rule kernel twice (ISSUE 44), on operands that reach it as
    they lie: no copy or transpose of a ``[256, 32, 128]`` operand in
    front of it or behind it, and the grouped product over its sorted
    (position, held expert) pairs once (ISSUE 47)."""
    import re
    import json
    import os
    import paddle_tpu.ops.pallas as pallas
    from benchmarks.harness import cell
    from paddle_tpu.ops.paged_cache import PagedKV, SlotState, StateLayer
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(pallas, "tpu_backend", lambda: True)
    with open(os.path.join(cell.BENCH, "configs",
                           "ling-3.0-flash-ep16-d14.json")) as f:
        config = dict(json.load(f), num_hidden_layers=3, layer_group_size=3)
    mod = cell.load_model(config)
    model, spec = mod._program_model(mod.program_config(config))
    fn = model.functional()[0]

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)
    params = {k: arr(s, d) for k, (s, d) in spec.items()}
    eng = config["engine"]
    R, M = eng["max_slots"], eng["max_blocks_per_seq"]
    rows, T = (R, 1) if program == "tick" else (1, eng["chunk_prefill_tokens"])
    lens = arr((rows,), jnp.int32)
    flag = arr((rows,), jnp.bool_)
    layers = model.paged_cache_layers()
    assert [isinstance(x, StateLayer) for x in layers] == [True, True, False]
    call = "decode" if program == "tick" else "chunk"
    caches = [
        SlotState(tuple(arr((R,) + tuple(s), d) for s, d in x.arrays),
                  None if program == "tick" else arr((rows,), jnp.int32),
                  lens, flag if program == "tick" else None,
                  None if program == "tick" else flag, call)
        if isinstance(x, StateLayer) else
        PagedKV(arr((eng["num_blocks"], eng["block_size"], 640),
                    jnp.bfloat16), None, arr((rows, M), jnp.int32), lens, 1,
                False, call)
        for x in layers]
    compiled = jax.jit(
        lambda p, ids, c, pos: fn(p, ids, kv_caches=c,
                                  positions=pos)).lower(
        params, arr((rows, T), jnp.int32), caches,
        arr((rows, T), jnp.int32)).compile()
    text = compiled.as_text()
    calls = text.count('custom_call_target="tpu_custom_call"')
    assert calls == (4 if program == "tick" else 3)
    if program == "chunk":
        assert "grouped_expert_mlp" in text
        entry = text[text.index("ENTRY"):]
        assert not re.findall(
            r"f32\[(?:256,32,128|8192,128|256,4096)\]\S* (?:copy|transpose)\(",
            entry)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
