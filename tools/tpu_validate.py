#!/usr/bin/env python
"""Kernel and program checks on the chip.

    python tools/tpu_validate.py            # exit 0 iff every check passed
    python tools/tpu_validate.py --only delta_rule_state    # these alone

Runs every Pallas kernel and every engine program that the CPU suite
only ever sees through the interpreter once through the real compiler
on whatever backend jax selects, each against its XLA reference: the
flash kernel's segment / window / backward variants, ``quant_matmul``,
the decode re-block, the ragged paged-attention kernel (single- and
multi-query, K/V and latent pools), the held experts' two kernels (a
tick's rows, a prompt call's sorted pairs), the delta rule's state step and
chunk form at a decay a head and a decay a channel (the channel's chunk
kernel beside its fusions), and the tick / patch
/ restore programs of the serving engine. ``chip_smoke.py`` covers the default serving route
end to end; this covers the kernels and programs off that route.

The checks run in ONE child process (a chip belongs to one process;
this parent never touches jax). Each prints its own line as it
finishes, the child ends with one ``KERNELS_JSON {...}`` line, and the
report lands in ``chiprun_out/tpu_validate.json``. The exit code is
non-zero iff a check is ``ok: false`` or the child died.
"""
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
OUT = os.path.join(REPO, "chiprun_out", "tpu_validate.json")

KERNEL_CHECK = r"""
import json, time, numpy as np
import jax, jax.numpy as jnp
import sys; sys.path.insert(0, %(repo)r)
from paddle_tpu.utils import compile_cache
compile_cache.enable()
dev = jax.devices()[0]
print("DEVICE_JSON " + json.dumps({"platform": dev.platform,
      "kind": dev.device_kind, "count": len(jax.devices()),
      "jax": jax.__version__}), flush=True)
results = {}

ONLY = %(only)r

def check(name, fn):
    # a failed check is RECORDED (the others still run); the parent
    # turns any ok:false into a non-zero exit
    import traceback
    if ONLY and name not in ONLY:
        return
    t0 = time.time()
    try:
        fn()
        results[name] = {"ok": True, "s": round(time.time() - t0, 1)}
    except Exception as e:
        traceback.print_exc()
        results[name] = {"ok": False, "error": repr(e)[:600]}
    print(name, results[name], flush=True)

rs = np.random.RandomState(0)
from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
from paddle_tpu.ops.attention import dense_attention, segment_mask

b, s, h, kv, d = 2, 512, 8, 4, 64
q = jnp.asarray(rs.randn(b, s, h, d), jnp.bfloat16)
k = jnp.asarray(rs.randn(b, s, kv, d), jnp.bfloat16)
v = jnp.asarray(rs.randn(b, s, kv, d), jnp.bfloat16)
seg = jnp.asarray(np.repeat(np.arange(1, 5), s // 4)[None].repeat(b, 0))

def seg_flash():
    out = flash_attention_bshd(q, k, v, causal=True, segment_ids=seg)
    ref = dense_attention(q, k, v, causal=True, attn_mask=segment_mask(seg))
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < 3e-2, err
    g = jax.grad(lambda q: flash_attention_bshd(
        q, k, v, causal=True, segment_ids=seg).astype(jnp.float32).sum())(q)
    np.asarray(g)
check("flash_segmented_fwd_bwd", seg_flash)

def win_flash():
    out = flash_attention_bshd(q, k, v, causal=True, window=128)
    ref = dense_attention(q, k, v, causal=True, window=128)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < 3e-2, err
    g = jax.grad(lambda q: flash_attention_bshd(
        q, k, v, causal=True, window=128).astype(jnp.float32).sum())(q)
    np.asarray(g)
check("flash_window_fwd_bwd", win_flash)

from paddle_tpu.quant.weight_only import (dequantize_weight,
                                          quantize_blockwise)
from paddle_tpu.ops.pallas.quant_matmul import quant_matmul_pallas
w = jnp.asarray(rs.randn(1024, 512), jnp.float32)
x = jnp.asarray(rs.randn(8, 1024), jnp.bfloat16)

def qmm(bits):
    def run():
        qw, sc = quantize_blockwise(w, bits=bits, block_size=128)
        out = quant_matmul_pallas(x, qw, sc, bits)
        ref = x @ dequantize_weight(qw, sc, bits, 128, jnp.bfloat16)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        rel = err / float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
        assert rel < 3e-2, (err, rel)
    return run
check("quant_matmul_int8", qmm(8))
check("quant_matmul_int4", qmm(4))

from paddle_tpu.ops.pallas.decode_attention import decode_attention_pallas
ck = jnp.asarray(rs.randn(8, 2048, kv, d), jnp.bfloat16)
cv = jnp.asarray(rs.randn(8, 2048, kv, d), jnp.bfloat16)
q1 = jnp.asarray(rs.randn(8, h, d), jnp.bfloat16)

def deco():
    out = decode_attention_pallas(q1, ck, cv, jnp.int32(1000),
                                  d ** -0.5)[:, None]
    mask = (jnp.arange(2048) <= 1000)[None, None, None, :]
    ref = dense_attention(q1[:, None], ck, cv, attn_mask=mask)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < 3e-2, err
check("decode_kernel", deco)

def ragged_cell_inputs(T=None):
    # the serving cell's geometry (qwen2-7b-d16: 8 slots, 128 blocks of
    # 16 tokens, 4 kv heads, group 7, head 128, bf16, a 2049-block pool
    # of [P, B, kvh*d] rows) with ragged lengths from an empty slot to a
    # full table
    R, P, B, M, kvh2, h2, d2 = 8, 2049, 16, 128, 4, 28, 128
    qq = jnp.asarray(rs.randn(*((R, h2, d2) if T is None
                                else (R, T, h2, d2))), jnp.bfloat16)
    kp = jnp.asarray(rs.randn(P, B, kvh2 * d2), jnp.bfloat16)
    vp = jnp.asarray(rs.randn(P, B, kvh2 * d2), jnp.bfloat16)
    tables = jnp.asarray(1 + rs.permutation(P - 1)[:R * M]
                         .reshape(R, M), jnp.int32)
    lens = jnp.asarray([0, 15, 16, 2040, 100, 576, 1023, 300], jnp.int32)
    return qq, kp, vp, tables, lens, kvh2

def ragged_paged_kernel():
    # the ragged kernel (the serving default) must compile and match the
    # dense gather on hardware at the cell's geometry
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention_pallas
    from paddle_tpu.ops.attention import dense_attention as da
    qq, kp, vp, tables, lens, kvh2 = ragged_cell_inputs()
    R, d2 = qq.shape[0], qq.shape[-1]
    out = ragged_paged_attention_pallas(qq, kp, vp, tables, lens,
                                        d2 ** -0.5, kvh2)
    ks = kp[tables].reshape(R, -1, kvh2, d2)
    vs = vp[tables].reshape(R, -1, kvh2, d2)
    kpos = jnp.arange(ks.shape[1])[None, :]
    ref = da(qq[:, None], ks, vs,
             attn_mask=(kpos <= lens[:, None])[:, None, None, :])[:, 0]
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < 3e-2, err
check("ragged_paged_attention_kernel", ragged_paged_kernel)

def ragged_paged_multiquery_kernel():
    # ISSUE 7: the speculative verify's multi-query rows (q [R, T, h, d];
    # query t of row r attends 0..len+t) must compile and match the
    # dense per-position reference on hardware — the serving spec tick
    # routes through this shape
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention_pallas
    from paddle_tpu.ops.attention import dense_attention as da
    T = 5
    qq, kp, vp, tables, lens, kvh2 = ragged_cell_inputs(T)
    R, d2 = qq.shape[0], qq.shape[-1]
    out = ragged_paged_attention_pallas(qq, kp, vp, tables, lens,
                                        d2 ** -0.5, kvh2)
    ks = kp[tables].reshape(R, -1, kvh2, d2)
    vs = vp[tables].reshape(R, -1, kvh2, d2)
    kpos = jnp.arange(ks.shape[1])[None, None, :]
    qpos = lens[:, None, None] + jnp.arange(T)[None, :, None]
    ref = da(qq, ks, vs, attn_mask=(kpos <= qpos)[:, None])
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < 3e-2, err
check("ragged_paged_multiquery_kernel", ragged_paged_multiquery_kernel)

def latent_paged_kernel():
    # the ragged kernel's latent mode (DeepSeek-V3 / GigaChat3.1 widths:
    # 64 query heads over one 640-column row a token, values its first
    # 512 columns, 64 rows, an 8193-block pool), single-query and
    # multi-query, against the dense gather
    from paddle_tpu.ops.paged_cache import (PagedKV, paged_decode_route,
                                            paged_latent_attention,
                                            paged_latent_attention_dense)
    R, P, B, M, h2, W, dv = 64, 8193, 16, 128, 64, 640, 512
    kp = jnp.asarray(rs.randn(P, B, W), jnp.bfloat16)
    tables = jnp.asarray(1 + rs.permutation(P - 1)[:R * M]
                         .reshape(R, M), jnp.int32)
    lens = jnp.asarray(([0, 15, 16, 2040, 100, 576, 1023, 300]
                        + list(rs.randint(0, 2040, R - 8))), jnp.int32)

    pk = PagedKV(kp, None, tables, lens)

    def attend(fn):
        return jax.jit(lambda q: fn(q, pk, dv, 192 ** -0.5))

    for T in (1, 3):
        qq = jnp.asarray(rs.randn(R, T, h2, W) * 0.3, jnp.bfloat16)
        assert dev.platform != "tpu" \
            or paged_decode_route(qq, kp, 1) == "ragged"
        got = attend(paged_latent_attention)(qq)
        ref = attend(paged_latent_attention_dense)(qq)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert got.shape == (R, T, h2, dv) and err < 3e-2, (T, err)
check("latent_paged_kernel", latent_paged_kernel)

def expert_share_kernel():
    # the held experts' weight-streaming kernel through
    # ExpertShareMLP.routed (one rank's 16 experts of width 2048 at
    # MiMo-V2.5's hidden size, a tick's 64 rows, 10 of 16 hit) against
    # the einsums over all held experts
    import paddle_tpu as pt
    from paddle_tpu.ops.pallas import expert_mlp
    from paddle_tpu.parallel import moe
    pt.seed(0)
    h2, m2, held, K = 4096, 2048, 16, 8
    layer = moe.ExpertShareMLP(h2, m2, 256, K, 16, held)
    params = {k: v.astype(jnp.bfloat16) for k, v in layer.named_parameters()}
    x = jnp.asarray(rs.randn(64, h2), jnp.bfloat16)
    ids = jnp.asarray(16 + (np.arange(64 * K).reshape(64, K) %% 10), jnp.int32)
    gates = jnp.asarray(rs.rand(64, K) * 0.25, jnp.float32)

    def routed(params, x, ids, gates):
        with layer.bound(params):
            return layer.routed(x, ids, gates)
    assert dev.platform != "tpu" \
        or expert_mlp.use_expert_kernel(x, params["w_gate"])
    got = jax.jit(routed)(params, x, ids, gates)
    gate = expert_mlp.use_expert_kernel
    expert_mlp.use_expert_kernel = lambda *_: False
    try:
        ref = jax.jit(routed)(params, x, ids, gates)
    finally:
        expert_mlp.use_expert_kernel = gate
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert got.shape == (64, h2) and err < 2e-2 * scale, (err, scale)
check("expert_share_kernel", expert_share_kernel)

def grouped_expert_kernel():
    # a prompt call's positions through ExpertShareMLP.routed (one
    # rank's 16 experts of width 1024 at Laguna-S-2.1's hidden size,
    # 1,024 positions x 10 choices): the grouped product over the
    # sorted (position, held expert) pairs against the einsums, at a
    # uniform choice of the 256 columns (about 640 pairs, one pass) and
    # with every position on 10 held experts (10,240 pairs, five passes)
    import paddle_tpu as pt
    from paddle_tpu.ops.pallas import expert_mlp
    from paddle_tpu.parallel import moe
    pt.seed(0)
    h2, m2, held, K, T = 3072, 1024, 16, 10, 1024
    layer = moe.ExpertShareMLP(h2, m2, 256, K, 16, held)
    params = {k: v.astype(jnp.bfloat16) for k, v in layer.named_parameters()}
    x = jnp.asarray(rs.randn(T, h2), jnp.bfloat16)
    gates = jnp.asarray(rs.rand(T, K) * 0.25, jnp.float32)

    def routed(params, x, ids, gates):
        with layer.bound(params):
            return layer.routed(x, ids, gates)
    assert dev.platform != "tpu" \
        or expert_mlp.use_grouped_kernel(x, params["w_gate"])
    uniform = np.stack([rs.permutation(256)[:K] for _ in range(T)])
    most = 16 + (np.arange(T)[:, None] + np.arange(K)) %% held
    for ids in (uniform, most):
        ids = jnp.asarray(ids, jnp.int32)
        got = jax.jit(routed)(params, x, ids, gates)
        gate = expert_mlp.use_grouped_kernel
        expert_mlp.use_grouped_kernel = lambda *_: False
        try:
            ref = jax.jit(routed)(params, x, ids, gates)
        finally:
            expert_mlp.use_grouped_kernel = gate
        scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        assert got.shape == (T, h2) and err < 2e-2 * scale, (err, scale)
check("grouped_expert_kernel", grouped_expert_kernel)

def unequal_head_paged_kernel():
    # the ragged kernel at MiMo-V2's two layer kinds (64 query heads,
    # key heads of 192 columns read as aligned 256-column spans, value
    # heads of 128, 64 rows): a full layer over the allocator's table,
    # and a window layer's sink and ring of 25 pages a slot written
    # round, single-query and multi-query, against the dense gather
    from paddle_tpu.ops.paged_cache import (PagedKV, paged_decode_attention,
                                            paged_decode_attention_dense,
                                            paged_decode_route)
    R, B, h2, dk, dv = 64, 16, 64, 192, 128
    lens = jnp.asarray(([0, 15, 16, 2040, 100, 576, 1023, 300]
                        + list(rs.randint(0, 2040, R - 8))), jnp.int32)
    for kvh2, M, window in ((4, 128, None), (8, 25, 128)):
        ring = window is not None
        P = R * M + 1
        kp = jnp.asarray(rs.randn(P, B, kvh2 * dk), jnp.bfloat16)
        vp = jnp.asarray(rs.randn(P, B, kvh2 * dv), jnp.bfloat16)
        tables = jnp.asarray(1 + np.arange(R * M).reshape(R, M), jnp.int32)
        sink = jnp.asarray(rs.randn(h2), jnp.float32) if ring else None
        pk = PagedKV(kp, vp, tables, lens, kvh2, ring)

        def attend(fn):
            return jax.jit(lambda q: fn(q, pk, dk ** -0.5, window, sink))

        for T in (1, 3):
            qq = jnp.asarray(rs.randn(R, T, h2, dk) * 0.3, jnp.bfloat16)
            assert dev.platform != "tpu" \
                or paged_decode_route(qq, kp, kvh2) == "ragged"
            got = attend(paged_decode_attention)(qq)
            ref = attend(paged_decode_attention_dense)(qq)
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                        - ref.astype(jnp.float32))))
            assert got.shape == (R, T, h2, dv) and err < 3e-2, \
                (kvh2, T, err)
check("unequal_head_paged_kernel", unequal_head_paged_kernel)

def ring_tick_program():
    # ISSUE 11: the fused tick program's token ring (device-resident ring
    # buffer + write cursors carried in the tick state, no per-tick
    # readback) must compile and stream correctly on hardware. The
    # negligible-compute stub keeps this a TICK-MACHINERY check, like
    # the loadgen's --model stub.
    from paddle_tpu.generation.paged import PagedEngine
    from paddle_tpu.generation.stub import TickStubModel
    eng = PagedEngine(TickStubModel(), max_slots=4, num_blocks=32,
                      block_size=8, max_blocks_per_seq=8,
                      prefill_buckets=(8,))
    for i in range(3):
        eng.submit(i, np.arange(1, 6)[None], max_new_tokens=12)
    res = eng.run()
    assert all(len(v) == 12 for v in res.values()), res
    assert eng.ring_drains > 0
check("ring_tick_program", ring_tick_program)

def rejection_spec_tick():
    # ISSUE 11: both rejection-sampled speculative tick shapes — the
    # all-greedy program (argmax prefix rule) and the mixed program
    # (per-position accept/residual-resample with per-row key folds) —
    # must compile on hardware; the ring rides both.
    from paddle_tpu.generation.paged import PagedEngine
    from paddle_tpu.generation.stub import TickStubModel

    def run(**kw):
        eng = PagedEngine(TickStubModel(), max_slots=4, num_blocks=32,
                          block_size=8, max_blocks_per_seq=8,
                          prefill_buckets=(8,), spec_tokens=3)
        eng.submit("g", np.asarray([1, 2, 3, 1, 2, 3])[None],
                   max_new_tokens=10)
        if kw.get("mixed"):
            eng.submit("s", np.asarray([2, 3, 4, 2, 3])[None],
                       max_new_tokens=10, temperature=0.8, seed=1)
        res = eng.run()
        assert all(len(v) == 10 for v in res.values()), res
    run()              # all-greedy spec program
    run(mixed=True)    # mixed greedy+sampled spec program
check("rejection_spec_tick", rejection_spec_tick)

def delta_patch_program():
    # ISSUE 14: slot transitions as descriptors — admit-row scatter plus
    # table-row append into the device-resident tick state — must
    # compile and stream correctly on hardware at a serving block
    # geometry (block_size 16 x 16 blocks/seq), in the MIXED tick
    # program too (a sampled row's key rides the descriptor; the check
    # below is all greedy). Churny short requests (more requests than
    # slots, budgets crossing the block grid) force admit/finish/growth
    # descriptors; every transition after the first rebuild rides the
    # queue.
    from paddle_tpu.generation.paged import PagedEngine
    from paddle_tpu.generation.stub import TickStubModel
    eng = PagedEngine(TickStubModel(), max_slots=4, num_blocks=64,
                      block_size=16, max_blocks_per_seq=16,
                      prefill_buckets=(16,))
    eng.submit("w", np.arange(1, 6)[None], max_new_tokens=2,
               temperature=0.8, seed=9)
    eng.run()
    fr0 = eng.full_rebuilds
    for i in range(8):
        # 9 + 24 = 33 tokens: crosses two block boundaries -> growth
        eng.submit(i, np.arange(1, 10)[None], max_new_tokens=24,
                   temperature=0.8 * (i & 1), seed=i)
    res = eng.run()
    assert all(len(v) == 24 for k, v in res.items() if k != "w"), res
    assert eng.patches_fused > 0
    assert eng.full_rebuilds == fr0, (eng.full_rebuilds, fr0)
check("delta_patch_program", delta_patch_program)

def fused_patch_tick_program():
    # ISSUE 19: the fused patch+tick program — the masked batched
    # scatter stage prepended to the tick, fed by the device-resident
    # [Q, D] descriptor queue — must compile as ONE executable on
    # hardware at the same geometry and absorb churn with zero
    # rebuilds after the warm-up and no dispatch of its own: the
    # dispatch counter must advance exactly once per tick + once per
    # prefill across a churny run.
    from paddle_tpu.generation.paged import PagedEngine
    from paddle_tpu.generation.stub import TickStubModel
    eng = PagedEngine(TickStubModel(), max_slots=4, num_blocks=64,
                      block_size=16, max_blocks_per_seq=16,
                      prefill_buckets=(16,))
    eng.submit("w", np.arange(1, 6)[None], max_new_tokens=2)
    eng.run()                      # warmup: compiles tick + prefill
    fr0, d0 = eng.full_rebuilds, eng.dispatch_count
    t0, p0 = eng.stats["decode_steps"], eng.stats["prefills"]
    for i in range(8):
        eng.submit(i, np.arange(1, 10)[None], max_new_tokens=24)
    res = eng.run()
    assert all(len(v) == 24 for k, v in res.items() if k != "w"), res
    assert eng.patches_fused > 0
    assert eng.full_rebuilds == fr0, (eng.full_rebuilds, fr0)
    ticks = eng.stats["decode_steps"] - t0
    prefills = eng.stats["prefills"] - p0
    assert eng.dispatch_count - d0 == ticks + prefills, \
        (eng.dispatch_count - d0, ticks, prefills)
check("fused_patch_tick_program", fused_patch_tick_program)

def spill_reupload_program():
    # ISSUE 17: the spill re-upload program — one batched H2D scatter
    # of a host-RAM arena span into freshly allocated blocks (donated
    # pools, pad rows onto garbage block 0) — must compile on hardware
    # and restore BITWISE: a fresh engine re-attached to the arena
    # serves the spilled prefix without re-prefilling it.
    from paddle_tpu.generation.paged import PagedEngine
    from paddle_tpu.generation.stub import TickStubModel
    from paddle_tpu.serving.kvspill import KVSpillArena
    arena = KVSpillArena(8 << 20, name="validate")

    def eng():
        e = PagedEngine(TickStubModel(), max_slots=4, num_blocks=32,
                        block_size=8, max_blocks_per_seq=8,
                        prefill_buckets=(8,), chunk_prefill_tokens=8,
                        enable_prefix_cache=True)
        e.attach_spill(arena)
        return e
    prompt = np.arange(1, 17)[None]
    e0 = eng()
    e0.submit("a", prompt, max_new_tokens=8)
    ref = e0.run()["a"]
    assert e0.spill_parked() > 0         # drain-spill the parked span
    e1 = eng()                           # fresh pools, same arena
    e1.submit("b", prompt, max_new_tokens=8)
    res = e1.run()["b"]
    assert res == ref, (res, ref)
    assert e1.stats["spill_restores"] > 0, e1.stats
    assert e1.stats["prefix_hit_tokens"] > 0, e1.stats
check("spill_reupload_program", spill_reupload_program)

def kv_xfer_restore_program():
    # ISSUE 18: the cross-replica restore program — a spilled span
    # serialized to the wire format (crc32 + geometry header),
    # injected into a DIFFERENT replica's arena, must compile the
    # same batched H2D scatter on hardware and restore BITWISE on the
    # receiving engine (live migration / peer fetch is this program
    # behind HTTP).
    from paddle_tpu.generation.paged import PagedEngine
    from paddle_tpu.generation.stub import TickStubModel
    from paddle_tpu.serving import kvxfer
    from paddle_tpu.serving.kvspill import KVSpillArena

    def eng(arena):
        e = PagedEngine(TickStubModel(), max_slots=4, num_blocks=32,
                        block_size=8, max_blocks_per_seq=8,
                        prefill_buckets=(8,), chunk_prefill_tokens=8,
                        enable_prefix_cache=True)
        e.attach_spill(arena)
        return e
    src = KVSpillArena(8 << 20, name="validate-xfer-src")
    dst = KVSpillArena(8 << 20, name="validate-xfer-dst")
    prompt = np.arange(1, 17)[None]
    e0 = eng(src)
    e0.submit("a", prompt, max_new_tokens=8)
    ref = e0.run()["a"]
    assert e0.spill_parked() > 0
    geo = e0._spill_geometry()
    ids = list(range(1, 17))
    chain = [c for c in e0._chunk_digests(ids, len(ids) - 1)
             if src.probe(c) is not None]
    assert chain, "no resident chain digest after spill"
    blob = kvxfer.export_span(src, chain[-1].hex(), geo,
                              gateway="validate")
    assert blob is not None
    assert kvxfer.inject_span(dst, blob, geo,
                              gateway="validate") is not None
    e1 = eng(dst)                       # fresh pools, PEER arena
    e1.submit("b", prompt, max_new_tokens=8)
    res = e1.run()["b"]
    assert res == ref, (res, ref)
    assert e1.stats["spill_restores"] > 0, e1.stats
    snap = kvxfer.counters_snapshot("validate")
    assert snap["kv_xfer_hits_total"] >= 1, snap
    assert snap["kv_xfer_checksum_failures_total"] == 0, snap
check("kv_xfer_restore_program", kv_xfer_restore_program)

def profilez_capture():
    # ISSUE 20: the /profilez capture path on hardware — tick-phase
    # profiling must not perturb the token stream (bitwise vs off),
    # the five phase totals must sum to the measured tick wall
    # (residual construction), and a bounded jax.profiler capture +
    # tickphase ring dump (what the gateway endpoint does) must land
    # without contending the single-trace owner.
    import os, tempfile
    from paddle_tpu.generation.paged import PagedEngine
    from paddle_tpu.generation.stub import TickStubModel
    from paddle_tpu.utils import observability as obs
    from paddle_tpu.utils.profiler import Profiler

    def run(profile):
        e = PagedEngine(TickStubModel(), max_slots=4, num_blocks=32,
                        block_size=8, max_blocks_per_seq=8,
                        prefill_buckets=(8,), chunk_prefill_tokens=8,
                        tick_profile=profile)
        for i in range(3):
            e.submit("r" + str(i), np.arange(1, 9)[None],
                     max_new_tokens=8)
        return e, e.run()
    e_on, res_on = run(True)
    e_off, res_off = run(False)
    assert res_on == res_off, "profile-on stream diverged"
    doc = e_on.tick_profile_doc()
    assert doc is not None and doc["ticks"] > 0
    bad = obs.validate_tickphase_doc(doc)
    assert not bad, bad
    d = tempfile.mkdtemp(prefix="profilez_")
    prof = Profiler(logdir=d)
    prof.start()
    try:
        e_cap, _ = run(True)
    finally:
        prof.stop()
    path = e_cap.dump_tick_profile(
        os.path.join(d, "tickphase_validate.json"))
    assert path and os.path.exists(path), path
check("profilez_capture", profilez_capture)

def prefill_flash():
    # the generate() prefill branch: flash at cache_index==0 must match
    # the masked-dense-over-cache path it replaced (llama.py)
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import llama_tiny
    pt.seed(0)
    mf = LlamaForCausalLM(llama_tiny(hidden_size=256,
                                     num_attention_heads=4,
                                     max_position_embeddings=512,
                                     dtype=jnp.bfloat16))
    pt.seed(0)
    md = LlamaForCausalLM(llama_tiny(hidden_size=256,
                                     num_attention_heads=4,
                                     max_position_embeddings=512,
                                     dtype=jnp.bfloat16,
                                     use_flash_attention=False))
    ids = jnp.asarray(rs.randint(0, 256, (2, 256)))
    cf = mf.init_kv_caches(2, 384)
    lf, _ = mf(ids, kv_caches=cf, cache_index=0)
    cd = md.init_kv_caches(2, 384)
    ld, _ = md(ids, kv_caches=cd, cache_index=0)
    err = float(jnp.max(jnp.abs(lf - ld)))
    # both paths are end-to-end bf16; flash vs dense differ by bf16
    # accumulation order, so judge RELATIVE to logit magnitude (the r5
    # absolute-5e-2 gate tripped at err=0.066 on |logits|~8 — pure noise)
    rel = err / max(float(jnp.max(jnp.abs(ld))), 1e-6)
    assert rel < 2.5e-2, (err, rel)
check("prefill_flash_vs_dense", prefill_flash)

def delta_rule_state():
    # ISSUE 38: the gated delta rule at the hybrid cell's shapes (32
    # rows, 30 heads, keys 96 / values 192, a 256-position chunk): the
    # decode step over the stored state (two heads a row: whole lane
    # tiles) and the chunkwise form, each against the position-by-
    # position scan; float32 at the highest matmul precision
    from paddle_tpu.ops import delta_rule as dr
    R, H, dk, dv, T = 32, 30, 96, 192, 256
    hp = dr.state_lane_heads(H, dv)
    assert hp == 2, hp
    f = lambda *s: jnp.asarray(rs.standard_normal(s), jnp.float32)
    q = dr.l2_normalize(f(R, T, H, dk)) * dk ** -0.5
    k = dr.l2_normalize(f(R, T, H, dk))
    v = f(R, T, H, dv)
    beta = jnp.asarray(rs.uniform(0.01, 1.99, (R, T, H)), jnp.float32)
    g = -jnp.asarray(np.exp(rs.uniform(-9, 1.5, (R, T, H))), jnp.float32)
    S0 = f(R, H, dk, dv)
    scan = jax.jit(jax.vmap(dr.gated_delta_scan))
    o_ref, S_ref = scan(q, k, v, g, beta, S0)
    # the chunk form, a row at a time (the engine's call is one row)
    chunk = jax.jit(jax.vmap(lambda *a: dr.gated_delta_chunk(*a)))
    o, S = chunk(q[:4], k[:4], v[:4], g[:4], beta[:4], S0[:4])
    scale = float(jnp.max(jnp.abs(o_ref)))
    err_o = float(jnp.max(jnp.abs(o - o_ref[:4]))) / scale
    err_S = float(jnp.max(jnp.abs(S[:, 0] - S_ref[:4]))) \
        / float(jnp.max(jnp.abs(S_ref)))
    assert err_o < 1e-4 and err_S < 1e-4, (err_o, err_S)
    # the decode step: every row's first position, a dead row kept; by
    # the route the state takes here (ISSUE 39: the one-pass Pallas
    # kernel) and by the jnp body's two fusions, the gate held shut
    from paddle_tpu.ops.pallas import delta_state as ds
    live = jnp.arange(R) %% 5 != 3
    o1_ref, S1_ref = scan(q[:, :1], k[:, :1], v[:, :1], g[:, :1],
                          beta[:, :1], S0)
    want = jnp.where(live[:, None, None, None], S1_ref, S0)
    packed = dr.pack_state(S0, hp)
    rest = (q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]), beta[:, 0], live)
    assert ds.use_state_kernel(packed), "the state kernel is not taken"
    gate, errs = ds.use_state_kernel, {}
    for route in ("kernel", "fusions"):
        if route == "fusions":
            ds.use_state_kernel = lambda S: False
        try:
            step = jax.jit(lambda *a: dr.delta_state_step(*a),
                           donate_argnums=(0,)).lower(packed,
                                                      *rest).compile()
        finally:
            ds.use_state_kernel = gate
        if dev.platform == "tpu":       # the interpreter is no call
            assert ("tpu_custom_call" in step.as_text()) \
                == (route == "kernel"), route
        S1, o1 = step(packed + 0.0, *rest)
        S1 = dr.unpack_state(S1, hp)
        dead = ~np.asarray(live)
        assert np.array_equal(np.asarray(S1)[dead], np.asarray(S0)[dead])
        e1 = float(jnp.max(jnp.abs(S1 - want))) \
            / float(jnp.max(jnp.abs(want)))
        e2 = float(jnp.max(jnp.abs(o1 - o1_ref[:, 0]))) \
            / float(jnp.max(jnp.abs(o1_ref)))
        assert e1 < 1e-5 and e2 < 1e-4, (route, e1, e2)
        errs[route] = (e1, e2)
    print("delta_rule_state: chunk err %%.1e / %%.1e, step err kernel "
          "%%.1e / %%.1e, fusions %%.1e / %%.1e"
          %% ((err_o, err_S) + errs["kernel"] + errs["fusions"]),
          flush=True)
check("delta_rule_state", delta_rule_state)

def kda_channel_state():
    # ISSUE 41: the delta rule at a decay a KEY CHANNEL at Ling-3.0-
    # flash's shapes (32 heads of 128 x 128, a head a lane tile, a
    # 256-position chunk): the one-pass state kernel (alpha a column
    # beside k and q) and the chunkwise form, random gates and every
    # log-decay at the bound -5, each against the scan
    from paddle_tpu.ops import delta_rule as dr
    from paddle_tpu.ops.pallas import delta_state as ds
    R, H, d, T = 16, 32, 128, 256
    f = lambda *s: jnp.asarray(rs.standard_normal(s), jnp.float32)
    q = dr.l2_normalize(f(R, T, H, d)) * d ** -0.5
    k = dr.l2_normalize(f(R, T, H, d))
    v = f(R, T, H, d)
    beta = jnp.asarray(rs.uniform(0.01, 0.99, (R, T, H)), jnp.float32)
    g = -5.0 * jax.nn.sigmoid(3.0 * f(R, T, H, d))
    S0 = f(R, H, d, d)
    scan = jax.jit(jax.vmap(dr.gated_delta_scan))
    chunk = jax.jit(jax.vmap(lambda *a: dr.gated_delta_chunk(*a)))
    errs = []
    for gates in (g[:2], jnp.full_like(g[:2], -5.0)):
        o_ref, S_ref = scan(q[:2], k[:2], v[:2], gates, beta[:2], S0[:2])
        o, S = chunk(q[:2], k[:2], v[:2], gates, beta[:2], S0[:2])
        assert bool(jnp.all(jnp.isfinite(o)) & jnp.all(jnp.isfinite(S)))
        errs += [float(jnp.max(jnp.abs(o - o_ref)))
                 / float(jnp.max(jnp.abs(o_ref))),
                 float(jnp.max(jnp.abs(S[:, 0] - S_ref)))
                 / float(jnp.max(jnp.abs(S_ref)))]
    assert max(errs) < 1e-4, errs
    live = jnp.arange(R) %% 5 != 3
    o1_ref, S1_ref = scan(q[:, :1], k[:, :1], v[:, :1], g[:, :1],
                          beta[:, :1], S0)
    want = jnp.where(live[:, None, None, None], S1_ref, S0)
    assert dr.state_lane_heads(H, d) == 1 and ds.use_state_kernel(S0)
    step = jax.jit(lambda *a: dr.delta_state_step(*a),
                   donate_argnums=(0,)).lower(
        S0, q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]), beta[:, 0],
        live).compile()
    if dev.platform == "tpu":           # the interpreter is no call
        assert "tpu_custom_call" in step.as_text()
    S1, o1 = step(S0 + 0.0, q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]),
                  beta[:, 0], live)
    dead = ~np.asarray(live)
    assert np.array_equal(np.asarray(S1)[dead], np.asarray(S0)[dead])
    e1 = float(jnp.max(jnp.abs(S1 - want))) / float(jnp.max(jnp.abs(want)))
    e2 = float(jnp.max(jnp.abs(o1 - o1_ref[:, 0]))) \
        / float(jnp.max(jnp.abs(o1_ref)))
    assert e1 < 1e-5 and e2 < 1e-4, (e1, e2)
    print("kda_channel_state: chunk err %%s, step err %%.1e / %%.1e"
          %% (["%%.1e" %% e for e in errs], e1, e2), flush=True)
check("kda_channel_state", kda_channel_state)

def delta_rule_chunk():
    # ISSUE 44: a prompt chunk's delta rule at a decay a key channel as
    # ONE Pallas kernel (ops/pallas/delta_chunk.py) at Ling-3.0-flash's
    # widths (32 heads of 128 x 128, 256 positions): the kernel and the
    # fusions it replaces (``_chunk_channel``, the gate held shut), each
    # against the scan: random gates, every log-decay at the bound -5, a
    # padded tail of one whole dead sub-chunk (which the kernel skips)
    # and a partial one, three packed segments
    from paddle_tpu.ops import delta_rule as dr
    from paddle_tpu.ops.pallas import delta_chunk as dc
    H, d, T = 32, 128, 256
    f = lambda *s: jnp.asarray(rs.standard_normal(s), jnp.float32)
    q = dr.l2_normalize(f(T, H, d)) * d ** -0.5
    k = dr.l2_normalize(f(T, H, d))
    v, S0 = f(T, H, d), f(H, d, d)
    beta = jnp.asarray(rs.uniform(0.01, 0.99, (T, H)), jnp.float32)
    g = -5.0 * jax.nn.sigmoid(3.0 * f(T, H, d))
    assert dc.use_chunk_kernel(q, v, g, dr.SUB_CHUNK)
    gate = dc.use_chunk_kernel
    scan = jax.jit(dr.gated_delta_scan)
    rel = lambda a, b: float(jnp.max(jnp.abs(a - b))) \
        / float(jnp.max(jnp.abs(b)))
    real = jnp.arange(T) < 150          # sub-chunk 3 dead, 2 partly
    pad = lambda x: jnp.where(real.reshape((T,) + (1,) * (x.ndim - 1)),
                              x, 0.0)
    cases = {"random": (g, beta), "bound": (jnp.full_like(g, -5.0), beta),
             "padded": (pad(g), pad(beta))}
    errs = {}
    try:
        for route, flag in (("kernel", True), ("fusions", False)):
            dc.use_chunk_kernel = lambda *a, flag=flag: flag
            chunk = jax.jit(lambda *a: dr.gated_delta_chunk(*a)) \
                .lower(q, k, v, g, beta, S0).compile()
            if dev.platform == "tpu":   # the interpreter is no call
                assert ("tpu_custom_call" in chunk.as_text()) == flag
            for name, (gg, bb) in cases.items():
                o_ref, S_ref = scan(q, k, v, gg, bb, S0)
                o, S = chunk(q, k, v, gg, bb, S0)
                assert bool(jnp.all(jnp.isfinite(o)) & jnp.all(jnp.isfinite(S)))
                n = 150 if name == "padded" else T
                errs[route, name] = (rel(o[:n], o_ref[:n]),
                                     rel(S[0], S_ref))
            # three segments of 100 / 30 / 90 positions and padding
            lens = (100, 30, 90)
            seg = jnp.asarray(np.repeat([0, 1, 2, 2], lens + (T - 220,)))
            here = jnp.arange(T) < 220
            gs, bs = (jnp.where(here[:, None, None], g, 0.0),
                      jnp.where(here[:, None], beta, 0.0))
            o, S = jax.jit(lambda *a: dr.gated_delta_chunk(
                *a, segments=3))(q, k, v, gs, bs, S0, seg)
            worst, at = 0.0, 0
            for i, n in enumerate(lens):
                sl = slice(at, at + n)
                o_ref, S_ref = scan(q[sl], k[sl], v[sl], g[sl], beta[sl],
                                    S0 if i == 0 else jnp.zeros_like(S0))
                worst = max(worst, rel(o[sl], o_ref), rel(S[i], S_ref))
                at += n
            errs[route, "packed"] = (worst, worst)
    finally:
        dc.use_chunk_kernel = gate
    assert max(max(e) for e in errs.values()) < 1e-4, errs
    print("delta_rule_chunk: " + "; ".join(
        "%%s %%s o %%.1e S %%.1e" %% (r, n, eo, eS)
        for (r, n), (eo, eS) in errs.items()), flush=True)
check("delta_rule_chunk", delta_rule_chunk)

def ling_programs():
    # ISSUE 41: the family's tick and chunk programs (slot state beside
    # a latent pool, the group-limited router's expert share) through
    # the real compiler at tiny widths: prompts of one to four chunks
    # and decode through PagedEngine against the no-cache forward
    import paddle_tpu as pt
    from paddle_tpu.generation.paged import PagedEngine
    from paddle_tpu.models.ling_hybrid import (LingHybridForCausalLM,
                                               ling_hybrid_tiny)
    pt.seed(0)
    model = LingHybridForCausalLM(ling_hybrid_tiny(
        num_hidden_layers=6, experts_held=4))
    fn, params = model.functional()
    prompts = [rs.randint(1, 256, n).tolist() for n in (5, 37, 16, 61)]
    worst = 0.0
    # float32 weights: the chip multiplies them in one bfloat16 pass
    # unless asked, and a router's choice then flips between the two
    # sides; both are traced at the highest precision here
    with jax.default_matmul_precision("highest"):
        eng = PagedEngine(model, max_slots=4, num_blocks=64, block_size=8,
                          max_blocks_per_seq=16, chunk_prefill_tokens=16)
        for i, p in enumerate(prompts):
            eng.submit(i, p, max_new_tokens=8)
        res = eng.run()
        for i, p in enumerate(prompts):
            lp = jax.nn.log_softmax(
                fn(params, jnp.asarray([p + res[i]]))[0], -1)
            want = [float(lp[len(p) - 1 + j, t])
                    for j, t in enumerate(res[i])]
            worst = max(worst, float(np.abs(
                np.asarray(want) - np.asarray(eng.logprobs[i])).max()))
    assert worst < 1e-3, worst
    st = eng.stats
    assert st["state_layer_ticks"] == 4 * st["decode_steps"] > 0
    if dev.platform == "tpu":
        assert st["state_kernel_ticks"] == st["state_layer_ticks"]
    assert st["moe_rows_routed_here"] > 0
    assert st["chunk_rule_layer_calls"] == 4 * st["prefill_chunks"] > 0
    print("ling_programs: logprob err %%.1e" %% worst, flush=True)
check("ling_programs", ling_programs)

def laguna_programs():
    # ISSUE 46: the family's tick and chunk programs (query groups of 3
    # and 5 over the same kv heads, a gate a head, a ring beside a whole
    # table, the softmax router's share beside a shared expert) through
    # the real compiler at tiny widths: prompts of one to thirteen
    # chunks, the later ones through the run-walking chunk attention,
    # and decode through PagedEngine against the no-cache forward
    import paddle_tpu as pt
    from paddle_tpu.generation.paged import PagedEngine
    from paddle_tpu.models.laguna import LagunaForCausalLM, laguna_tiny
    pt.seed(0)
    model = LagunaForCausalLM(laguna_tiny(experts_held=4))
    fn, params = model.functional()
    prompts = [rs.randint(1, 256, n).tolist() for n in (5, 37, 16, 200)]
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        eng = PagedEngine(model, max_slots=4, num_blocks=96, block_size=8,
                          max_blocks_per_seq=40, chunk_prefill_tokens=16)
        for i, p in enumerate(prompts):
            eng.submit(i, p, max_new_tokens=8)
        res = eng.run()
        for i, p in enumerate(prompts):
            lp = jax.nn.log_softmax(
                fn(params, jnp.asarray([p + res[i]]))[0], -1)
            want = [float(lp[len(p) - 1 + j, t])
                    for j, t in enumerate(res[i])]
            worst = max(worst, float(np.abs(
                np.asarray(want) - np.asarray(eng.logprobs[i])).max()))
    assert worst < 1e-3, worst
    st = eng.stats
    assert eng.decode_route() == ("ragged" if dev.platform == "tpu"
                                  else "dense")
    assert st["moe_layer_ticks"] == 2 * st["decode_steps"] > 0
    # a 40-page table is two runs of 32: the walk stops at the live one
    assert 0 < st["chunk_attn_positions_live"] \
        <= st["chunk_attn_positions_scored"]
    print("laguna_programs: logprob err %%.1e" %% worst, flush=True)
check("laguna_programs", laguna_programs)

print("KERNELS_JSON " + json.dumps(results), flush=True)
"""


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", nargs="*", default=[],
                    help="run these checks alone (names as reported)")
    args = ap.parse_args(argv)
    report = {"started": time.strftime("%Y-%m-%d %H:%M:%S")}
    t0 = time.time()
    proc = subprocess.run([sys.executable, "-c",
                           KERNEL_CHECK % {"repo": REPO,
                                           "only": args.only}],
                          stdout=subprocess.PIPE, text=True)
    sys.stdout.write(proc.stdout)
    report["rc"] = proc.returncode
    report["s"] = round(time.time() - t0, 1)
    for line in proc.stdout.splitlines():
        if line.startswith("DEVICE_JSON "):
            report["device"] = json.loads(line[len("DEVICE_JSON "):])
        if line.startswith("KERNELS_JSON "):
            report["kernels"] = json.loads(line[len("KERNELS_JSON "):])
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {OUT}", file=sys.stderr)
    kernels = report.get("kernels")
    if proc.returncode != 0 or not kernels:
        print(f"FAILED: the check process ended rc={proc.returncode} "
              f"without a full report", file=sys.stderr)
        return 1
    failed = sorted(k for k, v in kernels.items() if not v["ok"])
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
