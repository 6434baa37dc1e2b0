#!/usr/bin/env python
"""One-window decode-path profiler (round 6; round-5 history below).

The round-5 hardware run (2026-07-30, on code and an installation
that are both gone; its record files were deleted in PR 21) raised
three decode puzzles: the Pallas decode kernel timed slower than dense,
fused projections timed SLOWER than unfused, and int8 weight-only
decode timed slower than bf16. Each 'time' there was one whole
generate() call; this script separates compile/dispatch from
steady-state on-device time (long decode runs amortize the per-call
cost) and times each lever in isolation (the t64/t256 slope in sections
2-4 answers "why is fused/int8 slower": whole-call numbers are
dispatch-dominated, the slope is the comparable per-token cost).

Round 6 (ISSUE 6): the paged section now profiles all three tick
architectures — per-tick host path (the round-5 architecture),
device-resident fused tick, and the multi-tick scan — and splits each
tick into host scheduling vs program (dispatch+compute) vs the
measured per-dispatch floor, so dispatch overhead is a NUMBER, not a
suspicion. It ends with a ``PAGED_JSON`` line that bench.py ingests
as the ``paged_tokens_per_sec`` rung (before/after captured in the
same window). Writes DECODE_PROFILE_r06.json.

Round 7 (ISSUE 11): the paged section runs the async token ring
on/off A/B — ``fused_sync`` (one blocking D2H per dispatch, the r06
architecture) vs ``fused`` (ring drains, pipelined one dispatch
behind) with a ``blocking_d2h_per_tick`` column — and §6b sweeps the
REJECTION-SAMPLED speculative tick on a repetitive sampled stream
(accept rate, tokens/forward, the
``paged_sampled_spec_tokens_per_sec`` rung bench.py auto-ingests
beside the greedy spec rung).

Round 8 (ISSUE 14): §7 churn A/B — short-request traffic with a slot
transition every few ticks, ``delta_transitions`` on vs off (one-row
patch programs vs full mirror rebuild+re-upload per transition), with
uploads/tick, upload BYTES/tick and rebuild/patch counts per row and
the ``paged_churn_tokens_per_sec`` rung bench.py auto-ingests.

Round 9 (ISSUE 19): §7b widens the churn A/B to three modes — fused
(staged patch queue applied by the next tick's program, the engine
default) vs delta vs full rebuild — with a dispatches/tick column
pinning the one-dispatch-per-tick claim and the
``paged_churn_fused_tokens_per_sec`` rung.

Usage: timeout 2100 python tools/decode_profile.py
(budget covers ~20 cold generate compiles across base/fused/int8/int4
plus the attention and paged sections; every subsection banks as it
goes, so even a SIGTERM keeps what was measured)
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "DECODE_PROFILE_r06.json")

report = {"started": time.strftime("%Y-%m-%d %H:%M:%S")}


def bank():
    with open(OUT, "w") as f:
        json.dump(report, f, indent=1)


def main():
    import numpy as np
    import jax
    import jax.numpy as jnp

    report["device"] = str(jax.devices()[0].device_kind)
    bank()
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM

    import bench

    rs = np.random.RandomState(0)

    # --- 1) raw decode-attention: new kv-folded kernel vs dense, several
    # shapes (the bench shape first). np.asarray waits for the device;
    # iters amortize the per-call cost.
    from paddle_tpu.ops.attention import dense_attention
    from paddle_tpu.ops.pallas.decode_attention import decode_attention_pallas

    def time_it(jfn, *args, iters=100):
        np.asarray(jfn(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jfn(*args)
        np.asarray(out)
        return round((time.perf_counter() - t0) / iters * 1e3, 4)

    attn = {}
    if jax.devices()[0].platform == "cpu":
        # non-interpret pallas_call cannot lower on CPU, and interpret
        # timings say nothing about the 0.61x-dense hardware question —
        # skip straight to the sections a CPU run CAN answer (the r05
        # crash here used to eat sections 2-5's numbers too)
        report["attn_skipped"] = "cpu backend: kernel timing needs TPU"
        bank()
    else:
        for (b, T, h, kv, d) in ((8, 2048, 16, 8, 128),
                                 (8, 2048, 8, 4, 64),
                                 (1, 4096, 32, 8, 128)):
            try:
                ck = jnp.asarray(rs.randn(b, T, kv, d), jnp.bfloat16)
                cv = jnp.asarray(rs.randn(b, T, kv, d), jnp.bfloat16)
                q1 = jnp.asarray(rs.randn(b, h, d), jnp.bfloat16)
                idx = jnp.int32(T - 2)
                mask = (jnp.arange(T) <= T - 2)[None, None, None, :]
                jd = jax.jit(lambda q, k, v: dense_attention(
                    q[:, None], k, v, attn_mask=mask)[:, 0])
                jp = jax.jit(lambda q, k, v: decode_attention_pallas(
                    q, k, v, idx, d ** -0.5))
                err = float(jnp.max(jnp.abs(
                    jd(q1, ck, cv).astype(jnp.float32)
                    - jp(q1, ck, cv).astype(jnp.float32))))
                key = f"b{b}_T{T}_h{h}_kv{kv}_d{d}"
                attn[key] = {"dense_ms": time_it(jd, q1, ck, cv),
                             "pallas_ms": time_it(jp, q1, ck, cv),
                             "max_err": round(err, 4)}
                # HBM floor: read K+V once
                attn[key]["hbm_floor_ms"] = round(
                    2 * b * T * kv * d * 2 / 819e9 * 1e3, 4)
            except Exception as e:  # one shape must not eat the rest
                attn[f"b{b}_T{T}_h{h}_kv{kv}_d{d}_error"] = repr(e)[:200]
            report["attn"] = attn
            bank()

    # --- 2) end-to-end generate: long decode to amortize dispatch.
    # 256 new tokens vs 64: slope = per-token cost, intercept = overhead.
    pt.seed(0)
    cfg = bench._bench_config("tiny")
    model = LlamaForCausalLM(cfg)
    gen = {}

    def time_generate(m, bs, n_new):
        ids = jnp.asarray(rs.randint(0, m.config.vocab_size, (bs, 32)))
        out = m.generate(ids, max_new_tokens=n_new, temperature=0.0)
        np.asarray(out)      # compile
        t0 = time.perf_counter()
        out = m.generate(ids, max_new_tokens=n_new, temperature=0.0)
        np.asarray(out)
        return time.perf_counter() - t0

    try:
        for bs in (1, 8):
            t64 = time_generate(model, bs, 64)
            t256 = time_generate(model, bs, 256)
            per_tok_ms = (t256 - t64) / 192 * 1e3
            gen[f"bs{bs}"] = {
                "t64_s": round(t64, 4), "t256_s": round(t256, 4),
                "per_token_ms": round(per_tok_ms, 4),
                "dispatch_overhead_ms": round(
                    (t64 * 4 - t256) / 3 * 1e3, 2),
                "tokens_per_sec_steady": round(bs / per_tok_ms * 1e3, 1)}
            report["generate"] = gen
            bank()
    except Exception as e:
        gen["generate_error"] = repr(e)[:200]
        report["generate"] = gen
        bank()

    # weight-read floor for the tiny model: all params once per token
    n_params = sum(int(np.prod(v.shape))
                   for v in model.state_dict().values())
    report["weight_floor_ms_per_tok_bs1"] = round(
        n_params * 2 / 819e9 * 1e3, 4)
    bank()

    # --- 3) fused projections, steady-state
    try:
        from paddle_tpu.nn.fuse import fuse_projections
        pt.seed(0)
        fused = fuse_projections(LlamaForCausalLM(cfg))
        for bs in (1, 8):
            t64 = time_generate(fused, bs, 64)
            t256 = time_generate(fused, bs, 256)
            gen[f"fused_bs{bs}"] = {
                "per_token_ms": round((t256 - t64) / 192 * 1e3, 4)}
            report["generate"] = gen
            bank()
    except Exception as e:
        gen["fused_error"] = repr(e)[:200]
        report["generate"] = gen
        bank()

    # --- 4) int8/int4: kernel route vs forced-XLA-dequant route. Each
    # bits-width guarded on its own so an int4-specific compile failure
    # cannot cost the remaining rungs or section 5 (cf. bench.py).
    from paddle_tpu.quant import quantize_model
    for bits in (8, 4):
        try:
            for tag, disable in ((f"int{bits}_kernel", ""),
                                 (f"int{bits}_xla", "1")):
                os.environ["PADDLE_TPU_DISABLE_QUANT_KERNEL"] = disable
                pt.seed(0)
                qm = LlamaForCausalLM(cfg)
                quantize_model(qm, bits=bits, block_size=128,
                               skip=["lm_head", "embed"])
                for bs in (1, 8):
                    t64 = time_generate(qm, bs, 64)
                    t256 = time_generate(qm, bs, 256)
                    gen[f"{tag}_bs{bs}"] = {
                        "per_token_ms": round((t256 - t64) / 192 * 1e3, 4)}
                    report["generate"] = gen
                    bank()
        except Exception as e:
            gen[f"int{bits}_error"] = repr(e)[:200]
            report["generate"] = gen
            bank()
    os.environ.pop("PADDLE_TPU_DISABLE_QUANT_KERNEL", None)

    # --- 5) paged engine (ISSUE 6): per-tick cost + dispatch-vs-compute
    # split for each tick architecture. Per tick:
    #   tick_ms        = wall around step() (everything)
    #   program_ms     = the engine's decode-step histogram window (the
    #                    jitted call + the (nxt, lps, done) D2H sync)
    #   host_sched_ms  = tick_ms - program_ms (python scheduling,
    #                    mirror bookkeeping, upload staging)
    #   dispatch_floor_ms = a no-op jitted call, fully synced — the
    #                    floor every dispatch pays before any compute
    #   est_compute_ms = program_ms - dispatch_floor_ms
    #   dispatch_overhead_frac = 1 - est_compute_ms / tick_ms
    # The scan row divides its per-dispatch histogram window by K.
    from paddle_tpu.generation.paged import PagedEngine

    # every real tick pays dispatch + a blocking D2H (jax.device_get of
    # the (nxt, lps, done) readback), so the floor must sync EVERY call
    # — an unsynced loop would measure async enqueue throughput on
    # hardware, not the round trip (each np.asarray is that sync)
    noop = jax.jit(lambda x: x + 1)
    z = jnp.zeros((8,), jnp.float32)
    np.asarray(noop(z))
    t0 = time.perf_counter()
    for _ in range(100):
        np.asarray(noop(z))
    floor_ms = (time.perf_counter() - t0) / 100 * 1e3

    # ring on/off A/B (ISSUE 11): "fused_sync" is the r06 architecture
    # (one BLOCKING D2H per dispatch); "fused" is the async token ring
    # (drains ride one dispatch behind — blocking_d2h_per_tick shows
    # the readback amortized away); the scan row composes ring + K=8
    # (<= 1 drain per 8 ticks).
    paged = {"dispatch_floor_ms": round(floor_ms, 4)}
    rs2 = np.random.RandomState(1)
    for tag, kw in (("host_tick", dict(fused_tick=False)),
                    ("fused_sync", dict(ring_mode=False)),
                    ("fused", {}),
                    ("fused_scan8", dict(ticks_per_dispatch=8))):
        K = max(1, kw.get("ticks_per_dispatch", 1))
        eng = PagedEngine(model, max_slots=8, num_blocks=64,
                          block_size=32, max_blocks_per_seq=8,
                          prefill_buckets=(32,), **kw)
        for i in range(8):
            # 8 + 240 = 248 <= max_blocks_per_seq*block_size = 256: the
            # timed ticks never finish a request, so all 8 slots stay
            # busy for the whole window
            eng.submit(f"r{i}", rs2.randint(1, 255, (1, 8)),
                       max_new_tokens=240)
        for _ in range(-(-12 // K)):   # admit + compile
            eng.step()
        _, sum0, cnt0 = eng._h_decode.export()
        d0, u0 = eng.dispatch_count, eng.h2d_uploads
        s0, rd0 = eng.d2h_syncs, eng.ring_drains
        n_steps = max(1, 100 // K)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            eng.step()
        dt = time.perf_counter() - t0
        _, sum1, cnt1 = eng._h_decode.export()
        tick_ms = dt / (n_steps * K) * 1e3
        program_ms = (sum1 - sum0) / max(cnt1 - cnt0, 1) / K
        est_compute = max(program_ms - floor_ms / K, 0.0)
        paged[tag] = {
            "tick_ms": round(tick_ms, 3),
            "program_ms": round(program_ms, 3),
            "host_sched_ms": round(tick_ms - program_ms, 3),
            "est_compute_ms": round(est_compute, 3),
            "dispatch_overhead_frac": round(
                max(1 - est_compute / max(tick_ms, 1e-9), 0.0), 3),
            "tokens_per_sec": round(8 * n_steps * K / dt, 1),
            "dispatches_per_tick": round(
                (eng.dispatch_count - d0) / (n_steps * K), 2),
            "h2d_uploads_per_tick": round(
                (eng.h2d_uploads - u0) / (n_steps * K), 2),
            # the ISSUE 11 acceptance row: blocking readbacks per tick
            # (sync modes pay 1/dispatch; ring drains of ready data
            # count 0 here, ring drains that had to wait count 1)
            "blocking_d2h_per_tick": round(
                (eng.d2h_syncs - s0) / (n_steps * K), 3),
            "ring_drains_per_tick": round(
                (eng.ring_drains - rd0) / (n_steps * K), 3)}
        report["paged"] = paged
        bank()
    base = paged["host_tick"]["tokens_per_sec"]
    for tag in ("fused_sync", "fused", "fused_scan8"):
        paged[tag]["speedup_vs_host_tick"] = round(
            paged[tag]["tokens_per_sec"] / max(base, 1e-9), 2)
    paged["fused"]["speedup_vs_sync"] = round(
        paged["fused"]["tokens_per_sec"]
        / max(paged["fused_sync"]["tokens_per_sec"], 1e-9), 2)
    # headline rung for bench.py ingestion: the best architecture wins
    paged["paged_tokens_per_sec"] = max(
        paged[t]["tokens_per_sec"]
        for t in ("fused_sync", "fused", "fused_scan8"))
    report["paged"] = paged
    bank()

    # --- 6) speculative paged tick (ISSUE 7): accept-rate sweep + the
    # paged_spec_tokens_per_sec rung. A zeroed lm_head makes the tiny
    # model's greedy stream perfectly repetitive (token 0 forever), so
    # prompt-lookup accepts ~every draft — the BEST case; the same
    # spec engine on the random-weight model is the collapse case (the
    # adaptive-k EMA shuts drafting off). Each row carries the same
    # program-vs-host split as section 5 so multi-token commits'
    # dispatch amortization is a number.
    spec = {}
    try:
        pt.seed(0)
        rep_model = LlamaForCausalLM(cfg)
        rep_model.lm_head.weight = rep_model.lm_head.weight * 0.0

        def run_spec(m, new_tok=48, temperature=0.0, **kw):
            eng = PagedEngine(m, max_slots=8, num_blocks=64,
                              block_size=32, max_blocks_per_seq=8,
                              prefill_buckets=(32,), **kw)
            rs4 = np.random.RandomState(3)
            samp = dict(temperature=temperature) if temperature else {}
            eng.submit("warm", rs4.randint(1, 255, (1, 8)),
                       max_new_tokens=2, seed=0, **samp)
            eng.run()          # compile untimed
            for i in range(8):
                eng.submit(i, rs4.randint(1, 255, (1, 8)),
                           max_new_tokens=new_tok, seed=i + 1, **samp)
            # every counter is DELTA'd past the warm-up request, like
            # the _h_decode window — cumulative reads would bias the
            # short spec runs (~6 dispatches) far more than spec-off
            st0 = eng.stats
            _, sum0, cnt0 = eng._h_decode.export()
            _, tpf_sum0, tpf_cnt0 = eng._h_tpf.export()
            t0 = time.perf_counter()
            res = eng.run()
            dt = time.perf_counter() - t0
            _, sum1, cnt1 = eng._h_decode.export()
            _, tpf_sum1, tpf_cnt1 = eng._h_tpf.export()
            n_tok = sum(len(v) for key, v in res.items()
                        if key != "warm")
            st = eng.stats
            nd = max(st["decode_steps"] - st0["decode_steps"], 1)
            prop = st["spec_proposed"] - st0["spec_proposed"]
            # per-slot tokens-per-forward straight from the histogram:
            # one observe per (tick, active slot), value = accepted len
            tpf_sum = tpf_sum1 - tpf_sum0
            tpf_cnt = tpf_cnt1 - tpf_cnt0
            return {
                "tokens_per_sec": round(n_tok / dt, 1),
                "decode_dispatches": nd,
                "tokens_per_forward_per_slot": round(
                    tpf_sum / tpf_cnt, 2) if tpf_cnt else 1.0,
                "tokens_per_dispatch": round(n_tok / nd, 2),
                "program_ms_per_dispatch": round(
                    (sum1 - sum0) / max(cnt1 - cnt0, 1), 3),
                "accept_rate": round(
                    (st["spec_accepted"] - st0["spec_accepted"])
                    / prop, 4) if prop else 0.0,
            }

        spec["spec_off_repetitive"] = run_spec(rep_model)
        for k in (2, 4, 8):
            spec[f"spec_k{k}_repetitive"] = run_spec(rep_model,
                                                     spec_tokens=k)
            report["spec"] = spec
            bank()
        spec["spec_k4_random"] = run_spec(model, spec_tokens=4)
        b0 = spec["spec_off_repetitive"]["tokens_per_sec"]
        for key in spec:
            if key != "spec_off_repetitive":
                spec[key]["speedup_vs_spec_off"] = round(
                    spec[key]["tokens_per_sec"] / max(b0, 1e-9), 2)
        # the rung bench.py ingests alongside paged_tokens_per_sec
        paged["paged_spec_tokens_per_sec"] = max(
            spec[f"spec_k{k}_repetitive"]["tokens_per_sec"]
            for k in (2, 4, 8))
        report["spec"] = spec
        report["paged"] = paged
        bank()
    except Exception as e:
        spec["error"] = repr(e)[:300]
        report["spec"] = spec
        bank()

    # --- 6b) SAMPLED speculative ticks (ISSUE 11): the rejection-
    # sampled verify lets sampled rows ride spec ticks. A decisive
    # TABLE stub (token t argmaxes to (t+1) % 7 with a 12.0 margin —
    # the loadgen-style machinery-not-FLOPs trade) makes the sampled
    # stream repetitive at T=0.7, so accept rates mirror real
    # copy-heavy sampled traffic; spec-off on the same stream is the
    # 1.0 tokens/forward baseline. Rung:
    # paged_sampled_spec_tokens_per_sec (bench.py auto-ingests).
    sspec = {}
    try:
        import jax as _jax
        from paddle_tpu.generation.paged import (paged_chunk_attention,
                                                 paged_decode_attention,
                                                 paged_decode_write,
                                                 paged_prefill_write)

        class _SampCfg:
            vocab_size = 128
            num_hidden_layers = 1
            num_key_value_heads = 1
            head_dim = 8
            dtype = jnp.float32

        class SampStub:
            config = _SampCfg()

            def functional(self):
                d, V = 8, 128
                key = _jax.random.PRNGKey(0)
                params = dict(
                    emb=_jax.random.normal(key, (V, d)),
                    table=_jax.nn.one_hot((jnp.arange(V) + 1) % 7,
                                          V) * 12.0)

                def fn(params, tokens, kv_caches=None, positions=None,
                       paged_chunk=False, paged_decode=False):
                    x = params["emb"][tokens]
                    kv = x[:, :, None, :]
                    pk = kv_caches[0]
                    if tokens.shape[1] == 1 or paged_decode:
                        pk = paged_decode_write(pk, kv, kv)
                        o = paged_decode_attention(
                            x[:, :, None, :], pk)[:, :, 0]
                    else:
                        pk = paged_prefill_write(pk, kv, kv)
                        o = paged_chunk_attention(
                            x[:, :, None, :], pk, positions)[:, :, 0]
                    return (params["table"][tokens]
                            + 0.0 * jnp.sum(o, -1, keepdims=True)), [pk]

                return fn, params

        samp_model = SampStub()
        sspec["sampled_spec_off"] = run_spec(samp_model,
                                             temperature=0.7)
        for k in (2, 4):
            sspec[f"sampled_spec_k{k}"] = run_spec(
                samp_model, temperature=0.7, spec_tokens=k)
            report["sampled_spec"] = sspec
            bank()
        sb = sspec["sampled_spec_off"]["tokens_per_sec"]
        for key in sspec:
            if key != "sampled_spec_off":
                sspec[key]["speedup_vs_spec_off"] = round(
                    sspec[key]["tokens_per_sec"] / max(sb, 1e-9), 2)
        # the rung + its own baseline and tokens/forward: the stub is
        # compute-free, so the ABSOLUTE number only means anything
        # relative to sampled_spec_off on the same stub (on real
        # models the forward dominates and tokens/forward is the
        # transferable win — see docs/PERFORMANCE.md)
        best_k = max((2, 4), key=lambda k: sspec[
            f"sampled_spec_k{k}"]["tokens_per_sec"])
        paged["paged_sampled_spec_tokens_per_sec"] = \
            sspec[f"sampled_spec_k{best_k}"]["tokens_per_sec"]
        paged["paged_sampled_spec_off_tokens_per_sec"] = sb
        paged["paged_sampled_spec_tokens_per_forward"] = \
            sspec[f"sampled_spec_k{best_k}"][
                "tokens_per_forward_per_slot"]
        report["sampled_spec"] = sspec
        report["paged"] = paged
        bank()
    except Exception as e:
        sspec["error"] = repr(e)[:300]
        report["sampled_spec"] = sspec
        bank()
    # --- 7/7b) churn A/B/C (ISSUE 14 + 19): slot transitions under
    # serving-like traffic — short requests queued deep, so a finish +
    # admit lands every few ticks. Three transition modes:
    #   full_rebuild: a FULL host-mirror rebuild + re-upload per churn
    #     tick (the pre-ISSUE-14 path);
    #   delta: one descriptor-sized patch per transition — its own
    #     tiny dispatch (PR 12, kept as an explicit knob);
    #   fused (the engine default): descriptors staged into the
    #     device-resident queue by a plain upload and applied by the
    #     NEXT tick's program — one dispatch per tick, churn or not.
    # Rows report dispatches/tick (the ISSUE 19 claim), uploads/tick,
    # upload BYTES/tick, rebuild/patch/fused counts and tokens/s; the
    # delta row's throughput is the ``paged_churn_tokens_per_sec``
    # rung and the fused row's is ``paged_churn_fused_tokens_per_sec``,
    # both auto-ingested by bench.py beside the other paged rungs.
    # The stub keeps this a TRANSITION-MACHINERY A/B (like §6b's
    # decisive-table stub: the absolute number only means anything
    # relative to the other row on the same stub — on real models the
    # forward dominates and the transferable win is upload bytes +
    # zero rebuild stalls). Budgets are STAGGERED (max_new=4+i%5) so a
    # finish+admit lands every 1-2 ticks instead of 8 at once — the
    # serving churn shape; synchronized batch finishes amortize a full
    # rebuild over 8 transitions and favor the reference.
    churn = {}
    try:
        from paddle_tpu.generation.stub import TickStubModel

        def run_churn(n_req=96, **kw):
            eng = PagedEngine(TickStubModel(), max_slots=8,
                              num_blocks=64, block_size=32,
                              max_blocks_per_seq=8,
                              prefill_buckets=(32,), **kw)
            rs6 = np.random.RandomState(7)
            # two STAGGERED warm requests: the second's admit lands
            # mid-decode of the first, so the transition path (the
            # patch program in delta mode) compiles untimed like the
            # tick/prefill executables — a cold first patch otherwise
            # bills its trace+compile to the measured window
            eng.submit("warm", rs6.randint(1, 120, (1, 8)),
                       max_new_tokens=6)
            eng.step()
            eng.step()
            eng.submit("warm2", rs6.randint(1, 120, (1, 8)),
                       max_new_tokens=4)
            eng.run()
            for i in range(n_req):
                eng.submit(i, rs6.randint(1, 120, (1, 8)),
                           max_new_tokens=4 + i % 5)
            st0 = eng.stats
            u0, b0 = eng.h2d_uploads, eng.h2d_upload_bytes
            fr0, dp0 = eng.full_rebuilds, eng.delta_patches
            pf0, dc0 = eng.patches_fused, eng.dispatch_count
            t0 = time.perf_counter()
            res = eng.run()
            dt = time.perf_counter() - t0
            n_tok = sum(len(v) for key, v in res.items()
                        if key not in ("warm", "warm2"))
            ticks = max(eng.stats["decode_steps"]
                        - st0["decode_steps"], 1)
            return {
                "tokens_per_sec": round(n_tok / dt, 1),
                "decode_ticks": ticks,
                "full_rebuilds": eng.full_rebuilds - fr0,
                "delta_patches": eng.delta_patches - dp0,
                "patches_fused": eng.patches_fused - pf0,
                "dispatches_per_tick": round(
                    (eng.dispatch_count - dc0) / ticks, 3),
                "h2d_uploads_per_tick": round(
                    (eng.h2d_uploads - u0) / ticks, 3),
                "h2d_upload_bytes_per_tick": round(
                    (eng.h2d_upload_bytes - b0) / ticks, 1),
            }

        # best-of-3 per mode: single-core wall clocks on a shared box
        # are noisy and the A/B question is the achievable rate
        def best(**kw):
            rows = [run_churn(**kw) for _ in range(3)]
            return max(rows, key=lambda r: r["tokens_per_sec"])

        churn["full_rebuild"] = best(delta_transitions=False)
        churn["delta"] = best(patch_fuse=False)
        churn["fused"] = best()
        churn["delta"]["speedup_vs_rebuild"] = round(
            churn["delta"]["tokens_per_sec"]
            / max(churn["full_rebuild"]["tokens_per_sec"], 1e-9), 2)
        churn["fused"]["speedup_vs_rebuild"] = round(
            churn["fused"]["tokens_per_sec"]
            / max(churn["full_rebuild"]["tokens_per_sec"], 1e-9), 2)
        churn["fused"]["speedup_vs_delta"] = round(
            churn["fused"]["tokens_per_sec"]
            / max(churn["delta"]["tokens_per_sec"], 1e-9), 2)
        # the ISSUE 14 acceptance row: steady churn, zero full rebuilds
        churn["delta_zero_rebuilds"] = \
            churn["delta"]["full_rebuilds"] == 0
        # the ISSUE 19 acceptance rows: the fused run kept churn to
        # ~one dispatch per tick with zero standalone patch programs
        churn["fused_zero_standalone_patches"] = \
            churn["fused"]["delta_patches"] == 0 \
            and churn["fused"]["full_rebuilds"] == 0
        paged["paged_churn_tokens_per_sec"] = \
            churn["delta"]["tokens_per_sec"]
        paged["paged_churn_fused_tokens_per_sec"] = \
            churn["fused"]["tokens_per_sec"]
        report["churn"] = churn
        report["paged"] = paged
        bank()
    except Exception as e:
        churn["error"] = repr(e)[:300]
        report["churn"] = churn
        bank()

    # machine-ingestible line (bench.py merges DECODE_PROFILE_r06.json's
    # paged section into its decode rung when the file is present)
    print("PAGED_JSON " + json.dumps(paged), flush=True)
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # bank whatever we got plus the failure
        report["error"] = repr(e)[:400]
        bank()
        raise
