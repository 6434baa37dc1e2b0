#!/usr/bin/env python
"""Training-step bench (SURVEY.md §6): Llama train-step tokens/sec/chip +
MFU on the local chip. Prints EXACTLY ONE JSON line on stdout, ALWAYS —
success or failure — and exits non-zero unless every stage succeeded.
vs_baseline = achieved MFU / 0.40 (the reference's Llama-3 pretraining
MFU target in BASELINE.json).

  (a) PROBE first: a subprocess that only calls ``jax.devices()``. If the
      backend does not come up we stop *before* building any model and
      emit a failure JSON with the probe evidence.
  (b) HARD TOTAL BUDGET: everything (probe + all attempts + retries) fits
      in PADDLE_TPU_BENCH_BUDGET seconds (default 450s < 8 min); each
      subprocess timeout is clamped to the remaining budget.
  (c) ALWAYS-EMIT JSON: every exit path prints one machine-readable line —
      on failure ``{"error":..., "probe":..., "attempts":N, ...}``.
  (d) CONFIG LADDER: a tiny model first (compiles in seconds), then the
      ~470M headline config only if budget remains. The best successful
      rung wins.

This parent never touches jax: a chip belongs to one process at a time,
so each rung runs in a fresh child process, one after the other."""
import functools
import json
import os
import subprocess
import sys
import time

BATCH, SEQ = 8, 2048
TINY_BATCH, TINY_SEQ = 8, 1024


# ---------------------------------------------------------------- children

def _child_probe():
    """Backend-reachability probe: jax.devices() and nothing else."""
    t0 = time.time()
    import jax
    devs = jax.devices()
    print(json.dumps({
        "probe_ok": True,
        "n_devices": len(devs),
        "device_kind": devs[0].device_kind,
        "platform": devs[0].platform,
        "probe_s": round(time.time() - t0, 1),
    }))


def _bench_config(rung):
    from paddle_tpu.models import LlamaConfig
    import jax.numpy as jnp
    if os.environ.get("PADDLE_TPU_BENCH_SMOKE"):
        # machinery self-test (probe -> ladder -> JSON) on any backend; the
        # numbers it yields are meaningless.
        return LlamaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128, recompute=False, dtype=jnp.float32)
    if rung == "tiny":
        # ~67M params: compiles in seconds, still MXU-bound bf16 matmuls.
        return LlamaConfig(
            vocab_size=8192, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=4,
            max_position_embeddings=TINY_SEQ, rope_theta=500000.0,
            recompute=False, dtype=jnp.bfloat16)
    # headline: ~470M-param Llama shaped to saturate a single v5e (16G HBM)
    # with remat; same code path as the 8B recipe. The "_dots" variant
    # keeps weight-matmul outputs in HBM and reruns only elementwise
    # chains — fewer recompute FLOPs if the activations fit.
    policy = ("dots_with_no_batch_dims_saveable" if rung == "headline_dots"
              else "full")
    return LlamaConfig(
        vocab_size=32768, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=8, num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=SEQ, rope_theta=500000.0,
        recompute=True, recompute_policy=policy, dtype=jnp.bfloat16)


def _child_bench(rung):
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM, causal_lm_loss
    from paddle_tpu.utils import compile_cache
    from paddle_tpu.utils.profiler import device_peak_flops
    # persistent compilation cache: shared across rungs/attempts/processes.
    compile_cache.enable()

    batch, seq = (TINY_BATCH, TINY_SEQ) if rung == "tiny" else (BATCH, SEQ)
    if os.environ.get("PADDLE_TPU_BENCH_SMOKE"):
        batch, seq = 2, 128
    dev = jax.devices()[0]
    # None for a device with no published peak (the CPU): then the rung
    # reports no MFU at all, never one against an assumed peak
    peak = device_peak_flops(dev)
    pt.seed(0)
    cfg = _bench_config(rung)
    model = LlamaForCausalLM(cfg)
    fn, params = model.functional()
    n_params = sum(int(np.prod(v.shape)) for v in params.values())

    opt = pt.optimizer.AdamW(learning_rate=1e-4, multi_precision=True,
                             grad_clip=pt.optimizer.ClipGradByGlobalNorm(1.0))
    state = opt.init(params)
    ids = jnp.asarray(np.random.randint(0, cfg.vocab_size, (batch, seq)))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, state, step, ids):
        def loss_fn(p):
            return causal_lm_loss(fn(p, ids), ids)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, state = opt.apply(params, grads, state, step)
        return params, state, loss

    # warmup/compile (float() waits for the device)
    params, state, loss = train_step(params, state, jnp.int32(0), ids)
    float(loss)

    steps = 5 if rung == "tiny" else 10
    t0 = time.perf_counter()
    for i in range(1, steps + 1):
        params, state, loss = train_step(params, state, jnp.int32(i), ids)
    float(loss)
    dt = (time.perf_counter() - t0) / steps

    tokens_per_sec = batch * seq / dt
    # Honest 6N (VERDICT r1 weak#3): the input-embedding forward is a
    # gather, not a matmul, so its params don't belong in 6N; lm_head does
    # (it IS a matmul). mfu_legacy keeps round 1's all-params formula once
    # for continuity.
    embed_params = cfg.vocab_size * cfg.hidden_size
    matmul_params = n_params - embed_params
    attn_flops = 6 * cfg.num_hidden_layers * seq * cfg.hidden_size
    flops_per_token = 6 * matmul_params + attn_flops
    def over_peak(flops, digits, scale=1.0):
        if peak is None:
            return None
        return round(flops * tokens_per_sec / peak * scale, digits)

    print(json.dumps({
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": over_peak(flops_per_token, 3, 1 / 0.40),
        "mfu": over_peak(flops_per_token, 4),
        "mfu_legacy": over_peak(6 * n_params + attn_flops, 4),
        "config": rung,
        "params": n_params,
        "step_ms": round(dt * 1e3, 2),
        "device": dev.device_kind,
        "loss": round(float(loss), 4),
    }))


def _child_decode():
    """Decode-path bench (VERDICT r2 item 5): per-step latency of the old
    masked-dense attention over the full cache vs the new GQA-native
    decode path (Pallas kernel on TPU), plus end-to-end generate()
    tokens/s at bs=1 and bs=8. A phase that fails is recorded under an
    ``*_error`` key (the other phases still run) and the child then
    exits non-zero."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import paddle_tpu as pt
    from paddle_tpu.ops.attention import decode_attention, dense_attention
    from paddle_tpu.models import LlamaForCausalLM

    smoke = bool(os.environ.get("PADDLE_TPU_BENCH_SMOKE"))
    b, T, h, kv, d = (2, 256, 4, 2, 64) if smoke else (8, 2048, 16, 8, 128)
    rs = np.random.RandomState(0)
    dt = jnp.bfloat16
    q = jnp.asarray(rs.randn(b, 1, h, d), dt)
    ck = jnp.asarray(rs.randn(b, T, kv, d), dt)
    cv = jnp.asarray(rs.randn(b, T, kv, d), dt)
    idx = jnp.int32(T - 2)

    def dense_ref(q, ck, cv, idx):
        mask = (jnp.arange(T) <= idx)[None, None, None, :]
        return dense_attention(q, ck, cv, attn_mask=mask)

    def time_it(fn, *args, iters=50):
        jfn = jax.jit(fn)  # one wrapper: iterations hit the trace cache
        np.asarray(jfn(*args))  # compile + run to completion
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jfn(*args)
        np.asarray(out)  # device queue is FIFO: last done => all done
        return (time.perf_counter() - t0) / iters * 1e3  # ms

    ms_dense = time_it(dense_ref, q, ck, cv, idx)
    ms_decode = time_it(decode_attention, q, ck, cv, idx)

    # end-to-end generate tokens/s (static cache, while_loop decode)
    pt.seed(0)
    model = LlamaForCausalLM(_bench_config("tiny"))
    gen = {}
    new_tok = 16 if smoke else 64

    def time_generate(m, bs, tag):
        ids = jnp.asarray(rs.randint(0, m.config.vocab_size, (bs, 32)))
        out = m.generate(ids, max_new_tokens=new_tok, temperature=0.0)
        np.asarray(out)  # compile + force execution (see time_it)
        t0 = time.perf_counter()
        out = m.generate(ids, max_new_tokens=new_tok, temperature=0.0)
        np.asarray(out)
        dt_s = time.perf_counter() - t0
        gen[tag] = round(bs * new_tok / dt_s, 1)

    for bs in (1, 8):
        time_generate(model, bs, f"generate_tokens_per_sec_bs{bs}")

    # fused q/k/v + gate/up projections (VERDICT r3 item 2: attack the
    # decode while_loop body) — same weights, fewer matmul launches
    try:
        from paddle_tpu.nn.fuse import fuse_projections
        pt.seed(0)
        fused = fuse_projections(LlamaForCausalLM(_bench_config("tiny")))
        for bs in (1, 8):
            time_generate(fused, bs,
                          f"generate_fused_tokens_per_sec_bs{bs}")
    except Exception as e:  # keep the rung's other numbers
        gen["fused_error"] = repr(e)[:120]

    # int8/int4 weight-only decode: half/quarter the HBM bytes per token
    # — the main lever for the memory-bound decode regime (the int4
    # nibble path cleared its hardware compile-check in round 5)
    for bits in (8, 4):
        try:
            from paddle_tpu.quant import quantize_model
            pt.seed(0)
            qmodel = LlamaForCausalLM(_bench_config("tiny"))
            n_swapped = quantize_model(qmodel, bits=bits, block_size=128,
                                       skip=["lm_head", "embed"])
            assert n_swapped > 0, "quantize_model swapped nothing"
            for bs in (1, 8):
                time_generate(qmodel, bs,
                              f"generate_int{bits}_tokens_per_sec_bs{bs}")
        except Exception as e:
            gen[f"int{bits}_error"] = repr(e)[:120]

    # speculative decoding with a 1-layer draft of the same family
    # (VERDICT r3 weak #5: a measured tokens/s comparison)
    try:
        from paddle_tpu.generation.speculative import speculative_generate
        pt.seed(0)
        cfg = _bench_config("tiny")
        cfg.num_hidden_layers = 1
        draft = LlamaForCausalLM(cfg)
        ids = jnp.asarray(rs.randint(0, model.config.vocab_size, (1, 32)))
        out = speculative_generate(model, draft, ids,
                                   max_new_tokens=new_tok,
                                   num_draft_tokens=4)
        np.asarray(out)
        t0 = time.perf_counter()
        out, stats = speculative_generate(model, draft, ids,
                                          max_new_tokens=new_tok,
                                          num_draft_tokens=4,
                                          return_stats=True)
        np.asarray(out)
        dt_s = time.perf_counter() - t0
        gen["speculative_tokens_per_sec_bs1"] = round(new_tok / dt_s, 1)
        gen["speculative_tokens_per_forward"] = round(
            stats["tokens_per_forward"], 2)

        # random-init drafts accept ~nothing (tokens_per_forward ~1), so
        # the rung above is the floor. The CEILING — what a well-trained
        # draft buys — is draft == target: every proposal accepted.
        out = speculative_generate(model, model, ids,
                                   max_new_tokens=new_tok,
                                   num_draft_tokens=4)
        np.asarray(out)
        t0 = time.perf_counter()
        out, stats = speculative_generate(model, model, ids,
                                          max_new_tokens=new_tok,
                                          num_draft_tokens=4,
                                          return_stats=True)
        np.asarray(out)
        dt_s = time.perf_counter() - t0
        gen["speculative_ceiling_tokens_per_sec_bs1"] = round(
            new_tok / dt_s, 1)
        gen["speculative_ceiling_tokens_per_forward"] = round(
            stats["tokens_per_forward"], 2)
    except Exception as e:  # keep the rung's other numbers
        gen["speculative_error"] = repr(e)[:120]

    # paged continuous batching: mixed-length stream throughput
    try:
        from paddle_tpu.generation.paged import PagedEngine
        eng = PagedEngine(model, max_slots=8, num_blocks=64,
                          block_size=32, max_blocks_per_seq=8,
                          prefill_buckets=(32,))
        rs2 = np.random.RandomState(1)
        # warmup: compile the prefill + decode executables untimed,
        # like every other number in this rung
        eng.submit("warm", rs2.randint(1, model.config.vocab_size,
                                       (1, 32)), max_new_tokens=2)
        eng.run()
        for i in range(16):
            eng.submit(i, rs2.randint(1, model.config.vocab_size,
                                      (1, 32)), max_new_tokens=new_tok)
        t0 = time.perf_counter()
        res = eng.run()
        dt_s = time.perf_counter() - t0
        n_tok = sum(len(v) for v in res.values())
        gen["paged_tokens_per_sec"] = round(n_tok / dt_s, 1)
        gen["paged_active_slot_frac"] = round(
            eng.stats["active_slot_steps"]
            / max(eng.stats["slot_steps"], 1), 3)
    except Exception as e:
        gen["paged_error"] = repr(e)[:120]

    # prefix caching (round 5): 16 requests sharing a 64-token system
    # prompt — the cached run should skip most prefill chunks
    try:
        from paddle_tpu.generation.paged import PagedEngine
        rs3 = np.random.RandomState(2)
        sysp = rs3.randint(1, model.config.vocab_size, 64).tolist()
        reqs = [np.asarray([sysp + rs3.randint(
            1, model.config.vocab_size, 8).tolist()]) for _ in range(16)]
        for tag, pc in (("prefix_cache_on", True),
                        ("prefix_cache_off", False)):
            eng = PagedEngine(model, max_slots=8, num_blocks=96,
                              block_size=32, max_blocks_per_seq=8,
                              prefill_buckets=(32,),
                              chunk_prefill_tokens=32,
                              enable_prefix_cache=pc)
            # compile BOTH the miss path and (cache on) the adoption
            # path before timing: warm2 shares warm's prefix, so its
            # admission exercises the seen-seed + adoption scatters
            eng.submit("warm", reqs[0], max_new_tokens=2)
            eng.run()
            eng.submit("warm2", np.asarray([sysp + [9, 9]]),
                       max_new_tokens=2)
            eng.run()
            warm_chunks = eng.stats["prefill_chunks"]
            t0 = time.perf_counter()
            for i, ids in enumerate(reqs):
                eng.submit(i, ids, max_new_tokens=16)
            res = eng.run()
            dt_s = time.perf_counter() - t0
            # count only the timed requests (results accumulate the
            # warmups too) and only the timed batch's chunks
            n_tok = sum(len(res[i]) for i in range(len(reqs)))
            gen[f"paged_{tag}_tokens_per_sec"] = round(n_tok / dt_s, 1)
            gen[f"paged_{tag}_prefill_chunks"] = \
                eng.stats["prefill_chunks"] - warm_chunks
    except Exception as e:
        gen["prefix_cache_error"] = repr(e)[:120]

    print(json.dumps({"decode": {
        "attn_ms_dense": round(ms_dense, 3),
        "attn_ms_decode_kernel": round(ms_decode, 3),
        "attn_speedup": round(ms_dense / ms_decode, 2),
        "shape": f"b{b} T{T} h{h} kv{kv} d{d}",
        **gen,
    }}))
    failed = sorted(k for k in gen if k.endswith("_error"))
    if failed:
        sys.exit(f"decode rung phases failed: {failed}")


# ------------------------------------------------------------------ parent

def _run_child(mode, timeout):
    """Run one child rung; return (rc, parsed_json_or_None, stderr_tail)."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env={**os.environ, "_PADDLE_TPU_BENCH_CHILD": mode},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, timeout=timeout)
        rc, out = proc.returncode, proc.stdout.decode(errors="replace")
        err = proc.stderr.decode(errors="replace")
    except subprocess.TimeoutExpired as e:
        out = (e.stdout or b"").decode(errors="replace")
        err = (e.stderr or b"").decode(errors="replace")
        rc = 124
    parsed = None
    for line in reversed(out.strip().splitlines()):
        try:
            parsed = json.loads(line)
            break
        except ValueError:
            continue
    return rc, parsed, err[-800:]


def _ingest_rung(result, probe, filename, section_key, profile_field,
                 promote):
    """Fold one rung file (written by tools/serve_loadgen.py or
    tools/fleet_sim.py next to this script) into the bench result:
    always annotate ``result["decode"][profile_field]`` with the full
    section + provenance; promote the keys in ``promote`` (first one
    required for the file to count at all) only under the same-device
    + <6h freshness gate. Missing/corrupt files are ignored."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        filename)
    try:
        with open(path) as f:
            pj = json.load(f)
        section = pj.get(section_key)
        if not section or promote[0] not in section:
            return
        result.setdefault("decode", {})
        result["decode"][profile_field] = dict(
            section, profile_device=pj.get("device"),
            profile_started=pj.get("started"))
        try:
            age_s = time.time() - time.mktime(time.strptime(
                pj["started"], "%Y-%m-%d %H:%M:%S"))
        except (KeyError, ValueError):
            age_s = float("inf")
        if pj.get("device") == probe.get("device_kind") \
                and age_s < 6 * 3600:
            for key in promote:
                if key in section:
                    result["decode"].setdefault(key, section[key])
    except (OSError, ValueError):
        pass


def main():
    budget = float(os.environ.get("PADDLE_TPU_BENCH_BUDGET", 450))
    t0 = time.monotonic()

    def remaining():
        return budget - (time.monotonic() - t0)

    failures = []
    attempts = 0

    # (a) probe: does the backend come up? The first attempt is cheap
    # (25s) so a backend that hangs costs ~100s of the budget, not 150s.
    probe = None
    for probe_t in (25.0, 75.0):
        if remaining() < 20:
            break
        attempts += 1
        rc, parsed, err = _run_child(
            "probe", min(probe_t, max(remaining() - 10, 15)))
        if rc == 0 and parsed and parsed.get("probe_ok"):
            probe = parsed
            break
        failures.append({"stage": "probe", "rc": rc,
                         "stderr_tail": err[-300:]})
    if probe is None:
        print(json.dumps({
            "metric": "llama_train_tokens_per_sec_per_chip",
            "value": 0.0, "unit": "tokens/s/chip", "vs_baseline": 0.0,
            "error": "backend unreachable: jax.devices() probe failed/hung",
            "probe": failures, "attempts": attempts,
            "budget_s": budget, "elapsed_s": round(time.monotonic() - t0, 1),
        }))
        return 3

    # (b/d) ladder: bank a tiny number, then the headline config, then the
    # lighter-remat headline variant (kept only if it measures FASTER —
    # it can OOM or lose, in which case the plain headline stands).
    result = None
    for rung, max_t, min_t in (("tiny", 240.0, 45.0),
                               ("headline", 420.0, 150.0),
                               ("headline_dots", 300.0, 120.0)):
        if remaining() < min_t:
            break
        if rung == "headline_dots" and (result is None or
                                        result.get("config") != "headline"):
            continue  # only as an upgrade attempt over a banked headline
        attempts += 1
        rc, parsed, err = _run_child(rung, min(max_t, remaining() - 15))
        if rc == 0 and parsed and "value" in parsed:
            if rung == "headline_dots" and result is not None and \
                    parsed["value"] <= result["value"]:
                continue  # not an improvement; keep the plain headline
            result = parsed
        else:
            failures.append({"stage": rung, "rc": rc,
                             "stderr_tail": err[-300:]})
            # one retry per rung if the failure looks transient and the
            # budget allows; a hang (rc=124) is NOT retried — it would
            # just burn the rest of the budget the same way.
            transient = rc != 124 and any(
                s in err for s in ("UNAVAILABLE", "DEADLINE_EXCEEDED",
                                   "failed to connect", "Socket closed"))
            if transient and remaining() > min_t + 30:
                attempts += 1
                rc, parsed, err = _run_child(rung, min(max_t, remaining() - 15))
                if rc == 0 and parsed and "value" in parsed:
                    result = parsed
                else:
                    failures.append({"stage": rung + "_retry", "rc": rc,
                                     "stderr_tail": err[-300:]})

    # decode-path bench rides along if a training number is banked and
    # budget remains (its JSON merges into the result).
    if result is not None and remaining() > 70:
        attempts += 1
        rc, parsed, err = _run_child("decode", min(200.0, remaining() - 15))
        if parsed and "decode" in parsed:
            result["decode"] = parsed["decode"]
        if rc != 0:     # a failed phase inside it fails the stage too
            failures.append({"stage": "decode", "rc": rc,
                             "stderr_tail": err[-300:]})

    # Loadgen rung ingestion (serve_loadgen, ISSUE 9; fleet_sim):
    # annotate the banked bench with the profile either way, but
    # promote the headline keys
    # only when the file came from THIS window (same device kind,
    # started < 6h ago — a stale CPU-run file, or a week-old hardware
    # window's, must not masquerade as this run's number).
    if result is not None:
        _ingest_rung(result, probe, "SERVE_LOADGEN_r07.json", "gateway",
                     "gateway_profile",
                     ("gateway_tokens_per_sec", "gateway_p99_ttft_ms",
                      "kv_spill_hit_frac", "kv_spill_restored_tokens",
                      "kv_xfer_hit_frac", "recompute_tokens_saved",
                      "phase_breakdown"))
        _ingest_rung(result, probe, "SERVE_FLEET_r13.json", "fleet",
                     "fleet_profile",
                     ("fleet_tokens_per_sec", "goodput_per_replica"))
        _ingest_rung(result, probe, "FLEET_SIM_r16.json", "fleet_sim",
                     "fleet_sim_profile",
                     ("sim_decisions_per_sec", "alert_precision",
                      "alert_recall"))

    # (c) always emit exactly one JSON line; a stage that failed along
    # the way is listed in it AND fails the exit code.
    if result is not None:
        result["probe"] = {k: probe[k] for k in
                           ("device_kind", "probe_s", "n_devices")}
        result["attempts"] = attempts
        if failures:
            result["failures"] = failures
        print(json.dumps(result))
        return 5 if failures else 0
    print(json.dumps({
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": 0.0, "unit": "tokens/s/chip", "vs_baseline": 0.0,
        "error": "probe ok but all bench rungs failed",
        "probe": probe, "failures": failures, "attempts": attempts,
        "budget_s": budget, "elapsed_s": round(time.monotonic() - t0, 1),
    }))
    return 4


if __name__ == "__main__":
    mode = os.environ.get("_PADDLE_TPU_BENCH_CHILD")
    if mode == "probe":
        _child_probe()
    elif mode == "decode":
        _child_decode()
    elif mode in ("tiny", "headline", "headline_dots"):
        _child_bench(mode)
    else:
        sys.exit(main())
