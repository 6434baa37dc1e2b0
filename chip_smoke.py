#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

    python3 chip_smoke.py            # on a TPU machine, from the repo root

One process, no children. It builds one warmed ``PagedEngine`` per
device in ``jax.devices()`` over ``Qwen2ForCausalLM`` at the published
Qwen2-7B widths (depth cut, seeded random weights), wraps them in one
``paddle_tpu.serving.Gateway`` — the entry points docs/SERVING.md gives
a user — and sends a few requests over real HTTP/SSE on a loopback
socket. Then it checks what came out by the repo's own means and prints
two lines on stdout: one JSON object ``{"facts": {...}}`` describing the
run, and LAST, exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
with the device as JAX reports it — the line the accelerator check reads.

Any failed phase exits non-zero with the reason on stderr and neither
line: no TPU (the platform found is named), ``PADDLE_TPU_PALLAS_
INTERPRET`` set, a wrong answer, a decode program that fell off the
Pallas route, a misplaced array, a supervisor that had to rebuild
anything. Diagnostics go to stderr; stdout carries only those two lines.

The numbers in the facts (set-up seconds, serve seconds, token counts,
compile-cache entries) are facts about this run, not performance
figures.
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SEED = 0

# Qwen2-7B as published (paddle_tpu/models/qwen2.py: hidden 3584, 28
# heads / 4 kv heads, head_dim 128, FFN 18944, vocab 151936, biased
# q/k/v, bf16). ONLY depth is cut: 28 layers are 15.2 GB in bf16 and do
# not fit a 16 GB chip next to a KV pool. 16 layers are 9.6 GB of
# weights; with the 1.1 GB pool below and the 2 GB the reference forward
# needs for its float32 logits that is about 13 GB, and 20 layers would
# leave no headroom.
DEPTH = 16

# A serving geometry, not the 256-token test one: 8 slots, 2048 tokens a
# sequence (128 blocks of 16), a pool twice what 8 full sequences need,
# chunked prefill and the prefix cache on. Every other engine knob is at
# its default (fused tick, token ring, delta + fused patches).
GEOMETRY = dict(max_slots=8, block_size=16, max_blocks_per_seq=128,
                num_blocks=2049, chunk_prefill_tokens=256,
                enable_prefix_cache=True)

# |engine logprob - reference logprob| allowed for a greedy token. Both
# sides are bf16 forwards of the same weights whose last step (logits,
# log-softmax) is float32; they differ in the order of bf16 roundings —
# chunked prefill + the paged kernel's per-block online softmax against
# one dense causal pass. With these weights the logits have a spread of
# about 1.2, the chosen token sits near -7.5, and a token the model did
# not choose near -12.6. The worst difference measured on a v5e over the
# smoke's 128 greedy tokens was 0.0079 at 16 layers and 0.0040 at 8
# (PR 21 chip runs), so 0.05 leaves a factor of six for other seeds and
# depths, while attention output that is garbage for even one kv group
# moves these logprobs by whole units.
LOGPROB_ATOL = 0.05


class SmokeFailure(Exception):
    """A check did not hold; the message is the reason printed."""


def note(msg: str):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def require(cond, reason: str):
    if not cond:
        raise SmokeFailure(reason)


# ------------------------------------------------------------------ build
def build_engine(cfg, geometry, device):
    """One warmed engine with its own weights on ``device`` (an engine
    lives where its weights live). Also what a supervisor's
    ``engine_factory`` would call."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.generation.paged import PagedEngine
    from paddle_tpu.models.qwen2 import Qwen2ForCausalLM
    t0 = time.perf_counter()
    with jax.default_device(device):
        pt.seed(SEED)      # same weights on every replica
        engine = PagedEngine(Qwen2ForCausalLM(cfg), **geometry)
        t1 = time.perf_counter()
        warm(engine)
    note(f"{device}: weights + pools in {t1 - t0:.1f}s, programs "
         f"compiled and warmed in {time.perf_counter() - t1:.1f}s")
    return engine


def warm(engine):
    """Compile every program the traffic can reach BEFORE the gateway
    takes any: the supervisor's watchdog would read a cold compile at
    these widths as a hang. A greedy two-chunk prompt compiles the chunk
    prefill and the all-greedy tick; a sampled request on the same
    prefix compiles the mixed tick and the prefix-adoption path."""
    import numpy as np
    C, V = engine.chunk, engine.model.config.vocab_size
    ids = np.random.RandomState(SEED + 1).randint(1, V, C + 16).tolist()
    engine.submit("warm-greedy", ids[:C + 8], max_new_tokens=4)
    engine.run()
    engine.submit("warm-sampled", ids[:C] + ids[C + 8:], max_new_tokens=4,
                  temperature=0.8, top_k=50, top_p=0.95, seed=1)
    engine.run()
    for rid in ("warm-greedy", "warm-sampled"):
        require(len(engine.results.pop(rid)) == 4,
                f"warm-up request {rid} did not finish")
        engine.logprobs.pop(rid)


def build_engines(cfg, geometry, devices):
    """One engine per device, built side by side (XLA compiles outside
    the interpreter lock, so four chips cost about what one does)."""
    with concurrent.futures.ThreadPoolExecutor(len(devices)) as pool:
        futs = [pool.submit(build_engine, cfg, geometry, d)
                for d in devices]
        return [f.result() for f in futs]


def tokens_served(engine) -> int:
    """Tokens this engine has emitted: one per finished prefill plus one
    per decode-tick commit."""
    st = engine.stats
    return st["prefills"] + st["active_slot_steps"]


# ---------------------------------------------------------------- traffic
def make_traffic(cfg, geometry, n_replicas: int):
    """(wave, after): ``wave`` goes out concurrently, ``after`` one at a
    time once the wave is answered — a request can only adopt prefix
    blocks that already exist, so the second of the two that share a
    chunk-aligned prefix is sent after the first."""
    import numpy as np
    rs = np.random.RandomState(SEED + 2)
    C, V = geometry["chunk_prefill_tokens"], cfg.vocab_size
    cap = geometry["max_blocks_per_seq"] * geometry["block_size"]

    def ids(n):
        return rs.randint(1, V, n).tolist()

    shared = ids(2 * C)
    long_n = min(1000, cap // 2 - 24)
    wave = [
        dict(name="short", prompt=ids(24), max_new_tokens=32),
        dict(name="long", prompt=ids(long_n), max_new_tokens=24),
        dict(name="prefix-a", prompt=shared + ids(16), max_new_tokens=24),
        dict(name="sampled", prompt=ids(40), max_new_tokens=32,
             temperature=0.8, top_k=50, top_p=0.95, seed=1234),
    ]
    # enough concurrent streams that every replica gets one
    for i in range(2 * n_replicas - len(wave)):
        wave.append(dict(name=f"cover-{i}", prompt=ids(48 + 8 * i),
                         max_new_tokens=16))
    after = [
        dict(name="prefix-b", prompt=shared + ids(16), max_new_tokens=24),
        dict(name="nonstream", prompt=ids(64), max_new_tokens=24,
             stream=False),
    ]
    return wave, after


async def http_request(port: int, method: str, path: str, body=None):
    """One HTTP/1.1 exchange on the loopback socket. Returns (status,
    events): the parsed SSE ``data:`` events for an event stream, else
    the one parsed JSON (or raw text) body."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        payload = json.dumps(body).encode() if body is not None else b""
        writer.write((f"{method} {path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                      f"Content-Length: {len(payload)}\r\n\r\n").encode()
                     + payload)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        headers = {}
        while (line := await reader.readline()) not in (b"\r\n", b"\n", b""):
            k, _, v = line.decode("latin1").partition(":")
            headers[k.strip().lower()] = v.strip()
        if headers.get("content-type", "").startswith("text/event-stream"):
            events = []
            while line := await reader.readline():
                if line.startswith(b"data: "):
                    events.append(json.loads(line[6:]))
                    if events[-1].get("done"):
                        break
            return status, events
        raw = await reader.readexactly(int(headers["content-length"]))
        if headers.get("content-type", "").startswith("application/json"):
            return status, json.loads(raw)
        return status, raw.decode()
    finally:
        writer.close()
        await writer.wait_closed()


async def generate(port: int, spec: dict) -> dict:
    """POST one request; fold the answer into a record."""
    body = {k: v for k, v in spec.items() if k != "name"}
    status, ans = await http_request(port, "POST", "/v1/generate", body)
    rec = dict(spec, status=status)
    if status != 200:
        rec["error"] = ans
    elif spec.get("stream", True):
        done = ans[-1]
        rec.update(streamed=[e["token"] for e in ans[:-1]],
                   streamed_lps=[e["lp"] for e in ans[:-1]],
                   tokens=done.get("tokens"), logprobs=done.get("logprobs"),
                   finish_reason=done.get("finish_reason"),
                   error=done.get("error"))
    else:
        rec.update(tokens=ans.get("tokens"), logprobs=ans.get("logprobs"),
                   finish_reason=ans.get("finish_reason"))
    return rec


async def serve(engines, wave, after, timeout_s: float = 600.0):
    """Start a Gateway over ``engines`` exactly as docs/SERVING.md does,
    answer the traffic over HTTP, read /healthz and /metrics, drain."""
    from paddle_tpu.serving import Gateway
    gw = Gateway(engines, host="127.0.0.1", port=0)
    await gw.start()
    try:
        async def all_traffic():
            recs = list(await asyncio.gather(
                *(generate(gw.port, s) for s in wave)))
            for s in after:
                recs.append(await generate(gw.port, s))
            return recs
        records = await asyncio.wait_for(all_traffic(), timeout_s)
        _, health = await http_request(gw.port, "GET", "/healthz")
        _, metrics = await http_request(gw.port, "GET", "/metrics")
    finally:
        await gw.drain()
    return records, health, metrics


# ----------------------------------------------------------------- verify
def metric_total(metrics_text: str, name: str) -> float:
    """Sum of every sample of counter ``name`` in Prometheus text."""
    total = 0.0
    for line in metrics_text.splitlines():
        if line.startswith(name) and line[len(name):][:1] in ("{", " "):
            total += float(line.rsplit(" ", 1)[1])
    return total


def check_answers(records):
    for r in records:
        name = r["name"]
        require(r["status"] == 200,
                f"{name}: HTTP {r['status']}: {r.get('error')}")
        require(r.get("finish_reason") == "stop",
                f"{name}: finish_reason {r.get('finish_reason')!r} "
                f"{r.get('error')!r}")
        n = r["max_new_tokens"]
        require(len(r["tokens"]) == n and len(r["logprobs"]) == n,
                f"{name}: asked for {n} tokens, got {len(r['tokens'])} "
                f"with {len(r['logprobs'])} logprobs")
        if r.get("stream", True):
            require(r["streamed"] == r["tokens"],
                    f"{name}: streamed tokens differ from the final list")
            require(r["streamed_lps"] == r["logprobs"],
                    f"{name}: streamed logprobs differ from the final list")
        require(all(lp is not None and lp == lp and lp <= 0.0
                    for lp in r["logprobs"]),
                f"{name}: a logprob is missing, NaN or positive")


def check_gateway(health, metrics, prefix_hits_before: int):
    for name, rep in health["replicas"].items():
        eng, sched = rep["engine"], rep["scheduler"]
        require(eng["active_slots"] == 0 and eng["queued"] == 0
                and sched["queued"] == 0,
                f"/healthz: replica {name} still holds work at the end: "
                f"{eng['active_slots']} active, {eng['queued']} + "
                f"{sched['queued']} queued")
        require(rep["healthy"], f"/healthz: replica {name} is unhealthy")
    hits = sum(rep["engine"]["prefix_hit_tokens"]
               for rep in health["replicas"].values()) - prefix_hits_before
    require(hits > 0, "no prefix-cache hit: the second same-prefix request "
                      "adopted no blocks")
    # a supervisor that rebuilt a crashed engine and then answered is a
    # failure here, not a recovery
    require(health["failovers"] == 0,
            f"/healthz: {health['failovers']} failovers")
    for counter in ("gateway_failovers_total",
                    "gateway_watchdog_fires_total",
                    "replica_restarts_total"):
        n = metric_total(metrics, counter)
        require(n == 0, f"/metrics: {counter} is {n}, want 0")
    return hits


def check_route(engines):
    """The decode programs must hold the Pallas kernel: a shape gate that
    dropped them to the dense whole-table gather would still answer.
    (``main`` has already refused to run in interpret mode.)"""
    for i, e in enumerate(engines):
        route = e.decode_route()
        require(route == "ragged",
                f"replica {i}: decode attention took the {route!r} route, "
                f"want the ragged Pallas kernel")


def check_placement(engines, devices):
    """Replica i's params, KV pools and device tick state live on
    device i, and nowhere else."""
    import jax
    for i, (e, dev) in enumerate(zip(engines, devices)):
        groups = {"params": e.params, "pools": e.pools, "seen": e.seen,
                  "tick state": e._dev}
        for what, tree in groups.items():
            require(tree is not None, f"replica {i}: no {what}")
            for leaf in jax.tree_util.tree_leaves(tree):
                require(leaf.devices() == {dev},
                        f"replica {i}: {what} on {leaf.devices()}, "
                        f"want {{{dev}}}")


def check_same_weights(engines):
    """Every replica holds the same weights (one float32 sum per
    parameter, compared exactly), so replica 0's are the reference for
    all of them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if len(engines) < 2:
        return

    @jax.jit
    def sums(params):
        return [jnp.sum(v.astype(jnp.float32))
                for v in jax.tree_util.tree_leaves(params)]

    prints = [np.asarray(jax.device_get(sums(e.params))) for e in engines]
    for i, p in enumerate(prints[1:], 1):
        require(np.array_equal(p, prints[0]),
                f"replica {i}'s weights differ from replica 0's")


def reference_logprobs(engine, requests):
    """Teacher-forced logprobs of each request's generated tokens from a
    PLAIN forward of ``engine``'s weights on ``engine``'s device: no KV
    cache, no paging, no Pallas (``use_flash_attention=False`` — dense
    XLA attention). One jitted program for all requests: sequences are
    right-padded to one length, which a causal model cannot see."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cfg = engine.model.config
    n_max = max(len(r["tokens"]) for r in requests)
    longest = max(len(r["prompt"]) + len(r["tokens"]) for r in requests)
    L = -(-longest // 128) * 128

    @jax.jit
    def ref(params, ids, pos, tok):
        logits = engine.fn(params, ids)[0]                  # [L, V] f32
        rows = jax.nn.log_softmax(logits[pos], axis=-1)     # [n_max, V]
        return jnp.take_along_axis(rows, tok[:, None], axis=-1)[:, 0]

    flash, cfg.use_flash_attention = cfg.use_flash_attention, False
    try:
        out = []
        for r in requests:
            p, g = r["prompt"], r["tokens"]
            ids = np.zeros((1, L), np.int32)
            ids[0, :len(p) + len(g) - 1] = p + g[:-1]
            pos = np.zeros((n_max,), np.int32)
            pos[:len(g)] = len(p) - 1 + np.arange(len(g))
            tok = np.zeros((n_max,), np.int32)
            tok[:len(g)] = g
            out.append(np.asarray(ref(engine.params, ids, pos, tok))
                       [:len(g)])
        return out
    finally:
        cfg.use_flash_attention = flash


def check_logprobs(engine, records, atol: float) -> float:
    """Every greedy request's reported logprobs against the reference;
    returns the worst difference seen."""
    import numpy as np
    greedy = [r for r in records if not r.get("temperature")]
    worst = 0.0
    for r, ref in zip(greedy, reference_logprobs(engine, greedy)):
        diff = np.abs(np.asarray(r["logprobs"], np.float64) - ref)
        require(np.all(np.isfinite(ref)),
                f"{r['name']}: reference logprob not finite")
        k = int(diff.argmax())
        worst = max(worst, float(diff[k]))
        require(diff[k] <= atol,
                f"{r['name']}: logprob off by {diff[k]:.4f} at token {k} "
                f"(engine {r['logprobs'][k]:.4f}, reference {ref[k]:.4f}); "
                f"tolerance {atol}")
    return worst


# ------------------------------------------------------------------- main
def serve_and_verify(cfg, geometry, engines, devices,
                     atol: float = LOGPROB_ATOL) -> dict:
    """Serve the traffic over warmed ``engines`` (engine i on
    ``devices[i]``) and verify. Returns the facts of the run; raises
    ``SmokeFailure`` (or whatever a phase raised) otherwise."""
    served0 = [tokens_served(e) for e in engines]
    hits0 = sum(e.stats["prefix_hit_tokens"] for e in engines)
    wave, after = make_traffic(cfg, geometry, len(engines))
    t1 = time.perf_counter()
    records, health, metrics = asyncio.run(serve(engines, wave, after))
    serve_s = time.perf_counter() - t1
    note(f"{len(records)} requests answered in {serve_s:.1f}s")
    t2 = time.perf_counter()
    check_answers(records)
    hits = check_gateway(health, metrics, hits0)
    check_route(engines)
    check_placement(engines, devices)
    check_same_weights(engines)
    worst = check_logprobs(engines[0], records, atol)
    served = [tokens_served(e) - s0 for e, s0 in zip(engines, served0)]
    received = sum(len(r["tokens"]) for r in records)
    require(sum(served) == received,
            f"engines emitted {sum(served)} tokens, clients received "
            f"{received}")
    if len(engines) > 1:
        require(all(served), f"a replica served no token: {served}")
    return {"requests": len(records), "tokens_per_replica": served,
            "prefix_hit_tokens": hits,
            "max_logprob_diff": round(worst, 5), "logprob_atol": atol,
            "decode_route": engines[0].decode_route(),
            "serve_s": round(serve_s, 1),
            "verify_s": round(time.perf_counter() - t2, 1),
            "peak_device_bytes": [
                (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in devices]}


def report(devices, facts: dict):
    """The two stdout lines of a run that passed: the facts, then — alone
    and last, with exactly these keys — the result the accelerator check
    reads, the device as JAX reports it."""
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(json.dumps({"facts": dict(device=device, **facts)}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


def main() -> int:
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET"):
        note("FAIL: PADDLE_TPU_PALLAS_INTERPRET is set; the smoke must "
             "send every kernel through the real compiler")
        return 1
    import jax
    devices = jax.devices()
    dev = devices[0]
    note(f"platform={dev.platform} device_kind={dev.device_kind!r} "
         f"count={len(devices)} jax={jax.__version__}")
    if dev.platform != "tpu":
        note(f"FAIL: needs a TPU; jax found platform {dev.platform!r}")
        return 1
    from paddle_tpu.models.qwen2 import qwen2_7b
    from paddle_tpu.utils import compile_cache
    # every program goes into the cache, however quick its compile: a
    # time threshold would let a program near it miss one run and land
    # the next, and "a second run adds no entry" could not be checked
    cache_dir = compile_cache.enable(min_compile_time_s=0.0)
    entries_before = len(compile_cache.entries(cache_dir))
    cfg = qwen2_7b(num_hidden_layers=DEPTH)
    try:
        t0 = time.perf_counter()
        engines = build_engines(cfg, GEOMETRY, devices)
        setup_s = round(time.perf_counter() - t0, 1)
        note(f"{len(engines)} engine(s) built and warmed in {setup_s}s")
        facts = serve_and_verify(cfg, GEOMETRY, engines, devices)
    except SmokeFailure as e:
        note(f"FAIL: {e}")
        return 1
    report(devices, {
        "jax": jax.__version__,
        "model": {"family": "qwen2_7b", "hidden": cfg.hidden_size,
                  "heads": cfg.num_attention_heads,
                  "kv_heads": cfg.num_key_value_heads,
                  "head_dim": cfg.head_dim, "ffn": cfg.intermediate_size,
                  "vocab": cfg.vocab_size, "layers": cfg.num_hidden_layers,
                  "layers_published": qwen2_7b().num_hidden_layers,
                  "dtype": "bfloat16"},
        "geometry": dict(GEOMETRY, tokens_per_seq=GEOMETRY["block_size"]
                         * GEOMETRY["max_blocks_per_seq"]),
        "replicas": len(devices),
        "setup_s": setup_s,
        **facts,
        "compile_cache": {
            "dir": cache_dir, "entries_before": entries_before,
            "entries_after": len(compile_cache.entries(cache_dir))},
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
