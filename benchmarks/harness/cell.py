"""One run of one cell: build, warm, serve, measure, verify, report.

The process that holds the chip builds one warmed ``PagedEngine`` per
replica, hands them to ``paddle_tpu.serving.Gateway`` — the entry point
docs/SERVING.md gives a user — and starts the load generator as a child
process (``client.py``; it never imports jax). Everything one
configuration, mix, cell or per-layer metric needs is a file found by
the names in ``BENCHMARK.json``.
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import List

from . import stats, verify

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TRACE_OFFSET_S = 2.0        # into the window, past its first admissions
TRACE_S = 3.0               # traces are large and tracing slows the host


class BenchFailure(Exception):
    """The run cannot give a result; the message says why."""


def note(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- the files
def cell_spec(manifest: dict, workload: str, data_dir: str = BENCH) -> dict:
    """Everything a run of ``workload`` reads, found by name. The mixes
    and cells are looked up under ``data_dir`` (the tests keep tiny ones
    of their own), a configuration by the manifest's ``file``."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in the manifest; it "
                           f"has {sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == w["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = load_json(os.path.join(data_dir, "traffic",
                                 w["traffic"] + ".json"))
    cell = load_json(os.path.join(data_dir, "cells", workload + ".json"))

    def reported(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    e2e = [m for m in manifest["end_to_end"] if reported(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"workload": w, "config": config, "mix": mix, "cell": cell,
            "end_to_end": e2e, "per_layer": layer}


# ------------------------------------------------------------------ engines
def load_model(config: dict):
    """The family's file: ``build`` and the reference."""
    return load_module(os.path.join(BENCH, "models", config["model"] + ".py"),
                       "bench_model_" + config["model"])


def warm(engine, mix: dict, seed: int):
    """Compile every program this cell's traffic can reach BEFORE the
    gateway takes any, inside the engine's device scope (the tick thread
    shares the trace: PERF.md, "Warm-up that did not warm").

    - a greedy prompt compiles the chunk prefill and the all-greedy tick;
    - where the mix samples, a sampled request compiles the mixed tick;
    - where the mix shares prefixes, one request per adoptable length:
      admission seeds a row's seen-token mask with eager operations
      whose shapes follow the number of adopted tokens, so each multiple
      of the chunk up to the context is a set of small programs of its
      own (PERF.md Open questions: only the program can make it one);
    - one short request in every slot at once, for the bookkeeping that
      indexes by slot.
    """
    import numpy as np
    C, V = engine.chunk, engine.model.config.vocab_size
    shares = mix.get("tenants", 0) > 0 or "turns" in mix
    chunks = (engine.M * engine.B) // C - 1 if shares else 1
    ids = np.random.default_rng(int(seed) + 1).integers(
        1, V, chunks * C + 16 + engine.R).tolist()
    todo = [("warm-greedy", ids[:chunks * C + 8], {})]
    if mix.get("sampled_share", 0) > 0:
        todo.append(("warm-sampled", ids[:C] + ids[-8:],
                     dict(mix["sampling"], seed=1)))
    if shares:
        todo += [(f"warm-adopt-{k}", ids[:k * C] + ids[-8 - k:-k], {})
                 for k in range(1, chunks + 1)]
    for rid, prompt, kw in todo:
        engine.submit(rid, prompt, max_new_tokens=4, **kw)
        engine.run()
    wave = [(f"warm-slot-{i}", ids[i:i + 8], {}) for i in range(engine.R)]
    for rid, prompt, kw in wave:
        engine.submit(rid, prompt, max_new_tokens=4, **kw)
    engine.run()
    for rid, _, _ in todo + wave:
        if len(engine.results.pop(rid, [])) != 4:
            raise BenchFailure(f"warm-up request {rid} did not finish")
        engine.logprobs.pop(rid, None)


def build_engine(model_mod, spec: dict, seed: int, device, profile: bool):
    import jax
    from paddle_tpu.generation.paged import PagedEngine
    t0 = time.perf_counter()
    model = model_mod.build(spec["config"], seed, device)
    jax.block_until_ready(model.functional()[1])
    t1 = time.perf_counter()
    with jax.default_device(device):
        engine = PagedEngine(model, tick_profile=profile,
                             **spec["config"]["engine"])
        jax.block_until_ready(engine.pools)
        t2 = time.perf_counter()
        warm(engine, spec["mix"], seed)
    note(f"{device}: weights in {t1 - t0:.1f}s, engine and pools in "
         f"{t2 - t1:.1f}s, programs warmed in "
         f"{time.perf_counter() - t2:.1f}s")
    return engine


def build_engines(model_mod, spec, seed, devices, profile):
    """One engine per device, side by side (XLA compiles outside the
    interpreter lock). Every replica holds the same weights."""
    with concurrent.futures.ThreadPoolExecutor(len(devices)) as pool:
        futs = [pool.submit(build_engine, model_mod, spec, seed, d, profile)
                for d in devices]
        return [f.result() for f in futs]


def jit_cache_sizes(engines) -> List[int]:
    """How many traces the chunk and tick programs hold: the names are
    the engine's private ones, so a rename fails the run instead of
    letting "nothing was traced again" check nothing."""
    try:
        return [getattr(e, name)._cache_size() for e in engines
                for name in ("_chunk_jit", "_tick_greedy_jit", "_tick_jit")]
    except AttributeError as err:
        raise BenchFailure(f"the engine's jitted programs have moved "
                           f"({err}); harness/cell.py names them") from err


# ----------------------------------------------------------------- snapshots
def snapshot(gw, engines) -> dict:
    """The program's counters at one instant (public accessors only)."""
    return {"t": time.monotonic(), "health": gw.health(),
            "engines": [dict(e.stats) for e in engines],
            "tick_phase_ms": [e.tick_phase_totals for e in engines],
            "tick_wall_ms": [e.tick_wall_ms_total for e in engines]}


async def http_get(port: int, path: str):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(f"GET {path} HTTP/1.1\r\nHost: 127.0.0.1\r\n\r\n"
                     .encode())
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        n = 0
        while (line := await reader.readline()) not in (b"\r\n", b"\n", b""):
            k, _, v = line.decode("latin1").partition(":")
            if k.strip().lower() == "content-length":
                n = int(v)
        return status, await reader.readexactly(n)
    finally:
        writer.close()
        await writer.wait_closed()


def metric_total(text: str, name: str) -> float:
    """Sum of every sample of counter ``name`` in Prometheus text."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):][:1] in ("{", " "):
            total += float(line.rsplit(" ", 1)[1])
    return total


def capture_trace(logdir: str, seconds: float) -> dict:
    """A profiler trace of ``seconds`` of the live server (runs in a
    worker thread, so the gateway's loop keeps answering)."""
    import jax
    shutil.rmtree(logdir, ignore_errors=True)
    os.makedirs(logdir)
    jax.profiler.start_trace(logdir)
    ta = time.monotonic()
    time.sleep(seconds)
    tb = time.monotonic()
    jax.profiler.stop_trace()
    return {"ta": ta, "tb": tb}


# ---------------------------------------------------------------- the serve
async def serve(spec, engines, seed, seconds, trace, out_dir, compiles):
    """Gateway up, child started, window measured. Returns the sources
    the metrics read."""
    from paddle_tpu.serving import Gateway
    cell, mix, config = spec["cell"], spec["mix"], spec["config"]
    gw = Gateway(engines, host="127.0.0.1", port=0, trace_capacity=16384)
    await gw.start()
    loop = asyncio.get_running_loop()
    src: dict = {}
    try:
        status, _ = await http_get(gw.port, "/healthz")
        if status != 200:
            raise BenchFailure(f"/healthz answered {status}")
        src["ready"] = time.monotonic()
        t0 = src["ready"] + 1.5         # the child's start-up
        job = {"port": gw.port, "seed": seed, "mix": mix,
               "vocab": config["vocab_size"], "seconds": seconds,
               "rate": cell.get("rate_per_s"), "clients": cell.get("clients"),
               "max_context": (config["engine"]["block_size"]
                               * config["engine"]["max_blocks_per_seq"]),
               "t0": t0, "out": os.path.join(out_dir, "client.json")}
        job_path = os.path.join(out_dir, "job.json")
        with open(job_path, "w") as f:
            json.dump(job, f)
        if os.path.exists(job["out"]):
            os.remove(job["out"])
        w0 = t0 + float(mix.get("lead_in_s", 0.0))
        w1 = w0 + seconds
        child = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(HERE, "client.py"), job_path,
            stdout=sys.stderr)
        try:
            snaps = {}

            async def snap_at(name, t):
                await asyncio.sleep(max(t - time.monotonic(), 0))
                snaps[name] = snapshot(gw, engines)
                snaps[name]["compiles"] = compiles()

            from .client import heartbeat
            lag: dict = {}
            beat = asyncio.ensure_future(heartbeat(lag))
            tasks = [asyncio.ensure_future(snap_at("w0", w0)),
                     asyncio.ensure_future(snap_at("w1", w1))]
            if trace:
                async def traced():
                    await asyncio.sleep(max(
                        w0 + TRACE_OFFSET_S - time.monotonic(), 0))
                    before = snapshot(gw, engines)
                    times = await loop.run_in_executor(
                        None, capture_trace,
                        os.path.join(out_dir, "trace"),
                        min(TRACE_S, max(seconds - TRACE_OFFSET_S - 1, 0.5)))
                    return dict(times, before=before,
                                after=snapshot(gw, engines))
                tasks.append(asyncio.ensure_future(traced()))
            limit = (w1 + float(mix.get("drain_s", 0.0)) + 30
                     - time.monotonic())
            rc = await asyncio.wait_for(child.wait(), limit)
            done = await asyncio.gather(*tasks)
            beat.cancel()
            src["loop_lag_max_ms"] = lag["loop_lag_max_ms"]
        finally:
            if child.returncode is None:
                child.kill()
                await child.wait()
        if rc != 0:
            raise BenchFailure(f"the client child exited with {rc}")
        src["client"] = load_json(job["out"])
        src["snaps"] = snaps
        if trace:
            src["trace_times"] = done[-1]
        _, metrics_text = await http_get(gw.port, "/metrics")
        src["metrics_text"] = metrics_text.decode()
        src["health_end"] = gw.health()
        src["reqtrace"] = []
        for path in gw.dump_traces(os.path.join(out_dir, "reqtrace")):
            src["reqtrace"] += load_json(path)["entries"]
    finally:
        await gw.drain(timeout=10.0)
    return src


# ------------------------------------------------------------------ checks
def compare(model_mod, params, spec, client, seed):
    """The numbers that decide ``correct``: a sample of the window's
    greedy requests and, where the mix samples, one of its sampled
    requests, each through the reference. None without a greedy one."""
    n = int(spec["mix"].get("verify_requests", 8))
    picked = [verify.choose_sample(client["records"], client["w0"],
                                   client["w1"], seed, n, greedy=g)
              for g in (True, False)]
    if not picked[0]:
        return None
    if spec["mix"].get("sampled_share", 0) > 0 and not picked[1]:
        return None
    return verify.numbers(model_mod, params, spec["config"], *picked,
                          sampling=spec["mix"].get("sampling"))


def check_run(src, engines, devices, replicas) -> List[str]:
    """The bookkeeping that must hold for a run to count."""
    bad = verify.exact_checks(src["client"]["records"])
    for i, e in enumerate(engines):
        route = e.decode_route()
        if route not in ("ragged", "grid"):
            bad.append(f"replica {i}: decode attention took the {route!r} "
                       f"route, not a Pallas kernel")
    if src["health_end"]["failovers"]:
        bad.append(f"{src['health_end']['failovers']} failovers")
    for counter in ("gateway_failovers_total",
                    "gateway_watchdog_fires_total",
                    "replica_restarts_total"):
        n = metric_total(src["metrics_text"], counter)
        if n:
            bad.append(f"/metrics: {counter} is {n}, want 0")
    if replicas > 1:
        import jax
        for i, (e, dev) in enumerate(zip(engines, devices)):
            for leaf in jax.tree_util.tree_leaves((e.params, e.pools)):
                if leaf.devices() != {dev}:
                    bad.append(f"replica {i}: an array on {leaf.devices()}"
                               f", want {{{dev}}}")
                    break
        served = [b["active_slot_steps"] + b["prefills"]
                  - a["active_slot_steps"] - a["prefills"]
                  for a, b in zip(src["snaps"]["w0"]["engines"],
                                  src["snaps"]["w1"]["engines"])]
        if not all(served):
            bad.append(f"a replica served no token in the window: {served}")
    grew = src["snaps"]["w1"]["compiles"] - src["snaps"]["w0"]["compiles"]
    if grew:
        bad.append(f"{grew} compilations inside the measured window")
    if src["jit_sizes_end"] != src["jit_sizes_warm"]:
        bad.append(f"a tick or chunk program was traced again after "
                   f"warm-up: jit cache sizes {src['jit_sizes_warm']} -> "
                   f"{src['jit_sizes_end']}")
    return bad


# -------------------------------------------------------------------- a run
def run_cell(manifest: dict, workload: str, seed: int, seconds: float,
             trace: bool, t_process: float, *, data_dir: str = BENCH,
             require_tpu: bool = True, tamper=None) -> dict:
    """One run; returns the result object of the last stdout line.
    ``tamper(records)`` is for the tests: it breaks what the timed path
    produced before the comparison sees it."""
    spec = cell_spec(manifest, workload, data_dir)
    w, config = spec["workload"], spec["config"]
    import jax
    devices = jax.devices()
    note(f"jax and its devices up {time.monotonic() - t_process:.1f}s "
         f"after the process started")
    dev = devices[0]
    note(f"platform={dev.platform} device_kind={dev.device_kind!r} "
         f"count={len(devices)} jax={jax.__version__}")
    if require_tpu and dev.platform != "tpu":
        raise BenchFailure(f"needs a TPU; jax found platform "
                           f"{dev.platform!r}")
    if len(devices) < w["chips"]:
        raise BenchFailure(f"the cell needs {w['chips']} chips; jax found "
                           f"{len(devices)}")
    replicas = int(spec["cell"].get("replicas", w["chips"]))
    devices = devices[:replicas]

    from paddle_tpu.utils import compile_cache
    # every program goes into the cache, however quick its compile, so
    # that "a run after the first adds no entry" can be checked
    cache_dir = compile_cache.enable(min_compile_time_s=0.0)
    entries_before = len(compile_cache.entries(cache_dir))
    compile_events = [0]

    def on_event(name, *a, **kw):
        if name.endswith("backend_compile_duration"):
            compile_events[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    out_dir = os.path.join(ROOT, ".bench_out", workload)
    os.makedirs(out_dir, exist_ok=True)
    model_mod = load_model(config)
    engines = build_engines(model_mod, spec, seed, devices, bool(trace))
    warm_sizes = jit_cache_sizes(engines)
    src = asyncio.run(serve(spec, engines, seed, float(seconds), trace,
                            out_dir, lambda: compile_events[0]))
    setup_s = src["ready"] - t_process
    src["jit_sizes_warm"], src["jit_sizes_end"] = \
        warm_sizes, jit_cache_sizes(engines)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    client = src["client"]
    w0, w1, give_up = client["w0"], client["w1"], client["give_up"]
    if tamper is not None:
        tamper(client["records"])
    cm = stats.client_metrics(client["records"], w0, w1, give_up,
                              spec["cell"].get("slo"), spec["mix"]["loop"])
    cm["setup_s"] = {"value": setup_s}
    note("client: " + json.dumps(cm, sort_keys=True))
    # a stall of either event loop is the host's, and explains a far-off
    # tail before anything else does
    note(f"longest event-loop stall: gateway process "
         f"{src['loop_lag_max_ms']:.1f} ms, client child "
         f"{client['loop_lag_max_ms']:.1f} ms")

    # ---- correct: after the window, outside every timed part
    t_v = time.perf_counter()
    bad = check_run(src, engines, devices, replicas)
    nums = compare(model_mod, engines[0].params, spec, client, seed)
    limits = config["limits"]
    bad += verify.judge(nums, limits)
    note(f"compared {json.dumps(nums)} against limits "
         f"{json.dumps(limits)} in {time.perf_counter() - t_v:.1f}s")
    for b in bad[:20]:
        note(f"NOT CORRECT: {b}")
    entries_added = len(compile_cache.entries(cache_dir)) - entries_before

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": not bad, "attempted": cm["attempted"]["value"],
              "failed": cm["failed"]["value"], "metrics": {},
              "device": device}
    units = {m["name"]: m["unit"] for m in
             manifest["end_to_end"] + manifest["per_layer"]}
    if not trace:
        for m in spec["end_to_end"]:
            if m["name"] not in cm:
                raise BenchFailure(f"the run gave no {m['name']}")
            result["metrics"][m["name"]] = {
                "value": cm[m["name"]]["value"], "unit": m["unit"]}
        return result

    from . import trace as trace_mod
    xplane = trace_mod.find_xplane(os.path.join(out_dir, "trace"))
    if xplane is None:
        raise BenchFailure("the profiler left no .xplane.pb")
    reduced = trace_mod.reduce_trace(xplane)
    sources = {"client": cm, "records": client["records"],
               "window": (w0, w1), "snaps": src["snaps"],
               "reqtrace": src["reqtrace"], "trace": reduced,
               "trace_times": src["trace_times"], "config": config,
               "engine": config["engine"], "replicas": replicas,
               "device_kind": dev.device_kind, "peak_bytes": int(peak),
               "cache_entries_added": entries_added}
    for m in spec["per_layer"]:
        reader = load_module(os.path.join(BENCH, "layer_metrics",
                                          m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.reduce(sources)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value),
                                            "unit": units[m["name"]]}
    device["busy_s"] = reduced["busy_s"]
    device["window_s"] = reduced["window_s"]
    note("per-chip busy seconds: " + json.dumps(reduced["busy_s_per_chip"]))
    result["breakdown"] = {"device_ops": reduced["device_ops"],
                           "idle_gaps": reduced["idle_gaps"]}
    return result
