"""A builder's traced run whose span holds a wave's turnover (PR 31).

The benchmark traces `w0` + 2 to 5 s (`harness/cell.py:TRACE_OFFSET_S`),
which in the expert cells falls between two turnovers, and the Python
tracer slows a tick 3.4 times, so a span that starts before a turnover
never reaches it. This hook leaves `benchmarks/run.py` and its call
stacks as they are (the compile cache's keys hold op metadata, so
another entry script would compile everything again) and only moves the
span and takes it WITHOUT the Python tracer:

    PYTHONPATH=tools/turnover_trace TURNOVER_OFFSET_S=4.3 \
        python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds 51 --trace 1

from a checkout's root, on the chip. After the run it prints one line to
stderr, `TURNOVER {...}`: the trace's modules, its idle gaps, every
`_chunk_prefill*` call (start s, ms, module) and the ops inside them. The
result line's per-layer metrics are then NOT the benchmark's (another
span): use it for the prefill calls only. Offsets that held the first
turnover in PR 31: 4.3-4.6 s with packed calls, 5.6 s on its parent.
Without `TURNOVER_OFFSET_S` the hook does nothing.
"""
import atexit, glob, json, os, sys, time

off = os.environ.get("TURNOVER_OFFSET_S")
if off and os.path.exists(os.path.join(os.getcwd(), "benchmarks", "harness", "cell.py")):
    sys.path.insert(0, os.getcwd())
    from benchmarks.harness import cell, trace
    cell.TRACE_OFFSET_S = float(off)

    def capture_trace(logdir, seconds):
        import jax, shutil
        shutil.rmtree(logdir, ignore_errors=True)
        os.makedirs(logdir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
        ta = time.monotonic()
        time.sleep(seconds)
        tb = time.monotonic()
        jax.profiler.stop_trace()
        return {"ta": ta, "tb": tb}
    cell.capture_trace = capture_trace

    def report():
        wl = sys.argv[sys.argv.index("--workload") + 1]
        paths = glob.glob(os.path.join(os.getcwd(), ".bench_out", wl, "trace", "plugins", "profile", "*", "*.xplane.pb"))
        if not paths:
            return
        red = trace.reduce_trace(paths[0])
        out = {"window_s": red["window_s"], "busy_s": red["busy_s"], "modules": red["modules"], "idle_gaps": red["idle_gaps"]}
        ops, calls = {}, []
        for p in trace.read_planes(paths[0]).values():
            chunks = sorted((s, s + d, trace.module_name(raw)) for raw, s, d in p["modules"]
                            if trace.module_name(raw).startswith("_chunk_prefill"))
            calls += [(round(s, 4), round(1e3 * (e - s), 3), n) for s, e, n in chunks]
            iv = [(s, e) for s, e, _ in chunks]
            for raw, start, dur in p["ops"]:
                if trace._inside(iv, start):
                    k = trace.op_key(raw)
                    ops[k] = ops.get(k, 0.0) + dur
        out["chunk_calls"] = calls
        out["chunk_ops"] = trace.top(ops, 30)
        print("TURNOVER " + json.dumps(out), file=sys.stderr, flush=True)
    atexit.register(report)
