"""A builder's look at the host's round through the benchmark (PR 35).

A hook like `tools/turnover_trace`: `benchmarks/run.py` and its call
stacks stay as they are (the compile cache's keys hold op metadata), and
the environment says what to add:

    PYTHONPATH=tools/round_trace ROUND_GAPS=1 [ROUND_PROFILEZ=1|python] \
        python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds 51 --trace <0|1>

`ROUND_GAPS=1`: after the run, one stderr line `ROUND_GAPS {...}`: the
clients' median and 95th-percentile gap between consecutive tokens (ms,
from the child's records) in the window's first 2 s, in the next 3 s,
in the rest and in its last 20 s (`stop_trace` has returned by then in
a 51 s window). In a `--trace 1` run the engines run their tick
profiler from the start, the first stretch has no tracer, the second is
`capture_trace`'s, and `stop_trace` works into the third: the first
against an untraced run's is what the profiler costs when it is on, the
second against the first what the capture costs.

`ROUND_PROFILEZ=1` (with `--trace 1`): the capture goes through the
gateway's own `GET /profilez` (no Python tracer; `=python` asks for it
with `&python_tracer=1`) and the harness reads THAT trace. After the run
one stderr line `ROUND_PROFILEZ {...}`: the `/profilez` answer's own
words, and per host line of the `.xplane.pb` its `tick/<phase>` and
`loop/write` spans, the Python tracer's events (their names start with
`$`) and the other host events over all lines, beside the device planes'
event counts. Without either variable the hook does
nothing.
"""
import atexit, glob, json, os, shutil, sys, time

GAPS, PROFILEZ = os.environ.get("ROUND_GAPS"), os.environ.get("ROUND_PROFILEZ")
ROOT = os.getcwd()
if (GAPS or PROFILEZ) and "--workload" in sys.argv and os.path.exists(os.path.join(ROOT, "benchmarks", "harness", "cell.py")):
    sys.path.insert(0, ROOT)
    from benchmarks.harness import cell, stats

    def out_dir():
        return os.path.join(ROOT, ".bench_out", sys.argv[sys.argv.index("--workload") + 1])

    answer = {}
    if PROFILEZ:
        def capture_trace(logdir, seconds):
            import urllib.request
            from paddle_tpu.utils import observability as obs
            port = cell.load_json(os.path.join(os.path.dirname(logdir), "job.json"))["port"]
            run = os.path.join(os.path.dirname(logdir), "run")
            shutil.rmtree(run, ignore_errors=True)
            obs.configure(run)
            ask = f"http://127.0.0.1:{port}/profilez?duration_s={seconds}" + ("&python_tracer=1" if PROFILEZ == "python" else "")
            ta = time.monotonic()
            doc = json.loads(urllib.request.urlopen(ask, timeout=600).read())
            tb = time.monotonic()
            answer.update(doc, tickphase_files=len(doc["tickphase_files"]), seconds_to_answer=tb - ta)
            shutil.rmtree(logdir, ignore_errors=True)
            shutil.copytree(doc["jax_trace"], logdir)
            return {"ta": ta, "tb": tb}
        cell.capture_trace = capture_trace

    def gaps_report():
        path = os.path.join(out_dir(), "client.json")
        if not os.path.exists(path):
            return
        client = cell.load_json(path)
        w0, w1 = client["w0"], client["w1"]
        out = {}
        for name, a, b in (("first_2s", w0, w0 + 2), ("next_3s", w0 + 2, w0 + 5), ("rest", w0 + 5, w1), ("last_20s", w1 - 20, w1)):
            gaps = [1e3 * (t - s) for r in client["records"] for s, t in zip(r["token_times"], r["token_times"][1:]) if a <= t < b]
            out[name] = {"n": len(gaps), "p50": stats.percentile(gaps, 50), "p95": stats.percentile(gaps, 95)}
        print("ROUND_GAPS " + json.dumps(out), file=sys.stderr, flush=True)

    def profilez_report():
        from jax.profiler import ProfileData
        paths = glob.glob(os.path.join(out_dir(), "trace", "plugins", "profile", "*", "*.xplane.pb"))
        if not paths:
            return
        lines, devices, python, other = [], {}, 0, 0
        for plane in ProfileData.from_file(max(paths, key=os.path.getmtime)).planes:
            for line in plane.lines:
                names = {}
                for ev in line.events:
                    key = ev.name if ev.name == "loop/write" or ev.name.startswith("tick") else "(python)" if ev.name.startswith("$") else "(other)"
                    names[key] = names.get(key, 0) + 1
                if plane.name.startswith("/device:"):
                    devices[f"{plane.name} {line.name}"] = sum(names.values())
                else:
                    python += names.get("(python)", 0)
                    other += names.get("(other)", 0)
                    if any(not k.startswith("(") for k in names):
                        lines.append({"plane": plane.name, "line": line.name, "events": names})
        out = dict(answer, replicas=None, host_lines=lines, python_tracer_events=python, other_host_events=other, device_lines=devices)
        print("ROUND_PROFILEZ " + json.dumps(out), file=sys.stderr, flush=True)

    if PROFILEZ:
        atexit.register(profilez_report)
    if GAPS:
        atexit.register(gaps_report)
