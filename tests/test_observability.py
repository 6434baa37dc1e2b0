"""Unified observability layer (ISSUE 5): metrics registry, span
tracing, flight recorder, the trainer/serving wiring, and the
obs_report tool."""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.utils import observability as obs
from paddle_tpu.utils.observability import (FlightRecorder,
                                            MetricsRegistry, SpanTracer)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ================================================================ registry
class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("req_total", engine="e0")
        c.inc()
        c.inc(2)
        assert reg.counter("req_total", engine="e0") is c  # get-or-create
        assert c.value == 3
        with pytest.raises(ValueError):
            c.inc(-1)                       # counters only go up
        g = reg.gauge("depth")
        g.set(4)
        g.dec()
        assert g.value == 3
        h = reg.histogram("lat_ms")
        for v in (1, 2, 3, 4, 100):
            h.observe(v)
        s = h.stats()
        assert s["count"] == 5 and s["sum"] == 110
        assert s["min"] == 1 and s["max"] == 100
        assert s["p50"] <= s["p99"] <= 100

    def test_kind_collision_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")

    def test_snapshot_and_prometheus_text(self):
        reg = MetricsRegistry()
        reg.counter("served_total", engine="a").inc(7)
        reg.gauge("queue_depth").set(2)
        reg.histogram("wait_ms").observe(3.0)
        snap = reg.snapshot()
        assert snap['served_total{engine="a"}'] == 7
        assert snap["queue_depth"] == 2
        assert snap["wait_ms"]["count"] == 1
        text = reg.prometheus_text()
        assert "# TYPE served_total counter" in text
        assert 'served_total{engine="a"} 7' in text
        assert "# TYPE wait_ms histogram" in text
        assert 'wait_ms_bucket{le="+Inf"} 1' in text
        assert "wait_ms_count 1" in text

    def test_thread_safety(self):
        reg = MetricsRegistry()
        c = reg.counter("n")
        h = reg.histogram("h")

        def work():
            for _ in range(1000):
                c.inc()
                h.observe(1.0)

        ts = [threading.Thread(target=work) for _ in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert c.value == 4000
        assert h.stats()["count"] == 4000

    def test_publish_merges_into_logwriter(self, tmp_path):
        from paddle_tpu.utils.logging import LogWriter
        reg = MetricsRegistry()
        reg.counter("steps_total").inc(5)
        reg.histogram("step_ms").observe(8.0)
        with LogWriter(str(tmp_path)) as w:
            reg.publish(w, step=5)
        tags = {json.loads(l)["tag"]
                for l in open(w.path).read().splitlines()}
        assert "steps_total" in tags
        assert "step_ms:p50" in tags and "step_ms:p99" in tags


# ================================================================== spans
class TestSpanTracer:
    def test_spans_are_chrome_trace_shaped(self, tmp_path):
        tr = SpanTracer()
        with tr.span("train_step", step=7):
            time.sleep(0.002)
        tr.instant("fault_fire", site="preempt")
        path = tr.flush(str(tmp_path / "trace.json"))
        doc = json.load(open(path))          # Perfetto-loadable JSON
        assert "traceEvents" in doc and "run_id" in doc["otherData"]
        ev = next(e for e in doc["traceEvents"]
                  if e["name"] == "train_step")
        assert ev["ph"] == "X" and ev["dur"] >= 2000  # us
        assert ev["args"]["step"] == 7
        mark = next(e for e in doc["traceEvents"]
                    if e["name"] == "fault_fire")
        assert mark["ph"] == "i"

    def test_span_ring_keeps_recent_window(self, tmp_path):
        tr = SpanTracer(max_events=3)
        for i in range(5):
            with tr.span("s", i=i):
                pass
        evs = tr.snapshot()
        assert len(evs) == 3 and tr.dropped == 2
        # ring semantics: a crash-time flush needs the RECENT window
        assert [e["args"]["i"] for e in evs] == [2, 3, 4]

    def test_run_and_attempt_ids(self, monkeypatch):
        monkeypatch.delenv(obs.ENV_RUN_ID, raising=False)
        rid = obs.run_id()
        assert rid and os.environ[obs.ENV_RUN_ID] == rid
        assert obs.run_id() == rid           # stable once minted
        monkeypatch.setenv(obs.ENV_ATTEMPT, "3")
        assert obs.attempt_id() == 3
        monkeypatch.setenv(obs.ENV_ATTEMPT, "junk")
        assert obs.attempt_id() == 0


# ======================================================== flight recorder
class TestFlightRecorder:
    def test_ring_bounded_and_dump_schema(self, tmp_path):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            fr.record("step_end", step=i, ms=1.0)
        evs = fr.snapshot()
        assert len(evs) == 4                     # ring dropped the old
        assert [e["step"] for e in evs] == [6, 7, 8, 9]
        path = fr.dump(str(tmp_path / "flight.json"), reason="crash")
        doc = json.load(open(path))
        assert doc["reason"] == "crash" and doc["total_events"] == 10
        assert doc["events"][-1]["kind"] == "step_end"
        assert "run_id" in doc and "attempt" in doc

    def test_values_coerced_jsonable(self, tmp_path):
        fr = FlightRecorder()
        fr.record("x", arr=np.float32(1.5), obj=object(), ok="s")
        json.dumps(fr.snapshot())                # must not raise


# ============================================================= satellites
class TestSatellites:
    def test_get_logger_per_logdir(self, tmp_path):
        """REGRESSION: the old singleton ignored logdir after the first
        call, silently writing every stream into one directory."""
        from paddle_tpu.utils.logging import get_logger
        a = get_logger(str(tmp_path / "a"))
        b = get_logger(str(tmp_path / "b"))
        assert a is not b
        assert a is get_logger(str(tmp_path / "a"))   # cached per dir
        a.add_scalar("x", 1.0, 0)
        b.add_scalar("y", 2.0, 0)
        assert "x" in open(a.path).read()
        assert "y" in open(b.path).read()
        assert a.path != b.path

    def test_profiler_start_idempotent(self, monkeypatch, capsys):
        from paddle_tpu.utils import profiler as prof
        calls = []
        monkeypatch.setattr(prof.jax.profiler, "start_trace",
                            lambda d, **kw: calls.append(("start", d)))
        monkeypatch.setattr(prof.jax.profiler, "stop_trace",
                            lambda: calls.append(("stop", None)))
        p = prof.Profiler(logdir="x")
        p.start()
        p.start()                       # second start: warn, don't crash
        assert len([c for c in calls if c[0] == "start"]) == 1
        assert "already-active" in capsys.readouterr().err
        q = prof.Profiler(logdir="y")
        q.start()                       # other trace still open: degrade
        assert len([c for c in calls if c[0] == "start"]) == 1
        assert "already running" in capsys.readouterr().err
        q.stop()                        # q never owned the trace
        assert not [c for c in calls if c[0] == "stop"]
        p.stop()
        assert [c for c in calls if c[0] == "stop"]

    @pytest.mark.parametrize("asked", [False, True],
                             ids=["default", "python-tracer"])
    def test_profiler_traces_without_the_python_tracer_unless_asked(
            self, monkeypatch, asked):
        """ISSUE 35: a capture must not bend what it measures. The
        Python tracer triples a serving tick; the program's own spans
        name the host's time without it."""
        from paddle_tpu.utils import profiler as prof
        got = []
        monkeypatch.setattr(
            prof.jax.profiler, "start_trace",
            lambda d, profiler_options=None: got.append(
                (d, profiler_options)))
        monkeypatch.setattr(prof.jax.profiler, "stop_trace", lambda: None)
        p = prof.Profiler(logdir="x", python_tracer=True) if asked \
            else prof.Profiler(logdir="x")
        with p:
            pass
        (d, opts), = got
        assert d == "x"
        assert opts.python_tracer_level == int(asked)
        # the rest of the options are jax's defaults: the host's
        # TraceAnnotations and the device's ops stay in the trace
        fresh = prof.jax.profiler.ProfileOptions()
        assert opts.host_tracer_level == fresh.host_tracer_level > 0

    def test_steptimer_stop_without_start_raises(self):
        from paddle_tpu.utils.profiler import StepTimer
        t = StepTimer(flops_per_token=1.0, peak_flops=1.0)
        with pytest.raises(RuntimeError, match="no open window"):
            t.stop(tokens=1)
        t.start()
        t.stop(tokens=1)                # normal path unaffected
        with pytest.raises(RuntimeError):
            t.stop(tokens=1)            # window already closed


# ==================================================== serving == registry
def _mlp():
    from paddle_tpu import nn
    pt.seed(0)
    return nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 4))


class TestServingRegistryMigration:
    def test_batching_health_matches_registry_concurrent(self):
        """ACCEPTANCE + satellite: counter semantics identical to the
        pre-migration dicts under concurrent submit/cancel, and
        health() reads the same objects a registry snapshot exports."""
        from paddle_tpu.inference import BackpressureError, \
            BatchingPredictor
        bp = BatchingPredictor(_mlp(), max_batch=2, max_delay_ms=1,
                               max_queue=4)
        orig = bp.predictor.run

        def slow(*a):
            time.sleep(0.05)
            return orig(*a)
        bp.predictor.run = slow
        x = np.zeros((16,), np.float32)
        futs, rejected, attempts = [], 0, 24
        lock = threading.Lock()

        def submit_some():
            nonlocal rejected
            for _ in range(attempts // 4):
                try:
                    f = bp.submit(x)
                    with lock:
                        futs.append(f)
                except BackpressureError:
                    with lock:
                        rejected += 1
                time.sleep(0.001)

        ts = [threading.Thread(target=submit_some) for _ in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        cancelled = sum(f.cancel() for f in futs[-3:])  # race the worker
        bp.close()                                      # drain the rest
        h = bp.health()
        # conservation: every submitted request resolved exactly once
        assert h["submitted"] == len(futs)
        assert h["submitted"] + h["rejected"] == attempts
        assert h["rejected"] == rejected >= 1
        assert h["cancelled"] == cancelled
        assert h["served"] + h["cancelled"] + h["timeouts"] \
            + h["errors"] == h["submitted"]
        assert h["queued"] == 0
        # health() IS the registry: same numbers under the engine label
        snap = obs.registry().snapshot()
        eng = bp._obs_labels["engine"]
        for key in BatchingPredictor._STAT_KEYS:
            assert snap[f'serving_{key}_total{{engine="{eng}"}}'] \
                == h[key], key
        assert snap[f'serving_queue_wait_ms{{engine="{eng}"}}'][
            "count"] >= h["served"]

    def test_paged_health_matches_registry(self):
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny
        from paddle_tpu.generation.paged import PagedEngine
        pt.seed(0)
        eng = PagedEngine(LlamaForCausalLM(llama_tiny()), max_slots=2,
                          num_blocks=16, block_size=8,
                          max_blocks_per_seq=4, prefill_buckets=(16,),
                          max_queue=2)
        ids = np.arange(1, 5)[None]
        eng.submit("a", ids, max_new_tokens=2)
        eng.submit("b", ids, max_new_tokens=2)
        with pytest.raises(Exception):      # BackpressureError
            eng.submit("c", ids, max_new_tokens=2)
        out = eng.run()
        assert set(out) == {"a", "b"}
        eng.submit("gone", ids, max_new_tokens=2)
        assert eng.cancel("gone")
        # pre-migration dict semantics survive the registry move
        assert eng.stats["prefills"] == 2
        assert eng.stats["rejected"] == 1
        assert eng.stats["cancellations"] == 1
        assert eng.stats["decode_steps"] >= 1
        h = eng.health()
        snap = obs.registry().snapshot()
        label = eng._obs_labels["engine"]
        # the decode tick count (ISSUE 24) is the engine's own, beside
        # the registry's counters: not exported
        for key, v in eng.stats.items():
            assert h[key] == v
            if key != "decode_ticks":
                assert snap[f'paged_{key}_total{{engine="{label}"}}'] == v
        assert not any(k.startswith("paged_decode_ticks") for k in snap)
        # one device tick a dispatch here (no scan, no spec)
        assert eng.stats["decode_ticks"] == eng.stats["decode_steps"]
        assert snap[f'paged_decode_step_ms{{engine="{label}"}}'][
            "count"] == eng.stats["decode_steps"]


# ================================================= trainer e2e artifacts
class TestTrainerArtifacts:
    def test_preempt_run_produces_artifacts(self, tmp_path):
        """ACCEPTANCE: one toy run under an injected preempt yields,
        from a single run dir: a Prometheus snapshot, a
        Perfetto-loadable trace with step-numbered train_step spans,
        and a flight record whose tail holds the fault fire and the
        checkpoint-on-shutdown; obs_report renders p50/p99 + timeline
        from it."""
        import jax.numpy as jnp
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny
        from paddle_tpu.trainer import Trainer, TrainingArguments
        from paddle_tpu.utils import faults
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        import obs_report

        # fresh global registry/recorder: the ring and counters are
        # process-wide and earlier tests in this process have trained
        # and fired faults — the assertions below pin EXACT values
        obs.reset()
        rng = np.random.RandomState(0)
        batches = [jnp.asarray(rng.randint(0, 256, (4, 16)))
                   for _ in range(8)]
        args = TrainingArguments(output_dir=str(tmp_path), max_steps=20,
                                 logging_steps=2, save_steps=4,
                                 resume_from_checkpoint=False,
                                 prefetch_depth=0)
        tr = Trainer(LlamaForCausalLM(llama_tiny()),
                     pt.optimizer.AdamW(learning_rate=1e-4), args,
                     train_dataloader=batches)
        with faults.scoped("preempt@6"):
            with pytest.raises(SystemExit) as exc:
                tr.train()
        assert exc.value.code == args.preempt_exit_code
        run = os.path.join(str(tmp_path), "runs")

        # prometheus snapshot
        prom = open(os.path.join(run, "metrics.prom")).read()
        assert "train_steps_total" in prom
        assert "train_step_wall_ms_bucket" in prom
        assert 'fault_fires_total{site="preempt"} 1' in prom

        # perfetto trace: train_step spans carry step numbers
        trace = json.load(open(os.path.join(run, "trace_0.json")))
        steps = [e["args"]["step"] for e in trace["traceEvents"]
                 if e["name"] == "train_step"]
        assert steps and steps == sorted(steps)
        assert any(e["name"] == "checkpoint_save"
                   for e in trace["traceEvents"])

        # flight record: the tail shows fault fire -> latch -> exit ->
        # checkpoint-on-shutdown
        flight = json.load(open(os.path.join(run, "flight_0.json")))
        assert flight["reason"] == "preempt"
        kinds = [e["kind"] for e in flight["events"]]
        for kind in ("fault_fire", "preempt_latch", "preempt_exit",
                     "ckpt_save", "step_end"):
            assert kind in kinds, kind
        assert kinds.index("fault_fire") < kinds.index("preempt_exit")
        tail = kinds[kinds.index("preempt_exit"):]
        assert "ckpt_save" in tail     # the shutdown checkpoint

        # obs_report renders it
        s = obs_report.summarize(run)
        assert s["steps_recorded"] == 6
        assert s["step_ms"]["p99"] >= s["step_ms"]["p50"] > 0
        assert s["train"]["loss"] is not None
        assert s["counters"]["fault_fires"] >= 1
        timeline_kinds = {e["kind"] for e in s["timeline"]}
        assert {"fault_fire", "preempt_exit"} <= timeline_kinds
        text = obs_report.render(s)
        assert "p50" in text and "fault_fire" in text

    def test_crash_dumps_flight(self, tmp_path):
        """An exception escaping the train loop writes the postmortem
        window before propagating."""
        from paddle_tpu.trainer import Trainer, TrainingArguments
        from paddle_tpu import nn

        class Boom:
            """Raises INSIDE the loop (iter() itself succeeding), so
            the crash unwinds out of _train_loop."""

            def __iter__(self):
                return self

            def __next__(self):
                raise RuntimeError("feed exploded")

        pt.seed(0)
        model = nn.Linear(4, 4)
        args = TrainingArguments(output_dir=str(tmp_path), max_steps=3,
                                 resume_from_checkpoint=False,
                                 prefetch_depth=0, graceful_shutdown=False)
        tr = Trainer(model, pt.optimizer.SGD(learning_rate=0.1), args,
                     train_dataloader=Boom())
        with pytest.raises(RuntimeError, match="feed exploded"):
            tr.train()
        flight = json.load(open(
            os.path.join(str(tmp_path), "runs", "flight_0.json")))
        assert flight["reason"] == "crash:RuntimeError"
        assert any(e["kind"] == "crash" for e in flight["events"])


# =================================================================== elastic
def test_supervise_propagates_run_and_attempt_ids(tmp_path):
    """Children see $PADDLE_TPU_RUN_ID (stable) and $PADDLE_TPU_ATTEMPT
    (incremented per launch, preemption relaunches included) — the env
    contract that lets an elastic run's trace/flight files stitch."""
    from paddle_tpu.distributed.elastic import supervise
    from paddle_tpu.utils.shutdown import PREEMPTED_RC
    out = tmp_path / "attempts.txt"
    script = (
        "import os, sys\n"
        f"open({str(out)!r}, 'a').write(\n"
        "    os.environ['PADDLE_TPU_ATTEMPT'] + ' ' +\n"
        "    os.environ['PADDLE_TPU_RUN_ID'] + '\\n')\n"
        # first launch simulates a preemption; the relaunch succeeds
        f"sys.exit({PREEMPTED_RC} "
        "if os.environ['PADDLE_TPU_ATTEMPT'] == '0' else 0)\n")
    rc = supervise([sys.executable, "-c", script], max_restarts=0,
                   backoff_s=0.01)
    assert rc == 0
    lines = [l.split() for l in out.read_text().splitlines()]
    assert [l[0] for l in lines] == ["0", "1"]       # attempt ids
    assert lines[0][1] == lines[1][1]                # run id stable


def test_supervise_flushes_supervisor_telemetry(tmp_path):
    """REGRESSION: the supervisor's own registry/recorder — the only
    place the cross-attempt child launch/exit/rc story lives — must
    reach disk (flight_supervisor.json + metrics_supervisor.prom in the
    shared run dir), not die write-only with the process."""
    from paddle_tpu.distributed.elastic import supervise
    from paddle_tpu.utils.shutdown import PREEMPTED_RC
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import obs_report
    run = tmp_path / "runs"
    script = (
        "import os, sys\n"
        f"sys.exit({PREEMPTED_RC} "
        "if os.environ['PADDLE_TPU_ATTEMPT'] == '0' else 0)\n")
    rc = supervise([sys.executable, "-c", script], max_restarts=0,
                   backoff_s=0.01, run_dir=str(run))
    assert rc == 0
    flight = json.load(open(run / "flight_supervisor.json"))
    assert flight["reason"] == "supervise_exit"
    kinds = [e["kind"] for e in flight["events"]]
    assert kinds.count("elastic_child_launch") == 2
    exits = [e for e in flight["events"]
             if e["kind"] == "elastic_child_exit"]
    assert [e["rc"] for e in exits] == [PREEMPTED_RC, 0]
    prom = open(run / "metrics_supervisor.prom").read()
    assert "elastic_preemptions_total 1" in prom
    # and obs_report surfaces the supervisor's view
    s = obs_report.summarize(str(run))
    assert s["counters"]["elastic_preemptions"] == 1
    assert any(e["kind"] == "elastic_child_exit" for e in s["timeline"])
    # per-call isolation: a second supervise() in this process starts
    # from zero — no phantom counters/events from the first job
    run2 = tmp_path / "runs2"
    rc = supervise([sys.executable, "-c", "import sys; sys.exit(0)"],
                   max_restarts=0, backoff_s=0.01, run_dir=str(run2))
    assert rc == 0
    f2 = json.load(open(run2 / "flight_supervisor.json"))
    assert [e["kind"] for e in f2["events"]] == [
        "elastic_child_launch", "elastic_child_exit"]
    assert "elastic_preemptions_total 0" in \
        open(run2 / "metrics_supervisor.prom").read()


# ==================================================================== tool
def test_obs_report_check_mode():
    """CI self-test: schema drift between writer and reader fails."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import obs_report
    assert obs_report.self_check() == 0
