"""The readers of the program's own names (``harness/spans.py``) on a
recorded trace that carries them: three decode ticks of
``qwen2-7b-d16.batch-decode`` on a TPU v5e with the tick profiler on
(PR 24's first traced run; cut by ``make_span_fixture.py``), and on the
PR 23 fixture, which has neither scopes nor the host's line."""
import os

import pytest

from benchmarks.harness import cell, spans, trace
from benchmarks.tests import make_span_fixture, tiny

SCOPED = os.path.join(tiny.DATA, "v5e_scoped.xplane.pb")
PLAIN = os.path.join(tiny.DATA, "v5e_ticks.xplane.pb")
NEW = ("tick_attn_ms", "tick_kv_layout_ms", "tick_sample_ms",
       "tick_unscoped_share", "chunk_host_ms", "tick_commit_ms",
       "tick_dispatch_ms", "tick_emit_ms", "tick_admit_ms",
       "idle_unnamed_share")


def metric(name, sources):
    mod = cell.load_module(os.path.join(
        tiny.ROOT, "benchmarks", "layer_metrics", name + ".py"),
        "t_" + name.replace(".", "_"))
    return mod.reduce(sources)


def test_the_scope_list_is_the_programs(monkeypatch):
    from paddle_tpu.utils import observability as obs
    assert spans.scopes() == obs.TICK_SCOPES
    assert set(spans.NOT_WORK) <= set(obs.TICK_PHASES + obs.LOOP_PHASES)
    # a program from before the scopes names none: nothing is under one
    monkeypatch.delattr(obs, "TICK_SCOPES")
    assert spans.scopes() == ()
    assert spans.scope_of("jit(_fused_tick)/attn/dot_general:") is None


def test_an_op_takes_the_innermost_scope_of_its_op_name():
    assert spans.scope_of(
        "jit(_fused_tick_greedy)/attn/kv_layout/reshape:") == "kv_layout"
    assert spans.scope_of("jit(_fused_tick)/sample/jit(_where)/select_n:") \
        == "sample"
    # a name that merely contains a scope's letters is not under it
    assert spans.scope_of("jit(_fused_tick)/normalize/mul:") is None
    assert spans.scope_of("") is None and spans.scope_of(None) is None


def test_nested_spans_flatten_to_the_innermost_and_ops_to_their_own_time():
    line = [("tick", 1.0, 9.0), ("stage", 2.0, 6.0), ("h2d", 3.0, 4.0),
            ("dispatch", 6.0, 8.0), ("emit", 10.0, 11.0)]
    assert spans.innermost(line) == [
        (1.0, 2.0, "host"), (2.0, 3.0, "stage"), (3.0, 4.0, "h2d"),
        (4.0, 6.0, "stage"), (6.0, 8.0, "dispatch"), (8.0, 9.0, "host"),
        (10.0, 11.0, "emit")]
    flat = spans.innermost(line)
    got = spans.overlap((3.5, 10.5), flat, [s[0] for s in flat])
    assert got == {"h2d": 0.5, "stage": 2.0, "dispatch": 2.0, "host": 1.0,
                   "emit": 0.5}
    ops = [("while", 0.0, 10.0), ("a", 1.0, 2.0), ("b", 4.0, 3.0),
           ("c", 12.0, 1.0)]
    assert sorted(spans.self_times(ops)) == [
        ("a", 1.0, 2.0), ("b", 4.0, 3.0), ("c", 12.0, 1.0),
        ("while", 0.0, 5.0)]


def test_the_recorded_ticks_reduce_to_their_scopes(monkeypatch):
    r = spans.reduce_spans(SCOPED)
    old = trace.reduce_trace(SCOPED)
    assert r["ticks"] == 3 and r["tick_threads"] == 1
    ops = sum(r["by_scope"].values())
    # the ops run one after another: their time is the device's busy time
    assert ops == pytest.approx(old["busy_s"], rel=1e-3)
    # no entry of the table is named by a shape
    assert set(r["by_scope"]) - {None} == set(spans.scopes()) - {"chunk_attn"}
    monkeypatch.setattr(spans, "find_trace", lambda: SCOPED)
    src = {}
    # 16 kernels a tick at 0.59 ms (the old reader's tick_kernels: 48 in
    # 28.31 ms) and 0.02 ms of schedule building
    assert old["tick_kernels"]["s"] / 3 == pytest.approx(9.438e-3, abs=1e-6)
    assert metric("tick_attn_ms.sat", src) == pytest.approx(9.457, abs=2e-3)
    # 32 copies of a 33.5 MB pool a tick, 0.098 ms each
    assert metric("tick_kv_layout_ms.sat", src) == \
        pytest.approx(3.143, abs=2e-3)
    assert metric("tick_kv_layout_ms.rate", src) == \
        metric("tick_kv_layout_ms.sat", src)
    # a greedy tick: an argmax and a log-softmax over [8, 152064]
    assert metric("tick_sample_ms.rate", src) == \
        pytest.approx(0.0128, abs=2e-4)
    # the weight prefetch copies XLA adds carry no op_name
    assert metric("tick_unscoped_share.sat", src) == \
        pytest.approx(0.386, abs=2e-3)
    assert max(r["unscoped_ops"], key=r["unscoped_ops"].get) == \
        "copy bf16[3584,3584]"
    assert "_spans" in src              # read once, kept with the run


def test_the_idle_time_between_two_ticks_falls_under_its_host_phases():
    """By hand from the recorded line: the first module ends at
    76.984642 ms on the trace's clock and the second starts at
    81.592478. The host leaves ``tick/device`` at 79.019342, drains
    until 79.870672, commits for 0.175 ms, and is 1.439 ms into
    ``tick/dispatch`` when the device starts."""
    mods = sorted((s, s + d) for _, s, d in
                  trace.read_planes(SCOPED)["/device:TPU:0"]["modules"])
    gap = (mods[0][1], mods[1][0])
    assert gap == pytest.approx((76.984642e-3, 81.592478e-3), abs=1e-9)
    line = spans.innermost(spans.host_lines(SCOPED)[0])
    got = spans.overlap(gap, line, [s[0] for s in line])
    want = {"device": 2.034700, "host": 0.037650, "drain": 0.839800,
            "commit": 0.175470, "expire": 0.006490, "admit": 0.003690,
            "stage": 0.070860, "dispatch": 1.439176}
    assert {k: 1e3 * v for k, v in got.items()} == \
        pytest.approx(want, abs=1e-5)
    assert sum(want.values()) == pytest.approx(1e3 * (gap[1] - gap[0]),
                                               abs=1e-3)
    # over the three ticks: 9.652 ms idle, 5.432 of it under a phase in
    # which the host works (not device, not the residual)
    r = spans.reduce_spans(SCOPED)
    assert r["idle_s"] == pytest.approx(9.652e-3, abs=1e-6)
    assert r["idle_named_s"] == pytest.approx(5.432e-3, abs=1e-6)
    assert spans.idle_unnamed_share({"_spans": r}) == \
        pytest.approx(43.72, abs=0.01)
    assert sum(r["idle_by_phase"].values()) == pytest.approx(r["idle_s"])


def test_a_trace_without_the_programs_names_gives_nothing_to_read(
        monkeypatch):
    monkeypatch.setattr(spans, "find_trace", lambda: PLAIN)
    src = {}
    assert metric("idle_unnamed_share.sat", src) == 100.0
    assert metric("tick_unscoped_share.rate", src) == 100.0
    for name in ("tick_attn_ms.sat", "tick_attn_ms.rate",
                 "tick_kv_layout_ms.sat", "tick_sample_ms.rate"):
        assert metric(name, src) is None
    # and no trace at all (a checkout that never ran) is not an error
    monkeypatch.setattr(spans, "find_trace", lambda: None)
    assert metric("tick_attn_ms.sat", {}) is None
    assert metric("tick_unscoped_share.sat", {}) is None
    assert metric("idle_unnamed_share.rate", {}) is None


def snaps(phases0, phases1, stats0, stats1):
    return {"snaps": {"w0": {"tick_phase_ms": phases0, "engines": stats0},
                      "w1": {"tick_phase_ms": phases1, "engines": stats1}}}


def test_phase_metrics_divide_the_windows_totals_by_its_ticks():
    zero = dict.fromkeys(("commit", "stage", "h2d", "dispatch", "emit",
                          "sched", "admit", "chunk"), 1.0)
    end = dict(zero, commit=21.0, stage=11.0, h2d=6.0, dispatch=401.0,
               emit=61.0, sched=5.0, admit=7.0, chunk=31.0)
    src = snaps([zero, zero], [end, end],
                [{"decode_ticks": 10, "prefill_chunks": 2}] * 2,
                [{"decode_ticks": 110, "prefill_chunks": 12}] * 2)
    assert metric("tick_commit_ms.sat", src) == pytest.approx(0.2)
    assert metric("tick_dispatch_ms.rate", src) == pytest.approx(4.15)
    assert metric("tick_emit_ms.sat", src) == pytest.approx(0.6)
    assert metric("tick_admit_ms.rate", src) == pytest.approx(0.1)
    assert metric("chunk_host_ms", src) == pytest.approx(3.0)
    # the parent's profiler: five phases, no tick counts; or off
    old = {"host": 1.0, "h2d": 1.0, "dispatch": 1.0, "device": 1.0,
           "drain": 1.0}
    for src in (snaps([old], [old], [{"decode_steps": 1}],
                      [{"decode_steps": 9}]),
                snaps([None], [None], [{}], [{}]),
                snaps([zero], [end], [{"decode_ticks": 5,
                                       "prefill_chunks": 0}] * 1,
                      [{"decode_ticks": 5, "prefill_chunks": 0}])):
        for name in ("tick_commit_ms.rate", "tick_dispatch_ms.sat",
                     "tick_emit_ms.rate", "tick_admit_ms.rate",
                     "chunk_host_ms"):
            assert metric(name, src) is None


def test_the_manifest_lists_each_new_metric_in_the_cell_it_reads():
    m = tiny.real_manifest()
    new = {x["name"]: x for x in m["per_layer"]
           if x["name"].split(".")[0] in NEW}
    assert {n.split(".")[0] for n in new} == set(NEW) and len(new) == 17
    for name, x in new.items():
        want = "qwen2-7b-d16.batch-decode" if name.endswith(".sat") \
            else "qwen2-7b-d16.chat"
        assert x["workloads"] == [want] and x["better"] == "lower"


def test_cutting_the_fixture_again_changes_nothing(tmp_path):
    again = tmp_path / "again.xplane.pb"
    make_span_fixture.main(["", SCOPED, str(again), "3"])
    assert again.read_bytes() == open(SCOPED, "rb").read()
    assert os.path.getsize(SCOPED) < 300_000


def test_a_traced_rehearsal_reports_every_new_metric(monkeypatch):
    """``run_cell`` end to end on the CPU with the recorded trace in
    the place of the run's own (the CPU's has no device plane): the new
    files load, find the snapshots' phases and counters, and the result
    line carries every ``.sat`` one."""
    from benchmarks.harness import peaks
    from benchmarks.tests.test_rehearsal import run
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(trace, "find_xplane", lambda logdir: SCOPED)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    traced = run("tiny.batch", trace=True)
    want = {m["name"] for m in tiny.tiny_manifest()["per_layer"]
            if m["moves"] in ("tokens_per_s", "setup_s")}
    assert set(traced["metrics"]) == want
    assert {n for n in want if n.split(".")[0] in NEW} == {
        "tick_attn_ms.sat", "tick_kv_layout_ms.sat",
        "tick_unscoped_share.sat", "tick_commit_ms.sat",
        "tick_dispatch_ms.sat", "tick_emit_ms.sat",
        "idle_unnamed_share.sat"}
    for name in ("tick_commit_ms.sat", "tick_dispatch_ms.sat",
                 "tick_emit_ms.sat"):
        assert traced["metrics"][name]["value"] > 0
