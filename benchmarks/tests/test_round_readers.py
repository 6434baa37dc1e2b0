"""The readers of the host's round (``harness/readers_round.py``) on
synthetic sources with known totals between ``w0`` and ``before``, and
the cases in which they must report nothing."""
import copy
import os

import pytest

from benchmarks.harness import cell, readers_round
from benchmarks.tests import tiny

PHASES = ("host", "commit", "expire", "admit", "chunk", "stage", "h2d",
          "dispatch", "device", "drain", "sched", "lock", "emit", "idle")
SAT = ["host_round_ms.sat", "host_round_offcpu_share.sat",
       "event_loop_cpu_ms.sat", "emit_to_wire_ms.sat"]
RATE = ["host_round_ms.rate", "emit_to_wire_ms.rate"]
BENCH = os.path.join(tiny.ROOT, "benchmarks")


def snap(t, ticks, wall_ms, cpu_ms, stream):
    """One replica's counters as ``cell.snapshot`` holds them."""
    engine = {"decode_ticks": ticks, "decode_steps": ticks}
    engine.update({"phase_cpu_us." + p: int(cpu_ms.get(p, 0.0) * 1e3)
                   for p in PHASES})
    return {"t": t, "health": {"tokens": stream[0], "stream": dict(zip(
                readers_round.STREAM_KEYS, stream))},
            "engines": [engine],
            "tick_phase_ms": [{p: wall_ms.get(p, 0.0) for p in PHASES}],
            "tick_wall_ms": [sum(wall_ms.values())]}


def sources(ticks=100):
    """A stretch of ``ticks`` decode ticks of 64 rows: 9 ms of work a
    tick of which 6 on a CPU, 4 ms of waits, the loop 3 ms of CPU a
    tick, a token 0.5 ms on its way and 0.05 in its coroutine. The
    counters start far from 0 and go on after ``before``."""
    w0 = snap(10.0, 1000, dict(emit=500.0, dispatch=300.0, host=50.0,
                               device=700.0, idle=90.0, lock=1.0),
              dict(emit=400.0, dispatch=200.0, host=40.0, device=5.0,
                   idle=1.0), (64000, 32_000_000, 3_200_000, 7_000_000))
    n = ticks
    before = snap(12.0, 1000 + n,
                  dict(emit=500.0 + 4 * n, dispatch=300.0 + 3 * n,
                       host=50.0 + 2 * n, device=700.0 + 3.5 * n,
                       idle=90.0 + 0.25 * n, lock=1.0 + 0.25 * n),
                  dict(emit=400.0 + 3 * n, dispatch=200.0 + 2 * n,
                       host=40.0 + 1 * n, device=5.0 + 0.1 * n, idle=1.0),
                  (64000 + 64 * n, 32_000_000 + 32_000 * n,
                   3_200_000 + 3_200 * n, 7_000_000 + 3_000 * n))
    after = copy.deepcopy(before)
    after["t"] = 34.0
    after["tick_phase_ms"][0]["emit"] += 1e6        # across the tracer
    w1 = copy.deepcopy(after)                       # the tail: 50 ticks
    w1["t"] = 61.0                                  # at twice the cost
    w1["engines"][0]["decode_ticks"] += 50
    for p, ms in dict(emit=8, dispatch=6, host=4, device=7).items():
        w1["tick_phase_ms"][0][p] += ms * 50
    w1["health"]["stream"]["stream_tokens"] += 64 * 50
    w1["health"]["stream"]["emit_to_wire_us"] += 64 * 50 * 1000
    return {"snaps": {"w0": w0, "w1": w1},
            "trace_times": {"ta": 12.0, "tb": 15.0, "before": before,
                            "after": after}}


def reader(name):
    return cell.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "t_round_" + name.replace(".", "_"))


# a saturated cell's read the head alone, a rate cell's the tail too:
# 150 ticks, (100 x 9 + 50 x 18) ms of work, 9,600 tokens
WANT = {"host_round_ms.sat": 9.0, "host_round_ms.rate": 12.0,
        "host_round_offcpu_share.sat": 100.0 * 3 / 9,
        "event_loop_cpu_ms.sat": 3.0,
        "emit_to_wire_ms.sat": 0.5, "emit_to_wire_ms.rate": 2 / 3}


@pytest.mark.parametrize("name", SAT + RATE)
def test_a_reader_gives_the_stretchs_figure_a_tick(name, capsys):
    assert reader(name).reduce(sources()) == pytest.approx(WANT[name])
    err = capsys.readouterr().err
    if name in SAT:                                 # the table, once
        assert "100 decode ticks in 2.00 s" in err
        assert "emit          4.000    3.000" in err
        assert "device        3.500    0.100   (a wait)" in err
    else:
        assert "150 decode ticks in 29.00 s" in err


def test_a_rate_cells_tail_counts_only_inside_the_window():
    """A short run's ``stop_trace`` returns after the window closed:
    the head alone is read, and a head under 20 ticks reads nothing
    until the tail joins it."""
    src = sources()
    src["trace_times"]["after"]["t"] = 62.0
    assert reader("host_round_ms.rate").reduce(src) == pytest.approx(9.0)
    quiet = sources(ticks=15)
    assert reader("host_round_ms.sat").reduce(quiet) is None
    assert reader("host_round_ms.rate").reduce(quiet) == pytest.approx(
        (15 * 9 + 50 * 18) / 65)


def without_cpu_counters(src):
    for s in (src["snaps"]["w0"], src["trace_times"]["before"]):
        for e in s["engines"]:
            for k in [k for k in e if k.startswith("phase_cpu_us.")]:
                del e[k]


def without_stream(src):
    for s in (src["snaps"]["w0"], src["trace_times"]["before"]):
        del s["health"]["stream"]


def profiler_off(src):
    for s in (src["snaps"]["w0"], src["trace_times"]["before"]):
        s["tick_phase_ms"] = [None]


@pytest.mark.parametrize("name", SAT + RATE)
@pytest.mark.parametrize("spoil", [
    without_cpu_counters, without_stream, profiler_off,
    lambda src: src.pop("trace_times"),
    lambda src: src["trace_times"].pop("before"),
    lambda src: src.update(sources(ticks=19), snaps=dict(
        src["snaps"], w1=sources(ticks=19)["trace_times"]["after"])),
], ids=["parent-engine", "parent-gateway", "profiler-off", "untraced",
        "no-before", "under-20-ticks"])
def test_a_reader_reports_nothing_and_does_not_raise(name, spoil):
    src = sources()
    spoil(src)
    assert reader(name).reduce(src) is None


def test_a_silent_stream_leaves_the_tick_threads_figures():
    """No token written in the stretch (the counters stood still): the
    loop's two metrics have nothing to divide, the thread's two read."""
    src = sources()
    src["trace_times"]["before"]["health"]["stream"] = dict(
        src["snaps"]["w0"]["health"]["stream"])
    assert reader("host_round_ms.sat").reduce(src) == pytest.approx(9.0)
    assert reader("emit_to_wire_ms.sat").reduce(src) is None
    assert reader("event_loop_cpu_ms.sat").reduce(src) is None


def test_the_six_entries_name_their_cells_and_what_they_move():
    per_layer = {m["name"]: m for m in tiny.real_manifest()["per_layer"]}
    cells = [w["name"] for w in tiny.real_manifest()["workloads"]]
    saturated = [c for c in cells if c != "qwen2-7b-d16.chat"]
    for name in SAT:
        assert per_layer[name]["workloads"] == saturated
        assert per_layer[name]["moves"] == "tokens_per_s"
    for name in RATE:
        assert per_layer[name]["workloads"] == ["qwen2-7b-d16.chat"]
    assert per_layer["host_round_ms.rate"]["moves"] == "gap_p95_ms"
    assert per_layer["emit_to_wire_ms.rate"]["moves"] == "ttft_p50_ms"
    for name in SAT + RATE:
        assert per_layer[name]["source"] == "program_span"
        assert per_layer[name]["better"] == "lower"
    assert list(per_layer)[-6:] == [
        "host_round_ms.sat", "host_round_ms.rate",
        "host_round_offcpu_share.sat", "event_loop_cpu_ms.sat",
        "emit_to_wire_ms.sat", "emit_to_wire_ms.rate"]
