"""Arithmetic of the metrics that read the HOST's round of a decode
tick where it runs and with no tracer on: the tick thread's wall and
CPU by phase (the tick profiler's totals, ``PagedEngine.stats``'
``phase_cpu_us.<phase>``) and the event loop's token writes
(``Gateway.health()["stream"]``).

A saturated cell's (``.sat``) read ONE stretch of a ``--trace 1`` run,
its HEAD: from the window's first snapshot (``snaps["w0"]``) to the one
taken just before the profiler starts (``trace_times["before"]``,
``cell.TRACE_OFFSET_S`` later). The engines run their tick profiler
there and no tracer runs yet, so a figure is what an untraced server
would show plus the profiler's own brackets, over steady decode ticks
ahead of the wave's first turnover. A rate cell's (``.rate``) add the
run's TAIL, from the snapshot taken when ``stop_trace`` has returned
(``trace_times["after"]``) to the window's last (``snaps["w1"]``),
where that lies inside the window: a fixed arrival trace may leave the
head all but idle (chat's holds 15 decode ticks), and a rate cell has
no turnover to stay ahead of. Figures are per decode tick.

A reader returns None, and never raises, where the program lacks a
counter (the parent of the PR that added them), where the run kept no
``trace_times`` and where its stretches hold under ``MIN_TICKS`` decode
ticks. The whole table goes to stderr on first use.
"""
from __future__ import annotations

from typing import Dict, Optional

from .cell import note

MIN_TICKS = 20
# the phases in which the tick thread waits by design: for the device,
# for work, for a sibling replica's tick
WAITS = ("device", "idle", "lock")
CPU_KEY = "phase_cpu_us."
STREAM_KEYS = ("stream_tokens", "emit_to_wire_us", "loop_write_us",
               "event_loop_cpu_us")


def _stretch(a: dict, b: dict) -> dict:
    """The totals between two snapshots over every replica: decode
    ticks, the tick thread's wall and CPU ms by phase, the event
    loop's sums."""
    wall: Dict[str, float] = {}
    cpu: Dict[str, float] = {}
    ticks = 0
    for wa, wb, ea, eb in zip(a["tick_phase_ms"], b["tick_phase_ms"],
                              a["engines"], b["engines"]):
        ticks += eb["decode_ticks"] - ea["decode_ticks"]
        for p in wb:
            wall[p] = wall.get(p, 0.0) + wb[p] - wa[p]
            cpu[p] = cpu.get(p, 0.0) + (
                eb[CPU_KEY + p] - ea[CPU_KEY + p]) / 1e3
    sa, sb = a["health"]["stream"], b["health"]["stream"]
    return {"ticks": ticks, "wall_ms": wall, "cpu_ms": cpu,
            "stream": {k: sb[k] - sa[k] for k in STREAM_KEYS},
            "seconds": b["t"] - a["t"]}


def reduce_round(src, tail: bool = False) -> Optional[dict]:
    """The head's totals and, asked for, the tail's added to them."""
    try:
        times = src["trace_times"]
        r = _stretch(src["snaps"]["w0"], times["before"])
        if tail and times["after"]["t"] < src["snaps"]["w1"]["t"]:
            more = _stretch(times["after"], src["snaps"]["w1"])
            for k in ("wall_ms", "cpu_ms", "stream"):
                r[k] = {p: v + more[k][p] for p, v in r[k].items()}
            r["ticks"] += more["ticks"]
            r["seconds"] += more["seconds"]
    except (KeyError, TypeError):
        return None
    return r if r["ticks"] >= MIN_TICKS and r["wall_ms"] else None


def print_table(r: dict):
    n = r["ticks"]
    note(f"the host's round, profiler on and no tracer ({n} decode ticks "
         f"in {r['seconds']:.2f} s; ms a tick: wall, the thread's CPU):")
    for p, ms in sorted(r["wall_ms"].items(), key=lambda kv: -kv[1]):
        note(f"  {p:<10s} {ms / n:8.3f} {r['cpu_ms'][p] / n:8.3f}"
             + ("   (a wait)" if p in WAITS else ""))
    s = r["stream"]
    per = max(s["stream_tokens"], 1)
    note(f"  the event loop: {s['stream_tokens'] / n:.1f} tokens a tick, "
         f"{s['event_loop_cpu_us'] / 1e3 / n:.3f} ms of CPU a tick, push "
         f"to written {s['emit_to_wire_us'] / 1e3 / per:.3f} ms a token, "
         f"dequeue to written {s['loop_write_us'] / 1e3 / per:.3f}")


def round_of(src, tail: bool = False) -> Optional[dict]:
    """The run's reduction, made on first use and kept in ``src``."""
    key = "_round_tail" if tail else "_round"
    if key not in src:
        src[key] = reduce_round(src, tail)
        if src[key]:
            print_table(src[key])
    return src[key]


def _work(r: dict, side: str) -> float:
    return sum(ms for p, ms in r[side].items() if p not in WAITS)


def host_round_ms(src, tail: bool = False) -> Optional[float]:
    """The tick thread's wall a decode tick under every phase, of the
    tick and of the loop around it, that is not a wait by design."""
    r = round_of(src, tail)
    return _work(r, "wall_ms") / r["ticks"] if r else None


def host_round_offcpu_share(src) -> Optional[float]:
    """Of that wall, the share in which the thread was on no CPU: it
    wanted the interpreter lock, or a core."""
    r = round_of(src)
    wall = _work(r, "wall_ms") if r else 0.0
    return 100.0 * (wall - _work(r, "cpu_ms")) / wall if wall > 0 else None


def event_loop_cpu_ms(src) -> Optional[float]:
    """CPU the event loop's thread used, whatever for, a decode tick."""
    r = round_of(src)
    return r["stream"]["event_loop_cpu_us"] / 1e3 / r["ticks"] \
        if r and r["stream"]["stream_tokens"] else None


def emit_to_wire_ms(src, tail: bool = False) -> Optional[float]:
    """Mean time of a token from the tick thread's push to the return
    of the loop's ``writer.write``."""
    r = round_of(src, tail)
    n = r["stream"]["stream_tokens"] if r else 0
    return r["stream"]["emit_to_wire_us"] / 1e3 / n if n else None
