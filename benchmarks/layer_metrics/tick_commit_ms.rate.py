"""Tick profiler phase `commit` a decode tick: drained tokens appended to their requests, stop matching, request-trace events, finishes, rate cells. The window's snapshots lie either side of `capture_trace`, whose `stop_trace` works for about 19 s while the server runs and slows the tick thread: the reading is up to twice the untraced per-tick figure (`PERF.md` section 5 gives both) and compares only with other traced runs."""
from benchmarks.harness import spans

NAME = "tick_commit_ms.rate"
LAYER = "engine scheduler"
UNIT = "ms"
MOVES = "gap_p95_ms"
SOURCE = "program_span"


def reduce(sources):
    return spans.phase_ms(sources, ("commit",))
