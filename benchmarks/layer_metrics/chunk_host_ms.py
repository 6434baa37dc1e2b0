"""The tick thread's own time in a prefill chunk (tick profiler phase `chunk`: input staging and bookkeeping around the program, not its uploads, call or the wait for it), over the prefill chunks counted in the window. The window's snapshots lie either side of `capture_trace`, whose `stop_trace` works for about 19 s while the server runs and slows the tick thread: the reading is up to twice the untraced per-tick figure (`PERF.md` section 5 gives both) and compares only with other traced runs."""
from benchmarks.harness import spans

NAME = "chunk_host_ms"
LAYER = "engine scheduler"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def reduce(sources):
    return spans.phase_ms(sources, ("chunk",), per="prefill_chunks")
