"""The tick thread's wall a decode tick under every phase that is not a wait by design (all of obs.TICK_PHASES and obs.LOOP_PHASES but device, idle, lock), saturated cells: the host's round, which sets the pace where it is longer than the device's tick. Read from the window's first snapshot to the one before the profiler starts: the engines' tick profiler is on and no tracer runs, so it is what an untraced server shows (tick_emit_ms, tick_dispatch_ms, tick_commit_ms read across the tracer and read about twice this). A program without the CPU counters, or a stretch under 20 ticks, reports nothing."""
from benchmarks.harness import readers_round

NAME = "host_round_ms.sat"
LAYER = "engine scheduler"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "program_span"


def reduce(sources):
    return readers_round.host_round_ms(sources)
