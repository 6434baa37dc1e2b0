"""The tick thread's wall a decode tick under every phase that is not a wait by design (all of obs.TICK_PHASES and obs.LOOP_PHASES but device, idle, lock), rate cell: what the host adds to a tick that does not run ahead, chunk calls' host work included. Read in the run's two stretches with the profiler on and no tracer: from the window's first snapshot to the one before the profiler starts, and from the one after stop_trace returned to the window's last (the fixed arrival trace leaves the first all but idle). A program without the CPU counters, or under 20 ticks in them, reports nothing."""
from benchmarks.harness import readers_round

NAME = "host_round_ms.rate"
LAYER = "engine scheduler"
UNIT = "ms"
MOVES = "gap_p95_ms"
SOURCE = "program_span"


def reduce(sources):
    return readers_round.host_round_ms(sources, tail=True)
