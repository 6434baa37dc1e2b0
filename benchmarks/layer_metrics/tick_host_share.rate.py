"""Share of the tick's wall time not spent waiting for the device (tick_profile phases host + h2d + dispatch + drain), rate cells."""
from benchmarks.harness import readers

NAME = "tick_host_share.rate"
LAYER = "engine scheduler"
UNIT = "%"
MOVES = "gap_p95_ms"
SOURCE = "program_span"


def reduce(sources):
    return readers.tick_host_share(sources)
