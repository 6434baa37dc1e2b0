"""Share of the tick's wall time not spent waiting for the device (tick_profile phases host + h2d + dispatch + drain), saturated cells."""
from benchmarks.harness import readers

NAME = "tick_host_share.sat"
LAYER = "engine scheduler"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_span"


def reduce(sources):
    return readers.tick_host_share(sources)
