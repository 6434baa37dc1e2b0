"""Live rows per decode tick over the engine's slots, in the window."""
from benchmarks.harness import readers

NAME = "batch_occupancy"
LAYER = "engine scheduler"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    return readers.batch_occupancy(sources)
