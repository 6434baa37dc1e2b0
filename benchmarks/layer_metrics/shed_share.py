"""Requests the gateway shed (429) in the window over requests sent in it."""
from benchmarks.harness import readers

NAME = "shed_share"
LAYER = "front door and admission"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def reduce(sources):
    return readers.shed_share(sources)
