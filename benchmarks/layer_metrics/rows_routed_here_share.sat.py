"""Live rows of which at least one chosen expert is held by this rank, of all live rows, per expert layer and tick: the engine's moe_rows_routed_here over active_slot_steps x 12, in the window. Group-limited routing (4 of 8 groups a token) sends the others past this rank altogether."""
from benchmarks.harness import readers_ling

NAME = "rows_routed_here_share.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    return readers_ling.rows_routed_here_share(sources)
