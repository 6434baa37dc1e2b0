"""Live rows' router choices that fell on a zero-compute (identity) column, of all their choices: the engine's moe_zero_choices over moe_live_choices, in the window. 256 of the router's 768 columns are such; a choice there adds gate x hidden state and reads no expert."""
from benchmarks.harness import readers_longcat

NAME = "zero_expert_choice_share.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    return readers_longcat.zero_expert_choice_share(sources)
