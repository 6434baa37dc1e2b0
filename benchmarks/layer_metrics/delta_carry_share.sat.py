"""Prompt segments that started from the state the chunk before them left, of all prompt segments in the window: the engine's state_carries over state_carries + state_resets, both counted inside the chunk programs. 75-83 at 4-6 chunks a prompt; 0 for a program that restarts every chunk."""
from benchmarks.harness import readers_olmo_hybrid

NAME = "delta_carry_share.sat"
LAYER = "engine scheduler"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    return readers_olmo_hybrid.delta_carry_share(sources)
