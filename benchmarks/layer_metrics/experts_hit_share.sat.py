"""Held routed experts that got a token from a live row, per expert layer and tick, over the experts held: the engine's moe_experts_hit over moe_layer_ticks x n_routed_experts, in the window. What the traffic and the router leave of the rank's share idle."""
from benchmarks.harness import readers_moe

NAME = "experts_hit_share.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    return readers_moe.experts_hit_share(sources)
