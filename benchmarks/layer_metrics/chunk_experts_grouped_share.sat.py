"""Expert layers of the window's prompt calls whose forward multiplied only the (position, held expert) pairs the router chose, of all of them: the engine's chunk_experts_grouped_calls over chunk_experts_layer_calls, counted on the host at a prompt call's dispatch. 100 where every prompt call's positions take the grouped product; a program without the counters reports nothing."""
from benchmarks.harness import readers_chunk_experts

NAME = "chunk_experts_grouped_share.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    return readers_chunk_experts.chunk_experts_grouped_share(sources)
