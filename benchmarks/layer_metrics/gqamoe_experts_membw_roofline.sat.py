"""The weights of Laguna's held routed experts that got a token (3 x 3072 x 1024 x 2 B each, the engine's moe_experts_hit a layer and tick x 8 expert layers x the traced ticks) over 819 GB/s, over the device time under `experts` in the traced ticks."""
from benchmarks.harness import readers_laguna

NAME = "gqamoe_experts_membw_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_laguna.experts_membw_roofline(sources)
