"""The held 768-wide experts' kernel against its memory floor: experts that got a token x 3 x 2560 x 768 x 2 B (11.80 MB), x 12 expert layers, over 819 GB/s, over the device time under `experts` in the traced ticks."""
from benchmarks.harness import readers_ling

NAME = "kdamoe_experts_membw_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_ling.experts_membw_roofline(sources)
