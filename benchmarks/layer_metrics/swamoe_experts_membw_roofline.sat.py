"""MiMo-V2's held experts' weight read against its memory floor: experts hit a tick (the engine's counters) x 3 x hidden x expert width x 2 B over 819 GB/s, over the device time under the program's `experts` scope in the traced ticks. Memory-bound (about 2 FLOP per byte per token)."""
from benchmarks.harness import readers_mimo

NAME = "swamoe_experts_membw_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_mimo.experts_membw_roofline(sources)
