"""The channel-decay state step against its memory floor: the traced ticks x the window's mean of live rows a tick (engine counters) x 2 (read and write) x 32 x 128 x 128 x 4 B of float32 state a layer, x 12 layers, over 819 GB/s, over the device time under `delta_state` in the traced ticks."""
from benchmarks.harness import readers_ling

NAME = "kda_state_membw_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_ling.kda_state_membw_roofline(sources)
