"""The linear layers' decode step against its memory floor: rows updated x 2 (read and write) x 30 x 96 x 192 x 4 B of float32 state a layer, x 12 layers (unpadded), over 819 GB/s, over the device time under `delta_state` in the traced ticks."""
from benchmarks.harness import readers_olmo_hybrid

NAME = "delta_state_membw_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_olmo_hybrid.delta_state_membw_roofline(sources)
