"""Bytes the traced ticks must read (weights outside the routed experts once a tick + the held experts that got a token + the live rows' latent rows) over 819 GB/s, over their device time. The expert-parallel rank's tick_membw_roofline."""
from benchmarks.harness import readers_moe

NAME = "moe_tick_membw_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_moe.moe_tick_membw_roofline(sources)
