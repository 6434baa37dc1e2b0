"""Every byte a decode tick must move (weights outside the experts once, the experts hit, the live rows' 12 states read and written, their latent rows in 2 layers) over 819 GB/s, over the _fused_tick* modules' device time in the traced span: the share of the whole step."""
from benchmarks.harness import readers_ling

NAME = "kdamoe_tick_membw_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_ling.tick_membw_roofline(sources)
