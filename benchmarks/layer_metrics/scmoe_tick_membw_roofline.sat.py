"""Bytes the traced ticks of the double layer must read (weights outside the routed experts once a tick: 8 attentions, 8 dense FFNs, 4 routers, norms, head + the held experts that got a token + the live rows' latent rows of both attentions) over 819 GB/s, over their device time."""
from benchmarks.harness import readers_longcat

NAME = "scmoe_tick_membw_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_longcat.tick_membw_roofline(sources)
