"""Bytes the traced ticks of Laguna's block must read (weights outside the routed experts once a tick: 9 attentions with their head gates, the dense FFN, 8 routers and shared experts, norms, head + the held experts that got a token + the window layers' in-band and the full layers' whole-context K and V) over 819 GB/s, over their device time: the share of the whole step."""
from benchmarks.harness import readers_laguna

NAME = "gqamoe_tick_membw_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_laguna.tick_membw_roofline(sources)
