"""Bytes the traced ticks of MiMo-V2's block must read (weights outside the routed experts once a tick: 7 attentions, the dense FFN, 6 routers, norms, head + the held experts that got a token + the window layers' in-band and the full layers' whole-context K and V) over 819 GB/s, over their device time."""
from benchmarks.harness import readers_mimo

NAME = "swamoe_tick_membw_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_mimo.tick_membw_roofline(sources)
