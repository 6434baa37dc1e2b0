"""Olmo-Hybrid's full layers' decode kernel calls (a query group of one) against their memory floor: the live rows' whole-context K and V (context x 15,360 B x 4 layers, unpadded) over 819 GB/s, over the device time under `attn` in the traced ticks."""
from benchmarks.harness import readers_olmo_hybrid

NAME = "hybrid_attn_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_olmo_hybrid.attn_roofline(sources)
