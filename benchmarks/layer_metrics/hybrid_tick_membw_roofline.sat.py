"""Bytes the traced ticks of Olmo-Hybrid's stage must move (weights outside the embedding once a tick + the live rows' linear-layer states read and written + the full layers' whole-context K and V) over 819 GB/s, over their device time: the share of the whole step."""
from benchmarks.harness import readers_olmo_hybrid

NAME = "hybrid_tick_membw_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_olmo_hybrid.tick_membw_roofline(sources)
