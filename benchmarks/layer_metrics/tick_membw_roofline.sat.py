"""Bytes the traced ticks must read (weights once a tick + the live rows' K and V) over 819 GB/s, over their device time. Memory-bound by a wide margin at 8-32 rows. Saturated cells."""
from benchmarks.harness import readers

NAME = "tick_membw_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers.tick_membw_roofline(sources)
