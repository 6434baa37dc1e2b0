"""Bytes the traced ticks must read (weights once a tick + the live rows' K and V) over 819 GB/s, over their device time. Memory-bound by a wide margin at 8-32 rows. Rate cells."""
from benchmarks.harness import readers

NAME = "tick_membw_roofline.rate"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "gap_p95_ms"
SOURCE = "device_trace"


def reduce(sources):
    return readers.tick_membw_roofline(sources)
