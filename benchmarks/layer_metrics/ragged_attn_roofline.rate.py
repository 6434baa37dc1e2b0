"""The decode attention kernel (the Pallas kernel inside the tick programs, found in the device trace by its tpu_custom_call target) against its memory floor: K and V bytes of the live rows over 819 GB/s, over the kernel's device time. Memory-bound (about 14 FLOP per byte)."""
from benchmarks.harness import readers

NAME = "ragged_attn_roofline.rate"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "gap_p95_ms"
SOURCE = "device_trace"


def reduce(sources):
    return readers.ragged_attn_roofline(sources)
