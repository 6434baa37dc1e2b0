"""The full layers' prompt attention against causal attention's own operations: 2 x 2 x 128 FLOP a (query, key at or before it) pair and query head, x 144 heads over the 3 full layers, for the traced prompt calls (the calls in the trace x the window's mean of causal pairs a call, a prompt of L tokens being L (L + 1) / 2 pairs however it is chunked), over 197 TFLOP/s, over the device time under `chunk_attn`. The walk writes each run's float32 scores to HBM and reads them again: a low share is that, not a miscount."""
from benchmarks.harness import readers_laguna

NAME = "chunk_full_attn_flops_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_laguna.chunk_full_attn_flops_roofline(sources)
