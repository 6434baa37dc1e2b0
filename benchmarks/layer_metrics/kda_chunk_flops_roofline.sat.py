"""The chunkwise delta rule against the recurrence's own operations: 3 x 2 x 128 x 128 FLOP a prompt position and head (S^T k, the rank-one write, S^T q), x 32 heads x 12 layers, for the traced prompt calls' positions (the calls in the trace x the window's mean of prompt tokens a call), over 197 TFLOP/s, over the device time under `chunk_delta_state`. The form runs float32 at highest precision and solves a triangular system besides: a low share is its cost, not a miscount."""
from benchmarks.harness import readers_ling

NAME = "kda_chunk_flops_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_ling.kda_chunk_flops_roofline(sources)
