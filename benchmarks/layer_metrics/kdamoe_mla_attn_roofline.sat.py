"""The 2 latent layers' ragged decode kernel against its floor (the larger of 576 x 2 B a context token over 819 GB/s and 32 heads x 2 x (576 + 512) FLOP over 197 TFLOP/s), over the device time under `attn` in the traced ticks."""
from benchmarks.harness import readers_ling

NAME = "kdamoe_mla_attn_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_ling.mla_attn_roofline(sources)
