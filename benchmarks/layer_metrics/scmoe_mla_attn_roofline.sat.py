"""The latent decode kernel, two calls a double layer, against its floor: the larger of the live rows' latent bytes (1,152 B a token and attention) over 819 GB/s and their heads x 2 x (576 + 512) FLOP a token and attention over 197 TFLOP/s, over the device time under the program's `attn` scope in the traced ticks."""
from benchmarks.harness import readers_longcat

NAME = "scmoe_mla_attn_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_longcat.mla_attn_roofline(sources)
