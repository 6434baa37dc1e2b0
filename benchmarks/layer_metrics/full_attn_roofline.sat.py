"""MiMo-V2's full layers' decode kernel calls against their memory floor: the live rows' whole-context K and V (context x kv heads x (key + value width) x 2 B x full layers, unpadded) over 819 GB/s, over the device time under `attn` in the traced ticks."""
from benchmarks.harness import readers_mimo

NAME = "full_attn_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_mimo.full_attn_roofline(sources)
