"""MiMo-V2's window layers' decode kernel calls against their memory floor: the live rows' in-band K and V (min(context, window) x kv heads x (key + value width) x 2 B x window layers, unpadded) over 819 GB/s, over the device time under `attn_window` in the traced ticks."""
from benchmarks.harness import readers_mimo

NAME = "window_attn_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_mimo.window_attn_roofline(sources)
