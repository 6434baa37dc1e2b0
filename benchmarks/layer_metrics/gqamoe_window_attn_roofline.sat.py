"""Laguna's window layers' decode kernel calls against their memory floor: the live rows' in-band K and V (min(context, 512) x 8 kv heads x 2 x 128 x 2 B, summed over the 6 window layers: the engine's kv_window_tokens a tick x the traced ticks x 4,096 B) over 819 GB/s, over the device time under `attn_window` in the traced ticks."""
from benchmarks.harness import readers_laguna

NAME = "gqamoe_window_attn_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_laguna.window_attn_roofline(sources)
