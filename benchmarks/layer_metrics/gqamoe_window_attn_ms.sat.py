"""Device ms a decode tick of Laguna's block spends under the program's `attn_window` scope (the 6 window layers' ragged-kernel calls: a band of 512 positions, 72 query heads over 8 kv heads, a group of 9): op time inside the _fused_tick* modules of the traced span, over the modules."""
from benchmarks.harness import readers_laguna

NAME = "gqamoe_window_attn_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_laguna.window_attn_ms(sources)
