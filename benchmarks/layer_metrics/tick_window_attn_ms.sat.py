"""Device ms a decode tick spends under the program's `attn_window` scope (the window layers' ragged-kernel calls: a band of `sliding_window` positions, the sink folded into the online softmax): op time inside the _fused_tick* modules of the traced span, over the modules."""
from benchmarks.harness import readers_mimo

NAME = "tick_window_attn_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_mimo.window_attn_ms(sources)
