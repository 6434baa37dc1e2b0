"""Device ms a decode tick of Laguna's block spends under the program's `attn` scope (the 3 full layers' ragged-kernel calls over the whole context, 48 query heads over 8 kv heads, a group of 6): op time inside the _fused_tick* modules of the traced span, over the modules."""
from benchmarks.harness import readers_laguna

NAME = "gqamoe_full_attn_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_laguna.full_attn_ms(sources)
