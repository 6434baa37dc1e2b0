"""Laguna's full layers' decode kernel calls against their memory floor: the live rows' whole-context K and V (the engine's kv_context_tokens a tick x the traced ticks x 4,096 B) over 819 GB/s, over the device time under `attn` in the traced ticks."""
from benchmarks.harness import readers_laguna

NAME = "gqamoe_full_attn_roofline.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_laguna.full_attn_roofline(sources)
