"""Device ms a decode tick spends under the program's `attn_gate` scope (Laguna: every layer's [hidden, heads] gate projection, its sigmoid and the multiply a head before o_proj): op time inside the _fused_tick* modules of the traced span, over the modules."""
from benchmarks.harness import readers_laguna

NAME = "tick_attn_gate_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_laguna.attn_gate_ms(sources)
