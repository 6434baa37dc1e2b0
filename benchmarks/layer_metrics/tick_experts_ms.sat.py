"""Device ms a decode tick spends under the program's `experts` scope (the held routed experts' three products and their gates): op time inside the _fused_tick* modules of the traced span, over the modules, saturated cells."""
from benchmarks.harness import spans

NAME = "tick_experts_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return spans.scope_ms(sources, "experts")
