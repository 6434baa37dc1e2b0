"""Device ms a decode tick spends in its expert layers: op time under the program's `router`, `experts` and `shared_expert` scopes inside the _fused_tick* modules of the traced span, over the modules, saturated cells."""
from benchmarks.harness import spans

NAME = "tick_moe_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return spans.scope_ms(sources, "router", "experts", "shared_expert")
