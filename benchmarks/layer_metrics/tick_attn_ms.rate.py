"""Device ms a decode tick spends under the program's `attn` scope (schedule build and the ragged kernel; the pool's relayout has its own scope): op time inside the _fused_tick* modules of the traced span, over the modules, rate cells."""
from benchmarks.harness import spans

NAME = "tick_attn_ms.rate"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "gap_p95_ms"
SOURCE = "device_trace"


def reduce(sources):
    return spans.scope_ms(sources, "attn")
