"""Device ms a decode tick spends under `kv_layout`: the KV pool copied into the kernel's [P, B, kvh*d] layout, every layer, every tick, rate cells."""
from benchmarks.harness import spans

NAME = "tick_kv_layout_ms.rate"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "gap_p95_ms"
SOURCE = "device_trace"


def reduce(sources):
    return spans.scope_ms(sources, "kv_layout")
