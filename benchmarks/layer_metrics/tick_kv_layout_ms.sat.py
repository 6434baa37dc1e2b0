"""Device ms a decode tick spends under `kv_layout`: the KV pool copied into the kernel's [P, B, kvh*d] layout, every layer, every tick, saturated cells."""
from benchmarks.harness import spans

NAME = "tick_kv_layout_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return spans.scope_ms(sources, "kv_layout")
