"""Device ms a decode tick spends under the linear-attention layers' own scopes, `conv` + `delta_state` + `gate_norm` (the three convolutions and the tail's shift, the recurrence's state step, the per-head gated norm; their projections are under `qkv`): op time inside the _fused_tick* modules of the traced span, over the modules."""
from benchmarks.harness import readers_olmo_hybrid

NAME = "tick_linattn_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_olmo_hybrid.linattn_ms(sources)
