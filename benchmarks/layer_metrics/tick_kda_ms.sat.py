"""Device ms a decode tick spends under the Kimi-Delta-Attention layers' own scopes, `conv` + `decay_gate` + `delta_state` + `gate_norm` (the three convolutions and the tail's shift, the W_f product and the bounded sigmoid of the decay a key channel, the recurrence's state step, the per-head gated norm; their other projections are under `qkv`): op time inside the _fused_tick* modules of the traced span, over the modules."""
from benchmarks.harness import readers_ling

NAME = "tick_kda_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_ling.kda_ms(sources)
