"""Device ms a decode tick spends under `delta_state` in the Kimi-Delta-Attention layers: the channel-decay state step's kernel with its state read and write, op time inside the _fused_tick* modules of the traced span, over the modules."""
from benchmarks.harness import readers_ling

NAME = "tick_kda_state_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_ling.kda_state_ms(sources)
