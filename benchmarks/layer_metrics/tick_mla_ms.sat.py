"""Device ms a decode tick spends in latent attention proper: op time under the program's `absorb` scope (queries through W_uk, output through W_uv) and `attn` scope (the latent ragged kernel) inside the _fused_tick* modules of the traced span, over the modules, saturated cells."""
from benchmarks.harness import spans

NAME = "tick_mla_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return spans.scope_ms(sources, "absorb", "attn")
