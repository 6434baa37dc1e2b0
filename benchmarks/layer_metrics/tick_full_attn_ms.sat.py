"""Device ms a decode tick spends under the program's `attn` scope in a model of two layer kinds: the FULL layers' ragged-kernel calls over each row's whole context, the window layers' being under `attn_window`."""
from benchmarks.harness import readers_mimo

NAME = "tick_full_attn_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_mimo.full_attn_ms(sources)
