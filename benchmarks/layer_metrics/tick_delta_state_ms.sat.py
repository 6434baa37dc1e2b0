"""Device ms a decode tick spends under the program's `delta_state` scope: the gated delta rule's decode step over every live row's matrix states, 12 layers, with the state's read and write."""
from benchmarks.harness import readers_olmo_hybrid

NAME = "tick_delta_state_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_olmo_hybrid.delta_state_ms(sources)
