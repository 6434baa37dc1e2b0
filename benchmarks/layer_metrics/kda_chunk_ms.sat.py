"""Device ms a prompt call spends under `chunk_delta_state`: the chunkwise channel-decay delta rule of the 12 linear layers, op time inside the _chunk_prefill* modules of the traced span, over the modules."""
from benchmarks.harness import readers_ling

NAME = "kda_chunk_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_ling.kda_chunk_ms(sources)
