"""Device ms a prompt call spends under `experts`: the held experts' part of every expert layer (the sorted pairs' grouped product with its sort and gather; the einsums over every held expert at a program without it), op time inside the _chunk_prefill* modules of the traced span, over the modules."""
from benchmarks.harness import readers_chunk_experts

NAME = "chunk_experts_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_chunk_experts.chunk_experts_ms(sources)
