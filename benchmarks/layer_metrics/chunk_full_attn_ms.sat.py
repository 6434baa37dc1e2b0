"""Device ms a prompt call spends under `chunk_attn`: the full layers' attention of a chunk, an online softmax over the row's live runs of 32 pages (a first chunk's over the call's own rows), op time inside the _chunk_prefill* modules of the traced span, over the modules."""
from benchmarks.harness import readers_laguna

NAME = "chunk_full_attn_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_laguna.chunk_full_attn_ms(sources)
