"""Device ms a prompt call spends under `chunk_attn_window`: the band-keeping layers' attention of a chunk over their rings (one kernel a layer over tiles of the chunk's queries, each over the band it can see; the walk over every run of the ring at a program without it), op time inside the _chunk_prefill* modules of the traced span, over the modules."""
from benchmarks.harness import readers_chunk_attn

NAME = "chunk_window_attn_ms.sat"
LAYER = "kernels and model"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers_chunk_attn.chunk_window_attn_ms(sources)
