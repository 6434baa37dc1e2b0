"""K/V layers of the window's prompt chunks with cached context behind them whose attention ran as the one page-walking kernel a layer, of all of them: the engine's chunk_attn_kernel_calls over chunk_attn_layer_calls, counted on the host at such a call's dispatch. 100 where every such layer's shape passes the gate; a program without the counters, or a window without such a call, reports nothing."""
from benchmarks.harness import readers_chunk_attn

NAME = "chunk_attn_kernel_share.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    return readers_chunk_attn.chunk_attn_kernel_share(sources)
