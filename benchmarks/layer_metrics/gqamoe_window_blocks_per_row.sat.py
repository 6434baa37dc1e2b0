"""Pages a live row holds in one of Laguna's window layers: the engine's kv_window_blocks over live row-ticks and window layers, in the window. The band of 512 positions is 32-33 pages of 16; a layer kept whole would hold 256-432 at this cell's contexts."""
from benchmarks.harness import readers_laguna

NAME = "gqamoe_window_blocks_per_row.sat"
LAYER = "engine scheduler"
UNIT = "count"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    return readers_laguna.window_blocks_per_row(sources)
