"""Pages a live row holds in one window layer: the engine's kv_window_blocks over live row-ticks and window layers, in the window. The band of 128 positions is 8-9 pages of 16; a layer kept whole would hold 64-120 at this cell's contexts."""
from benchmarks.harness import readers_mimo

NAME = "window_blocks_per_row.sat"
LAYER = "engine scheduler"
UNIT = "count"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    return readers_mimo.window_blocks_per_row(sources)
