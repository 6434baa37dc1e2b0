"""Positions of their rows' tables that were live, of the positions the window's prompt chunks' attention scored, over the K/V layers of the calls with cached context behind them: the engine's chunk_attn_positions_live over chunk_attn_positions_scored. The walk scores whole runs of 32 pages up to the row's last token (a ring: every run of it), so it reads within one run of 100; a gather of the whole table reads the live share of the slot."""
from benchmarks.harness import readers_laguna

NAME = "chunk_live_share.sat"
LAYER = "tick and prefill programs"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    return readers_laguna.chunk_live_share(sources)
