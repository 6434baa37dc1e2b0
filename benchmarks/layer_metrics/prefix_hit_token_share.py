"""Prompt tokens adopted from the prefix cache over prompt tokens sent, in the window."""
from benchmarks.harness import readers

NAME = "prefix_hit_token_share"
LAYER = "engine scheduler"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "program_counter"


def reduce(sources):
    return readers.prefix_hit_token_share(sources)
