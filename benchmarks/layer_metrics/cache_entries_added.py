"""Compile-cache entries after the run minus before. Set-up adds none after a checkout's first run; the comparison after the window adds one the first time its sample falls in a new length bucket."""
NAME = "cache_entries_added"
LAYER = "compile cache"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"


def reduce(sources):
    return float(sources["cache_entries_added"])
