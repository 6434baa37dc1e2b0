"""memory_stats() peak_bytes_in_use on the fullest chip after the window, before the reference runs."""
NAME = "peak_hbm_gb"
LAYER = "device"
UNIT = "GB"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    return sources["peak_bytes"] / 1e9
