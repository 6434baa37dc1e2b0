"""Share of the tick modules' op time on the device under no name of the program's TICK_SCOPES: what the per-scope table cannot name; 100 for a program without scopes, saturated cells."""
from benchmarks.harness import spans

NAME = "tick_unscoped_share.sat"
LAYER = "tick and prefill programs"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return spans.unscoped_share(sources)
