"""Share of the tick modules' op time on the device under no name of the program's TICK_SCOPES: what the per-scope table cannot name; 100 for a program without scopes, rate cells."""
from benchmarks.harness import spans

NAME = "tick_unscoped_share.rate"
LAYER = "tick and prefill programs"
UNIT = "%"
MOVES = "gap_p95_ms"
SOURCE = "device_trace"


def reduce(sources):
    return spans.unscoped_share(sources)
