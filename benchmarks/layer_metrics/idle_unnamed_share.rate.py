"""Of the device's idle time inside the traced span, the share under no tick/<phase> span of the tick thread other than tick/device and tick/idle: idle time the host's own account does not explain; 100 for a trace without the host's line, rate cells."""
from benchmarks.harness import spans

NAME = "idle_unnamed_share.rate"
LAYER = "device"
UNIT = "%"
MOVES = "gap_p95_ms"
SOURCE = "device_trace"


def reduce(sources):
    return spans.idle_unnamed_share(sources)
