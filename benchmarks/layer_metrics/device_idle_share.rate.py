"""1 - union of device op intervals over the traced span (mean over the chips used), rate cells."""
from benchmarks.harness import readers

NAME = "device_idle_share.rate"
LAYER = "device"
UNIT = "%"
MOVES = "gap_p95_ms"
SOURCE = "device_trace"


def reduce(sources):
    return readers.device_idle_share(sources)
