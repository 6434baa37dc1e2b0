"""1 - union of device op intervals over the traced span (mean over the chips used), saturated cells."""
from benchmarks.harness import readers

NAME = "device_idle_share.sat"
LAYER = "device"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers.device_idle_share(sources)
