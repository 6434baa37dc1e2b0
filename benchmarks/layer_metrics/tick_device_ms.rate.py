"""Mean device duration of the _fused_tick* XLA modules in the traced span, rate cells."""
from benchmarks.harness import readers

NAME = "tick_device_ms.rate"
LAYER = "tick and prefill programs"
UNIT = "ms"
MOVES = "gap_p95_ms"
SOURCE = "device_trace"


def reduce(sources):
    return readers.tick_device_ms(sources)
