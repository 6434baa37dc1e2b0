"""Mean device duration of the _fused_tick* XLA modules in the traced span, saturated cells."""
from benchmarks.harness import readers

NAME = "tick_device_ms.sat"
LAYER = "tick and prefill programs"
UNIT = "ms"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers.tick_device_ms(sources)
