"""The _chunk_prefill* modules' share of the device's busy time in the traced span, saturated cells: what of the chip's work went to prompts and not to decode ticks. The span is 3 s of a wave of several: it reads a wave's turnover only when it holds one, so it swings with where the span falls."""
from benchmarks.harness import readers

NAME = "prefill_device_share.sat"
LAYER = "tick and prefill programs"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "device_trace"


def reduce(sources):
    return readers.prefill_device_share(sources)
