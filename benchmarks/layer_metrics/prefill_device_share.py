"""The _chunk_prefill modules' share of the device's busy time in the traced span."""
from benchmarks.harness import readers

NAME = "prefill_device_share"
LAYER = "tick and prefill programs"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "device_trace"


def reduce(sources):
    return readers.prefill_device_share(sources)
