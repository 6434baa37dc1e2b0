"""Median of the gateway's per-request queue wait (reqtrace queue_enter -> slot_take) over the window's requests."""
from benchmarks.harness import readers

NAME = "queue_wait_p50_ms"
LAYER = "front door and admission"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "program_span"


def reduce(sources):
    return readers.queue_wait_p50_ms(sources)
