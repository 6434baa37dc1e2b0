"""Device ms a decode tick spends under `penalty` and `sample` (repetition penalty, filters, the top-k/top-p sort, the draw), rate cells."""
from benchmarks.harness import spans

NAME = "tick_sample_ms.rate"
LAYER = "tick and prefill programs"
UNIT = "ms"
MOVES = "gap_p95_ms"
SOURCE = "device_trace"


def reduce(sources):
    return spans.scope_ms(sources, "penalty", "sample")
