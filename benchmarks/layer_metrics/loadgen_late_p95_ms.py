"""95th percentile of actual send minus due send, over the window's requests: a starved generator must not read as a fast server."""
NAME = "loadgen_late_p95_ms"
LAYER = "load generator"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "host_clock"


def reduce(sources):
    return sources["client"].get("loadgen_late_p95_ms", {}).get("value")
