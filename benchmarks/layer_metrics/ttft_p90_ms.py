"""90th percentile of due send -> first token: the tail of the wait for one of the slots. Not a bounded end-to-end metric: at four fifths of capacity the host machine's own stalls (some 100 ms in most runs, both processes at once) shift every wait of the busy period they fall into, so two runs of one trace read 10% apart (PERF.md section 2)."""
NAME = "ttft_p90_ms"
LAYER = "front door and admission"
UNIT = "ms"
MOVES = "ttft_p50_ms"
SOURCE = "host_clock"


def reduce(sources):
    return sources["client"].get("ttft_p90_ms", {}).get("value")
