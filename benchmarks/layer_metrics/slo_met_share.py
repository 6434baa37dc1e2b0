"""Share of the window's requests whose TTFT and mean gap between tokens both met the cell's limits (cells/<cell>.json); failed, shed and unfinished requests miss. A count over some hundred requests: it swings by several points from run to run, so it stands here and not among the bounded end-to-end metrics."""

NAME = "slo_met_share"
LAYER = "front door and admission"
UNIT = "%"
MOVES = "ttft_p50_ms"
SOURCE = "host_clock"


def reduce(sources):
    return sources["client"].get("slo_met_share", {}).get("value")
