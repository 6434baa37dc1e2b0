"""Decode ticks dispatched while the tick before them was still undrained (the engine's runahead_ticks over decode_steps, in the window), rate cells: the share of ticks for which the device had its next program queued behind the running one. A program without the counter reports nothing."""

NAME = "tick_runahead_share.rate"
LAYER = "engine scheduler"
UNIT = "%"
MOVES = "gap_p95_ms"
SOURCE = "program_counter"


def reduce(sources):
    a, b = sources["snaps"]["w0"]["engines"], sources["snaps"]["w1"]["engines"]
    if not all("runahead_ticks" in e for e in a + b):
        return None
    steps = sum(y["decode_steps"] - x["decode_steps"] for x, y in zip(a, b))
    ahead = sum(y["runahead_ticks"] - x["runahead_ticks"]
                for x, y in zip(a, b))
    return 100.0 * ahead / steps if steps else None
