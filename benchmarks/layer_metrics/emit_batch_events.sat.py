"""Stream events a hand-over from a tick thread to the event loop carried (health()['stream']: stream_batch_events over stream_batches between the window's snapshots), saturated cells: a worker buffers what a tick made (its rows' tokens, a finished request's done) and crosses to the loop once, so about the live rows where every slot decodes and 1 for a program that crosses a token at a time. A program without the counters reports nothing."""

NAME = "emit_batch_events.sat"
LAYER = "front door and admission"
UNIT = "count"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    a = sources["snaps"]["w0"]["health"].get("stream", {})
    b = sources["snaps"]["w1"]["health"].get("stream", {})
    if not all("stream_batches" in s and "stream_batch_events" in s
               for s in (a, b)):
        return None
    batches = b["stream_batches"] - a["stream_batches"]
    events = b["stream_batch_events"] - a["stream_batch_events"]
    return events / batches if batches else None
