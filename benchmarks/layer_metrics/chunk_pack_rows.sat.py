"""Prompts a prefill call held (the engine's prefill_segments over prefill_chunks between the window's snapshots), saturated cells: a step packs the prompts that start at position 0 first-fit into calls of at most one chunk of positions, a chunk with cached context behind it runs alone, so 1 is a call a prompt and the mix's lengths over the chunk bound it above. A program without the counter reports nothing."""

NAME = "chunk_pack_rows.sat"
LAYER = "tick and prefill programs"
UNIT = "count"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    a, b = sources["snaps"]["w0"]["engines"], sources["snaps"]["w1"]["engines"]
    if not all("prefill_segments" in e for e in a + b):
        return None
    calls = sum(y["prefill_chunks"] - x["prefill_chunks"]
                for x, y in zip(a, b))
    segments = sum(y["prefill_segments"] - x["prefill_segments"]
                   for x, y in zip(a, b))
    return segments / calls if calls else None
