"""Held routed experts whose weights a tick READ, per expert layer and tick, over the experts held: the engine's moe_experts_read over moe_layer_ticks x n_routed_experts, in the window. The kernel's list of experts hit by any row where a tick takes it (then experts_hit_share.sat, within the rows that are not live), 100 where every token is multiplied through every held expert. A program without the counter reports nothing."""

NAME = "experts_read_share.sat"
LAYER = "kernels and model"
UNIT = "%"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def reduce(sources):
    a, b = sources["snaps"]["w0"]["engines"], sources["snaps"]["w1"]["engines"]
    held = sources["config"].get("n_routed_experts")
    if not held or not all("moe_experts_read" in e and "moe_layer_ticks" in e
                           for e in a + b):
        return None
    read = sum(y["moe_experts_read"] - x["moe_experts_read"]
               for x, y in zip(a, b))
    ticks = sum(y["moe_layer_ticks"] - x["moe_layer_ticks"]
                for x, y in zip(a, b))
    return 100.0 * read / (ticks * held) if ticks else None
