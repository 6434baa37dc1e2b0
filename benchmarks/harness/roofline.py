"""Bytes a decode tick must read, from shapes. Kept with the benchmark
so that no PR that claims a gain can change the count.

A tick reads every weight once (layers, final norm, head; of the
embedding only the live rows, left out) and, for each live row, the K
and V of its whole context in every layer. At 8-32 rows the tick is
memory-bound by a wide margin (about 2 FLOP per weight byte per row
against the chip's 240 FLOP per byte), so bytes over the chip's
bandwidth is its floor. The count errs low — it leaves out activations,
the block tables, the sampling pass over the logits and every re-read —
so the share it gives can only understate how close a tick is to the
floor; a share over 100% is a bug here.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}


def weight_bytes_per_tick(config: dict) -> int:
    H, F = config["hidden_size"], config["intermediate_size"]
    heads, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    d = H // heads
    per_layer = (H * heads * d + heads * d          # q
                 + 2 * (H * kvh * d + kvh * d)      # k, v
                 + heads * d * H                    # o
                 + 3 * H * F                        # gate, up, down
                 + 2 * H)                           # two norms
    head = H * config["vocab_size"]     # tied or not, read once as the head
    n = config["num_hidden_layers"] * per_layer + head + H
    return n * BYTES[config["dtype"]]


def kv_bytes_per_token(config: dict) -> int:
    d = config["hidden_size"] // config["num_attention_heads"]
    return (2 * config["num_hidden_layers"] * config["num_key_value_heads"]
            * d * BYTES[config["dtype"]])


def tick_bytes(config: dict, ticks: int, context_tokens: int) -> int:
    """Bytes ``ticks`` decode ticks must read when, summed over them and
    over their live rows, the rows held ``context_tokens`` of context."""
    return (ticks * weight_bytes_per_tick(config)
            + context_tokens * kv_bytes_per_token(config))


def decode_attention_bytes(config: dict, context_tokens: int) -> int:
    """Bytes the decode attention of every layer must read for rows
    holding ``context_tokens`` of context in all: their K and V. (Its
    FLOPs, 4 x heads x head_dim per context token and layer, are about
    14 per byte here, far under the chip's 240: memory-bound.)"""
    return context_tokens * kv_bytes_per_token(config)
