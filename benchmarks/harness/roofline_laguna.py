"""Bytes a decode tick and operations a prompt call of Laguna's language
model must move, from the configuration's file: window and full
attention layers in one model over the same kv heads (``head_dim``
columns for keys and values alike), a query head count a layer
(``num_attention_heads_per_layer``) with a gate a head, a leading dense
FFN, expert layers beside a shared expert of which this chip holds one
expert-parallel rank's share. Kept with the benchmark so that no PR that
claims a gain can change the count.

A tick reads every weight outside the routed experts once (each layer's
attention projections, its head gate and norms, the dense FFN, each
expert layer's router and shared expert, the final norm, the head; of
the embedding only the live rows, left out), every held routed expert
that got a token once, and for each live row its cached K and V: in a
FULL layer those of its whole context, in a WINDOW layer those of the
``sliding_window`` positions its query still sees. Decode attention at 6
(full) and 9 (window) query heads a kv head does 2 x 2 x 6 x 128 / (2 x
2 x 128) = 6 and 9 FLOP per byte against the chip's 240: the floor of
each kernel call is its bytes over the bandwidth. A prompt call's
attention in the full layers is counted as causal attention needs it:
for every (query, key at or before it) pair of a prompt and every query
head a score and a value sum, 2 x 2 x ``head_dim`` FLOP, whatever the
chunking, against the bf16 peak. Every count errs low (no padding, no
page remainder, no masked half of a run); a share over 100% is a bug
here.
"""
from __future__ import annotations

from .roofline import BYTES

FULL, SLIDING = "full_attention", "sliding_attention"


def _item(config: dict) -> int:
    return BYTES[config["dtype"]]


def layers_of(config: dict, window: bool) -> int:
    """Layers of one kind in ``layer_types``."""
    return sum((t == SLIDING) == window for t in config["layer_types"])


def query_heads(config: dict, window: bool) -> int:
    """Query heads summed over the layers of one kind."""
    return sum(h for t, h in zip(config["layer_types"],
                                 config["num_attention_heads_per_layer"])
               if (t == SLIDING) == window)


def expert_layers(config: dict) -> int:
    return sum(t == "sparse" for t in config["mlp_layer_types"])


def expert_bytes(config: dict) -> int:
    """One routed expert: gate, up and down."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * _item(config))


def kv_bytes_per_token(config: dict) -> int:
    """One cached token's K and V in ONE layer, of either kind."""
    return (2 * config["num_key_value_heads"] * config["head_dim"]
            * _item(config))


def attention_weight_params(config: dict, heads: int) -> int:
    """q, k, v, o and the head gate of one layer of ``heads`` query
    heads."""
    H, d = config["hidden_size"], config["head_dim"]
    kvh = config["num_key_value_heads"]
    gate = H * heads if config["gating"] else 0
    return H * (heads + 2 * kvh) * d + heads * d * H + gate


def weight_bytes_outside_experts(config: dict) -> int:
    """What a tick reads whichever experts were chosen."""
    H, E = config["hidden_size"], config["num_experts_published"]
    n = sum(attention_weight_params(config, h) + 2 * H     # + two norms
            for h in config["num_attention_heads_per_layer"])
    n += ((config["num_hidden_layers"] - expert_layers(config))
          * 3 * H * config["intermediate_size"])           # dense FFNs
    n += expert_layers(config) * (
        H * E + 3 * H * config["shared_expert_intermediate_size"])
    n += H + H * config["vocab_size"]                      # norm, head
    return n * _item(config)


def window_attention_bytes(config: dict, band_tokens: int) -> int:
    """K and V the window layers' kernel calls must read for
    ``band_tokens`` in-band tokens, summed over live rows, ticks AND
    window layers (the engine's ``kv_window_tokens`` counts so)."""
    return band_tokens * kv_bytes_per_token(config)


def full_attention_bytes(config: dict, context_tokens: int) -> int:
    """K and V the full layers' kernel calls must read for
    ``context_tokens`` of context, summed over live rows, ticks AND full
    layers (the engine's ``kv_context_tokens`` counts so)."""
    return context_tokens * kv_bytes_per_token(config)


def tick_bytes(config: dict, ticks: int, experts_hit: float,
               band_tokens: int, context_tokens: int) -> float:
    """Bytes ``ticks`` decode ticks must read when ``experts_hit`` held
    experts got a token, summed over them and their layers, and the
    live rows held ``band_tokens`` / ``context_tokens`` summed over
    their window / full layers."""
    return (ticks * weight_bytes_outside_experts(config)
            + experts_hit * expert_bytes(config)
            + window_attention_bytes(config, band_tokens)
            + full_attention_bytes(config, context_tokens))


def causal_pairs(prompt_tokens: int) -> int:
    """(query, key at or before it) pairs of one prompt: what its chunks'
    attention in ONE whole-context layer scores in all, however it is
    chunked."""
    return prompt_tokens * (prompt_tokens + 1) // 2


def chunk_full_attention_flops(config: dict, pairs: float) -> float:
    """Operations the full layers' prompt attention needs for ``pairs``
    causal pairs: a score and a value sum a pair and query head."""
    return pairs * query_heads(config, False) * 2 * 2 * config["head_dim"]
