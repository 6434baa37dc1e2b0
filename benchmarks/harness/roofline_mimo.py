"""Bytes a decode tick of MiMo-V2's language model must move, from
shapes: full and window attention layers in one model (keys of
``head_dim`` columns, values of ``v_head_dim``, a head count a layer
kind), a leading dense FFN, expert layers of which this chip holds one
expert-parallel rank's share. Kept with the benchmark so that no PR that
claims a gain can change the count.

A tick reads every weight outside the routed experts once (each layer's
attention projections and norms, the dense FFN, each router and its
selection bias, the sinks, the final norm, the head; of the embedding
only the live rows, left out), every held routed expert that got a token
once, and for each live row its cached K and V: in a FULL layer those of
its whole context, in a WINDOW layer those of the ``sliding_window``
positions its query still sees. Decode attention at 16 (full) and 8
(window) query heads a kv head does about 2 x 16 x (192 + 128) / (2 x
(192 + 128)) = 16 and 8 FLOP per byte against the chip's 240: the floor
of each kernel call is its bytes over the bandwidth. Every count errs
low (no padding, no page remainder, the unpadded key head); a share over
100% is a bug here.
"""
from __future__ import annotations

from .roofline import BYTES


def _item(config: dict) -> int:
    return BYTES[config["dtype"]]


def layers_of(config: dict, window: bool) -> int:
    """Layers of one kind in ``hybrid_layer_pattern`` (1: a window layer)."""
    return sum(bool(p) == window for p in config["hybrid_layer_pattern"])


def expert_layers(config: dict) -> int:
    return sum(bool(f) for f in config["moe_layer_freq"])


def expert_bytes(config: dict) -> int:
    """One routed expert: gate, up and down."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * _item(config))


def kv_bytes_per_token(config: dict, window: bool) -> int:
    """One cached token's K and V in ONE layer of the kind."""
    kvh = config["swa_num_key_value_heads"] if window \
        else config["num_key_value_heads"]
    return kvh * (config["head_dim"] + config["v_head_dim"]) * _item(config)


def attention_weight_params(config: dict, window: bool) -> int:
    """q, k, v and o of one layer of the kind, and a window layer's
    sinks."""
    H, h = config["hidden_size"], config["num_attention_heads"]
    dk, dv = config["head_dim"], config["v_head_dim"]
    kvh = config["swa_num_key_value_heads"] if window \
        else config["num_key_value_heads"]
    sinks = h if config["add_swa_attention_sink_bias" if window
                        else "add_full_attention_sink_bias"] else 0
    return H * (h * dk + kvh * (dk + dv)) + h * dv * H + sinks


def weight_bytes_outside_experts(config: dict) -> int:
    """What a tick reads whichever experts were chosen."""
    H, E = config["hidden_size"], config["n_routed_experts_published"]
    n = sum(layers_of(config, w) * (attention_weight_params(config, w)
                                    + 2 * H)             # + two norms
            for w in (False, True))
    n += ((config["num_hidden_layers"] - expert_layers(config))
          * 3 * H * config["intermediate_size"])         # dense FFNs
    n += expert_layers(config) * (H * E + E)             # router, bias
    n += H + H * config["vocab_size"]                    # norm, head
    return n * _item(config)


def window_attention_bytes(config: dict, band_tokens: int) -> int:
    """K and V the window layers' kernel calls must read for rows that
    hold ``band_tokens`` inside their bands in all (a row's
    ``min(context, sliding_window)``, summed over rows and ticks)."""
    return (band_tokens * layers_of(config, True)
            * kv_bytes_per_token(config, True))


def full_attention_bytes(config: dict, context_tokens: int) -> int:
    """K and V the full layers' kernel calls must read for rows that
    hold ``context_tokens`` of context in all."""
    return (context_tokens * layers_of(config, False)
            * kv_bytes_per_token(config, False))


def tick_bytes(config: dict, ticks: int, experts_hit: float,
               band_tokens: int, context_tokens: int) -> float:
    """Bytes ``ticks`` decode ticks must read when ``experts_hit`` held
    experts got a token, summed over them and their layers."""
    return (ticks * weight_bytes_outside_experts(config)
            + experts_hit * expert_bytes(config)
            + window_attention_bytes(config, band_tokens)
            + full_attention_bytes(config, context_tokens))
