"""Bytes a decode tick of Olmo-Hybrid's language model must move, from
the configuration's file: Gated-DeltaNet linear-attention layers and
full-attention layers in one model by ``layer_types``. Kept with the
benchmark so that no PR that claims a gain can change the count.

A tick reads every weight outside the embedding once (each layer's
mixer, FFN and norms, the final norm, the head; of the embedding only
the live rows, left out). For each live row it reads AND writes the
matrix state of every head of every linear layer (``linear_num_value_
heads`` x ``linear_key_head_dim`` x ``linear_value_head_dim`` float32:
the decay touches every entry, so no update can write less), and reads
the K and V of the row's whole context in every full layer. The state
step does 3 x 2 FLOP an entry against 8 bytes, the attention at a query
group of one 2 FLOP a byte, against the chip's 240 FLOP a byte: the
floor of each is its bytes over the bandwidth. Every count errs low (no
lane padding, no page remainder, the convolution's tail and the small
vectors left out); a share over 100% is a bug here.
"""
from __future__ import annotations

from .roofline import BYTES

STATE_BYTES = 4         # the configuration's state_dtype, float32


def _item(config: dict) -> int:
    return BYTES[config["dtype"]]


def layers_of(config: dict, linear: bool) -> int:
    kind = "linear_attention" if linear else "full_attention"
    return sum(t == kind for t in config["layer_types"])


def state_bytes_per_row(config: dict) -> int:
    """One row's matrix states of ONE linear layer, read and written."""
    if config["state_dtype"] != "float32":
        raise ValueError("the count is of a float32 state")
    return (2 * config["linear_num_value_heads"]
            * config["linear_key_head_dim"]
            * config["linear_value_head_dim"] * STATE_BYTES)


def kv_bytes_per_token(config: dict) -> int:
    """One cached token's K and V in ONE full layer."""
    d = config["hidden_size"] // config["num_attention_heads"]
    return 2 * config["num_key_value_heads"] * d * _item(config)


def conv_channels(config: dict) -> int:
    return (2 * config["linear_num_key_heads"] * config["linear_key_head_dim"]
            + config["linear_num_value_heads"]
            * config["linear_value_head_dim"])


def mixer_params(config: dict, linear: bool) -> int:
    """One layer's mixer: its matrices and small vectors."""
    H = config["hidden_size"]
    if not linear:
        d = H // config["num_attention_heads"]
        q = config["num_attention_heads"] * d
        kv = config["num_key_value_heads"] * d
        return H * (q + 2 * kv) + q * H + q + kv        # + q_norm, k_norm
    hv, dv = (config["linear_num_value_heads"],
              config["linear_value_head_dim"])
    return (H * conv_channels(config)                   # q, k, v
            + 2 * H * hv * dv                           # gate, out
            + 2 * H * hv                                # a, b
            + conv_channels(config) * config["linear_conv_kernel_dim"]
            + 2 * hv + dv)                              # A_log, dt_bias, norm


def weight_bytes_per_tick(config: dict) -> int:
    """What every tick reads whatever its rows hold."""
    H = config["hidden_size"]
    ffn = 3 * H * config["intermediate_size"] + 2 * H   # + two norms
    n = sum(layers_of(config, lin) * (mixer_params(config, lin) + ffn)
            for lin in (False, True))
    n += H + H * config["vocab_size"]                   # norm, head
    return n * _item(config)


def delta_state_bytes(config: dict, row_ticks: int) -> int:
    """State the linear layers' decode steps must read and write for
    ``row_ticks`` live rows, summed over ticks."""
    return row_ticks * layers_of(config, True) * state_bytes_per_row(config)


def attention_bytes(config: dict, context_tokens: int) -> int:
    """K and V the full layers' kernel calls must read for rows that
    hold ``context_tokens`` of context in all."""
    return (context_tokens * layers_of(config, False)
            * kv_bytes_per_token(config))


def tick_bytes(config: dict, ticks: int, row_ticks: int,
               context_tokens: int) -> int:
    """Bytes ``ticks`` decode ticks must move."""
    return (ticks * weight_bytes_per_tick(config)
            + delta_state_bytes(config, row_ticks)
            + attention_bytes(config, context_tokens))
