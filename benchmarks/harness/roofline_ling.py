"""Bytes and operations a decode tick and a prompt call of Ling 3.0's
language model must move, from the configuration's file: Kimi-Delta-
Attention layers (a float32 state a head, a decay a key channel) and
latent-attention layers in one model, one expert-parallel rank's share
of the expert layers. Kept with the benchmark so that no PR that claims
a gain can change the count.

A tick reads every weight outside the routed experts once (each layer's
mixer, the dense layers' FFN, each expert layer's router and shared
expert, the norms, the head; of the embedding only the live rows, left
out) and every held routed expert that got a token once. For each live
row it reads AND writes the matrix state of every head of every linear
layer (``num_attention_heads`` x ``head_dim`` x ``head_dim`` float32:
the decay touches every entry, so no update can write less), and reads
the latent rows of its whole context in every latent layer
(``kv_lora_rank + qk_rope_head_dim`` values a token, keys and values at
once). The state step does 4 x 2 FLOP an entry against 8 bytes, far
under the chip's 240 FLOP a byte: its floor is its bytes over the
bandwidth; the latent kernel's is the larger of its two bounds. A prompt
call's recurrence is counted as the recurrence itself needs it, position
by position (``S^T k``, the rank-one write, ``S^T q``: 3 x 2 x dk x dv a
position and head), not as the chunkwise form spends it, against the
bf16 peak. Every count errs low (no lane padding of the cached row, the
convolution's tail, the decays and the small vectors left out, an expert
nobody chose counted as not read); a share over 100% is a bug here.
"""
from __future__ import annotations

from .roofline import BYTES

STATE_BYTES = 4         # the configuration's state_dtype, float32


def _item(config: dict) -> int:
    return BYTES[config["dtype"]]


def layers_of(config: dict, latent: bool) -> int:
    group = config["layer_group_size"]
    n = sum((i + 1) % group == 0 for i in range(config["num_hidden_layers"]))
    return n if latent else config["num_hidden_layers"] - n


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def expert_bytes(config: dict) -> int:
    """One routed expert: gate, up and down."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * _item(config))


def state_bytes_per_row(config: dict) -> int:
    """One row's matrix states of ONE linear layer, read and written."""
    if config["state_dtype"] != "float32":
        raise ValueError("the count is of a float32 state")
    return (2 * config["num_attention_heads"] * config["head_dim"] ** 2
            * STATE_BYTES)


def latent_bytes_per_token(config: dict) -> int:
    """One cached token's row in ONE latent layer (live columns)."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) \
        * _item(config)


def mixer_params(config: dict, latent: bool) -> int:
    """One layer's mixer: its matrices and small vectors."""
    H, heads = config["hidden_size"], config["num_attention_heads"]
    if latent:
        r, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
        nope, dv = config["qk_nope_head_dim"], config["v_head_dim"]
        return (H * heads * (nope + rope)               # q
                + H * (r + rope) + r                    # kv_a, its norm
                + r * heads * (nope + dv)               # kv_b
                + heads * dv * H + H * heads)           # o, head gate
    d = config["head_dim"]
    wide = heads * d
    return (3 * H * wide                                # q, k, v
            + 2 * H * wide                              # decay, out gates
            + wide * H + H * heads                      # o, beta
            + 3 * wide * config["short_conv_kernel_size"]
            + heads + wide + d)                         # A_log, dt_bias, norm


def weight_bytes_outside_experts(config: dict) -> int:
    """What a tick reads whichever experts were chosen."""
    H = config["hidden_size"]
    dense, moe = config["first_k_dense_replace"], expert_layers(config)
    n = sum(layers_of(config, lat) * (mixer_params(config, lat) + 2 * H)
            for lat in (False, True))                   # + two norms
    n += dense * 3 * H * config["intermediate_size"]
    n += moe * (H * config["num_experts_published"]     # router
                + config["num_experts_published"]       # its bias
                + config["num_shared_experts"] * 3 * H
                * config["moe_shared_expert_intermediate_size"])
    n += H + H * config["vocab_size"]                   # norm, head
    return n * _item(config)


def state_bytes(config: dict, row_ticks: int) -> int:
    """State the linear layers' decode steps must read and write for
    ``row_ticks`` live rows, summed over ticks."""
    return row_ticks * layers_of(config, False) * state_bytes_per_row(config)


def latent_attention_floor_s(config: dict, context_tokens: int,
                             peak: dict) -> float:
    """Least seconds the latent layers' decode kernels can take for rows
    holding ``context_tokens`` of context in all: the larger of bytes
    over bandwidth and operations over the bf16 peak (each head a score
    over latent + rope columns and a value sum over the latent)."""
    r, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    n = context_tokens * layers_of(config, True)
    flops = n * config["num_attention_heads"] * 2 * ((r + rope) + r)
    return max(n * latent_bytes_per_token(config) / peak["hbm_bytes_per_s"],
               flops / peak["bf16_flops"])


def tick_bytes(config: dict, ticks: int, row_ticks: int, experts_hit: float,
               context_tokens: int) -> float:
    """Bytes ``ticks`` decode ticks must move when their live rows were
    ``row_ticks`` in all, ``experts_hit`` held experts got a token
    (summed over ticks and layers) and the rows held ``context_tokens``
    of context in all."""
    return (ticks * weight_bytes_outside_experts(config)
            + state_bytes(config, row_ticks)
            + experts_hit * expert_bytes(config)
            + context_tokens * layers_of(config, True)
            * latent_bytes_per_token(config))


def chunk_delta_flops(config: dict, positions: int) -> int:
    """Operations the linear layers' recurrence needs for ``positions``
    prompt positions: ``S^T k``, the rank-one write and ``S^T q`` a
    position and head, 2 FLOP an entry each."""
    return (positions * layers_of(config, False)
            * config["num_attention_heads"] * 3 * 2 * config["head_dim"] ** 2)
