"""Bytes and operations a decode tick of the DeepSeek-V3 family must
move, from shapes: one expert-parallel rank's share of the expert
layers, multi-head latent attention over a latent cache. Kept with the
benchmark so that no PR that claims a gain can change the count.

A tick reads every weight outside the routed experts once (attention's
five projections, the dense layers' FFN, each expert layer's router and
shared expert, the norms, the head; of the embedding only the live
rows, left out), every held routed expert that got a token once, and
for each live row the latent rows of its whole context in every layer:
``kv_lora_rank + qk_rope_head_dim`` values a token, which are keys and
values at once. At 64 rows the weights are read at about 2 FLOP per
byte per row, far under the chip's 240: bytes over bandwidth is the
floor. Latent attention does ``heads`` x 2 x (576 + 512) FLOP on 1,152
bytes, 121 per byte: still under 240, and the larger of the two bounds
is taken. Every count errs low: no activations, no padding columns of
the cached row, no re-reads, an expert nobody chose counted as not
read; a share over 100% is a bug here.
"""
from __future__ import annotations

from .roofline import BYTES


def _item(config: dict) -> int:
    return BYTES[config["dtype"]]


def expert_bytes(config: dict) -> int:
    """One routed expert: gate, up and down."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * _item(config))


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def attention_weight_params(config: dict) -> int:
    """One layer's attention: q_a, q_b, kv_a, kv_b, o and the two
    low-rank norms."""
    H, heads = config["hidden_size"], config["num_attention_heads"]
    qr, r = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, dv = (config["qk_nope_head_dim"],
                      config["qk_rope_head_dim"], config["v_head_dim"])
    return (H * qr + qr + qr * heads * (nope + rope)
            + H * (r + rope) + r + r * heads * (nope + dv)
            + heads * dv * H)


def weight_bytes_outside_experts(config: dict) -> int:
    """What a tick reads whichever experts were chosen."""
    H = config["hidden_size"]
    dense, moe = config["first_k_dense_replace"], expert_layers(config)
    n = (config["num_hidden_layers"]
         * (attention_weight_params(config) + 2 * H)        # + two norms
         + dense * 3 * H * config["intermediate_size"]
         + moe * (H * config["n_routed_experts_published"]  # router
                  + config["n_routed_experts_published"]    # its bias
                  + config["n_shared_experts"] * 3 * H
                  * config["moe_intermediate_size"])
         + H + H * config["vocab_size"])                    # norm, head
    return n * _item(config)


def latent_bytes_per_token(config: dict) -> int:
    """A context token's cached rows over all layers (live columns)."""
    return (config["num_hidden_layers"]
            * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * _item(config))


def latent_attention_flops_per_token(config: dict) -> int:
    """Absorbed attention over one context token, all layers: each head
    a score over latent + rope columns and a value sum over the latent."""
    r, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return (config["num_hidden_layers"] * config["num_attention_heads"]
            * 2 * ((r + rope) + r))


def latent_attention_floor_s(config: dict, context_tokens: int,
                             peak: dict) -> float:
    """Least seconds the decode attention of every layer can take for
    rows holding ``context_tokens`` of context in all: the larger of
    bytes over bandwidth and operations over the bf16 peak."""
    return max(context_tokens * latent_bytes_per_token(config)
               / peak["hbm_bytes_per_s"],
               context_tokens * latent_attention_flops_per_token(config)
               / peak["bf16_flops"])


def tick_bytes(config: dict, ticks: int, experts_hit: float,
               context_tokens: int) -> float:
    """Bytes ``ticks`` decode ticks must read when ``experts_hit`` held
    experts got a token, summed over them and their layers, and their
    live rows held ``context_tokens`` of context in all."""
    return (ticks * weight_bytes_outside_experts(config)
            + experts_hit * expert_bytes(config)
            + context_tokens * latent_bytes_per_token(config))
