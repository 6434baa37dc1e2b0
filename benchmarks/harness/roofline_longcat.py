"""Bytes and operations a decode tick of LongCat-Flash's language model
must move, from shapes: the shortcut-connected double layer (two latent
attentions, two dense FFNs, one expert layer of which this chip holds
one expert-parallel rank's share). Kept with the benchmark so that no PR
that claims a gain can change the count.

A tick reads every weight outside the routed experts once (each layer's
two attentions with their norms, its two dense FFNs, its router and
selection bias, the final norm, the head; of the embedding only the live
rows, left out), every held routed expert that got a token once (a
choice that fell on a zero-compute column reads nothing), and for each
live row the latent rows of its whole context in every attention: two a
layer, ``kv_lora_rank + qk_rope_head_dim`` values a token each. The
bounds are ``roofline_moe_mla``'s (weights at about 2 FLOP per byte per
row, latent attention at 121 per byte, the chip at 240): bytes over
bandwidth for the weights, the larger of the two for the attention.
Every count errs low, as there; a share over 100% is a bug here.
"""
from __future__ import annotations

from .roofline import BYTES
from .roofline_moe_mla import attention_weight_params


def _item(config: dict) -> int:
    return BYTES[config["dtype"]]


def attentions(config: dict) -> int:
    """Attention sublayers, each with a latent cache of its own."""
    return 2 * config["num_layers"]


def expert_bytes(config: dict) -> int:
    """One routed expert: gate, up and down."""
    return (3 * config["hidden_size"] * config["expert_ffn_hidden_size"]
            * _item(config))


def weight_bytes_outside_experts(config: dict) -> int:
    """What a tick reads whichever columns were chosen."""
    H = config["hidden_size"]
    width = config["n_routed_experts_published"] + config["zero_expert_num"]
    n = (attentions(config)
         * (attention_weight_params(config) + 2 * H       # + two norms
            + 3 * H * config["ffn_hidden_size"])          # its dense FFN
         + config["num_layers"] * (H * width + width)     # router, bias
         + H + H * config["vocab_size"])                  # norm, head
    return n * _item(config)


def latent_bytes_per_token(config: dict) -> int:
    """A context token's cached rows over all attentions (live columns)."""
    return (attentions(config)
            * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * _item(config))


def latent_attention_flops_per_token(config: dict) -> int:
    """Absorbed attention over one context token, all attentions: each
    head a score over latent + rope columns and a value sum over the
    latent."""
    r, rope = config["kv_lora_rank"], config["qk_rope_head_dim"]
    return (attentions(config) * config["num_attention_heads"]
            * 2 * ((r + rope) + r))


def latent_attention_floor_s(config: dict, context_tokens: int,
                             peak: dict) -> float:
    """Least seconds the decode attention of every sublayer can take for
    rows holding ``context_tokens`` of context in all."""
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
