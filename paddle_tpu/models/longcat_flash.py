"""LongCat-Flash's language model (meituan-longcat; LongCat-Flash-Omni's
is the same): the shortcut-connected double layer (reference: the
published ``modeling_longcat_flash.py``; LongCat-Flash technical report,
"shortcut-connected MoE" and "zero-computation experts").

One layer holds TWO latent attentions, two dense SwiGLU FFNs and ONE
expert layer, on hidden state x:

    x1 = x  + A0(n_in0(x));    h0 = n_post0(x1)
    m  = M(h0)                                 # the shortcut
    x2 = x1 + F0(h0)
    x3 = x2 + A1(n_in1(x2))
    y  = x3 + F1(n_post1(x3)) + m

``m`` is consumed by the last add alone, so everything between is free
to run beside it; nothing here orders or overlaps it by hand. The
attentions are ``deepseek_v2.MLAttention`` with LongCat's two scale
factors (``mla_scale_q_lora``, ``mla_scale_kv_lora``) and no rope
scaling. ``M`` is ``parallel.moe.ExpertShareMLP`` with a softmax router
``zero_expert_num`` columns wider than the experts: a choice that falls
on one of those adds ``gate * h0`` (the identity kind) and reads no
weight; gates are the softmax scores themselves, not renormalised, times
``routed_scaling_factor``. No shared expert, no groups.

SERVING (``PagedEngine``): a layer keeps two cached latent rows a token,
one per attention, so the model presents ``2 * num_hidden_layers`` cache
layers (``paged_cache_layers``), each the DeepSeek family's latent row.
The expert layer is always one expert-parallel rank's share
(``experts_held`` of ``num_experts``; all of them by default).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.layer import Layer
from ..parallel.layers import ColumnParallelLinear, VocabParallelEmbedding
from ..parallel.moe import (SERVING_COUNTERS, ZERO_COUNTERS, ExpertShareMLP,
                            collect_counts)
from ..parallel.sharding import constraint
from .base import CausalLMBase
from .deepseek_v2 import DeepseekV2Config, MLAttention
from .llama import LlamaMLP


@dataclass
class LongcatFlashConfig(DeepseekV2Config):
    """The published ``config.json``'s own keys (``num_layers`` is
    ``num_hidden_layers`` here, ``n_routed_experts`` ``num_experts``).
    The names the shared layers read (``intermediate_size``,
    ``moe_intermediate_size``, ``num_experts_per_tok``) are set from
    them."""
    vocab_size: int = 131072
    hidden_size: int = 6144
    num_hidden_layers: int = 28            # double layers
    num_attention_heads: int = 64
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    q_lora_rank: Optional[int] = 1536
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    ffn_hidden_size: int = 12288           # the two dense FFNs
    expert_ffn_hidden_size: int = 2048
    num_experts: int = 512
    zero_expert_num: int = 256             # identity experts
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    num_shared_experts: int = 0

    def __post_init__(self):
        self.intermediate_size = self.ffn_hidden_size
        self.moe_intermediate_size = self.expert_ffn_hidden_size
        self.num_experts_per_tok = self.moe_topk
        if self.experts_held is None:
            self.experts_held = self.num_experts


def longcat_flash_tiny(**overrides) -> LongcatFlashConfig:
    base = dict(vocab_size=256, hidden_size=64, ffn_hidden_size=128,
                expert_ffn_hidden_size=32, num_hidden_layers=2,
                num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                num_experts=8, zero_expert_num=4, moe_topk=3,
                max_position_embeddings=256, rope_theta=10000.0,
                dtype=jnp.float32)
    base.update(overrides)
    return LongcatFlashConfig(**base)


class LongcatFlashHalf(Layer):
    """One attention sublayer with its two norms, and the dense FFN that
    follows it."""

    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          config.rms_norm_eps)
        self.self_attn = MLAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def attend(self, x, positions, kv_cache=None, **kw):
        """x -> (x + attention, its post-attention norm, new cache)."""
        # the named scopes are obs.TICK_SCOPES, as in deepseek_v2.py
        with jax.named_scope("norm"):
            h = self.input_layernorm(x)
        attn = self.self_attn(h, positions, kv_cache=kv_cache, **kw)
        new_cache = None
        if kv_cache is not None:
            attn, new_cache = attn
        with jax.named_scope("o_proj"):
            x = x + attn
        with jax.named_scope("norm"):
            h = self.post_attention_layernorm(x)
        return x, h, new_cache


class LongcatFlashDecoderLayer(Layer):
    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        self.halves = nn.LayerList([LongcatFlashHalf(config),
                                    LongcatFlashHalf(config)])
        self.moe = ExpertShareMLP(
            config.hidden_size, config.expert_ffn_hidden_size,
            num_experts=config.num_experts, top_k=config.moe_topk,
            first_expert=config.first_expert,
            experts_held=config.experts_held,
            zero_experts=config.zero_expert_num, scoring="softmax",
            norm_topk_prob=False,
            routed_scaling_factor=config.routed_scaling_factor)

    def forward(self, x, positions, kv_caches=None, **kw):
        """``kv_caches``: this layer's two caches, the first attention's
        first. Returns x, or (x, the two new caches)."""
        first, second = self.halves
        c0, c1 = kv_caches if kv_caches is not None else (None, None)
        x, h, c0 = first.attend(x, positions, kv_cache=c0, **kw)
        # the expert layer's parts have scopes of their own inside this
        with jax.named_scope("mlp"):
            shortcut = self.moe(h)
            x = x + first.mlp(h)
        x, h, c1 = second.attend(x, positions, kv_cache=c1, **kw)
        with jax.named_scope("mlp"):
            x = x + second.mlp(h) + shortcut
        x = constraint(x, ("dp", "fsdp"), "sp", None)
        return (x, [c0, c1]) if kv_caches is not None else x


class LongcatFlashModel(Layer):
    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList(
            [LongcatFlashDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        if config.dtype != jnp.float32:
            self.to(dtype=config.dtype)

    def forward(self, input_ids, positions=None, kv_caches=None,
                cache_index=None, attn_mask=None, attn_start=None,
                segment_ids=None):
        b, s = input_ids.shape
        if positions is None:
            start = cache_index if cache_index is not None else 0
            positions = start + jnp.arange(s)[None, :].repeat(b, axis=0)
            if attn_start is not None:
                # RoPE position 0 sits at each row's first REAL token
                positions = jnp.maximum(positions - attn_start[:, None], 0)
        with jax.named_scope("embed"):      # obs.TICK_SCOPES
            x = self.embed_tokens(input_ids)
        x = constraint(x, ("dp", "fsdp"), "sp", None)
        if kv_caches is None:
            for layer in self.layers:
                x = layer(x, positions, attn_mask=attn_mask)
        else:
            new_caches = []
            for i, layer in enumerate(self.layers):
                x, pair = layer(x, positions,
                                kv_caches=kv_caches[2 * i:2 * i + 2],
                                cache_index=cache_index,
                                attn_mask=attn_mask, attn_start=attn_start,
                                segment_ids=segment_ids)
                new_caches += pair
        with jax.named_scope("head"):
            x = self.norm(x)
        return (x, new_caches) if kv_caches is not None else x


class LongcatFlashForCausalLM(CausalLMBase):
    def __init__(self, config: Optional[LongcatFlashConfig] = None):
        super().__init__()
        config = config or LongcatFlashConfig()
        self.config = config
        self.model = LongcatFlashModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size,
                                            has_bias=False,
                                            gather_output=True)
        if config.dtype != jnp.float32:
            self.lm_head.to(dtype=config.dtype)

    def paged_cache_layers(self) -> int:
        """Cached rows a token has through the model: one per attention
        sublayer, two a layer. ``kv_caches`` everywhere is that long,
        a layer's first attention's before its second's."""
        return 2 * self.config.num_hidden_layers

    def paged_cache_rows(self):
        """What ``PagedEngine`` caches a token and cache layer: ONE
        latent row (deepseek_v2's)."""
        return ((1, self.config.latent_row_width),)

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        """(latent [b, T, kv_lora_rank], k_pe [b, T, rope_d]) per cache
        layer."""
        cfg = self.config
        dtype = dtype or cfg.dtype
        return [(jnp.zeros((batch_size, max_len, cfg.kv_lora_rank), dtype),
                 jnp.zeros((batch_size, max_len, cfg.qk_rope_head_dim),
                           dtype))
                for _ in range(self.paged_cache_layers())]

    def tick_counters(self):
        """Counters the expert layers add up inside a serving tick."""
        return SERVING_COUNTERS + (ZERO_COUNTERS
                                   if self.config.zero_expert_num else ())

    def count_tick(self, rows):
        """As ``DeepseekV2ForCausalLM.count_tick``."""
        return collect_counts(rows)

    def forward(self, input_ids, positions=None, kv_caches=None,
                cache_index=None, attn_mask=None, attn_start=None,
                segment_ids=None):
        out = self.model(input_ids, positions, kv_caches, cache_index,
                         attn_mask, attn_start=attn_start,
                         segment_ids=segment_ids)
        caches = None
        if kv_caches is not None:
            out, caches = out
        with jax.named_scope("head"):
            logits = self.lm_head(out).astype(jnp.float32)
        return (logits, caches) if kv_caches is not None else logits
