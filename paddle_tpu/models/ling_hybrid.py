"""Ling 3.0's language model (inclusionAI ``bailing_hybrid``; written from
the published ``config.json`` keys): Kimi-Delta-Attention linear layers
and latent-attention layers in ONE model, ``layer_group_size - 1`` of the
first to every one of the second, over a dense FFN in the leading layers
and a group-limited sigmoid router's experts in the others.

One block on hidden state x [T, hidden] (pre-norm; no bias anywhere)::

    x = x + mixer(RMSNorm(x));  x = x + FFN(RMSNorm(x))

- layer ``l`` with ``(l + 1) % layer_group_size == 0``: multi-head latent
  attention without a query low-rank (``models/deepseek_v2.py``'s
  ``MLAttention``, ``q_lora_rank`` None), a head's output times
  ``sigmoid(W_gate x)[head]`` (``mla_head_gate``; the config's
  ``gated_attention_proj_granularity_type`` head_wise).
- any other layer: Kimi Delta Attention (arXiv:2510.26692). ``q~ = W_q
  x``, ``k~ = W_k x``, ``v~ = W_v x`` (``num_attention_heads`` x
  ``head_dim`` each); each channel of the three through its own causal
  ``short_conv_kernel_size``-tap convolution and SiLU; a head's q and k
  L2-normalised, q then times ``head_dim^-0.5``; ``beta = sigmoid(W_b
  x)`` a head; a log-decay a KEY CHANNEL, bounded (``kda_safe_gate``):
  ``g = kda_lower_bound * sigmoid(exp(A_log[head]) * (W_f x + dt_bias))``
  in (``kda_lower_bound``, 0); the delta rule with that decay over a
  float32 state a head (``ops.delta_rule``); ``o = RMSNorm(o) * w *
  sigmoid(W_g x)`` a head, then W_o.
- FFN of layer ``l``: dense SwiGLU (``intermediate_size``) for ``l <
  first_k_dense_replace``; else DeepSeek-V3's ``noaux_tc`` router over
  ``num_experts`` columns in ``n_group`` groups, ``topk_group`` of them a
  token, with the shared expert (``parallel.moe``). A clamp on an
  expert's SwiGLU (``expert_swiglu_limit_list``) is NOT built: the config
  names it and not its form, and a configuration whose layers carry a
  non-zero limit is refused.

SERVING (``PagedEngine``): ``paged_cache_layers`` answers per layer: a
Kimi-Delta-Attention layer a ``StateLayer`` (the heads' matrix states and
the convolution's tail, by SLOT, as ``models/olmo_hybrid.py``'s), a
latent layer a ``CacheLayer`` of one latent row a token. With
``experts_held`` set the expert layers are one expert-parallel rank's
share (``ExpertShareMLP``). The multi-token-prediction layer is not
built.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.layer import Layer, Parameter
from ..ops import delta_rule
from ..ops.paged_cache import CacheLayer, StateLayer
from ..parallel.layers import (ColumnParallelLinear, RowParallelLinear,
                               VocabParallelEmbedding)
from ..parallel.moe import (ROUTED_COUNTERS, SERVING_COUNTERS,
                            ExpertShareMLP, MoEMLP, collect_counts)
from ..parallel.sharding import constraint
from .base import CausalLMBase
from .deepseek_v2 import DeepseekV2Config, MLAttention
from .llama import LlamaMLP
from .olmo_hybrid import GatedDeltaNet


@dataclass
class LingHybridConfig(DeepseekV2Config):
    """The published config's keys (``score_function`` is ``scoring``
    here; ``moe_shared_expert_intermediate_size`` equals
    ``moe_intermediate_size``, which the shared expert's width is read
    from)."""
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144
    num_hidden_layers: int = 42
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 128                    # a linear layer's keys, values
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 6e6
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_head_gate: bool = True
    # ---- which layer is which
    layer_group_size: int = 6
    # ---- Kimi Delta Attention
    short_conv_kernel_size: int = 4
    kda_safe_gate: bool = True
    kda_lower_bound: float = -5.0
    # ---- experts
    num_experts: int = 512
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    num_shared_experts: int = 1
    first_k_dense_replace: int = 2
    routed_scaling_factor: float = 2.5
    n_group: int = 8
    topk_group: int = 4
    scoring: str = "sigmoid"
    group_score_mode: str = "top2_sum"
    norm_topk_prob: bool = True
    # a layer's clamp on its experts' / its shared expert's SwiGLU
    # (None: none anywhere)
    expert_swiglu_limit_list: Optional[Tuple[float, ...]] = None
    share_expert_swiglu_limit_list: Optional[Tuple[float, ...]] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            limits = tuple(getattr(self, key) or ())[:n]
            if any(limits):
                raise ValueError(
                    f"{key} gives layers {[i for i, v in enumerate(limits) if v]}"
                    f" a non-zero limit on their SwiGLU; the config names "
                    f"the clamp and not its form, and it is not built")
        if not self.kda_safe_gate:
            raise ValueError(
                "kda_safe_gate is false: the chunkwise delta rule needs a "
                "channel's log-decay bounded (ops.delta_rule)")
        if self.kda_lower_bound < delta_rule.CHANNEL_LOG_DECAY_MIN:
            raise ValueError(
                f"kda_lower_bound {self.kda_lower_bound} is under "
                f"{delta_rule.CHANNEL_LOG_DECAY_MIN}, the least log-decay "
                f"a position the chunkwise delta rule factors in float32")
        if self.tie_word_embeddings:
            raise ValueError("bailing_hybrid's head is untied "
                             "(tie_word_embeddings is false)")
        if self.num_nextn_predict_layers:
            raise ValueError("the multi-token-prediction layer is not "
                             "built (num_nextn_predict_layers 0)")

    def is_latent(self, layer_idx: int) -> bool:
        return (layer_idx + 1) % self.layer_group_size == 0


def ling_hybrid_tiny(**overrides) -> LingHybridConfig:
    """Test-scale: one period of three (two Kimi-Delta-Attention layers,
    one latent), a dense layer then two expert layers; 8 linear heads of
    16, so that eight 16-wide values fill one 128-lane row of the stored
    state; 16 router columns in 4 groups, 2 groups and 3 experts a
    token."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=3, layer_group_size=3,
                num_attention_heads=8, num_key_value_heads=8, head_dim=16,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, num_experts=16, num_experts_per_tok=3,
                n_group=4, topk_group=2, moe_intermediate_size=32,
                first_k_dense_replace=1, max_position_embeddings=256,
                rope_theta=10000.0, dtype=jnp.float32)
    base.update(overrides)
    return LingHybridConfig(**base)


class KimiDeltaAttention(GatedDeltaNet):
    """A linear layer's mixer (module docstring): ``GatedDeltaNet``'s
    served forms over a decay a key channel, a write strength in (0, 1)
    and a sigmoid output gate."""

    def __init__(self, config: LingHybridConfig):
        Layer.__init__(self)    # the parameters below, not Gated DeltaNet's
        self.config = cfg = config
        h, d = cfg.num_attention_heads, cfg.head_dim
        self.geometry = (h, h, d, d, cfg.short_conv_kernel_size)
        col = lambda n: ColumnParallelLinear(           # noqa: E731
            cfg.hidden_size, n, has_bias=False, gather_output=False)
        self.q_proj, self.k_proj, self.v_proj = (col(h * d), col(h * d),
                                                 col(h * d))
        # the decay gate at full rank (``no_kda_lora``), the output gate
        self.f_proj, self.g_proj = col(h * d), col(h * d)
        self.b_proj = col(h)
        self.o_proj = RowParallelLinear(h * d, cfg.hidden_size,
                                        has_bias=False,
                                        input_is_parallel=True)
        self.conv_weight = Parameter(
            jnp.full((3 * h * d, cfg.short_conv_kernel_size),
                     1.0 / cfg.short_conv_kernel_size))
        self.A_log = Parameter(jnp.zeros((h,)))
        self.dt_bias = Parameter(jnp.zeros((h * d,)))
        self.o_norm = nn.RMSNorm(d, cfg.rms_norm_eps)

    def _project(self, x):
        cfg = self.config
        h, d = cfg.num_attention_heads, cfg.head_dim
        f32 = jnp.float32
        with jax.named_scope("qkv"):            # obs.TICK_SCOPES
            u = jnp.concatenate([self.q_proj(x), self.k_proj(x),
                                 self.v_proj(x)], -1)
            bb, gate = self.b_proj(x), self.g_proj(x)
        with jax.named_scope("decay_gate"):     # obs.TICK_SCOPES
            f = (self.f_proj(x).astype(f32) + self.dt_bias.astype(f32)) \
                .reshape(x.shape[:-1] + (h, d))
            g = cfg.kda_lower_bound * jax.nn.sigmoid(
                jnp.exp(self.A_log.astype(f32))[:, None] * f)
        return u, (g, bb), gate

    def chunk_rule(self):
        h, _, d, _, _ = self.geometry
        return ((h, d), (h, d), (h, d))     # a decay a key channel

    def _gates(self, g, b):
        """(log-decay [..., H, dk], beta [..., H]) float32."""
        return g, jax.nn.sigmoid(b.astype(jnp.float32))

    def _out_gate(self, gate):
        return jax.nn.sigmoid(gate)


class LingHybridDecoderLayer(Layer):
    def __init__(self, config: LingHybridConfig, layer_idx: int):
        super().__init__()
        cfg = config
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.is_latent = cfg.is_latent(layer_idx)
        if self.is_latent:
            self.self_attn = MLAttention(cfg)
        else:
            self.linear_attn = KimiDeltaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        moe = dict(num_experts=cfg.num_experts,
                   top_k=cfg.num_experts_per_tok,
                   num_shared_experts=cfg.num_shared_experts,
                   routed_scaling_factor=cfg.routed_scaling_factor,
                   norm_topk_prob=cfg.norm_topk_prob, n_group=cfg.n_group,
                   topk_group=cfg.topk_group, scoring=cfg.scoring,
                   group_score_mode=cfg.group_score_mode)
        if layer_idx < cfg.first_k_dense_replace:
            self.mlp = LlamaMLP(cfg)
        elif cfg.experts_held is not None:
            self.mlp = ExpertShareMLP(
                cfg.hidden_size, cfg.moe_intermediate_size,
                first_expert=cfg.first_expert,
                experts_held=cfg.experts_held, count_rows_routed=True,
                **moe)
        else:
            self.mlp = MoEMLP(
                cfg.hidden_size, cfg.moe_intermediate_size,
                capacity_factor=cfg.capacity_factor,
                aux_loss_weight=cfg.aux_loss_weight, **moe)

    @property
    def mixer(self):
        return self.self_attn if self.is_latent else self.linear_attn

    def forward(self, x, positions, kv_cache=None, segment_ids=None,
                attn_mask=None):
        # the named scopes are obs.TICK_SCOPES, as in deepseek_v2.py
        with jax.named_scope("norm"):
            h = self.input_layernorm(x)
        attn = self.mixer(h, positions, kv_cache=kv_cache,
                          segment_ids=segment_ids, attn_mask=attn_mask)
        new_cache = None
        if kv_cache is not None:
            attn, new_cache = attn
        with jax.named_scope("o_proj"):
            x = x + attn
        with jax.named_scope("norm"):
            h = self.post_attention_layernorm(x)
        # an expert layer's parts have scopes of their own inside this
        with jax.named_scope("mlp"):
            x = x + self.mlp(h)
        x = constraint(x, ("dp", "fsdp"), "sp", None)
        return (x, new_cache) if kv_cache is not None else x


class LingHybridModel(Layer):
    def __init__(self, config: LingHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        # each layer cast as it is built (llama.py: the float32 draws of
        # every layer at once do not fit beside the bf16 model)
        self.layers = nn.LayerList(
            [LingHybridDecoderLayer(config, i).to(dtype=config.dtype)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        if config.dtype != jnp.float32:
            self.embed_tokens.to(dtype=config.dtype)
            self.norm.to(dtype=config.dtype)

    def forward(self, input_ids, positions=None, kv_caches=None,
                attn_mask=None, segment_ids=None):
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.arange(s)[None, :].repeat(b, axis=0)
        with jax.named_scope("embed"):      # obs.TICK_SCOPES
            x = self.embed_tokens(input_ids)
        x = constraint(x, ("dp", "fsdp"), "sp", None)
        new_caches = [] if kv_caches is not None else None
        for i, layer in enumerate(self.layers):
            if kv_caches is not None:
                x, nc = layer(x, positions, kv_cache=kv_caches[i],
                              segment_ids=segment_ids)
                new_caches.append(nc)
            else:
                x = layer(x, positions, attn_mask=attn_mask)
        with jax.named_scope("head"):
            x = self.norm(x)
        return (x, new_caches) if kv_caches is not None else x


class LingHybridForCausalLM(CausalLMBase):
    def __init__(self, config: Optional[LingHybridConfig] = None):
        super().__init__()
        config = config or LingHybridConfig()
        self.config = config
        self.model = LingHybridModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size,
                                            has_bias=False,
                                            gather_output=True)
        if config.dtype != jnp.float32:
            self.lm_head.to(dtype=config.dtype)

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        raise NotImplementedError(
            "LingHybridForCausalLM serves through PagedEngine (a PagedKV "
            "or a SlotState a layer); the static whole-sequence cache is "
            "not built for layers that keep recurrent state")

    def paged_cache_layers(self):
        """What ``PagedEngine`` keeps for EACH layer: a latent layer's ONE
        latent row a token (``CacheLayer``), a Kimi-Delta-Attention
        layer's arrays a SLOT (``StateLayer``)."""
        latent = CacheLayer(((1, self.config.latent_row_width),))
        return [latent if layer.is_latent
                else StateLayer(layer.linear_attn.state_arrays(),
                                layer.linear_attn.chunk_rule())
                for layer in self.model.layers]

    def tick_counters(self):
        """Counters the expert layers add up inside a serving tick."""
        return SERVING_COUNTERS + ROUTED_COUNTERS \
            if self.config.experts_held is not None else ()

    def count_tick(self, rows):
        """As ``DeepseekV2ForCausalLM.count_tick``."""
        return collect_counts(rows)

    def forward(self, input_ids, positions=None, kv_caches=None,
                attn_mask=None, segment_ids=None):
        out = self.model(input_ids, positions, kv_caches,
                         attn_mask=attn_mask, segment_ids=segment_ids)
        caches = None
        if kv_caches is not None:
            out, caches = out
        with jax.named_scope("head"):
            logits = self.lm_head(out).astype(jnp.float32)
        return (logits, caches) if kv_caches is not None else logits
