"""MiMo-V2's language model (XiaomiMiMo ``mimo_v2``: MiMo-V2-Flash,
MiMo-V2.5; written from the published ``config.json`` keys): sliding
window and full attention layers in ONE model by a per-layer pattern,
key heads wider than value heads, and a sparse expert FFN.

One layer ``l`` on hidden state x [T, hidden]:

    h = RMSNorm(x);  q = h Wq [T, heads, d_k];  k = h Wk [T, kvh, d_k];
    v = h Wv [T, kvh, d_v]                      (no biases)

- ``hybrid_layer_pattern[l] == 0`` is a FULL layer: ``kvh`` =
  ``num_key_value_heads``, rope base ``rope_theta``, causal attention
  over the whole sequence. 1 is a WINDOW layer: ``kvh`` =
  ``swa_num_key_value_heads``, rope base ``swa_rope_theta``, query i
  sees keys ``i - j < sliding_window``, and one learned scalar a query
  head, the SINK, joins the softmax's denominator and carries no value.
- rotary on the first ``rotary_dim`` columns of each ``d_k``-wide head
  (``partial_rotary_factor`` x ``d_k``, rounded down to even), half-
  split pairs; the other columns pass through.
- scores ``q . k / sqrt(d_k)``; the values times
  ``attention_value_scale`` (applied to the attention's result, which
  is the same); ``x = x + concat_h(o) Wo``.
- ``moe_layer_freq[l] == 0``: a dense SwiGLU FFN of
  ``intermediate_size``; else a sigmoid router over ``num_experts`` with
  a selection bias, plain top-k, gates the chosen scores normalised
  (``parallel.moe``), no shared expert.

SERVING (``PagedEngine``): the two kinds of layer cache different rows
(``paged_cache_layers`` answers per layer: ``CacheLayer``): a full
layer K ``kvh x d_k`` and V ``kvh x d_v`` for every block of a
sequence; a window layer its own head count and ONLY THE BAND its
queries still reach, a ring of pages a slot (``PagedKV.ring``). Decode
and verify rows go through the ragged kernel (unequal key and value
widths, the sink as the online softmax's start, the window per call);
prompt chunks attend densely, a window layer over its band and chunk
alone. With ``experts_held`` the expert layers are one expert-parallel
rank's share (``ExpertShareMLP``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.layer import Layer, Parameter
from ..ops.attention import dense_attention
from ..ops.paged_cache import CacheLayer, write_and_attend
from ..parallel.layers import (ColumnParallelLinear, RowParallelLinear,
                               VocabParallelEmbedding)
from ..parallel.moe import (SERVING_COUNTERS, ExpertShareMLP, MoEMLP,
                            collect_counts)
from ..parallel.sharding import constraint
from .base import CausalLMBase
from .llama import LlamaMLP, apply_rotary, rotary_cos_sin


@dataclass
class MiMoV2Config:
    """The published config's keys, under the names the shared layers
    read where they differ (``n_routed_experts`` is ``num_experts``,
    ``layernorm_epsilon`` ``rms_norm_eps``)."""
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384         # the dense layers' FFN
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    head_dim: int = 192                    # keys and queries
    v_head_dim: int = 128
    num_key_value_heads: int = 4           # full layers
    swa_num_key_value_heads: int = 8       # window layers
    sliding_window: int = 128
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    # 1 = window layer / expert layer; None: the published period (a
    # full layer first, then 5 window : 1 full) and one leading dense FFN
    hybrid_layer_pattern: Optional[Tuple[int, ...]] = None
    moe_layer_freq: Optional[Tuple[int, ...]] = None
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    scoring: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    # serving one expert-parallel rank (None: the whole layer, with
    # training's capacity dispatch)
    first_expert: int = 0
    experts_held: Optional[int] = None
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.001
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.hybrid_layer_pattern is None:
            self.hybrid_layer_pattern = tuple(
                0 if i == 0 or i % 6 == 5 else 1 for i in range(n))
        if self.moe_layer_freq is None:
            self.moe_layer_freq = tuple(int(i > 0) for i in range(n))
        self.hybrid_layer_pattern = tuple(self.hybrid_layer_pattern)
        self.moe_layer_freq = tuple(self.moe_layer_freq)
        if len(self.hybrid_layer_pattern) != n \
                or len(self.moe_layer_freq) != n:
            raise ValueError("hybrid_layer_pattern and moe_layer_freq "
                             f"give one entry a layer ({n})")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor) // 2 * 2

    def is_window(self, layer_idx: int) -> bool:
        return bool(self.hybrid_layer_pattern[layer_idx])


def mimo_v2_tiny(**overrides) -> MiMoV2Config:
    """Test-scale: both layer kinds, unequal key and value widths,
    different kv head counts, a window several blocks wide."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=3, num_attention_heads=4, head_dim=24,
                v_head_dim=16, num_key_value_heads=1,
                swa_num_key_value_heads=2, sliding_window=12,
                hybrid_layer_pattern=(0, 1, 1), moe_layer_freq=(0, 1, 1),
                num_experts=8, num_experts_per_tok=2,
                moe_intermediate_size=32, max_position_embeddings=256,
                dtype=jnp.float32)
    base.update(overrides)
    return MiMoV2Config(**base)


class MiMoV2Attention(Layer):
    def __init__(self, config: MiMoV2Config, layer_idx: int):
        super().__init__()
        self.config = cfg = config
        self.is_window = cfg.is_window(layer_idx)
        self.window = cfg.sliding_window if self.is_window else None
        self.kv_heads = (cfg.swa_num_key_value_heads if self.is_window
                         else cfg.num_key_value_heads)
        self.theta = cfg.swa_rope_theta if self.is_window \
            else cfg.rope_theta
        h, kv, dk, dv = (cfg.num_attention_heads, self.kv_heads,
                         cfg.head_dim, cfg.v_head_dim)
        self.q_proj = ColumnParallelLinear(cfg.hidden_size, h * dk,
                                           has_bias=cfg.attention_bias,
                                           gather_output=False)
        self.k_proj = ColumnParallelLinear(cfg.hidden_size, kv * dk,
                                           has_bias=cfg.attention_bias,
                                           gather_output=False)
        self.v_proj = ColumnParallelLinear(cfg.hidden_size, kv * dv,
                                           has_bias=cfg.attention_bias,
                                           gather_output=False)
        self.o_proj = RowParallelLinear(h * dv, cfg.hidden_size,
                                        has_bias=False,
                                        input_is_parallel=True)
        if (cfg.add_swa_attention_sink_bias if self.is_window
                else cfg.add_full_attention_sink_bias):
            self.sink = Parameter(jnp.zeros((h,)))

    def _rope(self, x, positions):
        """Rotary on the leading ``rotary_dim`` columns of each head."""
        rd = self.config.rotary_dim
        cos, sin = rotary_cos_sin(positions, rd, self.theta, x.dtype)
        return jnp.concatenate(
            [apply_rotary(x[..., :rd], cos, sin), x[..., rd:]], axis=-1)

    def forward(self, x, positions, kv_cache=None, segment_ids=None,
                attn_mask=None):
        cfg = self.config
        b, s, _ = x.shape
        h, kv, dk, dv = (cfg.num_attention_heads, self.kv_heads,
                         cfg.head_dim, cfg.v_head_dim)
        # the named scopes are obs.TICK_SCOPES, as in llama.py
        with jax.named_scope("qkv"):
            q = self._rope(self.q_proj(x).reshape(b, s, h, dk), positions)
            k = self._rope(self.k_proj(x).reshape(b, s, kv, dk), positions)
            v = self.v_proj(x).reshape(b, s, kv, dv)
            q = constraint(q, None, None, "tp", None)
            k = constraint(k, None, None, "tp", None)
            v = constraint(v, None, None, "tp", None)
        sink = getattr(self, "sink", None)
        new_cache = None
        if kv_cache is not None:
            out, new_cache = write_and_attend(kv_cache, q, k, v, positions,
                                              segment_ids,
                                              window=self.window, sink=sink)
        else:
            out = dense_attention(q, k, v, causal=True, window=self.window,
                                  attn_mask=attn_mask, sink=sink)
        with jax.named_scope("o_proj"):
            out = (out * jnp.asarray(cfg.attention_value_scale, out.dtype)
                   ).reshape(b, s, h * dv)
            out = self.o_proj(out)
        return (out, new_cache) if kv_cache is not None else out


class MiMoV2DecoderLayer(Layer):
    def __init__(self, config: MiMoV2Config, layer_idx: int):
        super().__init__()
        cfg = config
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = MiMoV2Attention(cfg, layer_idx)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        moe = dict(num_experts=cfg.num_experts,
                   top_k=cfg.num_experts_per_tok, num_shared_experts=0,
                   routed_scaling_factor=cfg.routed_scaling_factor,
                   norm_topk_prob=cfg.norm_topk_prob, n_group=cfg.n_group,
                   topk_group=cfg.topk_group, scoring=cfg.scoring)
        if not cfg.moe_layer_freq[layer_idx]:
            self.mlp = LlamaMLP(cfg)
        elif cfg.experts_held is not None:
            self.mlp = ExpertShareMLP(
                cfg.hidden_size, cfg.moe_intermediate_size,
                first_expert=cfg.first_expert,
                experts_held=cfg.experts_held, **moe)
        else:
            self.mlp = MoEMLP(
                cfg.hidden_size, cfg.moe_intermediate_size,
                capacity_factor=cfg.capacity_factor,
                aux_loss_weight=cfg.aux_loss_weight, **moe)

    def forward(self, x, positions, kv_cache=None, segment_ids=None,
                attn_mask=None):
        with jax.named_scope("norm"):
            h = self.input_layernorm(x)
        attn = self.self_attn(h, positions, kv_cache=kv_cache,
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


class MiMoV2Model(Layer):
    def __init__(self, config: MiMoV2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        # each layer cast as it is built (llama.py: the float32 draws of
        # every layer at once do not fit beside the bf16 model)
        self.layers = nn.LayerList(
            [MiMoV2DecoderLayer(config, i).to(dtype=config.dtype)
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


class MiMoV2ForCausalLM(CausalLMBase):
    def __init__(self, config: Optional[MiMoV2Config] = None):
        super().__init__()
        config = config or MiMoV2Config()
        self.config = config
        self.model = MiMoV2Model(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size,
                                            has_bias=False,
                                            gather_output=True)
        if config.dtype != jnp.float32:
            self.lm_head.to(dtype=config.dtype)

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        raise NotImplementedError(
            "MiMoV2ForCausalLM serves through PagedEngine (a PagedKV a "
            "layer); the static whole-sequence cache is not built for "
            "layers of two kinds")

    def paged_cache_layers(self):
        """What ``PagedEngine`` caches a token in EACH layer
        (``ops.paged_cache.CacheLayer``: the (heads, width) of the K
        and of the V pool, and the window of a layer that keeps its
        band only)."""
        cfg = self.config
        out = []
        for layer in self.model.layers:
            a = layer.self_attn
            out.append(CacheLayer(((a.kv_heads, cfg.head_dim),
                                   (a.kv_heads, cfg.v_head_dim)), a.window))
        return out

    def tick_counters(self):
        """Counters the expert layers add up inside a serving tick."""
        return SERVING_COUNTERS if self.config.experts_held is not None \
            else ()

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
