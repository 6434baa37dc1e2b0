"""Laguna's language model (poolside ``laguna``: Laguna-S-2.1,
Laguna-XS.2; written from the published ``config.json`` keys): sliding
window and full attention layers in ONE model by ``layer_types``, a
different number of QUERY heads in each kind over the same kv heads, a
sigmoid gate a head on the attention's result, a rotary scheme a kind,
and a sparse expert FFN beside a shared expert.

One layer ``l`` on hidden state x [T, hidden]:

    h = RMSNorm(x);  H_l = num_attention_heads_per_layer[l]
    q = h Wq [T, H_l, d];  k = h Wk [T, kvh, d];  v = h Wv [T, kvh, d]

- ``layer_types[l] == "full_attention"``: causal attention over the
  whole sequence; rotary by ``rope_parameters["full_attention"]``: YaRN
  on the first ``partial_rotary_factor x d`` columns of a head, cos and
  sin times its ``attention_factor``, the other columns pass through.
  ``"sliding_attention"``: query i sees keys ``i - j <
  sliding_window``; plain rotary on every column
  (``rope_parameters["sliding_attention"]``). Half-split pairs.
- scores ``q . k / sqrt(d)``; query head ``a`` reads kv head ``a //
  (H_l / kvh)``; no sink.
- ``g = sigmoid(h Wg) [T, H_l]`` (``gating`` per-head): head ``a``'s
  result times ``g_a``; ``x = x + concat_a(g_a o_a) Wo``.
- ``mlp_layer_types[l] == "dense"``: a SwiGLU FFN of
  ``intermediate_size``; ``"sparse"``: a softmax router over
  ``num_experts``, plain top-k, the chosen probabilities normalised
  (``norm_topk_prob``) times ``moe_routed_scaling_factor``
  (``parallel.moe``), plus one ungated shared expert of
  ``shared_expert_intermediate_size``.

SERVING (``PagedEngine``): ``paged_cache_layers`` answers a
``CacheLayer`` a layer, K and V of ``kvh x d`` both; a window layer
keeps ONLY THE BAND its queries still reach (``PagedKV.ring``). Decode
rows go through the ragged kernel at the layer's own query group (H_l /
kvh), prompt chunks through ``paged_chunk_attention``. With
``experts_held`` the expert layers are one expert-parallel rank's share
(``ExpertShareMLP``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.layer import Layer
from ..ops.attention import dense_attention
from ..ops.paged_cache import CacheLayer, write_and_attend
from ..parallel.layers import (ColumnParallelLinear, RowParallelLinear,
                               VocabParallelEmbedding)
from ..parallel.moe import (SERVING_COUNTERS, ExpertShareMLP, MoEMLP,
                            collect_counts)
from ..parallel.sharding import constraint
from .base import CausalLMBase
from .llama import LlamaMLP, apply_rotary, rotary_cos_sin, yarn_params

FULL, SLIDING = "full_attention", "sliding_attention"


def _published_rope() -> Dict[str, Dict[str, Any]]:
    """Laguna-S-2.1's ``rope_parameters``."""
    return {
        FULL: dict(rope_type="yarn", rope_theta=500000.0, factor=128.0,
                   original_max_position_embeddings=8192, beta_slow=1.0,
                   beta_fast=32.0, attention_factor=1.4852030263919618,
                   partial_rotary_factor=0.5),
        SLIDING: dict(rope_type="default", rope_theta=10000.0,
                      partial_rotary_factor=1.0)}


@dataclass
class LagunaConfig:
    """The published config's keys (defaults: Laguna-S-2.1)."""
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288          # the dense layers' FFN
    num_hidden_layers: int = 48
    num_attention_heads: int = 48           # the full layers'
    num_key_value_heads: int = 8            # every layer's
    head_dim: int = 128
    sliding_window: int = 512
    # None: the published period (a full layer, then 3 window layers),
    # 72 query heads in a window layer, one leading dense FFN
    layer_types: Optional[Tuple[str, ...]] = None
    num_attention_heads_per_layer: Optional[Tuple[int, ...]] = None
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    rope_parameters: Dict[str, Dict[str, Any]] = field(
        default_factory=_published_rope)
    gating: Any = "per-head"                # False / None: no gate
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    moe_router_logit_softcapping: float = 0.0
    scoring: str = "softmax"
    # serving one expert-parallel rank (None: the whole layer, with
    # training's capacity dispatch)
    first_expert: int = 0
    experts_held: Optional[int] = None
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.001
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = tuple(FULL if i % 4 == 0 else SLIDING
                                     for i in range(n))
        if self.num_attention_heads_per_layer is None:
            self.num_attention_heads_per_layer = tuple(
                self.num_attention_heads if t == FULL
                else self.num_attention_heads * 3 // 2
                for t in self.layer_types)
        if self.mlp_layer_types is None:
            self.mlp_layer_types = tuple("dense" if i == 0 else "sparse"
                                         for i in range(n))
        for name in ("layer_types", "num_attention_heads_per_layer",
                     "mlp_layer_types"):
            setattr(self, name, tuple(getattr(self, name)))
            if len(getattr(self, name)) != n:
                raise ValueError(f"{name} gives one entry a layer ({n})")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types are {FULL!r} or {SLIDING!r}")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError("every layer's query heads divide into its "
                             f"{self.num_key_value_heads} kv heads")
        if self.moe_router_logit_softcapping:
            raise NotImplementedError("a capped router logit (the "
                                      "published value is 0: none)")

    def is_window(self, layer_idx: int) -> bool:
        return self.layer_types[layer_idx] == SLIDING


def laguna_tiny(**overrides) -> LagunaConfig:
    """Test-scale: both layer kinds, query groups of 3 and 5 over the
    same 2 kv heads, YaRN on half a head in the full layers and plain
    rotary in the window layers, a window several blocks wide, 8
    experts top-3 beside a shared one."""
    rope = _published_rope()
    rope[FULL].update(factor=8.0, original_max_position_embeddings=32)
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=3, num_attention_heads=6,
                num_key_value_heads=2, head_dim=16, sliding_window=12,
                layer_types=(FULL, SLIDING, SLIDING),
                num_attention_heads_per_layer=(6, 10, 10),
                mlp_layer_types=("dense", "sparse", "sparse"),
                rope_parameters=rope, num_experts=8, num_experts_per_tok=3,
                moe_intermediate_size=32,
                shared_expert_intermediate_size=32,
                max_position_embeddings=256, dtype=jnp.float32)
    base.update(overrides)
    return LagunaConfig(**base)


class LagunaAttention(Layer):
    def __init__(self, config: LagunaConfig, layer_idx: int):
        super().__init__()
        self.config = cfg = config
        self.heads = cfg.num_attention_heads_per_layer[layer_idx]
        self.window = cfg.sliding_window if cfg.is_window(layer_idx) \
            else None
        rope = cfg.rope_parameters[cfg.layer_types[layer_idx]]
        self.theta = float(rope["rope_theta"])
        d, kv = cfg.head_dim, cfg.num_key_value_heads
        self.rotary_dim = int(d * rope.get("partial_rotary_factor", 1.0)) \
            // 2 * 2
        if rope.get("rope_type", "default") == "yarn":
            self._inv_freq, self._rope_af = yarn_params(
                self.rotary_dim, self.theta, rope,
                cfg.max_position_embeddings)
        else:
            self._inv_freq, self._rope_af = None, 1.0
        self.q_proj = ColumnParallelLinear(cfg.hidden_size, self.heads * d,
                                           has_bias=cfg.attention_bias,
                                           gather_output=False)
        self.k_proj = ColumnParallelLinear(cfg.hidden_size, kv * d,
                                           has_bias=cfg.attention_bias,
                                           gather_output=False)
        self.v_proj = ColumnParallelLinear(cfg.hidden_size, kv * d,
                                           has_bias=cfg.attention_bias,
                                           gather_output=False)
        self.o_proj = RowParallelLinear(self.heads * d, cfg.hidden_size,
                                        has_bias=False,
                                        input_is_parallel=True)
        if cfg.gating:
            self.g_proj = nn.Linear(cfg.hidden_size, self.heads,
                                    bias_attr=False)

    def _rope(self, x, positions):
        """This kind's rotary on the leading ``rotary_dim`` columns of
        each head (YaRN's factor on those columns' cos and sin)."""
        rd = self.rotary_dim
        cos, sin = rotary_cos_sin(positions, rd, self.theta, x.dtype,
                                  self._inv_freq, self._rope_af)
        if rd == x.shape[-1]:
            return apply_rotary(x, cos, sin)
        return jnp.concatenate(
            [apply_rotary(x[..., :rd], cos, sin), x[..., rd:]], axis=-1)

    def forward(self, x, positions, kv_cache=None, segment_ids=None,
                attn_mask=None):
        cfg = self.config
        b, s, _ = x.shape
        h, kv, d = self.heads, cfg.num_key_value_heads, cfg.head_dim
        # the named scopes are obs.TICK_SCOPES, as in llama.py
        with jax.named_scope("qkv"):
            q = self._rope(self.q_proj(x).reshape(b, s, h, d), positions)
            k = self._rope(self.k_proj(x).reshape(b, s, kv, d), positions)
            v = self.v_proj(x).reshape(b, s, kv, d)
            q = constraint(q, None, None, "tp", None)
            k = constraint(k, None, None, "tp", None)
            v = constraint(v, None, None, "tp", None)
        new_cache = None
        if kv_cache is not None:
            out, new_cache = write_and_attend(kv_cache, q, k, v, positions,
                                              segment_ids,
                                              window=self.window)
        else:
            out = dense_attention(q, k, v, causal=True, window=self.window,
                                  attn_mask=attn_mask)
        if cfg.gating:
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(self.g_proj(x).astype(jnp.float32))
                out = (out * gate[..., None]).astype(out.dtype)
        with jax.named_scope("o_proj"):
            out = self.o_proj(out.reshape(b, s, h * d))
        return (out, new_cache) if kv_cache is not None else out


class LagunaDecoderLayer(Layer):
    def __init__(self, config: LagunaConfig, layer_idx: int):
        super().__init__()
        cfg = config
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LagunaAttention(cfg, layer_idx)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        moe = dict(num_experts=cfg.num_experts,
                   top_k=cfg.num_experts_per_tok, num_shared_experts=1,
                   shared_intermediate_size=(
                       cfg.shared_expert_intermediate_size),
                   routed_scaling_factor=cfg.moe_routed_scaling_factor,
                   norm_topk_prob=cfg.norm_topk_prob, scoring=cfg.scoring)
        if cfg.mlp_layer_types[layer_idx] == "dense":
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


class LagunaModel(Layer):
    def __init__(self, config: LagunaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        # each layer cast as it is built (llama.py: the float32 draws of
        # every layer at once do not fit beside the bf16 model)
        self.layers = nn.LayerList(
            [LagunaDecoderLayer(config, i).to(dtype=config.dtype)
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


class LagunaForCausalLM(CausalLMBase):
    def __init__(self, config: Optional[LagunaConfig] = None):
        super().__init__()
        config = config or LagunaConfig()
        self.config = config
        self.model = LagunaModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size,
                                            has_bias=False,
                                            gather_output=True)
        if config.dtype != jnp.float32:
            self.lm_head.to(dtype=config.dtype)

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        raise NotImplementedError(
            "LagunaForCausalLM serves through PagedEngine (a PagedKV a "
            "layer); the static whole-sequence cache is not built for "
            "layers of two kinds")

    def paged_cache_layers(self):
        """What ``PagedEngine`` caches a token in EACH layer
        (``ops.paged_cache.CacheLayer``): K and V of the shared kv
        heads, the window of a layer that keeps its band only, and the
        layer's own number of query heads."""
        cfg = self.config
        row = (cfg.num_key_value_heads, cfg.head_dim)
        return [CacheLayer((row, row), layer.self_attn.window,
                           layer.self_attn.heads)
                for layer in self.model.layers]

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
