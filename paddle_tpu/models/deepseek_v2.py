"""DeepSeek-V2/V3 family with Multi-head Latent Attention (reference:
PaddleNLP paddlenlp/transformers/deepseek_v2/modeling.py —
DeepseekV2Attention's q/kv low-rank compression, decoupled RoPE keys, and
the fine-grained MoE with shared experts).

MLA, TPU-native:
- TRAIN/PREFILL: expand the compressed latents to per-head K/V and run
  the ordinary fused attention (the MXU wants the big matmuls anyway).
- DECODE: the ABSORBED form — fold ``W_uk`` into the query so attention
  runs directly against the cached latent: scores = (q_nope W_uk) · c_kv
  + q_pe · k_pe, out = (probs · c_kv) W_uv. The KV cache per token is
  ``kv_lora_rank + qk_rope_head_dim`` floats instead of
  ``2 * heads * head_dim`` — the ~10-50x cache compression that lets one
  chip hold long contexts, and the whole point of MLA.
- RoPE uses DeepSeek's INTERLEAVED (complex-pair) convention, applied
  only to the decoupled q_pe / single-head k_pe dims.
- SERVING (``PagedEngine``): the cache is a LATENT PAGED POOL, one row a
  token and layer: latent, roped key, zeros up to whole 128-lane tiles
  (576 -> 640 columns at the published widths), laid out as the ragged
  kernel reads it. A prompt chunk writes its rows and attends in the
  expanded form over the row's gathered latents; decode and verify rows
  attend in the absorbed form through the kernel. With
  ``experts_held`` set the expert layers are one expert-parallel rank's
  share (``parallel.moe.ExpertShareMLP``) and nothing is dropped.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.layer import Layer
from ..ops.attention import dense_attention, segment_mask
from ..ops.paged_cache import (PagedKV, paged_chunk_rows,
                               paged_decode_write, paged_latent_attention,
                               paged_prefill_write)
from ..parallel.layers import (ColumnParallelLinear, RowParallelLinear,
                               VocabParallelEmbedding)
from ..parallel.moe import (SERVING_COUNTERS, ExpertShareMLP, MoEMLP,
                            collect_counts)
from ..parallel.sharding import constraint
from .base import CausalLMBase
from .llama import (LlamaConfig, LlamaMLP, causal_lm_loss,  # noqa: F401
                    yarn_get_mscale, yarn_params)


@dataclass
class DeepseekV2Config(LlamaConfig):
    vocab_size: int = 102400
    hidden_size: int = 2048
    intermediate_size: int = 10944         # dense layers' FFN width
    # ---- MLA
    q_lora_rank: Optional[int] = None      # None = full q proj (V2-Lite)
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # LongCat-Flash: the query after q_b times sqrt(hidden / q_lora_rank),
    # the normed latent before kv_b times sqrt(hidden / kv_lora_rank)
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # Ling 3.0 (``gated_attention_proj_granularity_type`` head_wise): a
    # head's attention output times sigmoid(W_gate x)[head], the gate
    # read from the layer's normed input
    mla_head_gate: bool = False
    # ---- MoE (DeepSeek fine-grained + shared)
    num_experts: int = 64                  # n_routed_experts
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1408
    num_shared_experts: int = 2            # n_shared_experts
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.0
    # DeepSeek group-limited-greedy routing (n_group=1 -> plain greedy)
    n_group: int = 1
    topk_group: int = 1
    # V3 router: sigmoid expert scores + top-2-sum group scores
    scoring: str = "softmax"
    group_score_mode: str = "max"
    # V3 yarn: get_mscale(factor, mscale_all_dim)^2 multiplies the
    # softmax scale (on top of the cos/sin attention factor)
    yarn_mscale_all_in_scale: bool = False
    # yarn context extension (HF rope_scaling dict: factor, beta_fast/slow,
    # mscale, mscale_all_dim, original_max_position_embeddings); None =
    # plain RoPE. Real DeepSeek-V2 checkpoints all ship yarn.
    rope_scaling: Optional[Dict[str, Any]] = None
    norm_topk_prob: bool = False           # normalize selected gates to 1
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.001
    # serving one expert-parallel rank: the routed experts
    # first_expert .. first_expert + experts_held - 1 live here (None =
    # the whole layer, with training's capacity dispatch)
    first_expert: int = 0
    experts_held: Optional[int] = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    attention_bias: bool = False
    # ---- V3 multi-token prediction (HF config name): D extra depth
    # modules, each predicting one token further ahead. The loss weight
    # (the paper's lambda, 0.3 early / 0.1 late) is a TRAINING
    # hyperparameter — pass it to deepseek_mtp_loss, not the config.
    num_nextn_predict_layers: int = 0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """Columns of one cached row in the paged pool: the latent and
        the roped key, padded with zeros to whole 128-lane tiles (the
        kernel fetches a page as one slab of whole tiles)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128


def deepseek_v2_tiny(**overrides) -> DeepseekV2Config:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16,
                num_experts=4, num_experts_per_tok=2,
                moe_intermediate_size=32, num_shared_experts=1,
                first_k_dense_replace=1, max_position_embeddings=128,
                dtype=jnp.float32)
    base.update(overrides)
    return DeepseekV2Config(**base)


def rope_interleaved(x, positions, theta: float, inv_freq=None,
                     attention_scaling: float = 1.0):
    """DeepSeek's complex-pair RoPE: pairs are (x[2i], x[2i+1]) and
    freqs index i — torch's view_as_complex convention, NOT rotate-half.
    x [b, s, h, d]; positions [b, s]. ``inv_freq``/``attention_scaling``
    override the plain schedule (yarn)."""
    d = x.shape[-1]
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2,
                                               dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [b, s, d/2]
    cos = jnp.cos(ang)[:, :, None, :] * attention_scaling
    sin = jnp.sin(ang)[:, :, None, :] * attention_scaling
    x1, x2 = x[..., ::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape).astype(x.dtype)


class MLAttention(Layer):
    """Multi-head Latent Attention (reference: DeepseekV2Attention)."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        cfg = config
        h = cfg.num_attention_heads
        if cfg.q_lora_rank is None:
            self.q_proj = ColumnParallelLinear(
                cfg.hidden_size, h * cfg.qk_head_dim,
                has_bias=cfg.attention_bias, gather_output=False)
        else:
            self.q_a_proj = nn.Linear(cfg.hidden_size, cfg.q_lora_rank,
                                      bias_attr=cfg.attention_bias or False)
            self.q_a_layernorm = nn.RMSNorm(cfg.q_lora_rank,
                                            cfg.rms_norm_eps)
            self.q_b_proj = ColumnParallelLinear(
                cfg.q_lora_rank, h * cfg.qk_head_dim, has_bias=False,
                gather_output=False)
        # [h, kv_lora_rank + rope_dim]: latent + the single decoupled key
        self.kv_a_proj_with_mqa = nn.Linear(
            cfg.hidden_size, cfg.kv_lora_rank + cfg.qk_rope_head_dim,
            bias_attr=cfg.attention_bias or False)
        self.kv_a_layernorm = nn.RMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps)
        self.kv_b_proj = ColumnParallelLinear(
            cfg.kv_lora_rank,
            h * (cfg.qk_nope_head_dim + cfg.v_head_dim),
            has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(h * cfg.v_head_dim, cfg.hidden_size,
                                        has_bias=cfg.attention_bias,
                                        input_is_parallel=True)
        if cfg.mla_head_gate:
            self.g_proj = nn.Linear(cfg.hidden_size, h, bias_attr=False)
        self.scale = cfg.qk_head_dim ** -0.5
        if getattr(cfg, "rope_scaling", None):
            self._inv_freq, self._rope_af = yarn_params(
                cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling,
                cfg.max_position_embeddings)
            msall = cfg.rope_scaling.get("mscale_all_dim", 0)
            if getattr(cfg, "yarn_mscale_all_in_scale", False) and msall:
                ms = yarn_get_mscale(cfg.rope_scaling["factor"], msall)
                self.scale = self.scale * ms * ms  # V3 semantics
        else:
            self._inv_freq, self._rope_af = None, 1.0

    def _queries(self, x, positions):
        cfg = self.config
        b, s, _ = x.shape
        h = cfg.num_attention_heads
        if cfg.q_lora_rank is None:
            q = self.q_proj(x)
        else:
            q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))
        q = q.reshape(b, s, h, cfg.qk_head_dim)
        if cfg.mla_scale_q_lora:        # nope and rope parts alike
            q = q * (cfg.hidden_size / cfg.q_lora_rank) ** 0.5
        q_nope = q[..., :cfg.qk_nope_head_dim]
        q_pe = rope_interleaved(q[..., cfg.qk_nope_head_dim:], positions,
                                cfg.rope_theta, self._inv_freq,
                                self._rope_af)
        return q_nope, q_pe

    def _latents(self, x, positions):
        """x -> (c_kv normed [b, s, r], k_pe roped [b, s, rope_d])."""
        cfg = self.config
        ckv = self.kv_a_proj_with_mqa(x)
        c, k_pe = (ckv[..., :cfg.kv_lora_rank],
                   ckv[..., cfg.kv_lora_rank:])
        c = self.kv_a_layernorm(c)
        if cfg.mla_scale_kv_lora:
            # the latent is cached scaled: keys' nope part and values
            # carry the factor through kv_b (expanded) and through the
            # absorbed products alike; the roped key does not
            c = c * (cfg.hidden_size / cfg.kv_lora_rank) ** 0.5
        k_pe = rope_interleaved(k_pe[:, :, None, :], positions,
                                cfg.rope_theta, self._inv_freq,
                                self._rope_af)[:, :, 0]
        return c, k_pe

    def _expand(self, c):
        """latent [b, s, r] -> (k_nope [b, s, h, nope], v [b, s, h, v])."""
        cfg = self.config
        h = cfg.num_attention_heads
        kv = self.kv_b_proj(c).reshape(
            c.shape[0], c.shape[1], h, cfg.qk_nope_head_dim + cfg.v_head_dim)
        return kv[..., :cfg.qk_nope_head_dim], kv[..., cfg.qk_nope_head_dim:]

    def _absorbed(self, q_nope, attend):
        """Attention in the ABSORBED form: the queries folded through
        ``W_uk`` into latent space, ``attend(q_lat) -> o_lat`` against
        cached latents (a static cache or the paged pool), the result
        through ``W_uv``. q_nope [b, s, h, nope] -> [b, s, h, v]."""
        cfg = self.config
        wkv = self.kv_b_proj.weight.reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        with jax.named_scope("absorb"):     # obs.TICK_SCOPES
            q_lat = jnp.einsum("bshn,rhn->bshr", q_nope,
                               wkv[..., :cfg.qk_nope_head_dim])
        o_lat = attend(q_lat)
        with jax.named_scope("absorb"):
            return jnp.einsum("bshr,rhv->bshv", o_lat,
                              wkv[..., cfg.qk_nope_head_dim:])

    def _expanded(self, q_nope, q_pe, c, k_pe, **mask):
        """Attention in the EXPANDED form: per-head keys and values made
        from the latents c [b, t, r] and the shared roped key k_pe
        [b, t, rope]."""
        k_nope, v = self._expand(c)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                      k_nope.shape[:3] + k_pe.shape[-1:])],
            axis=-1)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        return dense_attention(q, k, v, scale=self.scale, **mask)

    def _paged(self, pk, q_nope, q_pe, c, k_pe, positions,
               segment_ids=None):
        """Serving over the latent paged pool (ops/paged_cache.py), in
        whichever of the engine's calls the view says this is."""
        cfg = self.config
        r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        pad = cfg.latent_row_width - r - rope

        def row(lat, pe):       # the pool's columns: latent, key, zeros
            parts = [lat, pe.astype(lat.dtype)]
            if pad:
                parts.append(jnp.zeros(lat.shape[:-1] + (pad,), lat.dtype))
            return jnp.concatenate(parts, axis=-1)

        new = row(c, k_pe)[:, :, None, :]                   # [b, s, 1, W]
        if pk.call == "decode":
            pk = paged_decode_write(pk, new)
            out = self._absorbed(
                q_nope, lambda q_lat: paged_latent_attention(
                    row(q_lat, q_pe), pk, r, self.scale))
        elif pk.call == "packed":
            # a PACKED call: several prompts side by side, each from
            # its position 0, so the call's own latents are all a query
            # can see and no page is gathered or expanded
            pk = paged_prefill_write(pk, new, positions=positions[0],
                                     segments=segment_ids[0])
            with jax.named_scope("chunk_attn"):     # obs.TICK_SCOPES
                out = self._expanded(q_nope, q_pe, c, k_pe, causal=True,
                                     attn_mask=segment_mask(segment_ids))
        elif pk.call == "chunk":
            pk = paged_prefill_write(pk, new, positions=positions[0])
            with jax.named_scope("chunk_attn"):     # obs.TICK_SCOPES
                rows = paged_chunk_rows(pk)[:, :, 0]        # [1, T, W]
                keep = jnp.arange(rows.shape[1])[None, :] \
                    <= positions[0][:, None]                # [s, T]
                out = self._expanded(q_nope, q_pe, rows[..., :r],
                                     rows[..., r:r + rope],
                                     attn_mask=keep[None, None])
        else:
            pk = paged_prefill_write(pk, new)
            out = self._expanded(q_nope, q_pe, c, k_pe, causal=True)
        return out, pk

    def forward(self, x, positions, kv_cache=None, cache_index=None,
                attn_mask=None, attn_start=None, segment_ids=None):
        cfg = self.config
        b, s, _ = x.shape
        h = cfg.num_attention_heads
        with jax.named_scope("qkv"):        # obs.TICK_SCOPES
            q_nope, q_pe = self._queries(x, positions)
            c, k_pe = self._latents(x, positions)

        new_cache = None
        if isinstance(kv_cache, PagedKV):
            out, new_cache = self._paged(kv_cache, q_nope, q_pe, c, k_pe,
                                         positions, segment_ids)
        elif kv_cache is not None:
            cc, cpe = kv_cache  # [b, T, r], [b, T, rope_d]
            cc = jax.lax.dynamic_update_slice(cc, c.astype(cc.dtype),
                                              (0, cache_index, 0))
            cpe = jax.lax.dynamic_update_slice(cpe, k_pe.astype(cpe.dtype),
                                               (0, cache_index, 0))
            new_cache = (cc, cpe)
            T = cc.shape[1]
            kpos = jnp.arange(T)[None, None, None, :]
            qpos = cache_index + jnp.arange(s)[None, None, :, None]
            keep = kpos <= qpos
            if attn_start is not None:
                # left-padded serving rows: mask each row's pad prefix
                # out of the cache; pad-prefix queries keep themselves so
                # no softmax row is fully masked (cf. llama.py)
                pad_ok = kpos >= attn_start[:, None, None, None]
                self_ok = kpos == qpos
                keep = keep & (pad_ok | self_ok)

            def attend(q_lat):
                # attention runs over the compressed cache directly
                scores = (jnp.einsum("bshr,btr->bhst", q_lat, cc)
                          + jnp.einsum("bshd,btd->bhst", q_pe, cpe)
                          ).astype(jnp.float32) * self.scale
                scores = jnp.where(keep, scores, -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
                return jnp.einsum("bhst,btr->bshr", probs, cc)

            out = self._absorbed(q_nope, attend)
        else:
            out = self._expanded(q_nope, q_pe, c, k_pe,
                                 causal=attn_mask is None,
                                 attn_mask=attn_mask)
        if cfg.mla_head_gate:
            with jax.named_scope("head_gate"):  # obs.TICK_SCOPES
                gate = jax.nn.sigmoid(self.g_proj(x).astype(jnp.float32))
                out = (out * gate[..., None]).astype(out.dtype)
        with jax.named_scope("o_proj"):     # obs.TICK_SCOPES
            out = self.o_proj(out.reshape(b, s, h * cfg.v_head_dim))
        return (out, new_cache) if kv_cache is not None else out


class DeepseekV2DecoderLayer(Layer):
    def __init__(self, config: DeepseekV2Config, layer_idx: int):
        super().__init__()
        self.config = config
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          config.rms_norm_eps)
        self.self_attn = MLAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)
        self.is_dense = layer_idx < config.first_k_dense_replace
        moe = dict(num_experts=config.num_experts,
                   top_k=config.num_experts_per_tok,
                   num_shared_experts=config.num_shared_experts,
                   shared_intermediate_size=(config.moe_intermediate_size
                                             * config.num_shared_experts),
                   routed_scaling_factor=config.routed_scaling_factor,
                   norm_topk_prob=config.norm_topk_prob,
                   n_group=config.n_group, topk_group=config.topk_group,
                   scoring=config.scoring,
                   group_score_mode=config.group_score_mode)
        if self.is_dense:
            self.mlp = LlamaMLP(config)
        elif config.experts_held is not None:
            self.mlp = ExpertShareMLP(
                config.hidden_size, config.moe_intermediate_size,
                first_expert=config.first_expert,
                experts_held=config.experts_held, **moe)
        else:
            self.mlp = MoEMLP(
                config.hidden_size, config.moe_intermediate_size,
                capacity_factor=config.capacity_factor,
                aux_loss_weight=config.aux_loss_weight, **moe)

    def forward(self, x, positions, kv_cache=None, cache_index=None,
                attn_mask=None, attn_start=None, segment_ids=None):
        # the named scopes are obs.TICK_SCOPES, as in llama.py
        with jax.named_scope("norm"):
            h = self.input_layernorm(x)
        attn = self.self_attn(h, positions,
                              kv_cache=kv_cache, cache_index=cache_index,
                              attn_mask=attn_mask, attn_start=attn_start,
                              segment_ids=segment_ids)
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


class DeepseekV3MTP(Layer):
    """One V3 multi-token-prediction depth module (reference: DeepSeek-V3
    tech report §2.2 / HF checkpoint layout model.layers.{L+k}): RMSNorm
    the previous depth's hidden and the (k+1)-shifted token embedding,
    concat, project 2h -> h, run one full (MoE) decoder block. The final
    norm lives here; the LM head is SHARED with the main model."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        h = config.hidden_size
        self.enorm = nn.RMSNorm(h, config.rms_norm_eps)
        self.hnorm = nn.RMSNorm(h, config.rms_norm_eps)
        self.eh_proj = nn.Linear(2 * h, h, bias_attr=False)
        # MTP blocks are MoE in V3 (they sit past first_k_dense_replace)
        self.block = DeepseekV2DecoderLayer(config,
                                            config.num_hidden_layers)
        self.norm = nn.RMSNorm(h, config.rms_norm_eps)
        if config.dtype != jnp.float32:
            self.to(dtype=config.dtype)

    def forward(self, h_prev, emb_next, positions, attn_mask=None,
                kv_cache=None, cache_index=None):
        """Training path (no cache): returns the final-normed hidden for
        the shared lm_head. Decode path (kv_cache given — MTP-as-draft
        speculative decoding): returns ``(normed, pre, new_cache)`` so
        the caller can chain the PRE-norm block output as the next
        step's ``h_prev`` (Eagle-style self-draft)."""
        x = self.eh_proj(jnp.concatenate(
            [self.hnorm(h_prev), self.enorm(emb_next)], axis=-1))
        if kv_cache is not None:
            x, new_cache = self.block(x, positions, kv_cache=kv_cache,
                                      cache_index=cache_index,
                                      attn_mask=attn_mask)
            return self.norm(x), x, new_cache
        x = self.block(x, positions, attn_mask=attn_mask)
        return self.norm(x)


class DeepseekV2Model(Layer):
    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList(
            [DeepseekV2DecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        if config.dtype != jnp.float32:
            self.to(dtype=config.dtype)

    def forward(self, input_ids, positions=None, kv_caches=None,
                cache_index=None, attn_mask=None, attn_start=None,
                return_prenorm: bool = False, segment_ids=None):
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
        new_caches = [] if kv_caches is not None else None
        for i, layer in enumerate(self.layers):
            if kv_caches is not None:
                x, nc = layer(x, positions, kv_cache=kv_caches[i],
                              cache_index=cache_index, attn_mask=attn_mask,
                              attn_start=attn_start,
                              segment_ids=segment_ids)
                new_caches.append(nc)
            else:
                x = layer(x, positions, attn_mask=attn_mask)
        pre = x  # the MTP modules consume the PRE-final-norm hidden
        with jax.named_scope("head"):
            x = self.norm(x)
        if return_prenorm:
            return (x, pre, new_caches) if kv_caches is not None \
                else (x, pre)
        return (x, new_caches) if kv_caches is not None else x


class DeepseekV2ForCausalLM(CausalLMBase):
    def __init__(self, config: Optional[DeepseekV2Config] = None):
        super().__init__()
        config = config or DeepseekV2Config()
        self.config = config
        self.model = DeepseekV2Model(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size,
                                            has_bias=False,
                                            gather_output=True)
        if config.num_nextn_predict_layers > 0:
            self.mtp = nn.LayerList(
                [DeepseekV3MTP(config)
                 for _ in range(config.num_nextn_predict_layers)])
        if config.dtype != jnp.float32:
            self.lm_head.to(dtype=config.dtype)

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        """MLA cache: (latent [b, T, kv_lora_rank], k_pe [b, T, rope_d])
        per layer — kv_lora_rank + rope_d floats per token instead of
        2 * heads * head_dim."""
        cfg = self.config
        dtype = dtype or cfg.dtype
        return [(jnp.zeros((batch_size, max_len, cfg.kv_lora_rank), dtype),
                 jnp.zeros((batch_size, max_len, cfg.qk_rope_head_dim),
                           dtype))
                for _ in range(cfg.num_hidden_layers)]

    def init_mtp_cache(self, batch_size: int, max_len: int, dtype=None):
        """One MLA cache for the depth-0 MTP block (MTP-as-draft decode)."""
        cfg = self.config
        dtype = dtype or cfg.dtype
        return (jnp.zeros((batch_size, max_len, cfg.kv_lora_rank), dtype),
                jnp.zeros((batch_size, max_len, cfg.qk_rope_head_dim),
                          dtype))

    def paged_cache_rows(self):
        """What ``PagedEngine`` caches a token and layer, as (heads,
        width) of each pool array: ONE latent row."""
        return ((1, self.config.latent_row_width),)

    def tick_counters(self):
        """Counters the expert layers add up inside a serving tick."""
        return SERVING_COUNTERS if self.config.experts_held is not None \
            else ()

    def count_tick(self, rows):
        """Context manager: ``.total`` is the ``tick_counters`` of the
        forward traced inside it, in that order, counting the live
        ``rows`` [b] only."""
        return collect_counts(rows)

    def forward(self, input_ids, positions=None, kv_caches=None,
                cache_index=None, attn_mask=None, attn_start=None,
                return_mtp: bool = False, return_prenorm: bool = False,
                segment_ids=None):
        """``return_mtp`` (training-time, no cache): additionally return
        the list of MTP depth logits — depth k's logits[:, i] predict
        token i+2+k. The MTP chain consumes the pre-final-norm hidden
        and the (k+1)-shifted token embedding; the LM head is shared.

        ``return_prenorm`` (decode-time, works WITH caches): additionally
        return the pre-final-norm hidden — the MTP-as-draft speculative
        path feeds it to the depth modules."""
        if return_mtp:
            if kv_caches is not None:
                raise ValueError("return_mtp is a training-time path "
                                 "(no kv cache)")
            D = self.config.num_nextn_predict_layers
            if D == 0:
                raise ValueError("config.num_nextn_predict_layers == 0")
            out, pre = self.model(input_ids, positions, attn_mask=attn_mask,
                                  attn_start=attn_start,
                                  return_prenorm=True)
            logits = self.lm_head(out).astype(jnp.float32)
            b, s = input_ids.shape
            # the MTP blocks see the SAME attention context as the main
            # stack: per-row shifted positions (left padding) and any
            # segment/packing mask, sliced to each depth's length
            if positions is None:
                positions_full = jnp.arange(s)[None, :].repeat(b, axis=0)
                if attn_start is not None:
                    positions_full = jnp.maximum(
                        positions_full - attn_start[:, None], 0)
            else:
                positions_full = positions
            mtp_logits = []
            h = pre
            for k, mod in enumerate(self.mtp):
                # depth k: h[:, : s-1-k] pairs with emb of tokens shifted
                # k+1 right; the chained h shrinks by one each depth
                sl = s - 1 - k
                emb = self.model.embed_tokens(input_ids[:, k + 1:])
                am = (None if attn_mask is None
                      else attn_mask[:, :, :sl, :sl])
                h = mod(h[:, :sl], emb, positions_full[:, :sl],
                        attn_mask=am)
                mtp_logits.append(self.lm_head(h).astype(jnp.float32))
            return logits, mtp_logits
        out = self.model(input_ids, positions, kv_caches, cache_index,
                         attn_mask, attn_start=attn_start,
                         return_prenorm=return_prenorm,
                         segment_ids=segment_ids)
        caches = None
        pre = None
        if kv_caches is not None:
            if return_prenorm:
                out, pre, caches = out
            else:
                out, caches = out
        elif return_prenorm:
            out, pre = out
        with jax.named_scope("head"):
            logits = self.lm_head(out).astype(jnp.float32)
        if return_prenorm:
            # decode-time MTP-as-draft needs the pre-final-norm hidden
            # alongside the logits (generation/speculative.py)
            return (logits, pre, caches) if kv_caches is not None \
                else (logits, pre)
        return (logits, caches) if kv_caches is not None else logits


def deepseek_mtp_loss(logits, mtp_logits, labels, weight: float = 0.1,
                      ignore_index: int = -100):
    """V3 training objective: main next-token CE plus ``weight`` (the
    paper's lambda) times the mean over MTP depths of each depth's CE —
    depth k's logits[:, i] predict token i+2+k (reference: DeepSeek-V3
    tech report eq. 24-25)."""
    from ..nn import functional as F
    loss = causal_lm_loss(logits, labels, ignore_index)
    if not mtp_logits:
        return loss
    mtp = jnp.float32(0.0)
    for k, ml in enumerate(mtp_logits):
        sl = labels.shape[1] - 2 - k
        mtp = mtp + F.cross_entropy(ml[:, :sl], labels[:, 2 + k:],
                                    ignore_index=ignore_index,
                                    reduction="mean")
    return loss + weight * mtp / len(mtp_logits)
