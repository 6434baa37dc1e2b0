"""Llama-3 family (flagship; reference: PaddleNLP
paddlenlp/transformers/llama/modeling.py — LlamaAttention/LlamaMLP/
LlamaDecoderLayer/LlamaForCausalLM, fuse_attention_qkv and the
mp/sp-parallel code paths).

TPU-native design:
- GQA attention over the Pallas flash kernel (training) / dense XLA path
  with a static KV cache (decode) — no per-rank weight slicing: q/k/v/o are
  Column/RowParallelLinear so GSPMD shards heads over ``tp``.
- RoPE computed inline (fp32 angles, cast back) — XLA fuses it into the
  surrounding matmuls; no precomputed position table to keep in HBM.
- Activations sharded batch→("dp","fsdp"), seq→"sp" via constraint hints.
- Per-layer `jax.checkpoint` (remat) when config.recompute is on.
- bf16 params by default (fp32 master weights live in the optimizer).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn.layer import Layer, Parameter
from ..nn.recompute import POLICIES
from ..ops.attention import (decode_attention, dense_attention,
                             flash_attention, use_flash)
from ..ops.paged_cache import PagedKV, write_and_attend
from ..parallel.layers import (ColumnParallelLinear, RowParallelLinear,
                               VocabParallelEmbedding, parallel_matmul)
from ..parallel.sharding import constraint
from ..utils.rng import next_key
from .base import CausalLMBase


@dataclass
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    tie_word_embeddings: bool = False
    attention_bias: bool = False       # Qwen2 uses biased q/k/v projections
    initializer_range: float = 0.02
    recompute: bool = False
    # jax.checkpoint policy name (see nn.recompute.POLICIES): "full"
    # reruns everything; "dots_with_no_batch_dims_saveable" keeps weight
    # matmul outputs in HBM and reruns only the cheap elementwise chains —
    # the usual MFU winner when memory allows.
    recompute_policy: str = "full"
    use_flash_attention: bool = True
    # sliding-window attention (Qwen2/Mistral): each query attends only
    # the trailing `sliding_window` keys; None = full causal. HF-Qwen2
    # gating: only layers with index >= max_window_layers slide (None =
    # every layer slides)
    sliding_window: "Optional[int]" = None
    max_window_layers: "Optional[int]" = None
    # Llama-3.1+ rope_scaling (HF type "llama3": factor,
    # low/high_freq_factor, original_max_position_embeddings); None =
    # plain RoPE
    rope_scaling: "Optional[Dict[str, Any]]" = None
    sequence_parallel: bool = False  # ring attention over the sp axis
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def llama3_8b(**overrides) -> LlamaConfig:
    return LlamaConfig(**overrides)


def llama3_70b(**overrides) -> LlamaConfig:
    base = dict(hidden_size=8192, intermediate_size=28672,
                num_hidden_layers=80, num_attention_heads=64,
                num_key_value_heads=8)
    base.update(overrides)
    return LlamaConfig(**base)


def llama_tiny(**overrides) -> LlamaConfig:
    """Test-scale config (fits CPU mesh; same code paths as 8B)."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=128,
                rope_theta=10000.0, dtype=jnp.float32)
    base.update(overrides)
    return LlamaConfig(**base)


# ------------------------------------------------------------------- RoPE
def llama3_inv_freq(head_dim: int, theta: float,
                    rope_scaling: "Dict[str, Any]"):
    """Llama-3.1 frequency remap (matches transformers'
    _compute_llama3_parameters): low-frequency bands divide by `factor`,
    high-frequency bands stay, the middle band interpolates smoothly."""
    import numpy as np
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                           / head_dim))
    factor = rope_scaling["factor"]
    low_f = rope_scaling["low_freq_factor"]
    high_f = rope_scaling["high_freq_factor"]
    old_ctx = rope_scaling["original_max_position_embeddings"]
    wavelen = 2 * math.pi / inv
    out = np.where(wavelen > old_ctx / low_f, inv / factor, inv)
    smooth = (old_ctx / wavelen - low_f) / (high_f - low_f)
    smoothed = (1 - smooth) * out / factor + smooth * out
    medium = (wavelen >= old_ctx / high_f) & (wavelen <= old_ctx / low_f)
    return jnp.asarray(np.where(medium, smoothed, out))


def yarn_get_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN attention magnitude factor (one definition, used by both the
    frequency table and DeepSeek-V3's softmax-scale adjustment)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_params(dim: int, theta: float, rope_scaling: "Dict[str, Any]",
                max_position_embeddings: int):
    """YaRN context extension (Peng et al. 2023; matches transformers'
    _compute_yarn_parameters exactly): per-frequency blend between
    interpolated (factor-divided) and extrapolated frequencies via a
    linear ramp over the correction range, plus the attention factor
    that scales cos/sin magnitudes (HF folds mscale there, which scales
    q . k by attention_factor^2). Convention-agnostic: the returned
    inv_freq table indexes frequency i in [0, dim/2), valid for both
    rotate-half (Llama/Qwen) and interleaved (DeepSeek) RoPE."""
    import numpy as np
    factor = rope_scaling["factor"]
    attention_factor = rope_scaling.get("attention_factor")
    mscale = rope_scaling.get("mscale")
    mscale_all_dim = rope_scaling.get("mscale_all_dim")
    orig = (rope_scaling.get("original_max_position_embeddings")
            or max_position_embeddings)

    if attention_factor is None:
        if mscale and mscale_all_dim:
            attention_factor = float(yarn_get_mscale(factor, mscale)
                                     / yarn_get_mscale(factor,
                                                       mscale_all_dim))
        else:
            attention_factor = yarn_get_mscale(factor)
    beta_fast = rope_scaling.get("beta_fast") or 32
    beta_slow = rope_scaling.get("beta_slow") or 1

    def correction_dim(num_rot):
        return (dim * math.log(orig / (num_rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if rope_scaling.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inv_extra = 1.0 / pos_freqs
    inv_inter = 1.0 / (factor * pos_freqs)
    extra_factor = 1.0 - ramp
    inv_freq = inv_inter * (1 - extra_factor) + inv_extra * extra_factor
    # numpy, not a jax array: a model may be constructed under a trace
    # (jax.eval_shape, to learn its parameter shapes), and a constant
    # kept on the layer must not be that trace's
    return inv_freq.astype(np.float32), float(attention_factor)


ROPE_SCALING_TYPES = ("llama3", "yarn", "linear", "default")


def rope_params_from_scaling(head_dim: int, theta: float,
                             rope_scaling: "Optional[Dict[str, Any]]",
                             max_position_embeddings: int):
    """HF ``rope_scaling`` dict -> (inv_freq override or None,
    attention_scaling). Dispatches on type: llama3 (3.1 wavelength
    interpolation), yarn, linear (positional interpolation), default.
    Reference: transformers modeling_rope_utils ROPE_INIT_FUNCTIONS."""
    if not rope_scaling:
        return None, 1.0
    rtype = rope_scaling.get("rope_type", rope_scaling.get("type",
                                                           "default"))
    if rtype == "default":
        return None, 1.0
    if rtype == "llama3":
        return llama3_inv_freq(head_dim, theta, rope_scaling), 1.0
    if rtype == "yarn":
        return yarn_params(head_dim, theta, rope_scaling,
                           max_position_embeddings)
    if rtype == "linear":
        inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                          dtype=jnp.float32) / head_dim))
        return inv / rope_scaling["factor"], 1.0
    raise ValueError(f"rope_scaling type {rtype!r} not supported "
                     f"({'/'.join(ROPE_SCALING_TYPES)} are)")


def rotary_cos_sin(positions, head_dim: int, theta: float, dtype,
                   inv_freq=None, attention_scaling: float = 1.0):
    """positions [b, s] -> (cos, sin) [b, s, 1, head_dim/2], fp32 math.
    ``inv_freq`` overrides the plain schedule (Llama-3.1 / yarn / linear
    scaling); ``attention_scaling`` multiplies the magnitudes (YaRN's
    mscale — scales q.k by its square, as transformers does)."""
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                               dtype=jnp.float32)
                                    / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [b,s,hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if attention_scaling != 1.0:
        cos, sin = cos * attention_scaling, sin * attention_scaling
    return (cos[:, :, None, :].astype(dtype),
            sin[:, :, None, :].astype(dtype))


def apply_rotary(x, cos, sin):
    """x [b, s, h, d]; rotate-half convention (Llama/GPT-NeoX style)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# -------------------------------------------------------------- components
class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig, layer_idx: int = 0):
        super().__init__()
        self.config = config
        mwl = getattr(config, "max_window_layers", None)
        # HF-Qwen2 semantics: the window applies from max_window_layers on
        self.window = (config.sliding_window
                       if getattr(config, "sliding_window", None) is not None
                       and (mwl is None or layer_idx >= mwl) else None)
        rs = getattr(config, "rope_scaling", None)
        self._inv_freq, self._attn_scaling = rope_params_from_scaling(
            config.head_dim, config.rope_theta, rs,
            config.max_position_embeddings)
        h, kv = config.num_attention_heads, config.num_key_value_heads
        d = config.head_dim
        qkv_bias = config.attention_bias
        self.q_proj = ColumnParallelLinear(config.hidden_size, h * d,
                                           has_bias=qkv_bias, gather_output=False)
        self.k_proj = ColumnParallelLinear(config.hidden_size, kv * d,
                                           has_bias=qkv_bias, gather_output=False)
        self.v_proj = ColumnParallelLinear(config.hidden_size, kv * d,
                                           has_bias=qkv_bias, gather_output=False)
        self.o_proj = RowParallelLinear(h * d, config.hidden_size,
                                        has_bias=False, input_is_parallel=True)

    @staticmethod
    def _sp_degree() -> int:
        from ..distributed.env import get_mesh, has_mesh
        return get_mesh().shape.get("sp", 1) if has_mesh() else 1

    def forward(self, x, positions, kv_cache: Optional[Tuple] = None,
                cache_index=None, attn_mask=None, attn_start=None,
                segment_ids=None):
        cfg = self.config
        b, s, _ = x.shape
        nh, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        # the named scopes here and below are obs.TICK_SCOPES: what a
        # device trace calls the parts of a serving tick
        with jax.named_scope("qkv"):
            if hasattr(self, "qkv_proj"):
                # serving fusion (nn.fuse.fuse_projections): ONE matmul.
                # The fused columns are rank-interleaved [q_t|k_t|v_t per
                # tp rank t] so this split is shard-local under a tp
                # mesh: expose the T axis, slice heads inside each rank's
                # chunk, merge back (T == 1 degenerates to the plain
                # [q|k|v] split).
                qkv = self.qkv_proj(x)
                T = getattr(self, "_fused_tp", 1)
                qkv = qkv.reshape(b, s, T, (nh + 2 * kvh) // T, d)
                q = qkv[:, :, :, :nh // T].reshape(b, s, nh, d)
                k = qkv[:, :, :, nh // T:(nh + kvh) // T] \
                    .reshape(b, s, kvh, d)
                v = qkv[:, :, :, (nh + kvh) // T:].reshape(b, s, kvh, d)
            else:
                q = self.q_proj(x).reshape(b, s, nh, d)
                k = self.k_proj(x).reshape(b, s, kvh, d)
                v = self.v_proj(x).reshape(b, s, kvh, d)
            cos, sin = rotary_cos_sin(positions, cfg.head_dim,
                                      cfg.rope_theta, q.dtype,
                                      inv_freq=self._inv_freq,
                                      attention_scaling=self._attn_scaling)
            q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
            # heads sharded over tp
            q = constraint(q, None, None, "tp", None)
            k = constraint(k, None, None, "tp", None)
            v = constraint(v, None, None, "tp", None)

        new_cache = None
        if isinstance(kv_cache, PagedKV):
            # paged serving: the view says which of the engine's calls
            # this is (ops/paged_cache.py)
            out, new_cache = write_and_attend(kv_cache, q, k, v, positions,
                                              segment_ids,
                                              window=self.window)
        elif kv_cache is not None:
            # static-shape decode: write current k/v at cache_index
            ck, cv = kv_cache
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, cache_index, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, cache_index, 0, 0))
            new_cache = (ck, cv)
            if s == 1 and attn_start is None:
                # single-token decode: Pallas masked-MHA kernel (GQA-
                # native, no KV repeat) / grouped-einsum fallback
                out = decode_attention(q, ck, cv, cache_index,
                                       window=self.window)
            elif isinstance(cache_index, int) and cache_index == 0 \
                    and attn_start is None and cfg.use_flash_attention \
                    and use_flash(q, k, None, 0.0):
                # prefill at cache start: nothing earlier in the cache
                # can be attended, so this is plain causal attention
                # over the prompt — take the flash kernel instead of
                # the masked-dense-over-full-cache path (O(s*T) scores
                # and memory for a [s, T] mask). K/V go through the
                # cache dtype so prefill numerics match what decode
                # steps will read back
                out = flash_attention(q, k.astype(ck.dtype),
                                      v.astype(cv.dtype), causal=True,
                                      window=self.window)
            else:
                # prefill-with-cache (and left-padded serving batches):
                # mask positions beyond cache_index+s; with attn_start,
                # also mask each row's pad prefix out of the cache
                total = ck.shape[1]
                kpos = jnp.arange(total)[None, :]           # [1, T]
                qpos = cache_index + jnp.arange(s)[:, None]  # [s, 1]
                mask = (kpos <= qpos)[None, None]           # [1, 1, s, T]
                if self.window is not None:
                    mask = mask & \
                        (qpos - kpos < self.window)[None, None]
                if attn_start is not None:
                    pad_ok = kpos[None] >= attn_start[:, None, None]
                    # pad-prefix queries keep their own position: an
                    # all-masked softmax row is NaN, and that NaN would
                    # re-enter REAL rows in the next layer as 0 * NaN
                    # through masked-out values
                    self_ok = (kpos == qpos)[None]
                    mask = mask & (pad_ok | self_ok)[:, None]  # [b,1,s,T]
                out = dense_attention(q, ck, cv, attn_mask=mask)
        elif cfg.sequence_parallel and attn_mask is None and \
                self._sp_degree() > 1:
            # ring attention: seq stays sp-sharded; KV blocks rotate on
            # ICI. segment_ids (packed SFT) rotate with the KV blocks and
            # a sliding window narrows the causal band with GLOBAL
            # positions — both compose with context parallelism.
            import functools
            from jax.sharding import PartitionSpec as P
            from ..distributed.env import get_mesh
            from ..parallel.ring import ring_attention
            spec = P(("dp", "fsdp"), "sp", "tp", None)
            ring = functools.partial(ring_attention, axis_name="sp",
                                     causal=True, window=self.window)
            from jax import shard_map
            if segment_ids is not None:
                sspec = P(("dp", "fsdp"), "sp")
                out = shard_map(
                    lambda q, k, v, seg: ring(q, k, v, segment_ids=seg),
                    mesh=get_mesh(), in_specs=(spec,) * 3 + (sspec,),
                    out_specs=spec, check_vma=False)(q, k, v, segment_ids)
            else:
                out = shard_map(
                    ring, mesh=get_mesh(), in_specs=(spec,) * 3,
                    out_specs=spec, check_vma=False)(q, k, v)
        elif cfg.use_flash_attention and attn_mask is None and use_flash(q, k, None, 0.0):
            # segment_ids ride the flash kernel (packed sequences): the
            # same-segment mask applies inside the online softmax; a
            # sliding window narrows the causal band in-kernel
            out = flash_attention(q, k, v, causal=True,
                                  segment_ids=segment_ids,
                                  window=self.window)
        elif segment_ids is not None and attn_mask is None:
            from ..ops.attention import segment_mask
            out = dense_attention(q, k, v, causal=True,
                                  attn_mask=segment_mask(segment_ids),
                                  window=self.window)
        elif self.window is not None:
            # an explicit mask COMBINES with the window band (HF
            # intersects them); causal-decoder masks are within causal
            # context, so forcing causal=True only narrows
            out = dense_attention(q, k, v, causal=True,
                                  attn_mask=attn_mask, window=self.window)
        else:
            out = dense_attention(q, k, v, causal=attn_mask is None,
                                  attn_mask=attn_mask)
        with jax.named_scope("o_proj"):
            out = out.reshape(b, s, cfg.num_attention_heads * cfg.head_dim)
            out = self.o_proj(out)
        return (out, new_cache) if kv_cache is not None else out


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.gate_proj = ColumnParallelLinear(config.hidden_size,
                                              config.intermediate_size,
                                              has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(config.hidden_size,
                                            config.intermediate_size,
                                            has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(config.intermediate_size,
                                           config.hidden_size, has_bias=False,
                                           input_is_parallel=True)

    def forward(self, x):
        if hasattr(self, "gate_up_proj"):
            # serving fusion (nn.fuse.fuse_projections): ONE matmul with
            # rank-interleaved [gate_t|up_t] columns — shard-local split
            # under tp, plain halves when T == 1
            gu = self.gate_up_proj(x)
            T = getattr(self, "_fused_tp", 1)
            ffn = gu.shape[-1] // 2
            gu = gu.reshape(*gu.shape[:-1], T, 2, ffn // T)
            gate = gu[..., 0, :].reshape(*gu.shape[:-3], ffn)
            up = gu[..., 1, :].reshape(*gu.shape[:-3], ffn)
            return self.down_proj(F.silu(gate) * up)
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig, layer_idx: int = 0):
        super().__init__()
        self.config = config
        self.input_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LlamaAttention(config, layer_idx=layer_idx)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, positions, kv_cache=None, cache_index=None,
                attn_mask=None, attn_start=None, segment_ids=None):
        with jax.named_scope("norm"):
            h = self.input_layernorm(x)
        attn_out = self.self_attn(h, positions,
                                  kv_cache=kv_cache, cache_index=cache_index,
                                  attn_mask=attn_mask, attn_start=attn_start,
                                  segment_ids=segment_ids)
        new_cache = None
        if kv_cache is not None:
            attn_out, new_cache = attn_out
        # each residual add under the scope of the block it closes
        with jax.named_scope("o_proj"):
            x = x + attn_out
        with jax.named_scope("norm"):
            h = self.post_attention_layernorm(x)
        with jax.named_scope("mlp"):
            x = x + self.mlp(h)
        x = constraint(x, ("dp", "fsdp"), "sp", None)
        return (x, new_cache) if kv_cache is not None else x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.embed_tokens.weight = self.embed_tokens.weight.astype(config.dtype) \
            * jnp.asarray(config.initializer_range / 0.02, config.dtype)
        # compute-weight dtype (fp32 masters live in the optimizer). Each
        # layer is cast as it is built: weights are drawn in float32, and
        # holding every layer's draw until one cast at the end peaks at
        # three times the bf16 model — more than a 16 GB chip has at the
        # depths it can otherwise serve. Same values either way.
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(config, layer_idx=i).to(dtype=config.dtype)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               config.rms_norm_eps).to(dtype=config.dtype)

    def forward(self, input_ids, positions=None, kv_caches=None,
                cache_index=None, attn_mask=None, attn_start=None,
                segment_ids=None):
        b, s = input_ids.shape
        if positions is None:
            start = cache_index if cache_index is not None else 0
            positions = start + jnp.arange(s)[None, :].repeat(b, axis=0)
            if attn_start is not None:
                # left-padded rows: RoPE position 0 sits at each row's
                # first REAL token, not at the pad prefix
                positions = jnp.maximum(positions - attn_start[:, None], 0)
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        x = constraint(x, ("dp", "fsdp"), "sp", None)
        new_caches = [] if kv_caches is not None else None
        for i, layer in enumerate(self.layers):
            cache_i = kv_caches[i] if kv_caches is not None else None
            if self.config.recompute and kv_caches is None:
                out = jax.checkpoint(
                    lambda h, lyr=layer: lyr(h, positions, attn_mask=attn_mask,
                                             segment_ids=segment_ids),
                    prevent_cse=False,
                    policy=POLICIES[self.config.recompute_policy])(x)
            else:
                out = layer(x, positions, kv_cache=cache_i,
                            cache_index=cache_index, attn_mask=attn_mask,
                            attn_start=attn_start, segment_ids=segment_ids)
            if kv_caches is not None:
                x, nc = out
                new_caches.append(nc)
            else:
                x = out
        with jax.named_scope("head"):
            x = self.norm(x)
        return (x, new_caches) if kv_caches is not None else x


class LlamaForCausalLM(CausalLMBase):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(config.hidden_size,
                                                config.vocab_size,
                                                has_bias=False,
                                                gather_output=True)
            if config.dtype != jnp.float32:
                self.lm_head.to(dtype=config.dtype)

    def pipeline_functional(self, pp: int, logits_loss=None, vpp: int = 1):
        """1F1B pipeline train step over ``pp`` stages (Trainer pp path).
        ``logits_loss(logits, labels) -> scalar mean`` swaps the last-stage
        loss head (default: shifted causal-LM cross-entropy). ``vpp`` > 1
        interleaves that many virtual chunks per device (Megatron-style),
        shrinking the pipeline bubble vpp-fold."""
        return llama_pipeline_functional(self, pp, logits_loss=logits_loss,
                                         vpp=vpp)

    def forward(self, input_ids, positions=None, kv_caches=None,
                cache_index=None, attn_mask=None, attn_start=None,
                segment_ids=None):
        out = self.model(input_ids, positions, kv_caches, cache_index,
                         attn_mask, attn_start, segment_ids=segment_ids)
        caches = None
        if kv_caches is not None:
            out, caches = out
        with jax.named_scope("head"):
            if self.config.tie_word_embeddings:
                logits = parallel_matmul(out,
                                         self.model.embed_tokens.weight,
                                         transpose_y=True)
            else:
                logits = self.lm_head(out)
            logits = logits.astype(jnp.float32)  # CE in fp32 for stability
        return (logits, caches) if kv_caches is not None else logits


def causal_lm_loss(logits, labels, ignore_index: int = -100):
    """Shifted next-token CE: logits [b, s, v], labels [b, s]."""
    shift_logits = logits[:, :-1]
    shift_labels = labels[:, 1:]
    return F.cross_entropy(shift_logits, shift_labels,
                           ignore_index=ignore_index, reduction="mean")


# ------------------------------------------------------- pipeline parallel
def llama_pipeline_functional(model: "LlamaForCausalLM", pp: int,
                              logits_loss=None, vpp: int = 1):
    """Wire a LlamaForCausalLM into the 1F1B pipeline (reference:
    fleet.meta_parallel.PipelineLayer's LayerDesc segmentation — embedding
    at stage 0, ``num_hidden_layers/pp`` LlamaDecoderLayers per stage,
    final-norm+lm_head at the last stage).

    Returns ``vag(flat_params, tokens[M, b, s]) -> (loss, flat_grads)``:
    flat params stay the single source of truth (optimizer/checkpoint
    layout unchanged); the stage re-stack to [pp, layers_per_stage, ...]
    happens inside the jitted step, where XLA turns it into resharding.
    """
    from jax import lax as _lax

    from ..parallel.pipeline import pipeline_value_and_grad

    cfg = model.config
    L = cfg.num_hidden_layers
    S = pp * vpp  # global stages (vpp chunks per device when interleaved)
    if L % S != 0:
        raise ValueError(f"num_hidden_layers {L} % (pp*vpp) {S} != 0")
    if cfg.tie_word_embeddings:
        raise ValueError("pipeline requires untied embeddings (the tied "
                         "table would live on two stages)")
    n_per = L // S
    layer_fn, layer_p0 = model.model.layers[0].functional()
    embed_fn, _ = model.model.embed_tokens.functional()
    norm_fn, _ = model.model.norm.functional()
    lm_fn, _ = model.lm_head.functional()
    rel_keys = list(layer_p0)

    def _stage_stack(flat, k, g):
        """One global stage's [n_per, ...] stack for param k."""
        return jnp.stack([flat[f"model.layers.{g * n_per + i}.{k}"]
                          for i in range(n_per)])

    def split(flat):
        if vpp == 1:
            stages = {k: jnp.stack([_stage_stack(flat, k, g)
                                    for g in range(pp)])
                      for k in rel_keys}
        else:
            # [v, pp, n_per, ...]: chunk c on device d is global stage
            # g = c*pp + d (round-robin layout — consecutive stages on
            # consecutive devices so the interleaved ring handoff works)
            stages = {k: jnp.stack([
                jnp.stack([_stage_stack(flat, k, c * pp + d)
                           for d in range(pp)]) for c in range(vpp)])
                for k in rel_keys}
        embed = {k[len("model.embed_tokens."):]: v for k, v in flat.items()
                 if k.startswith("model.embed_tokens.")}
        head = {"norm": {k[len("model.norm."):]: v for k, v in flat.items()
                         if k.startswith("model.norm.")},
                "lm": {k[len("lm_head."):]: v for k, v in flat.items()
                       if k.startswith("lm_head.")}}
        return {"embed": embed, "stages": stages, "head": head}

    def merge(pp_grads):
        flat = {}
        for k, v in pp_grads["stages"].items():
            for g in range(S):
                for i in range(n_per):
                    layer = f"model.layers.{g * n_per + i}.{k}"
                    if vpp == 1:
                        flat[layer] = v[g, i]
                    else:
                        flat[layer] = v[g // pp, g % pp, i]
        flat.update({f"model.embed_tokens.{k}": v
                     for k, v in pp_grads["embed"].items()})
        flat.update({f"model.norm.{k}": v
                     for k, v in pp_grads["head"]["norm"].items()})
        flat.update({f"lm_head.{k}": v
                     for k, v in pp_grads["head"]["lm"].items()})
        return flat

    # MoE decoder layers return (x, aux_loss); the pipeline threads the
    # aux term through each stage's own backward (pp x ep composition)
    probe = jax.eval_shape(
        lambda lp: layer_fn(lp, jnp.zeros((1, 8, cfg.hidden_size)),
                            jnp.zeros((1, 8), jnp.int32)), layer_p0)
    layer_has_aux = isinstance(probe, (tuple, list))

    def stage_fn(sp, x):
        b, sl = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(sl)[None, :], (b, sl))

        if layer_has_aux:
            def one(carry, lp):
                xx, aux = carry
                yy, a = layer_fn(lp, xx, positions)
                return (yy, aux + a), None
            (y, aux), _ = _lax.scan(one, (x, jnp.float32(0.0)), sp)
            return y, aux

        def one(xx, lp):
            return layer_fn(lp, xx, positions), None
        y, _ = _lax.scan(one, x, sp)
        return y

    loss_head = logits_loss or causal_lm_loss

    def head_loss_fn(hp, y, labels):
        h = norm_fn(hp["norm"], y)
        logits = lm_fn(hp["lm"], h).astype(jnp.float32)
        return loss_head(logits, labels)

    if vpp == 1:
        run = pipeline_value_and_grad(embed_fn, stage_fn, head_loss_fn, pp)
    else:
        from ..parallel.pipeline_interleaved import \
            interleaved_pipeline_value_and_grad
        run = interleaved_pipeline_value_and_grad(
            embed_fn, stage_fn, head_loss_fn, pp, vpp)

    def vag(flat_params, tokens):
        loss, grads = run(split(flat_params), tokens, tokens)
        return loss, merge(grads)

    return vag
