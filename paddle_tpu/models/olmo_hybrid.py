"""Olmo-Hybrid's language model (allenai ``olmo_hybrid``; written from
the published ``config.json`` keys): Gated-DeltaNet linear-attention
layers and full-attention layers in ONE model by ``layer_types``, three
of the first to every one of the second.

One block on hidden state x [T, hidden] (the Olmo 2 / Olmo 3 order: the
norm sits on each sub-layer's OUTPUT; all matrices without bias)::

    x = x + RMSNorm(mixer(x));  x = x + RMSNorm(W_down(silu(W_gate x) * W_up x))

- ``layer_types[l] == "full_attention"``: q, k, v of
  ``num_attention_heads`` / ``num_key_value_heads`` heads of ``hidden /
  heads`` columns; an RMSNorm over the whole of q and of k before the
  split into heads; NO rotary (``rope_parameters.rope_theta`` is null:
  the recurrent layers carry position); causal softmax attention, W_o.
- ``"linear_attention"`` (Gated DeltaNet, arXiv:2412.06464): ``q~ = W_q
  x``, ``k~ = W_k x`` (``linear_num_key_heads`` x ``linear_key_head_dim``),
  ``v~ = W_v x`` (``linear_num_value_heads`` x ``linear_value_head_dim``);
  each channel of the three through its own causal
  ``linear_conv_kernel_dim``-tap convolution and SiLU; a head's q and k
  L2-normalised, q then times ``dk^-0.5``; ``beta = 2 sigmoid(W_b x)``
  (``linear_allow_neg_eigval``), ``alpha = exp(-exp(A_log) softplus(W_a x
  + dt_bias))`` a head; the gated delta rule over a float32 state a head
  (``ops.delta_rule``); ``o = RMSNorm_dv(o) * w * silu(W_g x)`` a head,
  then W_out.

SERVING (``PagedEngine``): ``paged_cache_layers`` answers per layer. A
full layer is a ``CacheLayer`` like any other's (pages of K and V, the
ragged kernel at a query group of one). A linear layer is a
``StateLayer``: it caches no keys or values but, PER SLOT, the matrix
state of every head and the last ``taps - 1`` inputs of the convolution;
the engine hands it a ``SlotState`` where the others get a ``PagedKV``.
A prompt chunk runs the chunkwise-parallel form from the state its
predecessor left (zero at position 0) and leaves its own at its last
real position; a decode tick updates the live rows' states in place.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.layer import Layer, Parameter
from ..ops import delta_rule
from ..ops.attention import dense_attention
from ..ops.paged_cache import CacheLayer, StateLayer, write_and_attend
from ..parallel.layers import (ColumnParallelLinear, RowParallelLinear,
                               VocabParallelEmbedding)
from ..parallel.sharding import constraint
from .base import CausalLMBase
from .llama import LlamaMLP

FULL, LINEAR = "full_attention", "linear_attention"


@dataclass
class OlmoHybridConfig:
    """The published config's keys (``rope_parameters.rope_theta`` null is
    ``rope_theta`` None: no rotary anywhere)."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    # None: the published period, three linear layers then a full one
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rope_theta: Optional[float] = None
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = tuple(FULL if i % 4 == 3 else LINEAR
                                     for i in range(n))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != n \
                or set(self.layer_types) - {FULL, LINEAR}:
            raise ValueError(f"layer_types gives one of {FULL!r}, "
                             f"{LINEAR!r} a layer ({n})")
        if self.rope_theta is not None:
            raise ValueError("olmo_hybrid's attention has no rotary "
                             "(rope_theta is null)")
        if self.tie_word_embeddings:
            raise ValueError("olmo_hybrid's head is untied "
                             "(tie_word_embeddings is false)")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear_num_value_heads is a multiple of "
                             "linear_num_key_heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def conv_channels(self) -> int:
        """q~, k~ and v~ side by side: what the convolution runs over."""
        return 2 * self.linear_num_key_heads * self.linear_key_head_dim \
            + self.linear_num_value_heads * self.linear_value_head_dim


def olmo_hybrid_tiny(**overrides) -> OlmoHybridConfig:
    """Test-scale: one period (three linear layers, one full), keys
    narrower than values, eight linear heads whose 16-wide values fill
    one 128-lane row of the stored state."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=4, num_attention_heads=4,
                num_key_value_heads=4, linear_num_key_heads=8,
                linear_num_value_heads=8, linear_key_head_dim=8,
                linear_value_head_dim=16, max_position_embeddings=256,
                dtype=jnp.float32)
    base.update(overrides)
    return OlmoHybridConfig(**base)


class OlmoHybridAttention(Layer):
    """A full-attention layer's mixer: QK-norm, no rotary."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = cfg = config
        h, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        self.q_proj = ColumnParallelLinear(cfg.hidden_size, h * d,
                                           has_bias=cfg.attention_bias,
                                           gather_output=False)
        self.k_proj = ColumnParallelLinear(cfg.hidden_size, kv * d,
                                           has_bias=cfg.attention_bias,
                                           gather_output=False)
        self.v_proj = ColumnParallelLinear(cfg.hidden_size, kv * d,
                                           has_bias=cfg.attention_bias,
                                           gather_output=False)
        self.o_proj = RowParallelLinear(h * d, cfg.hidden_size,
                                        has_bias=False,
                                        input_is_parallel=True)
        self.q_norm = nn.RMSNorm(h * d, cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(kv * d, cfg.rms_norm_eps)

    def forward(self, x, positions, kv_cache=None, segment_ids=None,
                attn_mask=None):
        cfg = self.config
        b, s, _ = x.shape
        h, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        # the named scopes are obs.TICK_SCOPES, as in llama.py
        with jax.named_scope("qkv"):
            q = self.q_norm(self.q_proj(x)).reshape(b, s, h, d)
            k = self.k_norm(self.k_proj(x)).reshape(b, s, kv, d)
            v = self.v_proj(x).reshape(b, s, kv, d)
            q = constraint(q, None, None, "tp", None)
            k = constraint(k, None, None, "tp", None)
            v = constraint(v, None, None, "tp", None)
        new_cache = None
        if kv_cache is not None:
            out, new_cache = write_and_attend(kv_cache, q, k, v, positions,
                                              segment_ids)
        else:
            out = dense_attention(q, k, v, causal=attn_mask is None,
                                  attn_mask=attn_mask)
        with jax.named_scope("o_proj"):
            out = self.o_proj(out.reshape(b, s, h * d))
        return (out, new_cache) if kv_cache is not None else out


class GatedDeltaNet(Layer):
    """A linear-attention layer's mixer (module docstring). What is Gated
    DeltaNet's own is in ``__init__``, ``_project``, ``_gates`` and
    ``_out_gate``; the three served forms of ``forward`` read only
    ``geometry`` and belong to any delta-rule mixer (models/
    ling_hybrid.py's Kimi Delta Attention overrides those four)."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = cfg = config
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        # key heads, value heads, their widths, the convolution's taps
        self.geometry = (hk, hv, dk, dv, cfg.linear_conv_kernel_dim)
        col = lambda n: ColumnParallelLinear(           # noqa: E731
            cfg.hidden_size, n, has_bias=False, gather_output=False)
        self.q_proj, self.k_proj = col(hk * dk), col(hk * dk)
        self.v_proj, self.g_proj = col(hv * dv), col(hv * dv)
        self.a_proj, self.b_proj = col(hv), col(hv)
        self.o_proj = RowParallelLinear(hv * dv, cfg.hidden_size,
                                        has_bias=False,
                                        input_is_parallel=True)
        # a channel's taps, the last one the position itself; no bias
        self.conv_weight = Parameter(
            jnp.full((cfg.conv_channels, cfg.linear_conv_kernel_dim),
                     1.0 / cfg.linear_conv_kernel_dim))
        # Gated DeltaNet's start: decay exp(-A dt), A = 1, dt about 0.01
        self.A_log = Parameter(jnp.zeros((hv,)))
        self.dt_bias = Parameter(jnp.full((hv,), -4.6))
        self.o_norm = nn.RMSNorm(dv, cfg.rms_norm_eps)

    def state_arrays(self):
        """What one SLOT keeps of this layer (``StateLayer.arrays``):
        the heads' matrix states, float32, ``state_lane_heads`` of them
        side by side in a row; the convolution's last inputs."""
        hk, hv, dk, dv, taps = self.geometry
        hp = delta_rule.state_lane_heads(hv, dv)
        return (((hv // hp, dk, hp * dv), jnp.float32),
                ((taps - 1, 2 * hk * dk + hv * dv), self.config.dtype))

    def chunk_rule(self):
        """The shapes one position gives the prompt chunk's delta rule
        (``StateLayer.rule``): q, v and the log-decay, here a head's."""
        _, hv, dk, dv, _ = self.geometry
        return ((hv, dk), (hv, dv), (hv,))

    def _heads(self, y):
        """The convolution's activated output [..., C] apart: q, k
        [..., Hv, dk] normalised (q scaled), v [..., Hv, dv]; float32."""
        hk, hv, dk, dv, _ = self.geometry
        lead = y.shape[:-1]
        q = y[..., :hk * dk].reshape(lead + (hk, dk))
        k = y[..., hk * dk:2 * hk * dk].reshape(lead + (hk, dk))
        v = y[..., 2 * hk * dk:].reshape(lead + (hv, dv))
        q = delta_rule.l2_normalize(q) * dk ** -0.5
        k = delta_rule.l2_normalize(k)
        if hv != hk:
            q = jnp.repeat(q, hv // hk, axis=-2)
            k = jnp.repeat(k, hv // hk, axis=-2)
        return q, k, v

    def _project(self, x):
        """x -> (q~, k~, v~ side by side [b, s, C], what ``_gates``
        reads, the output gate's input [b, s, Hv * dv])."""
        with jax.named_scope("qkv"):        # obs.TICK_SCOPES
            u = jnp.concatenate([self.q_proj(x), self.k_proj(x),
                                 self.v_proj(x)], -1)
            a, bb, gate = self.a_proj(x), self.b_proj(x), self.g_proj(x)
        return u, (a, bb), gate

    def _out_gate(self, gate):
        return jax.nn.silu(gate)

    def _gates(self, a, b):
        """(log-decay, beta) [..., Hv] float32 from the two projections."""
        f32 = jnp.float32
        g = -jnp.exp(self.A_log.astype(f32)) * jax.nn.softplus(
            a.astype(f32) + self.dt_bias.astype(f32))
        beta = jax.nn.sigmoid(b.astype(f32))
        if self.config.linear_allow_neg_eigval:
            beta = 2.0 * beta
        return g, beta

    def forward(self, x, positions, kv_cache=None, segment_ids=None,
                attn_mask=None):
        if attn_mask is not None:
            raise NotImplementedError(
                "a linear-attention layer takes no attention mask")
        b, s, _ = x.shape
        _, hv, dk, dv, taps = self.geometry
        u, gated, gate = self._project(x)
        hp = delta_rule.state_lane_heads(hv, dv)
        new_cache = None
        if kv_cache is None:
            # no cache: every row of the batch a sequence from zero state
            with jax.named_scope("conv"):
                tail = jnp.zeros((taps - 1, u.shape[-1]), u.dtype)
                y = jax.vmap(lambda ur: delta_rule.conv_chunk(
                    ur, self.conv_weight, tail)[0])(u)
                q, k, v = self._heads(jax.nn.silu(y))
            with jax.named_scope("chunk_delta_state"):
                g, beta = self._gates(*gated)
                S0 = jnp.zeros((hv, dk, dv), jnp.float32)
                o = jax.vmap(lambda *r: delta_rule.gated_delta_chunk(
                    *r, S0)[0])(q, k, v, g, beta)
        elif kv_cache.call == "decode" and s > 1:
            raise NotImplementedError(
                "a linear-attention layer has no multi-position decode "
                "rows: a rejected draft's positions cannot be taken back "
                "out of the state")
        elif kv_cache.call == "decode":
            # a decode tick: row r is slot r; one position a row
            S, tails = kv_cache.arrays
            live = kv_cache.live
            with jax.named_scope("conv"):
                y, tails = delta_rule.conv_step(u[:, 0], self.conv_weight,
                                                tails, live)
                q, k, v = self._heads(jax.nn.silu(y))
            with jax.named_scope("delta_state"):
                g, beta = self._gates(*(t[:, 0] for t in gated))
                S, o = delta_rule.delta_state_step(S, q, k, v, jnp.exp(g),
                                                   beta, live)
                o = o[:, None]
            new_cache = kv_cache._replace(arrays=(S, tails))
        else:
            # a prompt call (b == 1): one slot's chunk behind its earlier
            # chunks, its whole prompt, or a packed call's segments, each
            # from position 0
            S, tails = kv_cache.arrays
            slots, lens = kv_cache.slots, kv_cache.seq_lens
            nseg = lens.shape[0]
            fresh0 = kv_cache.fresh[0]
            with jax.named_scope("conv"):
                seg = segment_ids[0] if segment_ids is not None \
                    else jnp.zeros((s,), jnp.int32)
                # the real positions, and each segment's last one
                real = positions[0] < lens[seg]
                ends = jnp.max(jnp.where(
                    (seg[None, :] == jnp.arange(nseg)[:, None])
                    & real[None, :], jnp.arange(s)[None, :], 0), -1)
                tail0 = jnp.where(fresh0, 0, tails[slots[0]])
                y, new_tails = delta_rule.conv_chunk(
                    u[0], self.conv_weight, tail0, seg, ends)
                tails = tails.at[slots].set(new_tails, mode="drop")
                q, k, v = self._heads(jax.nn.silu(y))
            with jax.named_scope("chunk_delta_state"):
                g, beta = self._gates(*(t[0] for t in gated))
                # a padded position changes nothing
                g = jnp.where(real[:, None] if g.ndim == 2
                              else real[:, None, None], g, 0.0)
                beta = jnp.where(real[:, None], beta, 0.0)
                S0 = jnp.where(fresh0, 0.0,
                               delta_rule.unpack_state(S[slots[0]], hp))
                o, S_seg = delta_rule.gated_delta_chunk(
                    q, k, v, g, beta, S0, seg, segments=nseg)
                S = S.at[slots].set(delta_rule.pack_state(S_seg, hp),
                                    mode="drop")
                o = o[None]
            new_cache = kv_cache._replace(arrays=(S, tails))
        with jax.named_scope("gate_norm"):     # o is float32, and stays
            o = self.o_norm(o) * self._out_gate(
                gate.astype(jnp.float32).reshape(b, s, hv, dv))
            o = o.astype(x.dtype).reshape(b, s, hv * dv)
        with jax.named_scope("o_proj"):
            out = self.o_proj(o)
        return (out, new_cache) if kv_cache is not None else out


class OlmoHybridDecoderLayer(Layer):
    def __init__(self, config: OlmoHybridConfig, layer_idx: int):
        super().__init__()
        cfg = config
        self.is_linear = cfg.layer_types[layer_idx] == LINEAR
        if self.is_linear:
            self.linear_attn = GatedDeltaNet(cfg)
        else:
            self.self_attn = OlmoHybridAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)
        self.post_feedforward_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                     cfg.rms_norm_eps)

    @property
    def mixer(self):
        return self.linear_attn if self.is_linear else self.self_attn

    def forward(self, x, positions, kv_cache=None, segment_ids=None,
                attn_mask=None):
        out = self.mixer(x, positions, kv_cache=kv_cache,
                         segment_ids=segment_ids, attn_mask=attn_mask)
        new_cache = None
        if kv_cache is not None:
            out, new_cache = out
        with jax.named_scope("norm"):       # the norm on the OUTPUT
            x = x + self.post_attention_layernorm(out)
        with jax.named_scope("mlp"):
            h = self.mlp(x)
        with jax.named_scope("norm"):
            x = x + self.post_feedforward_layernorm(h)
        x = constraint(x, ("dp", "fsdp"), "sp", None)
        return (x, new_cache) if kv_cache is not None else x


class OlmoHybridModel(Layer):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        # each layer cast as it is built (llama.py: the float32 draws of
        # every layer at once do not fit beside the bf16 model)
        self.layers = nn.LayerList(
            [OlmoHybridDecoderLayer(config, i).to(dtype=config.dtype)
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


class OlmoHybridForCausalLM(CausalLMBase):
    def __init__(self, config: Optional[OlmoHybridConfig] = None):
        super().__init__()
        config = config or OlmoHybridConfig()
        self.config = config
        self.model = OlmoHybridModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size,
                                            has_bias=False,
                                            gather_output=True)
        if config.dtype != jnp.float32:
            self.lm_head.to(dtype=config.dtype)

    def init_kv_caches(self, batch_size: int, max_len: int, dtype=None):
        raise NotImplementedError(
            "OlmoHybridForCausalLM serves through PagedEngine (a PagedKV "
            "or a SlotState a layer); the static whole-sequence cache is "
            "not built for layers that keep recurrent state")

    def paged_cache_layers(self):
        """What ``PagedEngine`` keeps for EACH layer: a full layer's K
        and V rows a token (``CacheLayer``), a linear layer's arrays a
        SLOT (``StateLayer``)."""
        cfg = self.config
        kv = (cfg.num_key_value_heads, cfg.head_dim)
        return [StateLayer(layer.linear_attn.state_arrays(),
                           layer.linear_attn.chunk_rule())
                if layer.is_linear else CacheLayer((kv, kv))
                for layer in self.model.layers]

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
