"""Ling 3.0's language model (inclusionAI ``bailing_hybrid``) for the
benchmark: the model handed to the program, its weights, and the plain
reference that decides ``correct``.

``build`` constructs the PROGRAM's model (``paddle_tpu.models.
ling_hybrid``) at the sizes of a configuration file, holding ONE
expert-parallel rank's share of each expert layer, and fills it with
weights the BENCHMARK makes from the seed, on the device, in the type
they are served in. ``reference_rows`` is the yardstick: the decoder
written from the published ``config.json`` (keys in backticks) in float32
``jax.numpy`` at ``highest`` matmul precision, with no cache, no kernel,
no chunkwise form and no absorbed attention: the recurrence is a
``lax.scan`` over positions, the latent is expanded to keys and values.
x is [T, hidden], every matrix without bias, RMSNorm eps ``rms_norm_eps``:

- Block (ASSUMED pre-norm, as Ling 2.x): ``x = x + mixer(RMSNorm(x))``,
  ``x = x + FFN(RMSNorm(x))``; logits ``W_head RMSNorm(x)``, head untied.
- Layer ``l`` is latent attention where ``(l + 1) % layer_group_size ==
  0`` (ASSUMED: the rule of the family's earlier hybrid releases), else
  Kimi Delta Attention; its FFN is dense (``intermediate_size``) for ``l
  < first_k_dense_replace``, else the expert layer.
- Kimi Delta Attention (arXiv:2510.26692): ``q~ = W_q x``, ``k~ = W_k
  x``, ``v~ = W_v x`` (``num_attention_heads`` x ``head_dim`` each); each
  channel c through its own causal ``short_conv_kernel_size``-tap
  convolution and SiLU, zeros before position 0, ASSUMED no convolution
  bias; a head's ``q_t``, ``k_t`` divided by their L2 norms (``x /
  sqrt(sum x^2 + 1e-6)``), ``q_t`` then times ``head_dim^-0.5``;
  ``beta_t = sigmoid(W_b x_t)`` a head; a log-decay a KEY CHANNEL
  (ASSUMED the bounded form, ``kda_safe_gate``): ``g_t[h, c] =
  kda_lower_bound * sigmoid(exp(A_log[h]) * ((W_f x_t)[h, c] +
  dt_bias[h, c]))``. Per head, state S in R^{dk x dv}, zero before
  position 0, POSITION BY POSITION: ``S = Diag(exp(g_t)) S``; ``d_t =
  beta_t (v_t - S^T k_t)``; ``S = S + k_t d_t^T``; ``o_t = S^T q_t``.
  Then ``o_t = RMSNorm(o_t) * w * sigmoid(W_g x_t)`` a head (ASSUMED
  sigmoid, as Kimi Linear; w of ``head_dim`` shared by the heads) and
  ``W_o``. ASSUMED: state and recurrence in float32.
- Latent attention (``q_lora_rank`` null): ``q = W_q x`` (heads x
  (``qk_nope_head_dim`` + ``qk_rope_head_dim``)); ``[c, k^R] = W_kv_a
  x``, ``c = RMSNorm(c)`` (ASSUMED: ``use_qk_norm`` is this norm alone);
  ``k^R`` and q's last ``qk_rope_head_dim`` columns roped in interleaved
  pairs at ``rope_theta``, no scaling; ``[k^C, v] = W_kv_b c``; ``s_ij =
  (q^C_i . k^C_j + q^R_i . k^R_j) / sqrt(qk_head_dim)``, causal softmax;
  a head's output times ``sigmoid((W_gate x)[h])`` (ASSUMED: the
  ``head_wise`` gate is the softmax layers', read from the normed input);
  ``W_o``.
- Expert layer: DeepSeek-V3's ``noaux_tc`` rule (benchmarks/models/
  deepseek_v3.py ``_route``) over ``num_experts`` published columns in
  ``n_group`` groups, plus the shared expert.

The share: the router keeps its published width; the experts
``first_expert .. first_expert + num_experts - 1`` are held; what the
absent experts would add is left out, here as in the program.

Departures from the published description, each also marked DEPARTURE
where it is made: (1) the multi-token-prediction layer is not built;
(2) the rotary pairs stay interleaved (deepseek_v3.py's departure 2);
(3) the W8A8 control keeps the router and the recurrence in float32.

The weights: projections, embeddings, head and router at 0.02, norm
scales 1 +- 0.1, the selection bias at 0.01 (deepseek_v3.py's
generator); the convolution's taps at ``CONV_STD``. ``A_log`` uniform in
(-0.5, 0.5) a head and ``dt_bias`` uniform in (``DT_BIAS_MIN``, 0) a
channel: the mixer reads a normed input, so ``W_f x`` has deviation
about 1 and a channel's decay ``exp(-5 sigmoid(A (W_f x + dt_bias)))``
runs from under 0.5 (``dt_bias`` near 0) to over 0.999 (``dt_bias`` under
-9) across a head's channels, the token's own ``W_f x`` moving each by a
factor of e either way (``gate_spread`` measures it). The controls:
``mode="int8"`` computes the projections, the FFNs and the experts as
W8A8 would; ``mode="bf16_state"`` keeps every product in float32 and
rounds the STATE to bfloat16 after each position.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmarks.models import deepseek_v3
from benchmarks.models.deepseek_v3 import (LAYER, _head, _rope, _route,
                                           _swiglu)
from benchmarks.models.qwen2 import _rms_norm, matmul

CONV_STD = 0.5
A_LOG_HALF = 0.5        # A_log uniform in (-0.5, 0.5)
DT_BIAS_MIN = -12.0     # dt_bias uniform in (-12, 0)
QUERY_BLOCK = 256       # queries whose scores are alive at once
# Sequences of one reference pass, whatever the caller's count (which
# harness/verify.py sets from a whole [heads, L, L] of scores a row: one
# row at 2,048 positions). Here the scores live a QUERY_BLOCK at a time,
# and the recurrence is 2,048 sequential steps a layer however many rows
# share them: four rows a pass are a quarter of the steps (a linear
# layer's float32 activations are then 2 GB at 2,048 positions, which
# fits beside the engine).
ROWS_PER_PASS = 4
L2_EPS = 1e-6


def program_config(config: dict):
    """The program's own config object at this file's sizes."""
    import jax.numpy as jnp
    from paddle_tpu.models.ling_hybrid import LingHybridConfig
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["dtype"]]
    if config["state_dtype"] != "float32":
        raise ValueError("the program keeps its recurrent state in float32")
    if config["moe_shared_expert_intermediate_size"] \
            != config["moe_intermediate_size"]:
        raise ValueError("the shared expert's width is read from "
                         "moe_intermediate_size")
    return LingHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        rope_scaling=config["rope_scaling"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        mla_head_gate=config["gated_attention_proj_granularity_type"]
        == "head_wise",
        layer_group_size=config["layer_group_size"],
        short_conv_kernel_size=config["short_conv_kernel_size"],
        kda_safe_gate=config["kda_safe_gate"],
        kda_lower_bound=float(config["kda_lower_bound"]),
        num_experts=config["num_experts_published"],
        first_expert=config["first_expert"],
        experts_held=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_shared_experts=config["num_shared_experts"],
        first_k_dense_replace=config["first_k_dense_replace"],
        routed_scaling_factor=config["routed_scaling_factor"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        scoring=config["score_function"], group_score_mode="top2_sum",
        norm_topk_prob=config["norm_topk_prob"],
        expert_swiglu_limit_list=tuple(config["expert_swiglu_limit_list"]),
        share_expert_swiglu_limit_list=tuple(
            config["share_expert_swiglu_limit_list"]),
        attention_bias=config["use_qkv_bias"],
        tie_word_embeddings=config["tie_word_embeddings"],
        num_nextn_predict_layers=config["num_nextn_predict_layers"],
        dtype=dtype)


def _own_draws(weights):
    """``weights`` with the three kinds of array that are neither a
    projection nor a norm scale at THIS family's draws. deepseek_v3's
    generator made each as 0.02 z, z standard normal: u = Phi(z) is
    uniform, ``A_log = A_LOG_HALF (2 u - 1)``, ``dt_bias = DT_BIAS_MIN
    u``, taps ``CONV_STD`` z."""
    import jax
    import jax.numpy as jnp

    def own(name, w):
        kind = name.rpartition(".")[2]
        if kind not in ("A_log", "dt_bias", "conv_weight"):
            return w
        z = w.astype(jnp.float32) / deepseek_v3.WEIGHT_STD
        if kind == "conv_weight":
            return (CONV_STD * z).astype(w.dtype)
        u = jax.scipy.stats.norm.cdf(z)
        if kind == "A_log":
            return (A_LOG_HALF * (2.0 * u - 1.0)).astype(w.dtype)
        return (DT_BIAS_MIN * u).astype(w.dtype)

    return type(weights)((name, own(name, w))
                         for name, w in weights.items())


def make_weights(spec: Dict, seed: int, device) -> Dict:
    """Every array of ``spec`` drawn from ``seed`` on ``device``
    (deepseek_v3's generator), then this family's own draws."""
    return _own_draws(deepseek_v3.make_weights(spec, seed, device))


def fill_weights(params: Dict, seed: int):
    """New values for every array of ``params`` from ``seed``, in place
    of the old (deepseek_v3's, which keeps the mapping's type, order and
    placement: jit's cache keys on them)."""
    return _own_draws(deepseek_v3.fill_weights(params, seed))


def _program_model(cfg):
    """The program's model object WITHOUT its own weight draw, and the
    (shape, dtype) of each of its parameters (as deepseek_v3.py)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.ling_hybrid import LingHybridForCausalLM
    box = []

    def make():
        box.append(LingHybridForCausalLM(cfg))
        return dict(box[0].functional()[1])

    shapes = jax.eval_shape(make)
    pt.seed(0)          # the trace left a tracer in the global key
    return box[0], {k: (v.shape, v.dtype) for k, v in shapes.items()}


def build(config: dict, seed: int, device):
    """The program's ``LingHybridForCausalLM`` on ``device`` holding the
    benchmark's seeded weights, selection bias included."""
    import jax
    cfg = program_config(config)    # a program without the model: here
    with jax.default_device(device):
        model, spec = _program_model(cfg)
        model.set_state_dict(make_weights(spec, seed, device), strict=False)
    left = [k for k, v in model.functional()[1].items()
            if not isinstance(v, jax.Array) or isinstance(v, jax.core.Tracer)]
    if left:
        raise RuntimeError(f"parameters without seeded weights: {left[:3]}")
    return model


# ---------------------------------------------------------------- reference
def is_latent(config: dict, layer: int) -> bool:
    # ASSUMED: the last layer of each group of ``layer_group_size``
    return (layer + 1) % config["layer_group_size"] == 0


def _gates(w, h, *, cfg, mm):
    """(alpha [b, s, H, dk] a key channel, beta [b, s, H]) of a linear
    layer from its normed input h."""
    import jax
    import jax.numpy as jnp
    f32 = lambda name: w[name].astype(jnp.float32)      # noqa: E731
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    b, s, _ = h.shape
    f = (mm(h, f32("f_proj.weight")) + f32("dt_bias")).reshape(b, s, heads, d)
    # ASSUMED: the bounded gate, kda_lower_bound * sigmoid(A * .)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(f32("A_log"))[:, None] * f)
    return jnp.exp(g), jax.nn.sigmoid(mm(h, f32("b_proj.weight")))


def _delta_rule(q, k, v, alpha, beta, state_dtype=None):
    """The recurrence of ONE sequence, position by position. q, k [s, h,
    dk]; v [s, h, dv]; alpha [s, h, dk]; beta [s, h]. Returns o [s, h,
    dv]. ``state_dtype``: the state is rounded to it after each position
    (a control; None keeps float32)."""
    import jax
    import jax.numpy as jnp

    def step(S, x):
        qt, kt, vt, at, bt = x
        S = at[:, :, None] * S                  # row c times alpha_t[c]
        d = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[:, :, None] * d[:, None, :]
        if state_dtype is not None:     # a cast pair may be elided
            info = jnp.finfo(state_dtype)
            S = jax.lax.reduce_precision(S, info.nexp, info.nmant)
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, S0, (q, k, v, alpha, beta))[1]


def _kda(w, h, *, cfg, mode):
    """A Kimi-Delta-Attention layer's mixer on its normed input h [b, s,
    H] float32."""
    import jax
    import jax.numpy as jnp
    state_dtype = jnp.bfloat16 if mode == "bf16_state" else None
    mm = partial(matmul, mode=None if state_dtype else mode)
    f32 = lambda name: w[name].astype(jnp.float32)      # noqa: E731
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    taps = cfg["short_conv_kernel_size"]
    b, s, _ = h.shape
    u = jnp.concatenate([mm(h, f32("q_proj.weight")),
                         mm(h, f32("k_proj.weight")),
                         mm(h, f32("v_proj.weight"))], -1)
    # ASSUMED: no convolution bias; zeros before position 0
    ext = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    taps_w = f32("conv_weight")                         # [C, taps]
    y = jax.nn.silu(sum(ext[:, j:j + s] * taps_w[:, j] for j in range(taps)))
    q, k, v = (y[..., i * heads * d:(i + 1) * heads * d]
               .reshape(b, s, heads, d) for i in range(3))
    norm = lambda a: a / jnp.sqrt(                      # noqa: E731
        jnp.sum(a * a, -1, keepdims=True) + L2_EPS)
    q, k = norm(q) * d ** -0.5, norm(k)
    alpha, beta = _gates(w, h, cfg=cfg, mm=mm)
    o = jax.vmap(partial(_delta_rule, state_dtype=state_dtype))(
        q, k, v, alpha, beta)                           # [b, s, heads, d]
    o = _rms_norm(o, f32("o_norm.weight"), cfg["rms_norm_eps"])
    # ASSUMED: a sigmoid output gate
    o = o * jax.nn.sigmoid(mm(h, f32("g_proj.weight"))
                           .reshape(b, s, heads, d))
    return mm(o.reshape(b, s, heads * d), f32("o_proj.weight"))


def _mla(w, h, positions, *, cfg, mode):
    """A latent-attention layer's mixer on its normed input h [b, s, H]
    float32: the EXPANDED form, full causal attention."""
    import jax
    import jax.numpy as jnp
    mm = partial(matmul, mode=None if mode == "bf16_state" else mode)
    f32 = lambda name: w[name].astype(jnp.float32)      # noqa: E731
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    inv = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(0, rope, 2, dtype=np.float32) / rope)
    b, s, _ = h.shape
    q = mm(h, f32("q_proj.weight")).reshape(b, s, heads, nope + rope)
    ckv = mm(h, f32("kv_a_proj_with_mqa.weight"))
    c = _rms_norm(ckv[..., :r], f32("kv_a_layernorm.weight"), eps)
    # DEPARTURE 2: the pairs stay interleaved
    k_pe = _rope(ckv[..., None, r:], positions, inv, 1.0)[:, :, 0]
    q_pe = _rope(q[..., nope:], positions, inv, 1.0)
    kv = mm(c, f32("kv_b_proj.weight")).reshape(b, s, heads, nope + dv)
    j = jnp.arange(s)[None, :]
    att = []
    for lo in range(0, s, QUERY_BLOCK):  # the scores of a block at a time
        hi = lo + QUERY_BLOCK
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi, :, :nope],
                             kv[..., :nope])
                  + jnp.einsum("bqhd,bkd->bhqk", q_pe[:, lo:hi], k_pe)
                  ) / math.sqrt(nope + rope)
        i = lo + jnp.arange(scores.shape[2])[:, None]
        scores = jnp.where(i >= j, scores, -jnp.inf)
        att.append(jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(scores, axis=-1),
                              kv[..., nope:]))
    att = jnp.concatenate(att, 1)
    # ASSUMED: one gate a head, from the normed input
    att = att * jax.nn.sigmoid(mm(h, f32("g_proj.weight")))[..., None]
    return mm(att.reshape(b, s, heads * dv), f32("o_proj.weight"))


def _route_cfg(config: dict) -> dict:
    """The keys deepseek_v3's ``_route`` reads, from this family's."""
    return dict(config, n_routed_experts_published=config[
        "num_experts_published"])


def _layers(params, config, mode):
    """``run(x, positions, each=None) -> x`` through every layer;
    ``each(i, w, h)`` sees a layer's mixer weights and normed input."""
    import jax
    import jax.numpy as jnp
    eps = config["rms_norm_eps"]
    low = None if mode == "bf16_state" else mode
    kda = jax.jit(partial(_kda, cfg=config, mode=mode))
    mla = jax.jit(partial(_mla, cfg=config, mode=mode))
    swiglu = jax.jit(partial(_swiglu, mode=low))
    route = jax.jit(partial(_route, cfg=_route_cfg(config)))
    norm = jax.jit(lambda x, w: _rms_norm(x, w.astype(jnp.float32), eps))

    def run(x, positions, each=None):
        for i in range(config["num_hidden_layers"]):
            lp = f"{LAYER}{i}."
            latent = is_latent(config, i)
            key = lp + ("self_attn." if latent else "linear_attn.")
            w = {k[len(key):]: v for k, v in params.items()
                 if k.startswith(key)}
            h = norm(x, params[lp + "input_layernorm.weight"])
            if each is not None:
                each(i, w, h)
            x = x + (mla(w, h, positions) if latent else kda(w, h))
            h = norm(x, params[lp + "post_attention_layernorm.weight"])
            mlp = lambda name: params[lp + "mlp." + name]  # noqa: E731
            if i < config["first_k_dense_replace"]:
                x = x + swiglu(h, mlp("gate_proj.weight"),
                               mlp("up_proj.weight"),
                               mlp("down_proj.weight"))
                continue
            # the router in float32 in the control too (DEPARTURE 3)
            gates = route(h, mlp("gate"), mlp("expert_bias"))
            x = x + swiglu(h, mlp("shared_gate_proj"),
                           mlp("shared_up_proj"), mlp("shared_down_proj"))
            # the share: the held experts only, one at a time
            for e in range(config["num_experts"]):
                x = x + gates[..., config["first_expert"] + e, None] \
                    * swiglu(h, mlp("w_gate")[e], mlp("w_up")[e],
                             mlp("w_down")[e])
        # DEPARTURE 1: no multi-token-prediction layer follows
        return norm(x, params["model.norm.weight"])

    return run


def _embed(params, seqs, rows: int):
    import jax.numpy as jnp
    L = -(-max(len(s) for s in seqs) // 256) * 256
    ids = np.zeros((rows, L), np.int32)
    for r, s in enumerate(seqs):
        ids[r, :len(s)] = s
    pos = jnp.broadcast_to(jnp.arange(L)[None], ids.shape)
    return params["model.embed_tokens.weight"][jnp.asarray(ids)] \
        .astype(jnp.float32), pos


def reference_rows(params: Dict, config: dict,
                   sequences: Sequence[Sequence[int]],
                   starts: Sequence[int], read: Sequence[Sequence[int]],
                   mode: Optional[str] = None, rows_per_block: int = 4,
                   vocab_block: int = 16384,
                   top: int = 0) -> List[Dict[str, np.ndarray]]:
    """Teacher-force each of ``sequences`` through the plain decoder,
    once, and read the logits that predict its positions ``starts[i]:``
    (the interface and the returned fields are those of
    benchmarks/models/qwen2.py ``reference_rows``). ``mode`` computes
    every matrix product of a projection, an FFN or an expert as the
    lower precision would; the router and the recurrence stay float32.
    Layer by layer, rows in blocks, experts one at a time, the head in
    blocks of vocabulary columns."""
    import jax
    out: List[Dict[str, np.ndarray]] = []
    rows_per_block = max(rows_per_block, ROWS_PER_PASS)
    with jax.default_matmul_precision("highest"):
        run = _layers(params, config, mode)
        for lo in range(0, len(sequences), rows_per_block):
            seqs = list(sequences[lo:lo + rows_per_block])
            x = run(*_embed(params, seqs, rows_per_block))
            # the hidden state at position p predicts the token at p + 1
            ri, pi, tk, owner = [], [], [], []
            for r, s in enumerate(seqs):
                n = len(s) - starts[lo + r]
                ri += [r] * n
                pi += list(range(starts[lo + r] - 1, len(s) - 1))
                tk += list(read[lo + r])[:n]
                owner += [r] * n
            out += _head(params, config, x, (ri, pi, tk, owner), len(seqs),
                         top, None if mode == "bf16_state" else mode,
                         vocab_block)
    return out


def gate_spread(params: Dict, config: dict,
                sequence: Sequence[int]) -> List[Dict]:
    """The decays and write strengths the linear layers compute for one
    sequence, by layer: quantiles of ``alpha`` over positions, heads and
    channels, the shares of it under 0.5 and over 0.999, quantiles of
    ``beta``. What ``assumed.weights`` of a configuration quotes."""
    import jax
    rows = []
    n = len(sequence)

    def each(i, w, h):
        if is_latent(config, i):
            return
        alpha, beta = _gates(w, h, cfg=config, mm=partial(matmul, mode=None))
        a, b = np.asarray(alpha[0, :n]), np.asarray(beta[0, :n])
        qs = (0.01, 0.25, 0.5, 0.75, 0.99)
        # a head's channels from fast to slow: its median channel decays
        by_channel = np.median(a, axis=0)               # [H, dk]
        rows.append({
            "layer": i,
            "alpha_quantiles": np.quantile(a, qs).round(5).tolist(),
            "alpha_under_half": float((a < 0.5).mean()),
            "alpha_over_0.999": float((a > 0.999).mean()),
            "heads_with_both": float(np.mean(
                (by_channel.min(-1) < 0.5) & (by_channel.max(-1) > 0.999))),
            "beta_quantiles": np.quantile(b, qs).round(4).tolist()})

    with jax.default_matmul_precision("highest"):
        _layers(params, config, None)(*_embed(params, [list(sequence)], 1),
                                      each=each)
    return rows
