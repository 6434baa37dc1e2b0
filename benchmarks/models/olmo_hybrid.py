"""Olmo-Hybrid's language model for the benchmark: the model handed to
the program, its weights, and the plain reference that decides
``correct``.

``build`` constructs the PROGRAM's model (``paddle_tpu.models.
olmo_hybrid``) at the sizes of a configuration file and fills it with
weights the BENCHMARK makes from the seed, on the device, in the type
they are served in. ``reference_rows`` is the yardstick: the decoder
written from the published ``config.json`` (``model_type``
``olmo_hybrid``; keys in backticks) in float32 ``jax.numpy`` at
``highest`` matmul precision, with no cache, no kernel and no chunkwise
form: the recurrence is a ``lax.scan`` over positions. x is [T, hidden],
every matrix without bias, RMSNorm eps ``rms_norm_eps``:

- Block (ASSUMED: the Olmo 2 / Olmo 3 order, the norm on each
  sub-layer's OUTPUT): ``x = x + RMSNorm(mixer(x))``, ``x = x +
  RMSNorm(W_down(silu(W_gate x) * W_up x))``, width
  ``intermediate_size``; logits ``W_head RMSNorm(x)``, head untied.
- ``layer_types[l] == "full_attention"``: q, k, v of
  ``num_attention_heads`` = ``num_key_value_heads`` heads of ``hidden /
  heads`` columns; ASSUMED QK-norm as Olmo 2 / 3: an RMSNorm with a
  hidden-wide scale over the whole of q and of k before the split into
  heads; ASSUMED no rotary (``rope_parameters.rope_theta`` is null);
  ``s_ij = q_i . k_j / sqrt(d)``, causal softmax, ``W_o``.
- ``"linear_attention"`` (Gated DeltaNet, arXiv:2412.06464): ``q~ = W_q
  x``, ``k~ = W_k x`` (``linear_num_key_heads`` x
  ``linear_key_head_dim``), ``v~ = W_v x`` (``linear_num_value_heads`` x
  ``linear_value_head_dim``); each channel c of the three through its
  own causal ``linear_conv_kernel_dim``-tap convolution and SiLU, ``u_t
  = silu(sum_j w_c[j] u~_{t-3+j})``, zeros before position 0, ASSUMED no
  convolution bias; a head's ``q_t``, ``k_t`` divided by their L2 norms
  (``x / sqrt(sum x^2 + 1e-6)``), ``q_t`` then times ``dk^-0.5``;
  ``beta_t = 2 sigmoid(W_b x_t)`` a head (``linear_allow_neg_eigval``
  gives the 2); ``alpha_t = exp(-exp(A_log) softplus(W_a x_t +
  dt_bias))`` a head. Per head, state S in R^{dk x dv}, zero before
  position 0, POSITION BY POSITION: ``S = alpha_t S``; ``d_t = beta_t
  (v_t - S^T k_t)``; ``S = S + k_t d_t^T``; ``o_t = S^T q_t``. Then
  ``o_t = RMSNorm_dv(o_t) * w * silu(W_g x_t)`` a head (w of ``dv``
  shared by the heads) and ``W_out``. ASSUMED: state and recurrence in
  float32.

It imports nothing of ``paddle_tpu`` and reads only the weights made
here, by name, upcasting one matrix at a time so that it fits beside a
serving engine; attention in blocks of queries, the head in blocks of
vocabulary columns (qwen2.py's fold).

The weights: projections, embeddings and head at 0.02, norm scales 1 +-
0.1 (deepseek_v3.py's generator). ``A_log`` and ``dt_bias`` as Gated
DeltaNet starts them: ``A`` uniform in (0, 16), ``dt`` log-uniform in
(0.001, 0.1), so ``exp(A_log) softplus(dt_bias)`` spans 1e-5 .. 1.6 over
the heads before the token's own ``W_a x`` moves it; the two gate
projections ``W_a``, ``W_b`` at ``GATE_STD`` (why: beside it); the convolution's
taps at deviation ``CONV_STD``, so that a dropped or shifted tap changes
every channel. The W8A8 control (``mode="int8"``) computes the
projections and the FFN as the lower precision would, and keeps the
recurrence in float32; ``mode="bf16_state"`` is the other control: every
product in float32 and the STATE rounded to bfloat16 after each
position, what a state held in half the bytes would read.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmarks.models import deepseek_v3
from benchmarks.models.deepseek_v3 import LAYER, _head, _swiglu
from benchmarks.models.qwen2 import _rms_norm, matmul

CONV_STD = 0.5
# The two gate projections (W_a, W_b). Drawn at 0.02 like the others
# they would read a residual stream of magnitude 1-10 (the norms sit on
# the sub-layers' OUTPUTS, so it grows) as pre-activations of deviation
# 1.2-12: every decay 0 or 1 and every beta 0 or 2, which no trained
# layer has, and a decay that is a step function of the stream turns a
# bfloat16 rounding into a different memory (measured, PERF.md section
# 2). At 0.002 the pre-activations' deviation is 0.12-1.2: beta spans
# (0.2, 1.8) and the decay keeps the spread of its start.
GATE_STD = 0.002
A_MAX = 16.0
DT_MIN, DT_MAX = 0.001, 0.1
QUERY_BLOCK = 256       # queries whose scores are alive at once
L2_EPS = 1e-6


def program_config(config: dict):
    """The program's own config object at this file's sizes."""
    import jax.numpy as jnp
    from paddle_tpu.models.olmo_hybrid import OlmoHybridConfig
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["dtype"]]
    if config["state_dtype"] != "float32":
        raise ValueError("the program keeps its recurrent state in float32")
    return OlmoHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        layer_types=tuple(config["layer_types"]),
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=config["linear_allow_neg_eigval"],
        rope_theta=config["rope_parameters"]["rope_theta"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"],
        attention_bias=config["attention_bias"],
        tie_word_embeddings=config["tie_word_embeddings"], dtype=dtype)


def _own_draws(weights):
    """``weights`` with the three kinds of array that are neither a
    projection nor a norm scale at THIS family's draws. deepseek_v3's
    generator made each as 0.02 z, z standard normal: u = Phi(z) is
    uniform, and ``A_log = log(16 u)``, ``dt = 0.001 (100 ^ u)`` with
    ``dt_bias`` the softplus's inverse of it, taps ``CONV_STD`` z."""
    import jax
    import jax.numpy as jnp

    def own(name, w):
        if name.endswith(("a_proj.weight", "b_proj.weight")):
            return (w * (GATE_STD / deepseek_v3.WEIGHT_STD)).astype(w.dtype)
        kind = name.rpartition(".")[2]
        if kind not in ("A_log", "dt_bias", "conv_weight"):
            return w
        z = w.astype(jnp.float32) / deepseek_v3.WEIGHT_STD
        if kind == "conv_weight":
            return (CONV_STD * z).astype(w.dtype)
        u = jnp.clip(jax.scipy.stats.norm.cdf(z), 1e-3, 1.0 - 1e-3)
        if kind == "A_log":
            return jnp.log(A_MAX * u).astype(w.dtype)
        dt = DT_MIN * (DT_MAX / DT_MIN) ** u
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(w.dtype)

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
    from paddle_tpu.models.olmo_hybrid import OlmoHybridForCausalLM
    box = []

    def make():
        box.append(OlmoHybridForCausalLM(cfg))
        return dict(box[0].functional()[1])

    shapes = jax.eval_shape(make)
    pt.seed(0)          # the trace left a tracer in the global key
    return box[0], {k: (v.shape, v.dtype) for k, v in shapes.items()}


def build(config: dict, seed: int, device):
    """The program's ``OlmoHybridForCausalLM`` on ``device`` holding the
    benchmark's seeded weights."""
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
def _full_attention(w, x, *, cfg, mode):
    """A full-attention layer's mixer, x [b, s, H] float32."""
    import jax
    import jax.numpy as jnp
    mm = partial(matmul, mode=None if mode == "bf16_state" else mode)
    f32 = lambda name: w[name].astype(jnp.float32)      # noqa: E731
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    b, s, H = x.shape
    d = H // heads
    # ASSUMED: the norm over the whole of q and of k, then the heads
    q = _rms_norm(mm(x, f32("q_proj.weight")), f32("q_norm.weight"), eps)
    k = _rms_norm(mm(x, f32("k_proj.weight")), f32("k_norm.weight"), eps)
    v = mm(x, f32("v_proj.weight")).reshape(b, s, kvh, d)
    q = q.reshape(b, s, kvh, heads // kvh, d)   # ASSUMED: no rotary
    k = k.reshape(b, s, kvh, d)
    j = jnp.arange(s)[None, :]
    att = []
    for lo in range(0, s, QUERY_BLOCK):  # the scores of a block at a time
        qb = q[:, lo:lo + QUERY_BLOCK]
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k) / math.sqrt(d)
        i = lo + jnp.arange(qb.shape[1])[:, None]
        scores = jnp.where(i >= j, scores, -jnp.inf)
        att.append(jnp.einsum("bhgqk,bkhd->bqhgd",
                              jax.nn.softmax(scores, axis=-1), v))
    att = jnp.concatenate(att, 1).reshape(b, s, heads * d)
    return mm(att, f32("o_proj.weight"))


def _delta_rule(q, k, v, alpha, beta, state_dtype=None):
    """The recurrence of ONE sequence, position by position. q, k [s, h,
    dk]; v [s, h, dv]; alpha, beta [s, h]. Returns o [s, h, dv].
    ``state_dtype``: the state is rounded to it after each position (a
    control; None keeps float32)."""
    import jax
    import jax.numpy as jnp

    def step(S, x):
        qt, kt, vt, at, bt = x
        S = at[:, None, None] * S                       # decay first
        d = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[:, :, None] * d[:, None, :]
        if state_dtype is not None:     # a cast pair may be elided
            info = jnp.finfo(state_dtype)
            S = jax.lax.reduce_precision(S, info.nexp, info.nmant)
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, S0, (q, k, v, alpha, beta))[1]


def _linear_attention(w, x, *, cfg, mode):
    """A Gated-DeltaNet layer's mixer, x [b, s, H] float32."""
    import jax
    import jax.numpy as jnp
    state_dtype = jnp.bfloat16 if mode == "bf16_state" else None
    mm = partial(matmul, mode=None if state_dtype else mode)
    f32 = lambda name: w[name].astype(jnp.float32)      # noqa: E731
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    b, s, _ = x.shape
    u = jnp.concatenate([mm(x, f32("q_proj.weight")),
                         mm(x, f32("k_proj.weight")),
                         mm(x, f32("v_proj.weight"))], -1)
    # ASSUMED: no convolution bias; zeros before position 0
    ext = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    taps_w = f32("conv_weight")                         # [C, taps]
    y = sum(ext[:, j:j + s] * taps_w[:, j] for j in range(taps))
    y = jax.nn.silu(y)
    q = y[..., :hk * dk].reshape(b, s, hk, dk)
    k = y[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk)
    v = y[..., 2 * hk * dk:].reshape(b, s, hv, dv)
    norm = lambda a: a / jnp.sqrt(                      # noqa: E731
        jnp.sum(a * a, -1, keepdims=True) + L2_EPS)
    q, k = norm(q) * dk ** -0.5, norm(k)
    if hv != hk:
        q = jnp.repeat(q, hv // hk, axis=2)
        k = jnp.repeat(k, hv // hk, axis=2)
    beta = jax.nn.sigmoid(mm(x, f32("b_proj.weight")))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(f32("A_log")) * jax.nn.softplus(
        mm(x, f32("a_proj.weight")) + f32("dt_bias")))
    o = jax.vmap(partial(_delta_rule, state_dtype=state_dtype))(
        q, k, v, alpha, beta)                           # [b, s, hv, dv]
    o = _rms_norm(o, f32("o_norm.weight"), cfg["rms_norm_eps"])
    o = o * jax.nn.silu(mm(x, f32("g_proj.weight")).reshape(b, s, hv, dv))
    return mm(o.reshape(b, s, hv * dv), f32("o_proj.weight"))


def _layer(w, x, *, cfg, linear, mode):
    """One block: the norm on each sub-layer's OUTPUT (ASSUMED)."""
    import jax.numpy as jnp
    eps = cfg["rms_norm_eps"]
    key = "linear_attn." if linear else "self_attn."
    mixer = {k[len(key):]: v for k, v in w.items() if k.startswith(key)}
    out = (_linear_attention if linear else _full_attention)(
        mixer, x, cfg=cfg, mode=mode)
    x = x + _rms_norm(
        out, w["post_attention_layernorm.weight"].astype(jnp.float32), eps)
    h = _swiglu(x, w["mlp.gate_proj.weight"], w["mlp.up_proj.weight"],
                w["mlp.down_proj.weight"],
                None if mode == "bf16_state" else mode)
    return x + _rms_norm(
        h, w["post_feedforward_layernorm.weight"].astype(jnp.float32), eps)


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
    every matrix product of a projection as the lower precision would;
    the recurrence stays float32. Layer by layer, rows in blocks, the
    head in blocks of vocabulary columns."""
    import jax
    import jax.numpy as jnp
    kinds = {lin: jax.jit(partial(_layer, cfg=config, linear=lin, mode=mode))
             for lin in (False, True)}
    prefix = LAYER + "{}."
    out: List[Dict[str, np.ndarray]] = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(sequences), rows_per_block):
            seqs = list(sequences[lo:lo + rows_per_block])
            L = -(-max(len(s) for s in seqs) // 256) * 256
            ids = np.zeros((rows_per_block, L), np.int32)
            for r, s in enumerate(seqs):
                ids[r, :len(s)] = s
            x = params["model.embed_tokens.weight"][jnp.asarray(ids)] \
                .astype(jnp.float32)
            for i in range(config["num_hidden_layers"]):
                lp = prefix.format(i)
                w = {k[len(lp):]: v for k, v in params.items()
                     if k.startswith(lp)}
                x = kinds[config["layer_types"][i] == "linear_attention"](
                    w, x)
            x = _rms_norm(x, params["model.norm.weight"].astype(jnp.float32),
                          config["rms_norm_eps"])
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
