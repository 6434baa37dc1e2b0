"""MiMo-V2's language model (MiMo-V2.5's is of it) for the benchmark:
the model handed to the program, its weights, and the plain reference
that decides ``correct``.

``build`` constructs the PROGRAM's model (``paddle_tpu.models.
mimo_v2``) at the sizes of a configuration file, holding ONE
expert-parallel rank's share of each expert layer, and fills it with
weights the BENCHMARK makes from the seed, on the device, in the type
they are served in. ``reference_rows`` is the yardstick: the decoder
written from the published ``config.json`` (``model_type`` ``mimo_v2``;
keys in backticks) in float32 ``jax.numpy`` at ``highest`` matmul
precision, with no cache and no kernel; a window layer's band is a MASK
over the whole sequence. One layer ``l``, on x [T, hidden]:

- ``h = RMSNorm(x)``, eps ``layernorm_epsilon``. ``q = h Wq`` as [T,
  heads, 192]; ``k = h Wk`` as [T, kvh, 192]; ``v = h Wv`` as [T, kvh,
  128] (``head_dim``, ``v_head_dim``; no biases). ``hybrid_layer_pattern
  [l]`` 0 is a FULL layer: ``kvh`` = ``num_key_value_heads``, rope base
  ``rope_theta``; 1 is a WINDOW layer: ``kvh`` =
  ``swa_num_key_value_heads``, base ``swa_rope_theta``, window
  ``sliding_window``.
- rotary on the first 64 columns of each 192-wide head
  (``partial_rotary_factor`` 0.334 x 192 = 64.1 -> 64), the other 128
  pass through; half-split pairs, no scaling.
- ``s_ij = q_i . k_j / sqrt(192)``, causal; a window layer keeps ``i - j
  < sliding_window`` and has one learned scalar ``sink_h`` a query head
  (``add_swa_attention_sink_bias``) that joins the softmax's denominator
  and carries no value: ``p_ij = exp(s_ij - m) / (exp(sink_h - m) +
  sum_j exp(s_ij - m))``, ``m`` the maximum over the row's scores and
  the sink. ``o_i = sum_j p_ij (attention_value_scale v_j)``; ``x = x +
  concat_h(o) Wo``.
- ``h2 = RMSNorm(x)``. ``moe_layer_freq[l]`` 0: SwiGLU of width
  ``intermediate_size``. Else ``score = sigmoid(h2 Wr)`` over the
  published ``n_routed_experts`` (the router in float32), the
  ``num_experts_per_tok`` largest of ``score +
  e_score_correction_bias`` chosen (``n_group`` 1: a plain top-k),
  ``gate = score[chosen] / sum score[chosen]`` (``norm_topk_prob``),
  times ``routed_scaling_factor`` (null: 1); ``y = sum_e gate_e
  SwiGLU_e(h2)``, width ``moe_intermediate_size``; no shared expert.
  ``x = x + y``.
- after the last layer ``RMSNorm`` and an untied head.

It imports nothing of ``paddle_tpu`` and reads only the weights made
here, by name, upcasting one matrix or one expert at a time so that it
fits beside a serving engine; rows in blocks, so that sequences of
1,920 tokens fit.

The share (model-configs guide, section 4): the router keeps its
published width; of each expert layer the experts ``first_expert ..
first_expert + n_routed_experts - 1`` are held; what the absent experts
would add is left out, here as in the program, and that partial result
goes on to the next layer.

What the config does not say, each also in the configuration file's
``assumed`` (ASSUMED where it is made): the rotary pairs are half-split
(the family's convention); ``attention_value_scale`` multiplies the
values; no q/k norm (the config has no key for one);
``attention_chunk_size`` equals the window and is read as no second
mechanism; the vision tower, the audio encoder and the multi-token-
prediction layers are not built (text traffic, next-token logits). The
W8A8 control keeps the router in float32, as deepseek_v3.py's does and
for its reason.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmarks.models import deepseek_v3
from benchmarks.models.deepseek_v3 import LAYER, _head, _swiglu
from benchmarks.models.qwen2 import _rms_norm, matmul

# The selection bias (``e_score_correction_bias``): seeded, at
# deepseek_v3.py's deviation and for its reason: this router is that
# one (sigmoid scores of logits of deviation 0.02 x sqrt(4096) = 1.28
# over 256 columns, 8 a token, here in ONE group), its 8th and 9th
# scores 0.005-0.01 apart, so 0.01 changes the choice without making it
# alone, and the held experts' load stays near uniform choice (86.5% of
# them hit a layer and tick at 64 rows).
BIAS_STD = deepseek_v3.BIAS_STD
SINK_STD = 1.0
QUERY_BLOCK = 256       # queries whose scores are alive at once


def program_config(config: dict):
    """The program's own config object at this file's sizes."""
    import jax.numpy as jnp
    from paddle_tpu.models.mimo_v2 import MiMoV2Config
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["dtype"]]
    scaling = config["routed_scaling_factor"]
    return MiMoV2Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        head_dim=config["head_dim"], v_head_dim=config["v_head_dim"],
        num_key_value_heads=config["num_key_value_heads"],
        swa_num_key_value_heads=config["swa_num_key_value_heads"],
        sliding_window=config["sliding_window"],
        rope_theta=config["rope_theta"],
        swa_rope_theta=config["swa_rope_theta"],
        partial_rotary_factor=config["partial_rotary_factor"],
        attention_value_scale=config["attention_value_scale"],
        add_swa_attention_sink_bias=config["add_swa_attention_sink_bias"],
        add_full_attention_sink_bias=config["add_full_attention_sink_bias"],
        hybrid_layer_pattern=tuple(config["hybrid_layer_pattern"]),
        moe_layer_freq=tuple(config["moe_layer_freq"]),
        num_experts=config["n_routed_experts_published"],
        first_expert=config["first_expert"],
        experts_held=config["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        scoring=config["scoring_func"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=1.0 if scaling is None else scaling,
        n_group=config["n_group"], topk_group=config["topk_group"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["layernorm_epsilon"],
        attention_bias=config["attention_bias"],
        tie_word_embeddings=config["tie_word_embeddings"], dtype=dtype)


def _own_sinks(weights):
    """``weights`` with every sink at THIS family's deviation:
    deepseek_v3's generator, which makes them, draws a vector that is
    neither a norm scale nor a selection bias at the projections'."""
    k = SINK_STD / deepseek_v3.WEIGHT_STD
    return type(weights)(
        (name, (w * k).astype(w.dtype) if name.endswith(".sink") else w)
        for name, w in weights.items())


def make_weights(spec: Dict, seed: int, device) -> Dict:
    """Every array of ``spec`` drawn from ``seed`` on ``device``
    (deepseek_v3's generator: projections and experts 0.02, norm scales
    1 +- 0.1, selection biases ``BIAS_STD``), the sinks at
    ``SINK_STD``."""
    return _own_sinks(deepseek_v3.make_weights(spec, seed, device))


def fill_weights(params: Dict, seed: int):
    """New values for every array of ``params`` from ``seed``, in place
    of the old (deepseek_v3's, which keeps the mapping's type, order and
    placement: jit's cache keys on them)."""
    return _own_sinks(deepseek_v3.fill_weights(params, seed))


def _program_model(cfg):
    """The program's model object WITHOUT its own weight draw, and the
    (shape, dtype) of each of its parameters (as deepseek_v3.py)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.mimo_v2 import MiMoV2ForCausalLM
    box = []

    def make():
        box.append(MiMoV2ForCausalLM(cfg))
        return dict(box[0].functional()[1])

    shapes = jax.eval_shape(make)
    pt.seed(0)          # the trace left a tracer in the global key
    return box[0], {k: (v.shape, v.dtype) for k, v in shapes.items()}


def build(config: dict, seed: int, device):
    """The program's ``MiMoV2ForCausalLM`` on ``device`` holding the
    benchmark's seeded weights, sinks and selection bias included."""
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
def rotary_dim(config: dict) -> int:
    return int(config["head_dim"] * config["partial_rotary_factor"]) // 2 * 2


def _rope(x, positions, theta, rd):
    """x [b, s, h, d]: the first ``rd`` columns turn, column i with
    column i + rd/2 by pos / theta^(2i/rd) (ASSUMED: half-split pairs);
    the rest pass through."""
    import jax.numpy as jnp
    inv = 1.0 / theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = positions.astype(jnp.float32)[..., None] * inv    # [b, s, rd/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rd:]], -1)


def _attention(w, x, positions, *, cfg, window, mode):
    """x + attention(norm(x)) of a full (``window`` None) or a window
    layer, and the normed result for the FFN. x [b, s, H] float32; the
    band is a mask over the whole sequence."""
    import jax
    import jax.numpy as jnp
    mm = partial(matmul, mode=mode)
    f32 = lambda name: w[name].astype(jnp.float32)      # noqa: E731
    heads, eps = cfg["num_attention_heads"], cfg["layernorm_epsilon"]
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    kvh = cfg["num_key_value_heads"] if window is None \
        else cfg["swa_num_key_value_heads"]
    theta = float(cfg["rope_theta"] if window is None
                  else cfg["swa_rope_theta"])
    rd = rotary_dim(cfg)
    b, s, _ = x.shape
    h = _rms_norm(x, f32("input_layernorm.weight"), eps)
    q = mm(h, f32("self_attn.q_proj.weight")).reshape(b, s, heads, dk)
    k = mm(h, f32("self_attn.k_proj.weight")).reshape(b, s, kvh, dk)
    v = mm(h, f32("self_attn.v_proj.weight")).reshape(b, s, kvh, dv)
    q, k = _rope(q, positions, theta, rd), _rope(k, positions, theta, rd)
    q = q.reshape(b, s, kvh, heads // kvh, dk)
    sink = f32("self_attn.sink").reshape(1, kvh, heads // kvh, 1, 1) \
        if "self_attn.sink" in w else None
    # ASSUMED: attention_value_scale multiplies the values
    v = v * cfg["attention_value_scale"]
    j = jnp.arange(s)[None, :]
    att = []
    for lo in range(0, s, QUERY_BLOCK):  # the scores of a block at a time
        qb = q[:, lo:lo + QUERY_BLOCK]
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k) / math.sqrt(dk)
        i = lo + jnp.arange(qb.shape[1])[:, None]
        keep = i >= j
        if window is not None:
            keep &= i - j < window
        scores = jnp.where(keep, scores, -jnp.inf)
        if sink is None:
            probs = jax.nn.softmax(scores, axis=-1)
        else:                           # joins the denominator alone
            m = jnp.maximum(jnp.max(scores, -1, keepdims=True), sink)
            e = jnp.exp(scores - m)
            probs = e / (jnp.exp(sink - m) + jnp.sum(e, -1, keepdims=True))
        att.append(jnp.einsum("bhgqk,bkhd->bqhgd", probs, v))
    att = jnp.concatenate(att, 1)
    x = x + mm(att.reshape(b, s, heads * dv), f32("self_attn.o_proj.weight"))
    return x, _rms_norm(x, f32("post_attention_layernorm.weight"), eps)


def _route(h, router, bias, *, cfg):
    """The gate of every token for every one of the published experts,
    [b, s, E] float32, 0 where the token did not choose the expert:
    sigmoid scores; the ``num_experts_per_tok`` largest of score + bias
    (one group: a plain top-k); gates the chosen experts' SCORES (no
    bias), normalised over the chosen, times ``routed_scaling_factor``.
    Float32 in the control too."""
    import jax
    import jax.numpy as jnp
    E, k = cfg["n_routed_experts_published"], cfg["num_experts_per_tok"]
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("this reference routes in one group")
    scores = jax.nn.sigmoid(h @ router.astype(jnp.float32))
    chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), k)[1]
    picked = jnp.any(jnp.arange(E)[:, None] == chosen[..., None, :], -1)
    gates = jnp.where(picked, scores, 0.0)
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    scaling = cfg["routed_scaling_factor"]
    return gates * (1.0 if scaling is None else scaling)


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
    every matrix product but the router's as the lower precision would.
    Layer by layer, rows in blocks, experts one at a time, the head in
    blocks of vocabulary columns."""
    import jax
    import jax.numpy as jnp
    eps = config["layernorm_epsilon"]
    first = config["first_expert"]
    kinds = {win: jax.jit(partial(_attention, cfg=config, window=win,
                                  mode=mode))
             for win in (None, config["sliding_window"])}
    swiglu = jax.jit(partial(_swiglu, mode=mode))
    route = jax.jit(partial(_route, cfg=config))
    prefix = LAYER + "{}."
    out: List[Dict[str, np.ndarray]] = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(sequences), rows_per_block):
            seqs = list(sequences[lo:lo + rows_per_block])
            L = -(-max(len(s) for s in seqs) // 256) * 256
            ids = np.zeros((rows_per_block, L), np.int32)
            for r, s in enumerate(seqs):
                ids[r, :len(s)] = s
            pos = jnp.broadcast_to(jnp.arange(L)[None], ids.shape)
            x = params["model.embed_tokens.weight"][jnp.asarray(ids)] \
                .astype(jnp.float32)
            for i in range(config["num_hidden_layers"]):
                lp = prefix.format(i)
                w = {k[len(lp):]: v for k, v in params.items()
                     if k.startswith(lp) and ".mlp." not in k}
                win = config["sliding_window"] \
                    if config["hybrid_layer_pattern"][i] else None
                x, h = kinds[win](w, x, pos)
                mlp = lambda name: params[lp + "mlp." + name]  # noqa: E731
                if not config["moe_layer_freq"][i]:
                    x = x + swiglu(h, mlp("gate_proj.weight"),
                                   mlp("up_proj.weight"),
                                   mlp("down_proj.weight"))
                    continue
                gates = route(h, mlp("gate"), mlp("expert_bias"))
                # the share: the held experts only, one at a time
                for e in range(config["n_routed_experts"]):
                    x = x + gates[..., first + e, None] * swiglu(
                        h, mlp("w_gate")[e], mlp("w_up")[e],
                        mlp("w_down")[e])
            x = _rms_norm(x, params["model.norm.weight"].astype(jnp.float32),
                          eps)
            # the hidden state at position p predicts the token at p + 1
            ri, pi, tk, owner = [], [], [], []
            for r, s in enumerate(seqs):
                n = len(s) - starts[lo + r]
                ri += [r] * n
                pi += list(range(starts[lo + r] - 1, len(s) - 1))
                tk += list(read[lo + r])[:n]
                owner += [r] * n
            out += _head(params, config, x, (ri, pi, tk, owner), len(seqs),
                         top, mode, vocab_block)
    return out
