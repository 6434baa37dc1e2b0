"""Laguna's language model (Laguna-S-2.1's is of it) for the benchmark:
the model handed to the program, its weights, and the plain reference
that decides ``correct``.

``build`` constructs the PROGRAM's model (``paddle_tpu.models.laguna``)
at the sizes of a configuration file, holding ONE expert-parallel rank's
share of each expert layer, and fills it with weights the BENCHMARK
makes from the seed, on the device, in the type they are served in.
``reference_rows`` is the yardstick: the decoder written from the
published ``config.json`` (``model_type`` ``laguna``; keys in backticks)
in float32 ``jax.numpy`` at ``highest`` matmul precision, with no cache
and no kernel; a window layer's band is a MASK. One layer ``l``, on x
[T, hidden]:

- ``h = RMSNorm(x)``, eps ``rms_norm_eps``. ``H_l =
  num_attention_heads_per_layer[l]``. ``q = h Wq`` as [T, H_l, 128];
  ``k = h Wk``, ``v = h Wv`` as [T, 8, 128] (``num_key_value_heads``,
  ``head_dim``; no biases).
- rotary by ``rope_parameters[layer_types[l]]``. ``full_attention``:
  YaRN (``factor``, ``original_max_position_embeddings``, ``beta_fast``,
  ``beta_slow``, ``rope_theta``; the frequencies of transformers'
  ``_compute_yarn_parameters`` at dim = ``partial_rotary_factor`` x 128
  = 64) on the first 64 columns of a head, cos and sin times
  ``attention_factor``, the other 64 pass through.
  ``sliding_attention``: plain rotary, ``rope_theta`` 10000, all 128
  columns.
- ``s_ij = q_i . k_j / sqrt(128)``, causal; a sliding layer keeps ``i -
  j < sliding_window``; query head ``a`` reads kv head ``a // (H_l /
  8)``; no sink. ``g = sigmoid(h Wg)`` [T, H_l]; ``o_a <- g_a o_a``;
  ``x = x + concat_a(o_a) Wo``.
- ``h2 = RMSNorm(x)``. ``mlp_layer_types[l]`` ``dense``: SwiGLU of width
  ``intermediate_size``. ``sparse``: ``p = softmax(h2 Wr)`` over the
  published ``num_experts`` in float32, the ``num_experts_per_tok``
  largest chosen, ``w = p[chosen] / sum p[chosen]``
  (``norm_topk_prob``) times ``moe_routed_scaling_factor``; ``y = sum_e
  w_e SwiGLU_e(h2)`` of width ``moe_intermediate_size``, plus
  ``SwiGLU_shared(h2)`` of ``shared_expert_intermediate_size``, ungated.
  ``x = x + y + shared``.
- after the last layer ``RMSNorm`` and an untied head.

It imports nothing of ``paddle_tpu`` and reads only the weights made
here, by name, upcasting one matrix or one expert at a time so that it
fits beside a serving engine; queries in blocks (a full layer's block
scores the whole sequence under the causal mask, a window layer's only
the ``sliding_window`` keys behind it and its own), so that sequences of
6,912 tokens fit.

The share (model-configs guide, section 4): the router keeps its
published width; of each expert layer the experts ``first_expert ..
first_expert + num_experts - 1`` are held; what the absent experts would
add is left out, here as in the program, and that partial result goes on
to the next layer. The shared expert is computed here as on every chip.

What the config does not say, each also in the configuration file's
``assumed`` (ASSUMED where it is made; correcting one is one config
value and one line here): the router's score is a softmax (the key
family is Qwen2-MoE's); ``gating`` ``per-head`` is the headwise gate of
gated attention (arXiv:2505.06708): one sigmoid scalar a head on the
attention's result, read from the layer's normed input; the shared
expert has no gate (no key for one); no q/k norm (no key for one); the
rotary pairs are half-split and ``attention_factor`` multiplies the
rotary columns' cos and sin only (transformers applies it there). The
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

QUERY_BLOCK = 256       # queries whose scores are alive at once
FULL, SLIDING = "full_attention", "sliding_attention"


def program_config(config: dict):
    """The program's own config object at this file's sizes."""
    import jax.numpy as jnp
    from paddle_tpu.models.laguna import LagunaConfig
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["dtype"]]
    return LagunaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"],
        layer_types=tuple(config["layer_types"]),
        num_attention_heads_per_layer=tuple(
            config["num_attention_heads_per_layer"]),
        mlp_layer_types=tuple(config["mlp_layer_types"]),
        rope_parameters=config["rope_parameters"],
        gating=config["gating"],
        num_experts=config["num_experts_published"],
        first_expert=config["first_expert"],
        experts_held=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=(
            config["shared_expert_intermediate_size"]),
        norm_topk_prob=config["norm_topk_prob"],
        moe_routed_scaling_factor=config["moe_routed_scaling_factor"],
        moe_router_logit_softcapping=(
            config["moe_router_logit_softcapping"]),
        scoring=config["router_score"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"],
        attention_bias=config["attention_bias"],
        tie_word_embeddings=config["tie_word_embeddings"], dtype=dtype)


def _no_selection_bias(weights):
    """``weights`` with every ``expert_bias`` at zero: the program's
    expert layer has the slot, this router (ASSUMED a softmax, Qwen2-
    MoE's) has no such term, and deepseek_v3's generator draws one."""
    return type(weights)(
        (name, w * 0 if name.endswith(".expert_bias") else w)
        for name, w in weights.items())


def make_weights(spec: Dict, seed: int, device) -> Dict:
    """Every array of ``spec`` drawn from ``seed`` on ``device``
    (deepseek_v3's generator: projections, gate projections, experts and
    router 0.02, norm scales 1 +- 0.1), no selection bias."""
    return _no_selection_bias(deepseek_v3.make_weights(spec, seed, device))


def fill_weights(params: Dict, seed: int):
    """New values for every array of ``params`` from ``seed``, in place
    of the old (deepseek_v3's, which keeps the mapping's type, order and
    placement: jit's cache keys on them)."""
    return _no_selection_bias(deepseek_v3.fill_weights(params, seed))


def _program_model(cfg):
    """The program's model object WITHOUT its own weight draw, and the
    (shape, dtype) of each of its parameters (as deepseek_v3.py)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.laguna import LagunaForCausalLM
    box = []

    def make():
        box.append(LagunaForCausalLM(cfg))
        return dict(box[0].functional()[1])

    shapes = jax.eval_shape(make)
    pt.seed(0)          # the trace left a tracer in the global key
    return box[0], {k: (v.shape, v.dtype) for k, v in shapes.items()}


def build(config: dict, seed: int, device):
    """The program's ``LagunaForCausalLM`` on ``device`` holding the
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
def rotary_of(config: dict, kind: str):
    """(rotary columns, inverse frequencies [columns / 2], factor on cos
    and sin) of a layer of ``kind``, from ``rope_parameters[kind]``;
    YaRN as transformers' ``_compute_yarn_parameters`` computes it."""
    rp = config["rope_parameters"][kind]
    dim = int(config["head_dim"] * rp.get("partial_rotary_factor", 1.0)) \
        // 2 * 2
    base = float(rp["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    if rp.get("rope_type", "default") != "yarn":
        return dim, extra.astype(np.float32), 1.0
    factor, orig = rp["factor"], rp["original_max_position_embeddings"]

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv = extra / factor * ramp + extra * (1 - ramp)
    amp = rp.get("attention_factor")
    if amp is None:
        amp = 1.0 if factor <= 1 else 0.1 * math.log(factor) + 1.0
    return dim, inv.astype(np.float32), float(amp)


def _rope(x, positions, rd, inv, amp):
    """x [b, s, h, d]: the first ``rd`` columns turn, column i with
    column i + rd/2 by pos x inv[i] (ASSUMED: half-split pairs), cos and
    sin times ``amp`` (ASSUMED: on these columns only); the rest pass
    through."""
    import jax.numpy as jnp
    ang = positions.astype(jnp.float32)[..., None] * inv    # [b, s, rd/2]
    cos, sin = (jnp.cos(ang) * amp)[:, :, None], \
        (jnp.sin(ang) * amp)[:, :, None]
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rd:]], -1)


def _attention(w, x, positions, *, cfg, heads, kind, mode):
    """x + attention(norm(x)) of a layer of ``kind`` with ``heads``
    query heads, and the normed result for the FFN. x [b, s, H] float32,
    s a multiple of ``QUERY_BLOCK``; a block of queries at a time: a
    full layer's scores every key under the causal mask, a window
    layer's the ``sliding_window`` keys behind the block and the block's
    own under the band's mask."""
    import jax
    import jax.numpy as jnp
    mm = partial(matmul, mode=mode)
    f32 = lambda name: w[name].astype(jnp.float32)      # noqa: E731
    kvh, d, eps = cfg["num_key_value_heads"], cfg["head_dim"], \
        cfg["rms_norm_eps"]
    g = heads // kvh
    window = cfg["sliding_window"] if kind == SLIDING else None
    rd, inv, amp = rotary_of(cfg, kind)
    b, s, _ = x.shape
    h = _rms_norm(x, f32("input_layernorm.weight"), eps)
    q = mm(h, f32("self_attn.q_proj.weight")).reshape(b, s, heads, d)
    k = mm(h, f32("self_attn.k_proj.weight")).reshape(b, s, kvh, d)
    v = mm(h, f32("self_attn.v_proj.weight")).reshape(b, s, kvh, d)
    q, k = _rope(q, positions, rd, inv, amp), _rope(k, positions, rd, inv,
                                                    amp)
    QB = min(QUERY_BLOCK, s)
    qb = q.reshape(b, s // QB, QB, kvh, g, d).swapaxes(0, 1)
    # keys a block may see: all of them, or the band behind it (front
    # padded so that every block's slice has one shape)
    back = 0 if window is None else -(-(window - 1) // QB) * QB
    span = s if window is None else back + QB
    if window is not None:
        k = jnp.pad(k, ((0, 0), (back, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (back, 0), (0, 0), (0, 0)))

    def block(carry, xs):
        n, qn = xs
        lo = n * QB
        at = 0 if window is None else lo    # in the padded keys
        kk = jax.lax.dynamic_slice_in_dim(k, at, span, 1)
        vv = jax.lax.dynamic_slice_in_dim(v, at, span, 1)
        i = lo + jnp.arange(QB)[:, None]
        j = (0 if window is None else lo - back) + jnp.arange(span)[None]
        keep = (i >= j) & (j >= 0)
        if window is not None:
            keep &= i - j < window
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qn, kk) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return carry, jnp.einsum("bhgqk,bkhd->bqhgd", probs, vv)

    _, att = jax.lax.scan(block, 0, (jnp.arange(s // QB), qb))
    att = att.swapaxes(0, 1).reshape(b, s, heads, d)
    if cfg["gating"]:
        # ASSUMED: one sigmoid scalar a head, from the normed input
        att = att * jax.nn.sigmoid(mm(h, f32("self_attn.g_proj.weight"))
                                   )[..., None]
    x = x + mm(att.reshape(b, s, heads * d), f32("self_attn.o_proj.weight"))
    return x, _rms_norm(x, f32("post_attention_layernorm.weight"), eps)


def _route(h, router, *, cfg):
    """The weight of every token for every one of the published experts,
    [b, s, E] float32, 0 where the token did not choose the expert
    (ASSUMED: softmax scores): the ``num_experts_per_tok`` largest
    probabilities, normalised over the chosen (``norm_topk_prob``),
    times ``moe_routed_scaling_factor``. Float32 in the control too."""
    import jax
    import jax.numpy as jnp
    if cfg["router_score"] != "softmax" \
            or cfg["moe_router_logit_softcapping"]:
        raise ValueError("this reference routes by an uncapped softmax")
    E, k = cfg["num_experts_published"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ router.astype(jnp.float32), axis=-1)
    chosen = jax.lax.top_k(probs, k)[1]
    picked = jnp.any(jnp.arange(E)[:, None] == chosen[..., None, :], -1)
    gates = jnp.where(picked, probs, 0.0)
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    return gates * cfg["moe_routed_scaling_factor"]


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
    eps = config["rms_norm_eps"]
    first = config["first_expert"]
    kinds = {}
    for kind, heads in zip(config["layer_types"],
                           config["num_attention_heads_per_layer"]):
        if (kind, heads) not in kinds:
            kinds[kind, heads] = jax.jit(partial(
                _attention, cfg=config, heads=heads, kind=kind, mode=mode))
    swiglu = jax.jit(partial(_swiglu, mode=mode))
    route = jax.jit(partial(_route, cfg=config))
    prefix = LAYER + "{}."
    out: List[Dict[str, np.ndarray]] = []
    # every block of rows at the longest sequence's length: at one row a
    # block (verify._rows_per_block at these lengths) a length of its
    # own a block would be a compilation of every layer kind a sequence
    L = -(-max(len(s) for s in sequences) // 256) * 256
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(sequences), rows_per_block):
            seqs = list(sequences[lo:lo + rows_per_block])
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
                x, h = kinds[config["layer_types"][i],
                             config["num_attention_heads_per_layer"][i]](
                    w, x, pos)
                mlp = lambda name: params[lp + "mlp." + name]  # noqa: E731
                if config["mlp_layer_types"][i] == "dense":
                    x = x + swiglu(h, mlp("gate_proj.weight"),
                                   mlp("up_proj.weight"),
                                   mlp("down_proj.weight"))
                    continue
                gates = route(h, mlp("gate"))
                # ASSUMED: the shared expert is always on and ungated
                x = x + swiglu(h, mlp("shared_gate_proj"),
                               mlp("shared_up_proj"),
                               mlp("shared_down_proj"))
                # the share: the held experts only, one at a time
                for e in range(config["num_experts"]):
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
