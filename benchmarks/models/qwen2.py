"""The Qwen2 family for the benchmark: the model handed to the program,
its weights, and the plain reference that decides ``correct``.

``build`` constructs the PROGRAM's model (``paddle_tpu.models.qwen2``) at
the sizes of a configuration file and fills it with weights the
BENCHMARK makes from the seed, on the device, in the type they are
served in. ``reference_rows`` is the yardstick: the Qwen2
decoder written from its published description in float32 ``jax.numpy``
at ``highest`` matmul precision — RMSNorm, biased q/k/v, rotary
embedding in the half-rotation convention, grouped-query causal softmax
attention, SwiGLU, a tied or untied head. It imports nothing of
``paddle_tpu`` and reads only the weights made here, by name, upcasting
one layer at a time so that it fits beside a serving engine.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Sequence

import numpy as np

# Standard deviations of the seeded weights. Projections and embeddings
# at the family's initializer_range; q/k/v biases far above it, as the
# trained family's are, so that a dropped bias cannot hide; norm scales
# spread around 1 for the same reason.
WEIGHT_STD = 0.02
BIAS_STD = 0.25
NORM_STD = 0.1


def program_config(config: dict):
    """The program's own config object at this file's sizes."""
    import jax.numpy as jnp
    from paddle_tpu.models.qwen2 import Qwen2Config
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["dtype"]]
    return Qwen2Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        tie_word_embeddings=config["tie_word_embeddings"],
        attention_bias=True, dtype=dtype)


def _std(name: str) -> Optional[float]:
    """None for a norm scale (mean 1), else the normal's deviation."""
    if name.endswith("norm.weight") or "layernorm" in name:
        return None
    return BIAS_STD if name.endswith(".bias") else WEIGHT_STD


def seed_words(seed: int) -> np.ndarray:
    """Key data for jax's ``rbg`` generator from a seed of any size."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF,
                     0x9E3779B9, 0x85EBCA6B], np.uint32)


def _draw(spec: Dict):
    """A jitted program that draws every array of ``spec`` (name ->
    (shape, dtype)) from the key data it is given."""
    import jax

    def draw(words):
        key = jax.random.wrap_key_data(words, impl="rbg")
        new = {}
        for i, (name, (shape, dtype)) in enumerate(spec.items()):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
            std = _std(name)
            new[name] = (1.0 + NORM_STD * z if std is None
                         else std * z).astype(dtype)
        return new

    return jax.jit(draw)


LAYER = "model.layers."


def make_weights(spec: Dict, seed: int, device) -> Dict:
    """Every array of ``spec`` (name -> (shape, dtype)) drawn from
    ``seed`` on ``device``, in the type it is served in. One jitted
    program draws a decoder layer and is called once per layer with
    that layer's key; a second draws what lies outside the layers. (One
    program for the whole model compiled for two minutes: it unrolls
    some two hundred draws.)"""
    import jax
    import jax.numpy as jnp
    layers: Dict[int, Dict] = {}
    rest = {}
    for name, sd in spec.items():
        if name.startswith(LAYER):
            i, _, leaf = name[len(LAYER):].partition(".")
            layers.setdefault(int(i), {})[leaf] = sd
        else:
            rest[name] = sd
    words = seed_words(seed)
    out = {}
    with jax.default_device(device):
        draw_rest = _draw(rest)
        out.update(draw_rest(jnp.asarray(words)))
        draw_layer = _draw(layers[0]) if layers else None
        for i in sorted(layers):
            if layers[i] != layers[0]:
                raise ValueError(f"layer {i} differs in shape from layer 0")
            w = words.copy()
            w[2] += i + 1
            for leaf, v in draw_layer(jnp.asarray(w)).items():
                out[f"{LAYER}{i}.{leaf}"] = v
    return {name: out[name] for name in spec}


def fill_weights(params: Dict, seed: int):
    """New values for every array of ``params`` (names, shapes and types
    kept) from ``seed``. The old arrays are deleted first: a chip cannot
    hold the model twice."""
    spec = {k: (v.shape, v.dtype) for k, v in params.items()}
    device = next(iter(next(iter(params.values())).devices()))
    for v in params.values():
        v.delete()
    return make_weights(spec, seed, device)


def _program_model(cfg):
    """The program's model object WITHOUT its own weight draw, and the
    (shape, dtype) of each of its parameters. The constructor draws
    every weight leaf by leaf (about 20 s at the 7B widths, every run),
    and only the program can change that; so it runs under
    ``jax.eval_shape``, which traces it and draws nothing, and the
    object keeps placeholders until ``set_state_dict`` replaces every
    one. A constructor that cannot be traced fails the run."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.qwen2 import Qwen2ForCausalLM
    box = []

    def make():
        box.append(Qwen2ForCausalLM(cfg))
        return dict(box[0].functional()[1])

    shapes = jax.eval_shape(make)
    pt.seed(0)          # the trace left a tracer in the global key
    return box[0], {k: (v.shape, v.dtype) for k, v in shapes.items()}


def build(config: dict, seed: int, device):
    """The program's ``Qwen2ForCausalLM`` on ``device`` holding the
    benchmark's seeded weights."""
    import jax
    with jax.default_device(device):
        model, spec = _program_model(program_config(config))
        model.set_state_dict(make_weights(spec, seed, device), strict=False)
    left = [k for k, v in model.functional()[1].items()
            if not isinstance(v, jax.Array) or isinstance(v, jax.core.Tracer)]
    if left:
        raise RuntimeError(f"parameters without seeded weights: {left[:3]}")
    return model


# ---------------------------------------------------------------- reference
def _rms_norm(x, w, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _rope(x, positions, theta):
    """x [b, s, h, d]; pairs (i, i + d/2) rotate by pos * theta^(-2i/d)."""
    import jax.numpy as jnp
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv     # [b, s, d/2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fake_int8(a, axis):
    """``a`` rounded to 255 levels with one scale along ``axis``."""
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(a / scale), -127, 127) * scale


def matmul(x, w, mode: Optional[str]):
    """``x @ w`` in the reference's precision, or as the lower precision
    would compute it. int8: the W8A8 recipe a v5e's int8 unit invites —
    weights rounded to int8 with a scale per output channel, activations
    to int8 with a scale per token, products summed exactly (as an int32
    accumulator would) and scaled back."""
    if mode is None:
        return x @ w
    if mode != "int8":
        raise ValueError(f"unknown lower precision {mode!r}")
    return _fake_int8(x, -1) @ _fake_int8(w, 0)


def _layer(w, x, positions, *, heads, kv_heads, eps, theta, mode):
    """One decoder layer, x [b, s, H] float32, full causal attention."""
    import jax
    import jax.numpy as jnp
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    mm = partial(matmul, mode=mode)
    b, s, H = x.shape
    d = H // heads
    g = heads // kv_heads
    h = _rms_norm(x, w["input_layernorm.weight"], eps)
    q = (mm(h, w["self_attn.q_proj.weight"]) + w["self_attn.q_proj.bias"]
         ).reshape(b, s, heads, d)
    k = (mm(h, w["self_attn.k_proj.weight"]) + w["self_attn.k_proj.bias"]
         ).reshape(b, s, kv_heads, d)
    v = (mm(h, w["self_attn.v_proj.weight"]) + w["self_attn.v_proj.bias"]
         ).reshape(b, s, kv_heads, d)
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    q = q.reshape(b, s, kv_heads, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / math.sqrt(d)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(b, s, H)
    x = x + mm(att, w["self_attn.o_proj.weight"])
    h = _rms_norm(x, w["post_attention_layernorm.weight"], eps)
    gate = mm(h, w["mlp.gate_proj.weight"])
    up = mm(h, w["mlp.up_proj.weight"])
    return x + mm(jax.nn.silu(gate) * up, w["mlp.down_proj.weight"])


def _head_block(x, w, read, base, valid, carry, *, mode, transpose):
    """Fold one block of vocabulary columns (the first ``valid`` of them
    real, the rest padding) into the running best logit, best token, sum
    of exponentials, the logit of ``read`` and the ``K`` largest logits
    (``K`` is the carry's last width; 0 keeps none)."""
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    if transpose:                       # tied head: rows of the embedding
        w = w.T
    logits = matmul(x, w, mode)                             # [n, vb]
    logits = jnp.where(jnp.arange(logits.shape[-1]) < valid, logits,
                       -jnp.inf)
    import jax
    best, tok, sumexp, at, top = carry
    if top.shape[-1]:
        top = jax.lax.top_k(jnp.concatenate([top, logits], -1),
                            top.shape[-1])[0]
    m = jnp.max(logits, -1)
    new_best = jnp.maximum(best, m)
    sumexp = sumexp * jnp.exp(best - new_best) + jnp.sum(
        jnp.exp(logits - new_best[:, None]), -1)
    tok = jnp.where(m > best, base + jnp.argmax(logits, -1), tok)
    idx = read - base
    inside = (idx >= 0) & (idx < logits.shape[-1])
    got = jnp.take_along_axis(
        logits, jnp.clip(idx, 0, logits.shape[-1] - 1)[:, None], -1)[:, 0]
    return new_best, tok, sumexp, jnp.where(inside, got, at), top


def reference_rows(params: Dict, config: dict,
                   sequences: Sequence[Sequence[int]],
                   starts: Sequence[int], read: Sequence[Sequence[int]],
                   mode: Optional[str] = None, rows_per_block: int = 4,
                   vocab_block: int = 16384,
                   top: int = 0) -> List[Dict[str, np.ndarray]]:
    """Teacher-force each of ``sequences`` (a prompt and the tokens
    served after it) through the plain decoder, once, and read the
    logits that predict its positions ``starts[i]:``. For sequence i and
    each such position j, with ``read[i][j - starts[i]]`` the token to
    look up there, returns ``best`` (the largest logit), ``best_token``,
    ``lse`` (log of the sum of exponentials), ``at`` (the logit of the
    token looked up) and, with ``top`` > 0, ``top``: the ``top`` largest
    logits in falling order. ``mode`` computes the whole pass with weights held
    in a lower precision: the control. Layer by layer, rows in blocks,
    the head in blocks of vocabulary columns: never more than one
    float32 layer and one block of logits alive."""
    import jax
    import jax.numpy as jnp
    heads, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    tied = config["tie_word_embeddings"]
    embed = params["model.embed_tokens.weight"]
    head = embed if tied else params["lm_head.weight"]
    V = config["vocab_size"]
    layer = jax.jit(partial(_layer, heads=heads, kv_heads=kvh, eps=eps,
                            theta=theta, mode=mode))
    head_block = jax.jit(partial(_head_block, mode=mode, transpose=tied))
    prefix = "model.layers.{}."
    names = [k[len(prefix.format(0)):] for k in params
             if k.startswith(prefix.format(0))]
    out: List[Dict[str, np.ndarray]] = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(sequences), rows_per_block):
            seqs = list(sequences[lo:lo + rows_per_block])
            L = -(-max(len(s) for s in seqs) // 256) * 256
            ids = np.zeros((rows_per_block, L), np.int32)
            for r, s in enumerate(seqs):
                ids[r, :len(s)] = s
            pos = jnp.broadcast_to(jnp.arange(L)[None], ids.shape)
            x = embed[jnp.asarray(ids)].astype(jnp.float32)
            for i in range(config["num_hidden_layers"]):
                w = {n: params[prefix.format(i) + n] for n in names}
                x = layer(w, x, pos)
            x = _rms_norm(x, params["model.norm.weight"].astype(jnp.float32),
                          eps)
            # the hidden state at position p predicts the token at p + 1
            rows, toks, owner = [], [], []
            for r, s in enumerate(seqs):
                n = len(s) - starts[lo + r]
                rows += [(r, p) for p in range(starts[lo + r] - 1,
                                               len(s) - 1)]
                toks += list(read[lo + r])[:n]
                owner += [r] * n
            n_rows = len(rows)
            pad = -(-n_rows // 256) * 256
            ri = np.zeros((pad,), np.int32)
            pi = np.zeros((pad,), np.int32)
            tk = np.zeros((pad,), np.int32)
            ri[:n_rows] = [a for a, _ in rows]
            pi[:n_rows] = [b for _, b in rows]
            tk[:n_rows] = toks
            h = x[jnp.asarray(ri), jnp.asarray(pi)]         # [pad, H]
            tkd = jnp.asarray(tk)
            carry = (jnp.full((pad,), -jnp.inf, jnp.float32),
                     jnp.zeros((pad,), jnp.int32),
                     jnp.zeros((pad,), jnp.float32),
                     jnp.full((pad,), -jnp.inf, jnp.float32),
                     jnp.full((pad, top), -jnp.inf, jnp.float32))
            for base in range(0, V, vocab_block):
                hi = min(base + vocab_block, V)
                wb = head[base:hi] if tied else head[:, base:hi]
                if hi - base < vocab_block:     # one shape for the tail
                    padw = vocab_block - (hi - base)
                    wb = jnp.pad(wb, ((0, padw), (0, 0)) if tied
                                 else ((0, 0), (0, padw)))
                carry = head_block(h, wb, tkd, jnp.int32(base),
                                   jnp.int32(hi - base), carry)
            best, tok, sumexp, at, topv = (np.asarray(c)[:n_rows]
                                           for c in carry)
            owner = np.asarray(owner)
            for r in range(len(seqs)):
                sel = owner == r
                out.append({"best": best[sel], "best_token": tok[sel],
                            "lse": best[sel] + np.log(sumexp[sel]),
                            "at": at[sel], "top": topv[sel]})
    return out
