"""The DeepSeek-V3 family (GigaChat3.1-702B-A36B is of it) for the
benchmark: the model handed to the program, its weights, and the plain
reference that decides ``correct``.

``build`` constructs the PROGRAM's model (``paddle_tpu.models.
deepseek_v2``) at the sizes of a configuration file, holding ONE
expert-parallel rank's share of each expert layer, and fills it with
weights the BENCHMARK makes from the seed, on the device, in the type
they are served in. ``reference_rows`` is the yardstick: the decoder
written from its published description (DeepSeek-V3 technical report
section 2.1; ``modeling_deepseek.py`` of the published checkpoint) in
float32 ``jax.numpy`` at ``highest`` matmul precision, with no cache and
no kernel: RMSNorm, low-rank queries, multi-head latent attention in
the EXPANDED form (per-head keys and values made from the latent), a
decoupled rotary key in the complex-pair convention under yarn, a
sigmoid router with a selection bias and group-limited top-k, routed
and shared SwiGLU experts, an untied head. It imports nothing of
``paddle_tpu`` and reads only the weights made here, by name, upcasting
one matrix or one expert at a time so that it fits beside a serving
engine.

The share (model-configs guide, section 4): the router keeps its
published width; of each expert layer the experts ``first_expert ..
first_expert + n_routed_experts - 1`` are held; what the absent experts
would add is left out, here as in the program, and that partial result
goes on to the next layer.

Departures from the published description, each also marked DEPARTURE
where it is made:

1. the multi-token-prediction layer is not built (next-token logits do
   not depend on it);
2. the rotary pairs stay interleaved: the published code first permutes
   each head to half-split order and rotates halves; the permutation is
   common to queries and keys, so every q . k is unchanged;
3. the W8A8 control keeps the router in float32: the program's router
   is float32 whatever the weights' precision, and a control that moved
   the routing by rounding the router would not be the nearest
   precision below the program's.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmarks.models.qwen2 import (NORM_STD, WEIGHT_STD, _head_block,
                                     _rms_norm, matmul, seed_words)

# The selection bias (``e_score_correction_bias``): the trained one
# spreads the load. A seeded one has to be wide enough that dropping it
# changes which experts are chosen, and narrow enough to leave the load
# spread: seeded routers' logits have deviation 0.02 x sqrt(hidden) =
# 1.7, so the top 8 of a token's 128 eligible sigmoid scores lie in
# 0.93-1.0, 0.005-0.01 apart, and a bias wider than that decides the
# selection alone (every token then picks the experts with the largest
# bias). Held experts that got a token, a layer and tick, at 64 rows (my
# chip run, PR 26, seed 2600021021): 36% at deviation 0.1, 55% at 0.05,
# 68% at 0.03, 77% at 0.02, 83% at 0.01, 86% at 0 (uniform choice:
# 86.5%).
BIAS_STD = 0.01
LAYER = "model.layers."


def program_config(config: dict):
    """The program's own config object at this file's sizes."""
    import jax.numpy as jnp
    from paddle_tpu.models.deepseek_v2 import DeepseekV2Config
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["dtype"]]
    return DeepseekV2Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        rope_scaling=dict(config["rope_scaling"]),
        yarn_mscale_all_in_scale=True,
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        num_experts=config["n_routed_experts_published"],
        first_expert=config["first_expert"],
        experts_held=config["n_routed_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_shared_experts=config["n_shared_experts"],
        first_k_dense_replace=config["first_k_dense_replace"],
        routed_scaling_factor=config["routed_scaling_factor"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        scoring=config["scoring_func"], group_score_mode="top2_sum",
        norm_topk_prob=config["norm_topk_prob"],
        attention_bias=config["attention_bias"],
        tie_word_embeddings=config["tie_word_embeddings"],
        num_nextn_predict_layers=config["num_nextn_predict_layers"],
        dtype=dtype)


def _std(name: str) -> Optional[float]:
    """None for a norm scale (mean 1), else the normal's deviation."""
    if name.endswith("norm.weight"):
        return None
    return BIAS_STD if name.endswith("expert_bias") else WEIGHT_STD


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


def make_weights(spec: Dict, seed: int, device) -> Dict:
    """Every array of ``spec`` (name -> (shape, dtype)) drawn from
    ``seed`` on ``device``, in the type it is served in. One jitted
    program per KIND of layer (the leading dense layers, the expert
    layers), called once per layer with that layer's key; one more for
    what lies outside the layers."""
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
        out.update(_draw(rest)(jnp.asarray(words)))
        kinds: List = []            # (a layer's spec, its draw)
        for i in sorted(layers):
            draw = next((d for s, d in kinds if s == layers[i]), None)
            if draw is None:
                draw = _draw(layers[i])
                kinds.append((layers[i], draw))
            w = words.copy()
            w[2] += i + 1
            for leaf, v in draw(jnp.asarray(w)).items():
                out[f"{LAYER}{i}.{leaf}"] = v
    return {name: out[name] for name in spec}


def fill_weights(params: Dict, seed: int):
    """New values for every array of ``params`` (names, shapes and types
    kept) from ``seed``. The old arrays are deleted first: a chip cannot
    hold the model twice."""
    import jax
    # in sorted order, as ``build`` draws them (``jax.eval_shape`` hands
    # its dict back sorted): the same seed then gives the same weights
    spec = {k: (params[k].shape, params[k].dtype) for k in sorted(params)}
    placed = {k: v.sharding for k, v in params.items()}
    device = next(iter(next(iter(params.values())).devices()))
    for v in params.values():
        v.delete()
    # the same mapping type and key order, and committed to their device,
    # as the arrays they replace were: any of these is another key in
    # jit's cache, and the chunk and tick programs would be traced again
    # inside the next window (the engine's params are an OrderedDict;
    # handed a dict, chip_limits.py read no finished request for a
    # process's second seed)
    new = make_weights(spec, seed, device)
    return type(params)((k, jax.device_put(new[k], placed[k]))
                        for k in placed)


def _program_model(cfg):
    """The program's model object WITHOUT its own weight draw (traced
    under ``jax.eval_shape``, as benchmarks/models/qwen2.py does), and
    the (shape, dtype) of each of its parameters."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.deepseek_v2 import DeepseekV2ForCausalLM
    box = []

    def make():
        box.append(DeepseekV2ForCausalLM(cfg))
        return dict(box[0].functional()[1])

    shapes = jax.eval_shape(make)
    pt.seed(0)          # the trace left a tracer in the global key
    return box[0], {k: (v.shape, v.dtype) for k, v in shapes.items()}


def build(config: dict, seed: int, device):
    """The program's ``DeepseekV2ForCausalLM`` on ``device`` holding the
    benchmark's seeded weights, selection bias included."""
    import jax
    cfg = program_config(config)    # a program without the share: here
    with jax.default_device(device):
        model, spec = _program_model(cfg)
        model.set_state_dict(make_weights(spec, seed, device), strict=False)
    left = [k for k, v in model.functional()[1].items()
            if not isinstance(v, jax.Array) or isinstance(v, jax.core.Tracer)]
    if left:
        raise RuntimeError(f"parameters without seeded weights: {left[:3]}")
    return model


# ---------------------------------------------------------------- reference
def yarn(config: dict):
    """(inverse frequencies [rope/2], factor on cos and sin, factor on
    the softmax scale) of the rotary key under yarn, as published
    (``DeepseekV3YarnRotaryEmbedding`` and the attention's
    ``softmax_scale``)."""
    rs = config["rope_scaling"]
    dim, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inv = extra / factor * ramp + extra * (1 - ramp)
    return (inv.astype(np.float32),
            mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"]),
            mscale(rs["mscale_all_dim"]) ** 2)


def _rope(x, positions, inv, amp):
    """x [b, s, h, d]: the pair (x[2i], x[2i+1]) turns by pos * inv[i].
    DEPARTURE 2: the pairs stay where they are (see the module's text)."""
    import jax.numpy as jnp
    ang = positions.astype(jnp.float32)[..., None] * inv    # [b, s, d/2]
    cos, sin = (jnp.cos(ang) * amp)[:, :, None], (jnp.sin(ang) * amp)[:, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(w, x, positions, *, cfg, mode):
    """x + attention(norm(x)): multi-head latent attention, expanded.
    x [b, s, H] float32, full causal attention."""
    import jax
    import jax.numpy as jnp
    mm = partial(matmul, mode=mode)
    f32 = lambda name: w[name].astype(jnp.float32)      # noqa: E731
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    inv, amp, scale_up = yarn(cfg)
    b, s, _ = x.shape
    h = _rms_norm(x, f32("input_layernorm.weight"), eps)
    q = _rms_norm(mm(h, f32("self_attn.q_a_proj.weight")),
                  f32("self_attn.q_a_layernorm.weight"), eps)
    q = mm(q, f32("self_attn.q_b_proj.weight")).reshape(
        b, s, heads, nope + rope)
    ckv = mm(h, f32("self_attn.kv_a_proj_with_mqa.weight"))
    c = _rms_norm(ckv[..., :r], f32("self_attn.kv_a_layernorm.weight"), eps)
    k_pe = _rope(ckv[..., None, r:], positions, inv, amp)  # one head
    q_pe = _rope(q[..., nope:], positions, inv, amp)
    kv = mm(c, f32("self_attn.kv_b_proj.weight")).reshape(
        b, s, heads, nope + dv)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0])
              ) * ((nope + rope) ** -0.5 * scale_up)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., nope:])
    x = x + mm(att.reshape(b, s, heads * dv), f32("self_attn.o_proj.weight"))
    return x, _rms_norm(x, f32("post_attention_layernorm.weight"), eps)


def _swiglu(h, gate, up, down, mode):
    import jax
    import jax.numpy as jnp
    gate, up, down = (a.astype(jnp.float32) for a in (gate, up, down))
    return matmul(jax.nn.silu(matmul(h, gate, mode)) * matmul(h, up, mode),
                  down, mode)


def _route(h, router, bias, *, cfg):
    """The gate of every token for every one of the published experts,
    [b, s, E] float32, 0 where the token did not choose the expert:
    sigmoid scores; selection by score + bias, first of the ``topk_group``
    best groups by the sum of their two best members, then of the
    ``num_experts_per_tok`` best of those groups' experts; gates the
    chosen experts' SCORES (no bias), normalised over all the chosen,
    times ``routed_scaling_factor``. Float32 in the control too
    (DEPARTURE 3)."""
    import jax
    import jax.numpy as jnp
    E, G = cfg["n_routed_experts_published"], cfg["n_group"]
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(h @ router.astype(jnp.float32))
    choice = scores + bias.astype(jnp.float32)
    grouped = choice.reshape(h.shape[:-1] + (G, E // G))
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)
    best = jax.lax.top_k(group_score, cfg["topk_group"])[1]
    in_group = jnp.any(jnp.arange(G)[:, None] == best[..., None, :], -1)
    # masked to 0, as published (the program masks to -inf; they part
    # only if fewer than k experts of the chosen groups score above 0)
    choice = jnp.where(jnp.repeat(in_group, E // G, -1), choice, 0.0)
    chosen = jax.lax.top_k(choice, k)[1]                    # [b, s, k]
    picked = jnp.any(jnp.arange(E)[:, None] == chosen[..., None, :], -1)
    gates = jnp.where(picked, scores, 0.0)
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return gates * cfg["routed_scaling_factor"]


def _head(params, config, x, rows_of, n_seqs, top, mode, vocab_block):
    """The head over blocks of vocabulary columns (qwen2.py's fold)."""
    import jax
    import jax.numpy as jnp
    head_block = jax.jit(partial(_head_block, mode=mode, transpose=False))
    head, V = params["lm_head.weight"], config["vocab_size"]
    ri, pi, tk, owner = rows_of
    n_rows = len(owner)
    pad = -(-n_rows // 256) * 256
    fill = lambda a: np.pad(np.asarray(a, np.int32),        # noqa: E731
                            (0, pad - n_rows))
    h = x[jnp.asarray(fill(ri)), jnp.asarray(fill(pi))]     # [pad, H]
    tkd = jnp.asarray(fill(tk))
    carry = (jnp.full((pad,), -jnp.inf, jnp.float32),
             jnp.zeros((pad,), jnp.int32),
             jnp.zeros((pad,), jnp.float32),
             jnp.full((pad,), -jnp.inf, jnp.float32),
             jnp.full((pad, top), -jnp.inf, jnp.float32))
    for base in range(0, V, vocab_block):
        hi = min(base + vocab_block, V)
        wb = head[:, base:hi]
        if hi - base < vocab_block:         # one shape for the tail
            wb = jnp.pad(wb, ((0, 0), (0, vocab_block - (hi - base))))
        carry = head_block(h, wb, tkd, jnp.int32(base),
                           jnp.int32(hi - base), carry)
    best, tok, sumexp, at, topv = (np.asarray(c)[:n_rows] for c in carry)
    owner = np.asarray(owner)
    out = []
    for r in range(n_seqs):
        sel = owner == r
        out.append({"best": best[sel], "best_token": tok[sel],
                    "lse": best[sel] + np.log(sumexp[sel]),
                    "at": at[sel], "top": topv[sel]})
    return out


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
    attention = jax.jit(partial(_attention, cfg=config, mode=mode))
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
                x, h = attention(w, x, pos)
                mlp = lambda name: params[lp + "mlp." + name]  # noqa: E731
                if i < config["first_k_dense_replace"]:
                    x = x + swiglu(h, mlp("gate_proj.weight"),
                                   mlp("up_proj.weight"),
                                   mlp("down_proj.weight"))
                    continue
                gates = route(h, mlp("gate"), mlp("expert_bias"))
                x = x + swiglu(h, mlp("shared_gate_proj"),
                               mlp("shared_up_proj"),
                               mlp("shared_down_proj"))
                # the share: the held experts only, one at a time
                for e in range(config["n_routed_experts"]):
                    x = x + gates[..., first + e, None] * swiglu(
                        h, mlp("w_gate")[e], mlp("w_up")[e],
                        mlp("w_down")[e])
            # DEPARTURE 1: no multi-token-prediction layer follows
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
