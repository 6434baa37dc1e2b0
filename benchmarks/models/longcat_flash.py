"""LongCat-Flash's language model (LongCat-Flash-Omni's is the same)
for the benchmark: the model handed to the program, its weights, and the
plain reference that decides ``correct``.

``build`` constructs the PROGRAM's model (``paddle_tpu.models.
longcat_flash``) at the sizes of a configuration file, holding ONE
expert-parallel rank's share of each expert layer, and fills it with
weights the BENCHMARK makes from the seed, on the device, in the type
they are served in. ``reference_rows`` is the yardstick: the decoder
written from its published description (``modeling_longcat_flash.py`` of
the published checkpoint; LongCat-Flash technical report, sections on
shortcut-connected MoE and zero-computation experts) in float32
``jax.numpy`` at ``highest`` matmul precision, with no cache and no
kernel. One of its layers, on hidden state x:

    x1 = x  + A0(n_in0(x));    h0 = n_post0(x1)
    m  = M(h0)
    x2 = x1 + F0(h0)
    x3 = x2 + A1(n_in1(x2))
    y  = x3 + F1(n_post1(x3)) + m

A0, A1: multi-head latent attention in the EXPANDED form (per-head keys
and values made from the latent), a decoupled rotary key with no rope
scaling, the query times sqrt(hidden / q_lora_rank) after ``q_b`` and
the normed latent times sqrt(hidden / kv_lora_rank) before ``kv_b``.
F0, F1: dense SwiGLU. M: a softmax router over ``n_routed_experts +
zero_expert_num`` columns with no bias term in its product, the
``moe_topk`` best by score + ``e_score_correction_bias``, gated by the
score itself (not renormalised) times ``routed_scaling_factor``; a
chosen column under ``n_routed_experts`` is a SwiGLU expert, one above
it the identity. Untied head. It imports nothing of ``paddle_tpu`` and
reads only the weights made here, by name, upcasting one matrix or one
expert at a time so that it fits beside a serving engine.

The share (model-configs guide, section 4): the router keeps its
published 768 columns; of each layer the experts ``first_expert ..
first_expert + n_routed_experts - 1`` are held; what the absent experts
would add is left out, here as in the program, and that partial result
goes on to the next layer. The identity experts' part is in whole: in a
deployment the token's own rank computes it.

Departures from the published description, each also marked DEPARTURE
where it is made:

1. the omni model's audio and vision encoders and its codec decoder are
   not built: the catalog's configuration is the language model's, and
   text-only traffic does not reach them;
2. the rotary pairs stay interleaved: the published code first permutes
   each head to half-split order and rotates halves; the permutation is
   common to queries and keys, so every q . k is unchanged;
3. the W8A8 control keeps the router in float32, as deepseek_v3.py's
   does and for its reason: the program's router is float32 whatever
   the weights' precision.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmarks.models import deepseek_v3
from benchmarks.models.deepseek_v3 import LAYER, _head, _rope, _swiglu
from benchmarks.models.qwen2 import _rms_norm, matmul

# The selection bias (``e_score_correction_bias``): the trained one
# spreads the load. A seeded one has to change the choice without
# deciding it alone (deepseek_v3.py's note). Here the scores are a
# softmax over 768 columns of logits with deviation 0.02 x sqrt(6144) =
# 1.57: a token's 12 best lie between about 0.012 and 0.06, the 12th and
# 13th 0.0006 apart, so deepseek_v3's 0.01 would choose alone. An i.i.d.
# simulation of that router at 64 rows, deviation -> rows whose chosen
# set changes when the bias is dropped / held experts hit a layer and
# tick: 0.0003 -> 26% / 63.7%; 0.001 -> 66% / 63.4%; 0.003 -> 97% /
# 59.9%; 0.01 -> 100% / 29.7% (uniform choice: 63.5%). On the chip at
# 0.001 (my chip run, PR 30, seed 2600030021): 63.0% of the held experts
# hit, 33.4% of the choices on zero columns (256 / 768 = 33.3%).
BIAS_STD = 0.001


def program_config(config: dict):
    """The program's own config object at this file's sizes."""
    import jax.numpy as jnp
    from paddle_tpu.models.longcat_flash import LongcatFlashConfig
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config["dtype"]]
    return LongcatFlashConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        ffn_hidden_size=config["ffn_hidden_size"],
        expert_ffn_hidden_size=config["expert_ffn_hidden_size"],
        num_hidden_layers=config["num_layers"],
        num_attention_heads=config["num_attention_heads"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        mla_scale_q_lora=config["mla_scale_q_lora"],
        mla_scale_kv_lora=config["mla_scale_kv_lora"],
        num_experts=config["n_routed_experts_published"],
        first_expert=config["first_expert"],
        experts_held=config["n_routed_experts"],
        zero_expert_num=config["zero_expert_num"],
        moe_topk=config["moe_topk"],
        routed_scaling_factor=config["routed_scaling_factor"],
        attention_bias=config["attention_bias"], dtype=dtype)


def _own_bias(weights):
    """``weights`` with every selection bias at THIS family's deviation:
    deepseek_v3's generator, which makes them, draws a bias at its own."""
    k = BIAS_STD / deepseek_v3.BIAS_STD
    return type(weights)(
        (name, (w * k).astype(w.dtype) if name.endswith("expert_bias")
         else w) for name, w in weights.items())


def make_weights(spec: Dict, seed: int, device) -> Dict:
    """Every array of ``spec`` drawn from ``seed`` on ``device``
    (deepseek_v3's generator: projections and experts 0.02, norm scales
    1 +- 0.1), the selection biases at ``BIAS_STD``."""
    return _own_bias(deepseek_v3.make_weights(spec, seed, device))


def fill_weights(params: Dict, seed: int):
    """New values for every array of ``params`` from ``seed``, in place
    of the old (deepseek_v3's, which keeps the mapping's type, order and
    placement: jit's cache keys on them)."""
    return _own_bias(deepseek_v3.fill_weights(params, seed))


def _program_model(cfg):
    """The program's model object WITHOUT its own weight draw, and the
    (shape, dtype) of each of its parameters (as deepseek_v3.py)."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.models.longcat_flash import LongcatFlashForCausalLM
    box = []

    def make():
        box.append(LongcatFlashForCausalLM(cfg))
        return dict(box[0].functional()[1])

    shapes = jax.eval_shape(make)
    pt.seed(0)          # the trace left a tracer in the global key
    return box[0], {k: (v.shape, v.dtype) for k, v in shapes.items()}


def build(config: dict, seed: int, device):
    """The program's ``LongcatFlashForCausalLM`` on ``device`` holding
    the benchmark's seeded weights, selection bias included."""
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
def _attention(w, x, positions, *, cfg, mode):
    """x + attention(norm(x)), and the post-attention norm of that:
    multi-head latent attention, expanded. x [b, s, H] float32, full
    causal attention."""
    import jax
    import jax.numpy as jnp
    mm = partial(matmul, mode=mode)
    f32 = lambda name: w[name].astype(jnp.float32)      # noqa: E731
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    r, H = cfg["kv_lora_rank"], cfg["hidden_size"]
    q_scale = (H / cfg["q_lora_rank"]) ** 0.5 if cfg["mla_scale_q_lora"] \
        else 1.0
    kv_scale = (H / r) ** 0.5 if cfg["mla_scale_kv_lora"] else 1.0
    inv = (1.0 / float(cfg["rope_theta"])
           ** (np.arange(0, rope, 2, dtype=np.float32) / rope))
    b, s, _ = x.shape
    h = _rms_norm(x, f32("input_layernorm.weight"), eps)
    q = _rms_norm(mm(h, f32("self_attn.q_a_proj.weight")),
                  f32("self_attn.q_a_layernorm.weight"), eps)
    q = mm(q, f32("self_attn.q_b_proj.weight")).reshape(
        b, s, heads, nope + rope) * q_scale     # nope and rope parts alike
    ckv = mm(h, f32("self_attn.kv_a_proj_with_mqa.weight"))
    c = _rms_norm(ckv[..., :r], f32("self_attn.kv_a_layernorm.weight"),
                  eps) * kv_scale
    # DEPARTURE 2 (deepseek_v3._rope): the pairs stay interleaved
    k_pe = _rope(ckv[..., None, r:], positions, inv, 1.0)   # one head
    q_pe = _rope(q[..., nope:], positions, inv, 1.0)
    kv = mm(c, f32("self_attn.kv_b_proj.weight")).reshape(
        b, s, heads, nope + dv)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope])
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0])
              ) * (nope + rope) ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., nope:])
    x = x + mm(att.reshape(b, s, heads * dv), f32("self_attn.o_proj.weight"))
    return x, _rms_norm(x, f32("post_attention_layernorm.weight"), eps)


def _route(h, router, bias, *, cfg):
    """The gate of every token for every router column, [b, s, E + Z]
    float32, 0 where the token did not choose the column: softmax scores
    over ALL the columns; the ``moe_topk`` best by score + bias; gates
    the chosen columns' SCORES (no bias), not renormalised, times
    ``routed_scaling_factor``. Float32 in the control too (DEPARTURE
    3)."""
    import jax
    import jax.numpy as jnp
    scores = jax.nn.softmax(h @ router.astype(jnp.float32), axis=-1)
    chosen = jax.lax.top_k(scores + bias.astype(jnp.float32),
                           cfg["moe_topk"])[1]              # [b, s, k]
    picked = jnp.any(jnp.arange(scores.shape[-1])[:, None]
                     == chosen[..., None, :], -1)
    return jnp.where(picked, scores, 0.0) * cfg["routed_scaling_factor"]


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
    first, E = config["first_expert"], config["n_routed_experts_published"]
    attention = jax.jit(partial(_attention, cfg=config, mode=mode))
    swiglu = jax.jit(partial(_swiglu, mode=mode))
    route = jax.jit(partial(_route, cfg=config))
    out: List[Dict[str, np.ndarray]] = []

    def half(x, lp, j, pos):
        """The j-th attention of layer ``lp`` and what its FFN adds."""
        hp = f"{lp}halves.{j}."
        w = {k[len(hp):]: v for k, v in params.items()
             if k.startswith(hp) and ".mlp." not in k}
        x, h = attention(w, x, pos)
        mlp = lambda name: params[f"{hp}mlp.{name}.weight"]  # noqa: E731
        return x, h, swiglu(h, mlp("gate_proj"), mlp("up_proj"),
                            mlp("down_proj"))

    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(sequences), rows_per_block):
            seqs = list(sequences[lo:lo + rows_per_block])
            L = -(-max(len(s) for s in seqs) // 256) * 256
            ids = np.zeros((rows_per_block, L), np.int32)
            for r, s in enumerate(seqs):
                ids[r, :len(s)] = s
            pos = jnp.broadcast_to(jnp.arange(L)[None], ids.shape)
            # DEPARTURE 1: token embeddings only, no encoder's
            x = params["model.embed_tokens.weight"][jnp.asarray(ids)] \
                .astype(jnp.float32)
            for i in range(config["num_layers"]):
                lp = f"{LAYER}{i}."
                moe = lambda name: params[lp + "moe." + name]  # noqa: E731
                x, h0, ffn = half(x, lp, 0, pos)
                gates = route(h0, moe("gate"), moe("expert_bias"))
                # the identity experts, whole; then the share: the held
                # experts only, one at a time
                m = jnp.sum(gates[..., E:], -1, keepdims=True) * h0
                for e in range(config["n_routed_experts"]):
                    m = m + gates[..., first + e, None] * swiglu(
                        h0, moe("w_gate")[e], moe("w_up")[e],
                        moe("w_down")[e])
                x, _, ffn1 = half(x + ffn, lp, 1, pos)
                x = x + ffn1 + m            # the shortcut joins here
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
