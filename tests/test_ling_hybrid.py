"""ISSUE 41: Ling 3.0's Kimi-Delta-Attention layers (a decay for every key
channel) beside gated latent-attention layers and a group-limited
router's experts, served through slot state AND a latent pool in one
engine.

Contracts pinned here at ``ling_hybrid_tiny`` widths in float32 (six
layers in two periods of three: four linear layers, two latent; 8 heads
of 16; a dense layer then five expert layers holding experts 4-7 of 16 in
4 groups), each against the benchmark's plain reference
(``benchmarks/models/ling_hybrid.py``: the recurrence a scan over
positions, the latent expanded) on its own seeded weights, comparing
LOGITS and streamed logprobs:

- THE RECURRENCE at a decay a channel: the chunkwise form and the decode
  step against the position-by-position scan, on random gates and with
  every log-decay at the bound -5 and at 0, a carried state, segments
  packed side by side; the one-pass Pallas step (the interpreter) against
  the jnp body for a decay a head and a decay a channel.
- FULL FORWARD: every logit of every position; a dropped head gate,
  another bound of the decay, a dropped tap, another scaling of the
  routed part and another norm epsilon each fail.
- THE ENGINE: chunked prefill then decode through ``PagedEngine`` over
  ``StateLayer`` and a latent ``CacheLayer`` side by side; a packed call
  equals separate calls; a reused slot starts from zero; each refusal
  raises with its sentence; the counters count.

Tolerances: both sides are float32 (the reference at ``highest``
precision, which the CPU gives the program too): 1e-4 on logits and
logprobs leaves a factor of 50 over the 2e-6 read here; the departures
move 2e-3 to 0.3. The chunk form's factors reach e^80 at the bound: 1e-4
on outputs of magnitude 1 there (read: 8e-6).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.ops.paged_cache import CacheLayer, StateLayer, state_step_route
from paddle_tpu.ops import delta_rule

TOL = 1e-4
BENCH = {
    "model": "ling_hybrid", "dtype": "float32", "state_dtype": "float32",
    "use_qkv_bias": False, "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 6, "layer_group_size": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 8,
    "num_key_value_heads": 8, "head_dim": 16, "short_conv_kernel_size": 4,
    "kda_safe_gate": True, "kda_lower_bound": -5, "q_lora_rank": None,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16,
    "gated_attention_proj_granularity_type": "head_wise",
    "rope_theta": 10000.0, "rope_scaling": None, "num_experts": 4,
    "num_experts_published": 16, "first_expert": 4,
    "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
    "score_function": "sigmoid", "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "num_shared_experts": 1,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32,
    "expert_swiglu_limit_list": [0] * 6,
    "share_expert_swiglu_limit_list": [0] * 6,
    "num_nextn_predict_layers": 0, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
}
KINDS = [StateLayer, StateLayer, CacheLayer] * 2


@pytest.fixture(scope="module")
def ref():
    from benchmarks.harness import cell
    return cell.load_model(BENCH)


@pytest.fixture(scope="module")
def model(ref):
    return ref.build(BENCH, 11, jax.devices()[0])


def _engine(model, **kw):
    kw.setdefault("chunk_prefill_tokens", 16)
    return PagedEngine(model, max_slots=kw.pop("max_slots", 3),
                       num_blocks=96, block_size=4, max_blocks_per_seq=24,
                       **kw)


def _prompts(lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


def _lp_error(ref, model, prompt, tokens, lps, config=BENCH):
    rows = ref.reference_rows(dict(model.functional()[1]), config,
                              [prompt + tokens], [len(prompt)], [tokens],
                              vocab_block=256)[0]
    return (float(np.abs(np.asarray(lps) - (rows["at"] - rows["lse"])).max()),
            bool((rows["best_token"] == np.asarray(tokens)).all()))


# ------------------------------------------------------------ the recurrence
def _inputs(T, H=3, dk=8, dv=16, seed=0, g_all=None, channel=True):
    """q, k normalised; beta across (0, 1); a channel's log-decay
    anywhere in (-5, 0) (``g_all``: every one of them that value)."""
    rng = np.random.default_rng(seed)
    q = delta_rule.l2_normalize(jnp.asarray(
        rng.normal(size=(T, H, dk)), jnp.float32)) * dk ** -0.5
    k = delta_rule.l2_normalize(jnp.asarray(
        rng.normal(size=(T, H, dk)), jnp.float32))
    v = jnp.asarray(rng.normal(size=(T, H, dv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.01, 0.99, (T, H)), jnp.float32)
    shape = (T, H, dk) if channel else (T, H)
    g = -5.0 * jax.nn.sigmoid(jnp.asarray(3 * rng.normal(size=shape),
                                          jnp.float32))
    if g_all is not None:
        g = jnp.full_like(g, g_all)
    S0 = jnp.asarray(rng.normal(size=(H, dk, dv)), jnp.float32)
    return q, k, v, g, beta, S0


@pytest.mark.parametrize("T,sub,g_all", [
    (50, 64, None), (64, 64, None), (100, 64, None), (37, 16, None),
    (131, 32, None), (1, 64, None), (256, 64, None),
    (70, 64, -5.0), (256, 64, -5.0), (70, 16, -5.0), (70, 64, 0.0)])
def test_the_chunkwise_form_is_the_scan_at_a_decay_a_channel(T, sub, g_all):
    """Also where every factor of the pairwise products is at its
    largest: sixteen positions at -5 grow a column by e^80."""
    q, k, v, g, beta, S0 = _inputs(T, g_all=g_all)
    o_ref, S_ref = delta_rule.gated_delta_scan(q, k, v, g, beta, S0)
    o, S = delta_rule.gated_delta_chunk(q, k, v, g, beta, S0, sub=sub)
    assert np.isfinite(o).all() and np.isfinite(S).all()
    assert np.abs(o - o_ref).max() < TOL
    assert np.abs(S[0] - S_ref).max() < TOL


def test_a_channel_decay_that_is_a_heads_is_the_heads():
    """Every channel of a head at that head's decay: the two chunk forms
    and the two scans read the same numbers."""
    q, k, v, g, beta, S0 = _inputs(90, seed=2, channel=False)
    wide = jnp.broadcast_to(g[..., None], g.shape + (q.shape[-1],))
    o_h, S_h = delta_rule.gated_delta_chunk(q, k, v, g, beta, S0)
    o_c, S_c = delta_rule.gated_delta_chunk(q, k, v, wide, beta, S0)
    assert np.abs(o_h - o_c).max() < 1e-5
    assert np.abs(S_h - S_c).max() < 1e-5
    o_s, _ = delta_rule.gated_delta_scan(q, k, v, wide, beta, S0)
    assert np.abs(o_s - o_c).max() < 1e-5


@pytest.mark.parametrize("sub", [16, 64])
def test_segments_neither_share_state_nor_decay(sub):
    """Three prompts side by side and padding behind the last: each
    segment's outputs and final state are those of the segment alone
    from zero, the first's from the carried state."""
    lens, T = (23, 5, 41), 80
    q, k, v, g, beta, S0 = _inputs(T, seed=3)
    seg = jnp.asarray(np.repeat([0, 1, 2, 2], lens + (T - sum(lens),)))
    real = jnp.arange(T) < sum(lens)
    g = jnp.where(real[:, None, None], g, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0)
    o, S = delta_rule.gated_delta_chunk(q, k, v, g, beta, S0, seg,
                                        segments=4, sub=sub)
    at = 0
    for s, n in enumerate(lens):
        sl = slice(at, at + n)
        o_ref, S_ref = delta_rule.gated_delta_scan(
            q[sl], k[sl], v[sl], g[sl], beta[sl],
            S0 if s == 0 else jnp.zeros_like(S0))
        assert np.abs(o[sl] - o_ref).max() < 1e-5
        assert np.abs(S[s] - S_ref).max() < 1e-5
        at += n


@pytest.mark.parametrize("H,dk,dv,hp", [(8, 16, 16, 8), (6, 8, 16, 1),
                                        (4, 8, 64, 2), (32, 128, 128, 1)])
def test_the_decode_step_is_one_position_of_the_scan(H, dk, dv, hp):
    assert delta_rule.state_lane_heads(H, dv) == hp
    R = 3
    rows = [_inputs(1, H, dk, dv, seed=r) for r in range(R)]
    q, k, v, g, beta, S0 = (jnp.stack([r[i] for r in rows])
                            for i in range(6))
    live = jnp.asarray([True, False, True])
    S, o = delta_rule.delta_state_step(
        delta_rule.pack_state(S0, hp), q[:, 0], k[:, 0], v[:, 0],
        jnp.exp(g[:, 0]), beta[:, 0], live)
    S = delta_rule.unpack_state(S, hp)
    for r in range(R):
        o_ref, S_ref = delta_rule.gated_delta_scan(*rows[r])
        assert np.abs(o[r] - o_ref[0]).max() < 1e-5
        assert np.abs(S[r] - (S_ref if live[r] else S0[r])).max() < 1e-5


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("channel", [False, True], ids=["head", "channel"])
@pytest.mark.parametrize("R,H,dk,dv,fit", [
    (4, 8, 16, 16, None),       # the tiny twin: 8 heads a row
    (5, 4, 16, 64, 2),          # two heads a tile, a slot a step
    (3, 2, 8, 128, None),       # one head a row, whole tiles
    (2, 32, 128, 128, 1),       # the cell's heads: a slot a step
])
def test_the_state_kernel_is_the_jnp_body_and_the_scan(
        kernels, monkeypatch, channel, R, H, dk, dv, fit):
    """The one-pass Pallas kernel (the interpreter here) over three
    positions, for a decay a head [R, H] and a decay a channel [R, H,
    dk], each against the jnp body of ``delta_state_step`` (the gate
    held shut) and against the scan; a row that is not live keeps its
    state bit for bit. A channel's kernel has a name of its own."""
    from paddle_tpu.ops.pallas import delta_state
    hp = delta_rule.state_lane_heads(H, dv)
    if fit:
        monkeypatch.setattr(delta_state, "_VMEM_STATE",
                            4 * fit * H * dk * dv * 4)
    T = 3
    rows = [_inputs(T, H, dk, dv, seed=10 + r, channel=channel)
            for r in range(R)]
    q, k, v, g, beta, S0 = (jnp.stack([r[i] for r in rows])
                            for i in range(6))
    alive = jnp.arange(R) % 3 != 1
    S = delta_rule.pack_state(S0, hp)
    assert delta_state.use_state_kernel(S)
    step = lambda *a: delta_rule.delta_state_step(*a)       # noqa: E731
    jaxpr = str(jax.make_jaxpr(step)(S, q[:, 0], k[:, 0], v[:, 0],
                                     jnp.exp(g[:, 0]), beta[:, 0], alive))
    assert ("delta_state_step_channel" in jaxpr) == channel
    assert "pallas_call" in jaxpr
    for t in range(T):
        a = (q[:, t], k[:, t], v[:, t], jnp.exp(g[:, t]), beta[:, t], alive)
        S_new, o = jax.jit(step)(S, *a)
        with monkeypatch.context() as m:
            m.setattr(delta_state, "use_state_kernel", lambda _S: False)
            S_body, o_body = jax.jit(lambda *a: step(*a))(S, *a)
        assert np.abs(S_new - S_body).max() < 1e-6
        assert np.abs(o - o_body).max() < 1e-6
        dead = ~np.asarray(alive)
        assert np.array_equal(np.asarray(S_new)[dead], np.asarray(S)[dead])
        S = S_new
    S = delta_rule.unpack_state(S, hp)
    for r in range(R):
        o_ref, S_ref = delta_rule.gated_delta_scan(*rows[r])
        if alive[r]:
            assert np.abs(S[r] - S_ref).max() < 1e-5
            assert np.abs(o[r] - o_ref[-1]).max() < 1e-5
        else:
            assert np.array_equal(S[r], S0[r])


# ------------------------------------------------------------ full forward
def test_every_logit_of_the_no_cache_forward(ref, model):
    fn, params = model.functional()
    ids = np.asarray(_prompts([83, 83], seed=4))
    logits = np.asarray(fn(params, jnp.asarray(ids)))
    for r in range(2):
        rows = ref.reference_rows(dict(params), BENCH, [ids[r].tolist()],
                                  [1], [ids[r, 1:].tolist()],
                                  vocab_block=256)[0]
        lse = np.log(np.exp(logits[r, :-1]).sum(-1))
        assert np.abs(logits[r, :-1].max(-1) - rows["best"]).max() < TOL
        assert np.abs(lse - rows["lse"]).max() < TOL
        at = np.take_along_axis(logits[r, :-1], ids[r, 1:, None], -1)[:, 0]
        assert np.abs(at - rows["at"]).max() < TOL


@pytest.mark.parametrize("told", ["head_gate", "bound", "tap", "scaling",
                                  "eps"])
def test_a_dropped_term_fails_the_same_comparison(ref, model, told):
    """The reference told of a model one term away from the served
    one."""
    fn, params = model.functional()
    ids = _prompts([61], seed=5)[0]
    logits = np.asarray(fn(params, jnp.asarray([ids])))[0, :-1]
    cfg, w = dict(BENCH), dict(params)
    if told == "bound":         # the decay's lower bound
        cfg["kda_lower_bound"] = -1
    elif told == "scaling":
        cfg["routed_scaling_factor"] = 1.0
    elif told == "eps":
        cfg["rms_norm_eps"] = 1e-2
    elif told == "tap":
        w = {k: v.at[:, 0].set(0) if k.endswith(".conv_weight") else v
             for k, v in w.items()}
    elif told == "head_gate":   # sigmoid(0) = 1/2 a head, not the gate
        w = {k: jnp.zeros_like(v) if k.endswith("self_attn.g_proj.weight")
             else v for k, v in w.items()}
    rows = ref.reference_rows(w, cfg, [ids], [1], [ids[1:]],
                              vocab_block=256)[0]
    assert np.abs(logits.max(-1) - rows["best"]).max() > 20 * TOL


# --------------------------------------------------------------- the engine
def test_the_model_says_what_each_layer_keeps(model):
    """State layers beside a LATENT pool: 8 heads' 16 x 16 states side
    by side in one 128-lane row and three inputs of 384 channels a slot;
    one 128-wide latent row (32 + 8 live columns) a token."""
    layers = model.paged_cache_layers()
    assert [type(x) for x in layers] == KINDS
    assert layers[0].arrays == (((1, 16, 128), jnp.float32),
                                ((3, 384), jnp.float32))
    assert layers[2] == CacheLayer(((1, 128),))
    eng = _engine(model)
    state = ((3, 1, 16, 128), (3, 3, 384))
    assert [tuple(a.shape for a in p) for p in eng.pools] == \
        [state, state, ((96, 4, 128),)] * 2 + [((2,),)]
    assert all(k in eng.stats for k in (
        "state_layer_ticks", "state_rows_updated", "state_resets",
        "moe_experts_hit", "moe_rows_routed_here"))
    assert "moe_rows_routed_here" in eng.health()


@pytest.mark.parametrize("mode", ["chunked", "whole", "host"])
def test_prefill_then_decode_against_the_reference(ref, model, mode):
    """Prompts of one to four chunks (none a multiple of the chunk), six
    requests over three slots so that every slot is reused."""
    kw = {"chunked": {}, "whole": {"chunk_prefill_tokens": None},
          "host": {"fused_tick": False}}[mode]
    eng = _engine(model, **kw)
    prompts = _prompts([5, 37, 16, 23, 9, 61])
    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new_tokens=8)
    res = eng.run()
    for i, p in enumerate(prompts):
        err, same = _lp_error(ref, model, p, res[i], eng.logprobs[i])
        assert err < TOL and same, (i, err)
    st = eng.stats
    if mode == "chunked":
        assert (st["state_resets"], st["state_carries"]) == (6, 6)
        assert st["state_layer_ticks"] == 4 * st["decode_steps"]
        assert st["state_kernel_ticks"] == st["state_layer_ticks"] * (
            state_step_route(eng.pools[0][0]) == "kernel")
        assert st["state_rows_updated"] == 4 * 6 * 7
        # five expert layers a tick; a live row is routed here in a
        # layer or it is not
        assert st["moe_layer_ticks"] == 5 * st["decode_steps"]
        assert 0 < st["moe_rows_routed_here"] <= 5 * 6 * 7
        assert st["moe_rows_routed_here"] <= st["moe_local_assignments"]


def test_the_kernels_serve_both_layer_kinds(ref, model, kernels):
    """Under the interpreter the linear layers' step takes the channel
    kernel in every state layer of every tick and the latent layers the
    ragged kernel's latent mode; the stream is the reference's."""
    eng = _engine(model)
    assert state_step_route(eng.pools[0][0]) == "kernel"
    assert eng.decode_route() == "ragged"
    prompts = _prompts([5, 37, 16], seed=12)
    for i, p in enumerate(prompts):
        eng.submit(i, p, max_new_tokens=8)
    res = eng.run()
    for i, p in enumerate(prompts):
        err, same = _lp_error(ref, model, p, res[i], eng.logprobs[i])
        assert err < TOL and same, (i, err)
    st = eng.stats
    assert st["state_kernel_ticks"] == st["state_layer_ticks"] \
        == 4 * st["decode_steps"] > 0


def test_a_packed_call_of_three_prompts_equals_three_calls(ref, model):
    prompts = _prompts([7, 4, 5], seed=7)       # 16 positions hold all
    together = _engine(model)
    for i, p in enumerate(prompts):
        together.submit(i, p, max_new_tokens=6)
    together.run()
    assert together.stats["prefill_segments"] == 3
    assert together.stats["prefill_chunks"] == 1
    for i, p in enumerate(prompts):
        alone = _engine(model)
        alone.submit(i, p, max_new_tokens=6)
        alone.run()
        assert alone.results[i] == together.results[i]
        assert np.abs(np.asarray(alone.logprobs[i])
                      - np.asarray(together.logprobs[i])).max() < 2e-5
        err, same = _lp_error(ref, model, p, together.results[i],
                              together.logprobs[i])
        assert err < TOL and same


def test_a_reused_slot_starts_from_zero(ref, model):
    """Every state array filled with NaN behind a finished request: the
    next one in the slot would stream them if it did not start from
    zero. The latent pool's stale rows lie behind the new lengths."""
    eng = _engine(model, max_slots=1)
    first, second = _prompts([21, 37], seed=9)
    eng.submit("a", first, max_new_tokens=5)
    eng.run()
    eng.pools = [tuple(jnp.full_like(a, jnp.nan) for a in p)
                 if isinstance(layer, StateLayer) else p
                 for layer, p in zip(eng._layout, eng.pools)] \
        + eng.pools[len(eng._layout):]
    eng.submit("b", second, max_new_tokens=5)
    eng.run()
    err, same = _lp_error(ref, model, second, eng.results["b"],
                          eng.logprobs["b"])
    assert err < TOL and same


@pytest.mark.parametrize("what,sentence", [
    ("prefix", "a prefix's blocks hold no state to adopt"),
    ("spec", "a rejected draft's positions cannot be taken back"),
    ("spill", "recurrent state"),
    ("export", "recurrent state"),
])
def test_what_cannot_hold_over_state_layers_raises(model, what, sentence):
    """The engine has no branch on a family: what it refuses over
    Olmo-Hybrid's state layers it refuses here, beside a latent pool,
    with the same sentences."""
    with pytest.raises(ValueError, match=sentence):
        if what == "prefix":
            _engine(model, enable_prefix_cache=True)
        elif what == "spec":
            _engine(model, spec_tokens=2)
        elif what == "spill":
            _engine(model).attach_spill(object())
        else:
            _engine(model)._spill_geometry()


@pytest.mark.parametrize("key,value,sentence", [
    ("expert_swiglu_limit_list", (0, 0, 4.0), "names the clamp and not its "
                                              "form"),
    ("share_expert_swiglu_limit_list", (0, 5.0, 0), "non-zero limit"),
    ("kda_lower_bound", -8.0, "is under -5.0"),
    ("kda_safe_gate", False, "bounded"),
    ("num_nextn_predict_layers", 1, "multi-token-prediction"),
])
def test_the_family_refuses_what_it_does_not_build(key, value, sentence):
    from paddle_tpu.models import ling_hybrid_tiny
    with pytest.raises(ValueError, match=sentence):
        ling_hybrid_tiny(**{key: value})
    # a limit on a layer that is not kept refuses nothing
    ling_hybrid_tiny(expert_swiglu_limit_list=(0, 0, 0, 4.0))


def test_which_layer_is_which():
    """The published depth: latent attention at 5, 11, ..., 41, dense
    FFNs in layers 0 and 1."""
    from paddle_tpu.models import LingHybridConfig
    cfg = LingHybridConfig()
    assert [i for i in range(42) if cfg.is_latent(i)] == \
        [5, 11, 17, 23, 29, 35, 41]
    assert cfg.latent_row_width == 640 and cfg.qk_head_dim == 192
    assert delta_rule.state_lane_heads(cfg.num_attention_heads,
                                       cfg.head_dim) == 1
