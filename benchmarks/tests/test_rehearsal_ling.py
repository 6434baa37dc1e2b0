"""Ling's rehearsal at tiny widths on the CPU, the kernels in interpret
mode: its cell through ``cell.run_cell`` and the real client child
(prompts of one or two chunks, answers of different lengths, so rows end
on different ticks), and what decides ``correct`` shown to fail: the int8
control, the bfloat16-state control, a token altered where it is
produced, and the reference told of a model one term away from the
served one. Then each count of ``harness/roofline_ling.py`` against one
done by hand at the published widths, and the new readers on a
fixture."""
import os
import time

import numpy as np
import pytest

from benchmarks.harness import cell, readers_ling, roofline_ling, verify
from benchmarks.tests import tiny, tiny_ling

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
GENERIC = {"tick_commit_ms.sat", "tick_dispatch_ms.sat", "tick_emit_ms.sat",
           "idle_unnamed_share.sat", "tick_unscoped_share.sat"}
NEW_TRACED = {"tick_kda_ms.sat", "tick_kda_state_ms.sat",
              "kda_state_membw_roofline.sat", "kda_chunk_ms.sat",
              "kda_chunk_flops_roofline.sat",
              "kdamoe_experts_membw_roofline.sat",
              "kdamoe_mla_attn_roofline.sat",
              "kdamoe_tick_membw_roofline.sat"}
READERS = (readers_ling.kda_ms, readers_ling.kda_state_ms,
           readers_ling.kda_state_membw_roofline, readers_ling.kda_chunk_ms,
           readers_ling.kda_chunk_flops_roofline,
           readers_ling.experts_membw_roofline,
           readers_ling.mla_attn_roofline, readers_ling.tick_membw_roofline,
           readers_ling.rows_routed_here_share)


@pytest.fixture(scope="module")
def interpret():
    old = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    yield
    if old is None:
        del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
    else:
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = old


def run(trace=False, tamper=None, seed=2**31 + 41):
    return cell.run_cell(tiny_ling.manifest(), tiny_ling.CELL, seed, 10.0,
                         trace, time.monotonic(), data_dir=tiny.DATA,
                         require_tpu=False, tamper=tamper)


def test_the_cell_runs_and_is_correct(interpret):
    result = run()
    assert set(result) == KEYS and result["correct"] is True
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] >= 1


def test_a_token_altered_where_it_is_produced_is_not_correct(interpret):
    def tamper(records):
        for r in records:
            if r["tokens"]:
                r["tokens"][-1] = r["final_tokens"][-1] = \
                    (r["tokens"][-1] + 101) % 256
    assert run(tamper=tamper)["correct"] is False


def test_the_traced_run_reads_the_counters(interpret, monkeypatch):
    """Against the recorded, scoped trace of a Qwen run: the counter
    metrics are read from this run's own counters, and the accepted
    ``.sat`` metrics the committed manifest lists the cell under read as
    they do on the chip. The metrics of the linear layers' scopes find no
    ``conv`` / ``decay_gate`` / ``delta_state`` / ``chunk_delta_state`` op
    in that trace and those of the experts' no ``experts`` op: they return
    nothing and raise nothing, as on a program that lacks the scopes."""
    from benchmarks.harness import peaks, trace
    fixture = os.path.join(tiny.DATA, "v5e_scoped.xplane.pb")
    monkeypatch.setattr(trace, "find_xplane", lambda logdir: fixture)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    traced = run(trace=True)
    assert traced["correct"] is True
    got = traced["metrics"]
    # 4 of 16 experts held in 4 groups, 2 groups and 3 experts a token
    assert 0 < got["rows_routed_here_share.sat"]["value"] < 100
    assert 0 < got["experts_hit_share.sat"]["value"] <= 100
    assert got["experts_read_share.sat"]["value"] \
        >= got["experts_hit_share.sat"]["value"]
    assert GENERIC | {"batch_occupancy", "tick_runahead_share.sat",
                      "tick_attn_ms.sat", "chunk_pack_rows.sat"} <= set(got)
    # the recorded trace's `attn` ops are read as the latent layers'
    # calls, and its tick modules' time by the whole tick's share
    assert set(got) & NEW_TRACED == {"kdamoe_mla_attn_roofline.sat",
                                     "kdamoe_tick_membw_roofline.sat"}
    assert not set(got) & {"tick_membw_roofline.sat",
                           "ragged_attn_roofline.sat",
                           "mla_attn_roofline.sat",
                           "delta_state_membw_roofline.sat"}


def test_another_familys_run_gives_the_new_readers_nothing():
    """What the driver's traced runs of the parent see: a program with
    no such counters and a configuration without the family's keys."""
    src = {"config": {"kv_lora_rank": 512, "n_routed_experts": 16},
           "snaps": {"w0": {"engines": [{}]}, "w1": {"engines": [{}]}}}
    for read in READERS:
        assert read(src) is None
    # the family's configuration over a program without the counter
    src["config"] = {"kda_lower_bound": -5, "layer_group_size": 6,
                     "kv_lora_rank": 512, "num_experts_published": 512}
    assert readers_ling.rows_routed_here_share(src) is None


@pytest.fixture(scope="module")
def published():
    return cell.load_json(os.path.join(
        tiny.ROOT, "benchmarks", "configs", "ling-3.0-flash-ep16-d14.json"))


def test_the_readers_on_a_fixture(monkeypatch, published):
    """One traced tick of 8 ms under ``delta_state``, 0.5 under ``conv``,
    0.4 under ``decay_gate``, 0.25 under ``gate_norm``, 5 ms under
    ``experts`` and 0.5 ms under ``attn``; two live rows a tick by the
    window's counters, at contexts 1,000 and 100; 20 held experts hit a
    layer; one prompt call of 12 ms under ``chunk_delta_state`` where the
    window's calls served 200 positions each; at the published widths."""
    from benchmarks.harness import spans
    ms = {"delta_state": 8.0, "conv": 0.5, "decay_gate": 0.4,
          "gate_norm": 0.25, "experts": 5.0, "attn": 0.5}
    monkeypatch.setattr(spans, "scope_ms",
                        lambda src, *s: sum(ms[x] for x in s))
    monkeypatch.setattr(spans, "spans_of", lambda src: {"ticks": 1})
    src = {"config": published, "device_kind": "TPU v5 lite",
           "trace_times": {"ta": 10.0, "tb": 13.0},
           "trace": {"modules": {"_fused_tick_greedy": {"n": 1,
                                                        "s": 0.020}}},
           "_chunk_spans": {"calls": 1,
                            "by_scope": {"chunk_delta_state": 0.012}},
           "window": (0.0, 51.0),
           "records": [
               {"prompt": [1] * 999, "due": 1.0,
                "token_times": [9.0, 11.0]},
               {"prompt": [1] * 99, "due": 2.0,
                "token_times": [9.5, 12.0, 14.0]},
               {"prompt": [1] * 102, "due": 3.0, "token_times": [10.5]}],
           "snaps": {
               "w0": {"engines": [{"moe_experts_hit": 0,
                                   "moe_layer_ticks": 0,
                                   "moe_rows_routed_here": 100,
                                   "active_slot_steps": 50,
                                   "state_rows_updated": 0,
                                   "state_layer_ticks": 0,
                                   "prefill_chunks": 4}]},
               "w1": {"engines": [{"moe_experts_hit": 240,
                                   "moe_layer_ticks": 12,
                                   "moe_rows_routed_here": 500,
                                   "active_slot_steps": 150,
                                   "state_rows_updated": 2400,
                                   "state_layer_ticks": 1200,
                                   "prefill_chunks": 10}]}}}
    assert readers_ling.kda_ms(src) == 9.15
    assert readers_ling.kda_state_ms(src) == 8.0
    state = 2 * 12 * 2 * 32 * 128 * 128 * 4    # rows x layers x r/w bytes
    assert readers_ling.kda_state_membw_roofline(src) == pytest.approx(
        100 * state / 819e9 / 8e-3)
    assert readers_ling.kda_chunk_ms(src) == 12.0
    flops = 200 * 12 * 32 * 6 * 128 * 128
    assert readers_ling.kda_chunk_flops_roofline(src) == pytest.approx(
        100 * flops / 197e12 / 12e-3)
    experts = 20 * 12 * 3 * 2560 * 768 * 2
    assert readers_ling.experts_membw_roofline(src) == pytest.approx(
        100 * experts / 819e9 / 5e-3)
    latent = (1000 + 100) * 2 * 576 * 2
    assert readers_ling.mla_attn_roofline(src) == pytest.approx(
        100 * latent / 819e9 / 0.5e-3)      # bytes bound at 32 heads
    weights = roofline_ling.weight_bytes_outside_experts(published)
    assert readers_ling.tick_membw_roofline(src) == pytest.approx(
        100 * (weights + state + experts + latent) / 819e9 / 20e-3)
    assert readers_ling.rows_routed_here_share(src) == pytest.approx(
        100 * 400 / (100 * 12))


def test_the_prompt_calls_ops_are_read_by_scope(monkeypatch):
    """``chunk_spans_of``: the ops inside ``_chunk_prefill*`` modules by
    the program's scope (``spans.reduce_spans`` reads the ticks' only):
    a prompt call of 10 ms with 4 ms under ``chunk_delta_state`` and 2
    under ``conv``, and a tick whose ops are not counted."""
    from benchmarks.harness import spans, trace
    planes = {"/device:TPU:0": {
        "modules": [("jit__chunk_prefill_packed(1)", 0.0, 0.010),
                    ("jit__fused_tick_greedy(2)", 0.020, 0.010)],
        "ops": [("%a = f32[8] fusion(", 0.001, 0.004),
                ("%b = f32[8] fusion(", 0.006, 0.002),
                ("%c = f32[8] fusion(", 0.021, 0.003)]}}
    names = {"/device:TPU:0": {
        "%a = f32[8] fusion(": "jit(_chunk_prefill_packed)/chunk_delta_state/dot:",
        "%b = f32[8] fusion(": "jit(_chunk_prefill_packed)/conv/mul:",
        "%c = f32[8] fusion(": "jit(_fused_tick_greedy)/delta_state/x:"}}
    monkeypatch.setattr(spans, "find_trace", lambda: "a.xplane.pb")
    monkeypatch.setattr(trace, "read_planes", lambda path: planes)
    monkeypatch.setattr(spans, "op_names", lambda path: names)
    src = {"config": {"kda_lower_bound": -5, "layer_group_size": 6,
                      "kv_lora_rank": 512, "num_experts_published": 512}}
    got = readers_ling.chunk_spans_of(src)
    assert got["calls"] == 1
    assert got["by_scope"] == pytest.approx(
        {"chunk_delta_state": 0.004, "conv": 0.002})
    assert readers_ling.kda_chunk_ms(src) == pytest.approx(4.0)
    # no trace, or a trace without prompt calls: nothing, and no error
    monkeypatch.setattr(spans, "find_trace", lambda: None)
    assert readers_ling.kda_chunk_ms({"config": src["config"]}) is None


@pytest.fixture(scope="module")
def served(interpret):
    """A tiny engine's own tokens and logprobs through chunked prefill
    (one, three and five chunks) and decode, with the benchmark's seeded
    weights."""
    import jax
    from paddle_tpu.generation.paged import PagedEngine
    config = cell.load_json(os.path.join(
        tiny.DATA, "configs", "ling-hybrid-tiny.json"))
    model_mod = cell.load_model(config)
    model = model_mod.build(config, 5, jax.devices()[0])
    engine = PagedEngine(model, **config["engine"])
    rng = np.random.default_rng(3)
    sample = []
    for i, n in enumerate((5, 47, 70)):
        prompt = rng.integers(1, 256, n).tolist()
        engine.submit(f"r{i}", prompt, max_new_tokens=12)
        engine.run()
        sample.append({"prompt": prompt, "tokens": engine.results[f"r{i}"],
                       "lps": engine.logprobs[f"r{i}"]})
    return config, model_mod, engine, sample


def test_the_reference_agrees_and_the_controls_do_not(served):
    import jax
    config, model_mod, engine, sample = served
    assert engine.decode_route() == "ragged"
    # per period two linear layers' (state, tail) and a latent layer's
    # one pool; the two counters of the prompt calls behind them
    assert [len(p) for p in engine.pools] == [2, 2, 1] * 2 + [1]
    assert engine.pools[0][0].shape == (4, 1, 16, 128)     # 8 heads a row
    assert engine.pools[2][0].shape == (65, 8, 128)        # a latent row
    st = engine.stats
    assert st["state_kernel_ticks"] == st["state_layer_ticks"] > 0
    nums = verify.numbers(model_mod, engine.params, config, sample)
    assert nums["tokens"] == 36 and nums["finite"]
    assert verify.judge(nums, config["limits"]) == []
    for mode in ("int8", "bf16_state"):
        control = verify.control_numbers(model_mod, engine.params, config,
                                         sample, mode=mode)
        assert control["logprob_rms"] > 3 * config["limits"]["logprob_rms"]
        assert verify.judge(dict(nums, **{k: control[k] for k in (
            "argmax_gap_max", "logprob_rms")}), config["limits"])
    # weights are the benchmark's own, a pure function of the seed
    again = model_mod.build(config, 5, jax.devices()[0]).functional()[1]
    other = model_mod.build(config, 6, jax.devices()[0]).functional()[1]
    for k in ("model.layers.1.linear_attn.A_log",
              "model.layers.0.linear_attn.conv_weight",
              "model.layers.3.linear_attn.dt_bias",
              "model.layers.2.self_attn.g_proj.weight",
              "model.layers.1.mlp.expert_bias"):
        assert np.array_equal(engine.params[k], again[k])
        assert not np.array_equal(engine.params[k], other[k])
    get = lambda end: np.concatenate([                  # noqa: E731
        np.asarray(v, np.float64).ravel()
        for k, v in engine.params.items() if k.endswith(end)])
    assert np.abs(get(".A_log")).max() <= model_mod.A_LOG_HALF
    bias = get(".dt_bias")
    assert model_mod.DT_BIAS_MIN <= bias.min() < -10 and -2 < bias.max() <= 0
    taps = get(".conv_weight")
    assert 0.8 * model_mod.CONV_STD < taps.std() < 1.2 * model_mod.CONV_STD


def test_a_heads_channels_decay_from_fast_to_slow(served):
    """``gate_spread``, what a configuration's ``assumed.weights``
    quotes: by layer, decays under a half and over 0.999 both, in most
    heads, and write strengths inside (0, 1)."""
    config, model_mod, engine, sample = served
    rows = model_mod.gate_spread(dict(engine.params), config,
                                 sample[2]["prompt"])
    assert [r["layer"] for r in rows] == [0, 1, 3, 4]
    for r in rows:
        assert r["alpha_under_half"] > 0.05 and r["alpha_over_0.999"] > 0.1
        assert r["heads_with_both"] >= 0.5
        assert 0 < r["beta_quantiles"][0] < r["beta_quantiles"][-1] < 1


def _untold(params, told):
    """The served weights with what ``told`` drops dropped."""
    import jax.numpy as jnp
    if told == "a tap":         # the oldest tap of every channel
        return {k: v.at[:, 0].set(0) if k.endswith(".conv_weight") else v
                for k, v in params.items()}
    if told == "the head gate":     # sigmoid(0): a half a head
        return {k: jnp.zeros_like(v)
                if k.endswith("self_attn.g_proj.weight") else v
                for k, v in params.items()}
    if told == "the selection bias":
        return {k: jnp.zeros_like(v) + (jnp.arange(v.shape[0]) % 2)
                if k.endswith(".expert_bias") else v
                for k, v in params.items()}
    return params


@pytest.mark.parametrize("told", [
    {"kda_lower_bound": -1},                # the decay's bound
    {"routed_scaling_factor": 1.0},
    {"topk_group": 4},                      # no group limit
    {"rms_norm_eps": 1e-2},                 # a norm's epsilon
    "a tap", "the head gate", "the selection bias",
], ids=lambda t: t if isinstance(t, str)
    else "-".join(f"{k}={v}" for k, v in t.items()))
def test_a_model_that_differs_from_the_served_one_is_not_correct(served,
                                                                 told):
    """The comparison that decides ``correct``, with the reference told
    of a model one term away from what was served: each fails at least
    one of the configuration's limits."""
    config, model_mod, engine, sample = served
    other = dict(config, **told) if isinstance(told, dict) else config
    nums = verify.numbers(model_mod, _untold(engine.params, told), other,
                          sample)
    assert verify.judge(nums, config["limits"])


def test_new_weights_in_place_are_the_seeds_and_trace_nothing_again(
        interpret):
    """``fill_weights`` is how ``chip_limits.py`` reads many seeds in one
    process (test_rehearsal_moe.py says what it must keep)."""
    import jax
    spec = cell.cell_spec(tiny_ling.manifest(), tiny_ling.CELL,
                          data_dir=tiny.DATA)
    model_mod = cell.load_model(spec["config"])
    engine = cell.build_engine(model_mod, spec, 5, jax.devices()[0], False)
    before = cell.jit_cache_sizes([engine])
    old = engine.params
    engine.params = model_mod.fill_weights(engine.params, 6)
    assert type(engine.params) is type(old)
    assert list(engine.params) == list(old)
    engine.submit("r", list(range(1, 76)), max_new_tokens=4)
    engine.run()
    assert cell.jit_cache_sizes([engine]) == before
    built = model_mod.build(spec["config"], 6,
                            jax.devices()[0]).functional()[1]
    assert all(np.array_equal(engine.params[k], built[k]) for k in built)


# ---------------------------------------------------------------- the counts
def test_the_configuration_is_the_catalogs_but_for_its_cuts(published):
    """Every number of the catalog's ``config`` under the same key, but
    for the four keys ``reduced`` lists (checked against the issue's
    arithmetic where the catalog is not installed)."""
    assert set(published["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_nextn_predict_layers"}
    widths = {"hidden_size": 2560, "intermediate_size": 6144,
              "moe_intermediate_size": 768, "head_dim": 128,
              "num_attention_heads": 32, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "num_experts_per_tok": 8, "n_group": 8,
              "topk_group": 4, "short_conv_kernel_size": 4,
              "layer_group_size": 6, "first_k_dense_replace": 2,
              "kda_lower_bound": -5, "rope_theta": 6000000,
              "routed_scaling_factor": 2.5}
    assert {k: published[k] for k in widths} == widths
    assert (published["num_hidden_layers"], published["num_experts"],
            published["num_experts_published"], published["vocab_size"],
            published["num_nextn_predict_layers"]) == (14, 32, 512, 19648, 0)
    assert len(published["expert_swiglu_limit_list"]) == 42
    assert not any(published["expert_swiglu_limit_list"][:14])
    assert not any(published["share_expert_swiglu_limit_list"][:14])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        import json
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ling-3.0-flash")
        differ = {k for k, v in row["config"].items()
                  if published.get(k) != v}
        assert differ == set(published["reduced"])
        assert published["source"] == row["source_url"]


def test_layer_counts(published):
    assert roofline_ling.layers_of(published, False) == 12
    assert roofline_ling.layers_of(published, True) == 2
    assert roofline_ling.expert_layers(published) == 12


def test_state_latent_and_expert_bytes(published):
    # 32 heads x 128 x 128 float32, read and written
    assert roofline_ling.state_bytes_per_row(published) \
        == 2 * 32 * 128 * 128 * 4 == 4_194_304
    assert roofline_ling.latent_bytes_per_token(published) == 576 * 2
    assert roofline_ling.expert_bytes(published) == 3 * 2560 * 768 * 2 \
        == 11_796_480


def test_weight_bytes_outside_experts(published):
    kda = (5 * 2560 * 4096 + 4096 * 2560 + 2560 * 32 + 3 * 4096 * 4
           + 32 + 4096 + 128)
    mla = (2560 * 32 * 192 + 2560 * 576 + 512 + 512 * 32 * 256
           + 32 * 128 * 2560 + 2560 * 32)
    assert kda == 63_049_888 and mla == 31_965_696
    assert roofline_ling.mixer_params(published, False) == kda
    assert roofline_ling.mixer_params(published, True) == mla
    norms = 2 * 2560
    by_hand = 2 * (12 * (kda + norms) + 2 * (mla + norms)
                   + 2 * 3 * 2560 * 6144
                   + 12 * (2560 * 512 + 512 + 3 * 2560 * 768)
                   + 2560 + 2560 * 19648)
    assert roofline_ling.weight_bytes_outside_experts(published) == by_hand
    # the issue's mixers 1.64 + dense 0.19 + routers and shared 0.17 +
    # head 0.10 = 2.10 GB; held with the experts and the embedding 6.73
    assert by_hand == pytest.approx(2.10e9, rel=1e-2)
    assert by_hand + 12 * 32 * 11_796_480 + 2 * 2560 * 19648 \
        == pytest.approx(6.73e9, rel=2e-3)


def test_tick_bytes_and_chunk_flops(published):
    weights = roofline_ling.weight_bytes_outside_experts(published)
    assert roofline_ling.state_bytes(published, 128) \
        == 128 * 12 * 4_194_304
    assert roofline_ling.tick_bytes(published, 10, 1280, 3320.0, 1_500_000) \
        == (10 * weights + 1280 * 12 * 4_194_304 + 3320.0 * 11_796_480
            + 1_500_000 * 2 * 1152)
    # the issue's tick at 128 rows, 86.5% of the held experts hit and
    # contexts around 1,200: 13.0 GB less the tails it leaves out
    tick = roofline_ling.tick_bytes(published, 1, 128, 0.865 * 384,
                                    128 * 1200)
    assert tick == pytest.approx(12.8e9, rel=1e-2)
    assert roofline_ling.state_bytes(published, 128) / tick \
        == pytest.approx(0.5, abs=0.02)
    assert roofline_ling.chunk_delta_flops(published, 256) \
        == 256 * 12 * 32 * 6 * 128 * 128
    floor = roofline_ling.latent_attention_floor_s(
        published, 1000, {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12})
    assert floor == pytest.approx(1000 * 2 * 1152 / 819e9)
