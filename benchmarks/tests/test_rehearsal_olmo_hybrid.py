"""Olmo-Hybrid's rehearsal at tiny widths on the CPU, the kernel in
interpret mode: its cell through ``cell.run_cell`` and the real client
child (prompts of three to five chunks, the state carried from each to
the next), and what decides ``correct`` shown to fail: the int8 control,
a token altered where it is produced, and the reference told of a model
that differs from the served one by one term of the linear layer. Then
each count of ``harness/roofline_olmo_hybrid.py`` against one done by
hand at the published widths, and the new readers on a fixture."""
import os
import time

import numpy as np
import pytest

from benchmarks.harness import (cell, readers_olmo_hybrid,
                                roofline_olmo_hybrid, verify)
from benchmarks.tests import tiny, tiny_olmo_hybrid

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
GENERIC = {"tick_commit_ms.sat", "tick_dispatch_ms.sat", "tick_emit_ms.sat",
           "idle_unnamed_share.sat", "tick_unscoped_share.sat"}
NEW_TRACED = {"tick_linattn_ms.sat", "tick_delta_state_ms.sat",
              "delta_state_membw_roofline.sat", "hybrid_attn_roofline.sat",
              "hybrid_tick_membw_roofline.sat"}


@pytest.fixture(scope="module")
def interpret():
    old = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    yield
    if old is None:
        del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
    else:
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = old


def run(trace=False, tamper=None, seed=2**31 + 19):
    return cell.run_cell(tiny_olmo_hybrid.manifest(), tiny_olmo_hybrid.CELL,
                         seed, 10.0, trace, time.monotonic(),
                         data_dir=tiny.DATA, require_tpu=False,
                         tamper=tamper)


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
    metric is read from this run's own counters, and the accepted
    ``.sat`` metrics the committed manifest lists the cell under read as
    they do on the chip. The metrics of the linear layers' scopes find
    no ``conv`` / ``delta_state`` / ``gate_norm`` op in that trace,
    return nothing and raise nothing, as on a program that lacks the
    scopes."""
    from benchmarks.harness import peaks, trace
    fixture = os.path.join(tiny.DATA, "v5e_scoped.xplane.pb")
    monkeypatch.setattr(trace, "find_xplane", lambda logdir: fixture)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    traced = run(trace=True)
    assert traced["correct"] is True
    got = traced["metrics"]
    # prompts of 40-70 tokens in chunks of 16: 3-5 segments, one reset
    assert 100 * 2 / 3 <= got["delta_carry_share.sat"]["value"] <= 80
    assert got["chunk_pack_rows.sat"]["value"] == 1.0   # prompts over a chunk
    assert GENERIC | {"batch_occupancy", "tick_runahead_share.sat",
                      "tick_attn_ms.sat"} <= set(got)
    # the recorded trace's `attn` ops are read as the full layers'
    # calls, and its tick modules' time by the whole tick's share
    assert set(got) & NEW_TRACED == {"hybrid_attn_roofline.sat",
                                     "hybrid_tick_membw_roofline.sat"}
    assert not set(got) & {"tick_membw_roofline.sat",
                           "ragged_attn_roofline.sat",
                           "tick_kv_layout_ms.sat"}


def test_another_familys_run_gives_the_new_readers_nothing():
    """What the driver's traced runs of the parent see: a program with
    no such counters and a configuration without the family's keys."""
    src = {"config": {"kv_lora_rank": 512, "n_routed_experts": 16},
           "snaps": {"w0": {"engines": [{}]}, "w1": {"engines": [{}]}}}
    for read in (readers_olmo_hybrid.linattn_ms,
                 readers_olmo_hybrid.delta_state_ms,
                 readers_olmo_hybrid.delta_state_membw_roofline,
                 readers_olmo_hybrid.attn_roofline,
                 readers_olmo_hybrid.tick_membw_roofline,
                 readers_olmo_hybrid.delta_carry_share):
        assert read(src) is None
    # the family's configuration over a program without the counters
    src["config"] = {"layer_types": [], "linear_key_head_dim": 96,
                     "linear_value_head_dim": 192}
    assert readers_olmo_hybrid.delta_carry_share(src) is None


@pytest.fixture(scope="module")
def published():
    return cell.load_json(os.path.join(
        tiny.ROOT, "benchmarks", "configs", "olmo-hybrid-7b-d16.json"))


def test_the_readers_on_a_fixture(monkeypatch, published):
    """One traced tick of 2 ms under ``delta_state``, 0.5 under ``conv``,
    0.25 under ``gate_norm`` and 1 ms under ``attn``, two live rows at
    contexts 1,000 and 100, at the published widths."""
    from benchmarks.harness import spans
    ms = {"delta_state": 2.0, "conv": 0.5, "gate_norm": 0.25, "attn": 1.0}
    monkeypatch.setattr(spans, "scope_ms",
                        lambda src, *s: sum(ms[x] for x in s))
    monkeypatch.setattr(spans, "spans_of", lambda src: {"ticks": 1})
    src = {"config": published, "device_kind": "TPU v5 lite",
           "trace_times": {"ta": 10.0, "tb": 13.0},
           "trace": {"modules": {"_fused_tick_greedy": {"n": 1,
                                                        "s": 0.020}}},
           "records": [
               {"prompt": [1] * 999, "token_times": [9.0, 11.0]},
               {"prompt": [1] * 99, "token_times": [9.5, 12.0, 14.0]}],
           "snaps": {
               "w0": {"engines": [{"state_carries": 10,
                                   "state_resets": 5}]},
               "w1": {"engines": [{"state_carries": 50,
                                   "state_resets": 15}]}}}
    assert readers_olmo_hybrid.linattn_ms(src) == 2.75
    assert readers_olmo_hybrid.delta_state_ms(src) == 2.0
    state = 2 * 12 * 2 * 30 * 96 * 192 * 4     # rows x layers x r/w bytes
    whole = (1000 + 100) * 4 * 15_360
    assert readers_olmo_hybrid.delta_state_membw_roofline(src) \
        == pytest.approx(100 * state / 819e9 / 2e-3)
    assert readers_olmo_hybrid.attn_roofline(src) == pytest.approx(
        100 * whole / 819e9 / 1e-3)
    weights = roofline_olmo_hybrid.weight_bytes_per_tick(published)
    assert readers_olmo_hybrid.tick_membw_roofline(src) == pytest.approx(
        100 * (weights + state + whole) / 819e9 / 20e-3)
    assert readers_olmo_hybrid.delta_carry_share(src) == 80.0


@pytest.fixture(scope="module")
def served(interpret):
    """A tiny engine's own tokens and logprobs through chunked prefill
    (one, three and five chunks) and decode, with the benchmark's seeded
    weights."""
    import jax
    from paddle_tpu.generation.paged import PagedEngine
    config = cell.load_json(os.path.join(
        tiny.DATA, "configs", "olmo-hybrid-tiny.json"))
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


def test_the_reference_agrees_and_the_control_does_not(served):
    import jax
    config, model_mod, engine, sample = served
    assert engine.decode_route() == "ragged"
    # three linear layers' (state, tail), a full layer's (K, V), and the
    # two counters of the prompt calls
    assert [len(p) for p in engine.pools] == [2, 2, 2, 2, 1]
    assert engine.pools[0][0].shape == (4, 1, 8, 128)      # 8 heads a row
    assert engine.pools[0][1].shape == (4, 3, 256)
    nums = verify.numbers(model_mod, engine.params, config, sample)
    assert nums["tokens"] == 36 and nums["finite"]
    assert verify.judge(nums, config["limits"]) == []
    control = verify.control_numbers(model_mod, engine.params, config,
                                     sample)
    assert control["logprob_rms"] > 3 * config["limits"]["logprob_rms"]
    assert verify.judge(dict(nums, **{k: control[k] for k in (
        "argmax_gap_max", "logprob_rms")}), config["limits"])
    # weights are the benchmark's own, a pure function of the seed
    again = model_mod.build(config, 5, jax.devices()[0]).functional()[1]
    other = model_mod.build(config, 6, jax.devices()[0]).functional()[1]
    for k in ("model.layers.1.linear_attn.A_log",
              "model.layers.0.linear_attn.conv_weight",
              "model.layers.2.linear_attn.dt_bias",
              "model.layers.3.self_attn.q_norm.weight",
              "model.layers.0.mlp.up_proj.weight"):
        assert np.array_equal(engine.params[k], again[k])
        assert not np.array_equal(engine.params[k], other[k])
    get = lambda end: np.concatenate([                  # noqa: E731
        np.asarray(v, np.float64).ravel()
        for k, v in engine.params.items() if k.endswith(end)])
    # Gated DeltaNet's start: A in (0, 16), dt in (0.001, 0.1); taps wide
    A, dt = np.exp(get(".A_log")), np.log1p(np.exp(get(".dt_bias")))
    assert 0 < A.min() and A.max() <= model_mod.A_MAX and A.std() > 2
    assert model_mod.DT_MIN * 0.99 <= dt.min() \
        and dt.max() <= model_mod.DT_MAX * 1.01
    taps = get(".conv_weight")
    assert 0.8 * model_mod.CONV_STD < taps.std() < 1.2 * model_mod.CONV_STD


def _untold(params, told):
    """The served weights with what ``told`` drops dropped."""
    import jax.numpy as jnp
    if told == "a tap":         # the oldest tap of every channel
        return {k: v.at[:, 0].set(0) if k.endswith(".conv_weight") else v
                for k, v in params.items()}
    if told == "the decay":     # alpha pinned at 1
        return {k: jnp.full_like(v, -30.0) if k.endswith(".A_log") else v
                for k, v in params.items()}
    return params


@pytest.mark.parametrize("told", [
    {"linear_allow_neg_eigval": False},     # beta without its factor 2
    {"rms_norm_eps": 1e-2},                 # a norm's epsilon
    "a tap", "the decay",
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


def test_a_swapped_token_is_not_correct(served):
    config, model_mod, engine, sample = served
    broken = [dict(r, tokens=list(r["tokens"])) for r in sample]
    broken[1]["tokens"][3] = (broken[1]["tokens"][3] + 101) % 256
    nums = verify.numbers(model_mod, engine.params, config, broken)
    assert verify.judge(nums, config["limits"])


def test_new_weights_in_place_are_the_seeds_and_trace_nothing_again(
        interpret):
    """``fill_weights`` is how ``chip_limits.py`` reads many seeds in one
    process (test_rehearsal_moe.py says what it must keep); and a prompt
    of five chunks after a warm-up of two reaches no new program: a
    continuation's position is an argument, not a shape."""
    import jax
    spec = cell.cell_spec(tiny_olmo_hybrid.manifest(), tiny_olmo_hybrid.CELL,
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
def test_layer_counts(published):
    assert roofline_olmo_hybrid.layers_of(published, True) == 12
    assert roofline_olmo_hybrid.layers_of(published, False) == 4


def test_state_and_kv_bytes(published):
    # 30 heads x 96 x 192 float32, read and written
    assert roofline_olmo_hybrid.state_bytes_per_row(published) \
        == 2 * 30 * 96 * 192 * 4 == 4_423_680
    # 30 kv heads x 128 x (K + V) x 2 B: no grouping
    assert roofline_olmo_hybrid.kv_bytes_per_token(published) == 15_360
    assert roofline_olmo_hybrid.conv_channels(published) == 11_520


def test_weight_bytes_per_tick(published):
    full = 4 * 3840 * 3840 + 2 * 3840
    linear = (3840 * 11520 + 2 * 3840 * 5760 + 2 * 3840 * 30
              + 11520 * 4 + 2 * 30 + 192)
    assert full == 58_990_080 and linear == 88_750_332
    assert roofline_olmo_hybrid.mixer_params(published, False) == full
    assert roofline_olmo_hybrid.mixer_params(published, True) == linear
    ffn = 3 * 3840 * 11008 + 2 * 3840
    by_hand = 2 * (4 * (full + ffn) + 12 * (linear + ffn)
                   + 3840 + 3840 * 100352)
    assert roofline_olmo_hybrid.weight_bytes_per_tick(published) == by_hand
    # the issue's 7.43 GB a tick, and 8.20 GB held with the embedding
    assert by_hand == pytest.approx(7.43e9, rel=2e-3)
    assert by_hand + 2 * 3840 * 100352 == pytest.approx(8.20e9, rel=2e-3)


def test_state_attention_and_tick_bytes(published):
    assert roofline_olmo_hybrid.delta_state_bytes(published, 32) \
        == 32 * 12 * 4_423_680
    assert roofline_olmo_hybrid.attention_bytes(published, 32 * 1470) \
        == 32 * 1470 * 4 * 15_360
    weights = roofline_olmo_hybrid.weight_bytes_per_tick(published)
    assert roofline_olmo_hybrid.tick_bytes(published, 10, 320, 470_400) \
        == (10 * weights + 320 * 12 * 4_423_680 + 470_400 * 61_440)
    # the issue's tick at 32 rows and contexts around 1,470: 12.0 GB
    assert roofline_olmo_hybrid.tick_bytes(published, 1, 32, 32 * 1470) \
        == pytest.approx(12.0e9, rel=5e-3)


def test_a_bfloat16_state_is_read_by_the_other_control(served):
    """``mode="bf16_state"``: every product in float32 and the state
    rounded to bfloat16 after each position. At tiny widths it reads far
    over the float32 program's own error (what it reads at the published
    widths is in the configuration's ``limits_from``)."""
    config, model_mod, engine, sample = served
    nums = verify.numbers(model_mod, engine.params, config, sample)
    half = verify.control_numbers(model_mod, engine.params, config, sample,
                                  mode="bf16_state")
    assert half["logprob_rms"] > 20 * nums["logprob_rms"]
