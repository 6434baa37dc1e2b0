"""MiMo-V2's rehearsal at tiny widths on the CPU, kernels in interpret
mode: its cell through ``cell.run_cell`` and the real client child (a
mix of prompts four to six windows deep, three to five chunks each),
and what decides ``correct`` shown to fail: the int8 control, a token
altered where it is produced, and the reference told of a model that
differs from the served one by a sink, by one position of the band, by
the value scale. Then each count of ``harness/roofline_mimo.py`` against
one done by hand at the published widths, and the new readers on a
fixture."""
import os
import time

import numpy as np
import pytest

from benchmarks.harness import cell, readers_mimo, roofline_mimo, verify
from benchmarks.tests import tiny, tiny_mimo

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
GENERIC = {"tick_commit_ms.sat", "tick_dispatch_ms.sat", "tick_emit_ms.sat",
           "idle_unnamed_share.sat", "tick_unscoped_share.sat"}
NEW_TRACED = {"tick_window_attn_ms.sat", "tick_full_attn_ms.sat",
              "window_attn_roofline.sat", "full_attn_roofline.sat",
              "swamoe_experts_membw_roofline.sat",
              "swamoe_tick_membw_roofline.sat"}


@pytest.fixture(scope="module")
def interpret():
    old = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    yield
    if old is None:
        del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
    else:
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = old


def run(trace=False, tamper=None, seed=2**31 + 17):
    return cell.run_cell(tiny_mimo.manifest(), tiny_mimo.CELL, seed, 10.0,
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
    metric is read from this run's own counters, and the accepted
    ``.sat`` metrics the committed manifest lists the cell under read as
    they do on the chip. The metrics of the window and expert scopes
    find no ``attn_window`` / ``experts`` op in that trace, return
    nothing and raise nothing, as on a program that lacks the scopes."""
    from benchmarks.harness import peaks, trace
    fixture = os.path.join(tiny.DATA, "v5e_scoped.xplane.pb")
    monkeypatch.setattr(trace, "find_xplane", lambda logdir: fixture)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    traced = run(trace=True)
    assert traced["correct"] is True
    got = traced["metrics"]
    # window 12 over pages of 8: a band is 2 or 3 pages, the ring 5
    assert 1 < got["window_blocks_per_row.sat"]["value"] <= 3
    assert 0 < got["experts_hit_share.sat"]["value"] <= 100
    assert got["chunk_pack_rows.sat"]["value"] == 1.0   # prompts over a chunk
    assert GENERIC | {"batch_occupancy", "tick_runahead_share.sat"} \
        <= set(got)
    # the recorded trace's `attn` ops are read as the full layers'
    # calls, and its tick modules' time by the whole tick's share
    assert set(got) & NEW_TRACED == {"tick_full_attn_ms.sat",
                                     "full_attn_roofline.sat",
                                     "swamoe_tick_membw_roofline.sat"}
    assert not set(got) & {"tick_attn_ms.sat", "tick_membw_roofline.sat",
                           "ragged_attn_roofline.sat"}


def test_another_familys_run_gives_the_new_readers_nothing():
    """What the driver's traced runs of the parent see: a program with
    no such counters and a configuration without the family's keys."""
    src = {"config": {"kv_lora_rank": 512, "n_routed_experts": 16},
           "snaps": {"w0": {"engines": [{}]}, "w1": {"engines": [{}]}}}
    for read in (readers_mimo.window_attn_ms, readers_mimo.full_attn_ms,
                 readers_mimo.window_attn_roofline,
                 readers_mimo.full_attn_roofline,
                 readers_mimo.experts_membw_roofline,
                 readers_mimo.tick_membw_roofline,
                 readers_mimo.window_blocks_per_row):
        assert read(src) is None


def test_the_readers_on_a_fixture(monkeypatch):
    """One traced tick of 2 ms under ``attn_window`` and 1 ms under
    ``attn``, two live rows at contexts 1,000 and 100 (one over, one
    under the window), at the published widths."""
    from benchmarks.harness import spans
    config = cell.load_json(os.path.join(
        tiny.ROOT, "benchmarks", "configs", "mimo-v2.5-ep16-d7.json"))
    ms = {"attn_window": 2.0, "attn": 1.0, "experts": 4.0}
    monkeypatch.setattr(spans, "scope_ms",
                        lambda src, *s: sum(ms[x] for x in s))
    monkeypatch.setattr(spans, "spans_of", lambda src: {"ticks": 1})
    src = {"config": config, "device_kind": "TPU v5 lite",
           "trace_times": {"ta": 10.0, "tb": 13.0},
           "trace": {"modules": {"_fused_tick_greedy": {"n": 1,
                                                        "s": 0.010}}},
           "records": [
               {"prompt": [1] * 999, "token_times": [9.0, 11.0]},
               {"prompt": [1] * 99, "token_times": [9.5, 12.0, 14.0]}],
           "snaps": {
               "w0": {"engines": [{"moe_experts_hit": 0,
                                   "moe_layer_ticks": 0,
                                   "kv_window_blocks": 0,
                                   "active_slot_steps": 0}]},
               "w1": {"engines": [{"moe_experts_hit": 84,
                                   "moe_layer_ticks": 6,
                                   "kv_window_blocks": 85,
                                   "active_slot_steps": 2}]}}}
    assert readers_mimo.window_attn_ms(src) == 2.0
    assert readers_mimo.full_attn_ms(src) == 1.0
    band = (128 + 100) * 5 * 5120           # tokens x layers x bytes
    whole = (1000 + 100) * 2 * 2560
    assert readers_mimo.window_attn_roofline(src) == pytest.approx(
        100 * band / 819e9 / 2e-3)
    assert readers_mimo.full_attn_roofline(src) == pytest.approx(
        100 * whole / 819e9 / 1e-3)
    assert readers_mimo.experts_membw_roofline(src) == pytest.approx(
        100 * 84 * 50_331_648 / 819e9 / 4e-3)
    outside = roofline_mimo.weight_bytes_outside_experts(config)
    assert readers_mimo.tick_membw_roofline(src) == pytest.approx(
        100 * (outside + 84 * 50_331_648 + band + whole) / 819e9 / 10e-3)
    assert readers_mimo.window_blocks_per_row(src) == 85 / (2 * 5)


@pytest.fixture(scope="module")
def served(interpret):
    """A tiny engine's own tokens and logprobs at contexts several
    windows deep, with the benchmark's seeded weights."""
    import jax
    from paddle_tpu.generation.paged import PagedEngine
    config = cell.load_json(os.path.join(
        tiny.DATA, "configs", "mimo-v2-tiny.json"))
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
    assert [len(p) for p in engine.pools] == [2, 2, 2]
    nums = verify.numbers(model_mod, engine.params, config, sample)
    assert nums["tokens"] == 36 and nums["finite"]
    assert verify.judge(nums, config["limits"]) == []
    control = verify.control_numbers(model_mod, engine.params, config,
                                     sample)
    assert control["logprob_rms"] > 3 * config["limits"]["logprob_rms"]
    assert verify.judge(dict(nums, **{k: control[k] for k in (
        "argmax_gap_max", "logprob_rms")}), config["limits"])
    # weights are the benchmark's own, a pure function of the seed: the
    # sinks at deviation 1, the selection bias at this family's
    again = model_mod.build(config, 5, jax.devices()[0]).functional()[1]
    other = model_mod.build(config, 6, jax.devices()[0]).functional()[1]
    for k in ("model.layers.1.mlp.expert_bias", "model.layers.1.mlp.w_up",
              "model.layers.2.self_attn.sink",
              "model.layers.0.mlp.up_proj.weight"):
        assert np.array_equal(engine.params[k], again[k])
        assert not np.array_equal(engine.params[k], other[k])
    assert engine.params["model.layers.1.mlp.w_up"].shape == (4, 64, 32)
    assert engine.params["model.layers.1.mlp.gate"].shape == (64, 8)
    assert "model.layers.0.self_attn.sink" not in engine.params
    sinks = np.concatenate([np.asarray(v) for k, v in engine.params.items()
                            if k.endswith(".sink")])
    assert 0.5 * model_mod.SINK_STD < sinks.std() < 2 * model_mod.SINK_STD
    bias = np.concatenate([np.asarray(v) for k, v in engine.params.items()
                           if k.endswith("expert_bias")])
    assert 0.3 * model_mod.BIAS_STD < bias.std() < 2 * model_mod.BIAS_STD


@pytest.mark.parametrize("told", [
    {"add_swa_attention_sink_bias": False},     # a dropped sink
    {"sliding_window": 13},                     # a band off by one
    {"sliding_window": 11},
    {"attention_value_scale": 1.0},             # the value scale left out
], ids=lambda t: "-".join(f"{k}={v}" for k, v in t.items()))
def test_a_model_that_differs_from_the_served_one_is_not_correct(served,
                                                                 told):
    """The comparison that decides ``correct``, with the reference told
    of a model one mechanism away from what was served: each fails at
    least one of the configuration's limits."""
    config, model_mod, engine, sample = served
    other = dict(config, **told)
    params = engine.params
    if told.get("add_swa_attention_sink_bias") is False:
        params = {k: v for k, v in params.items()
                  if not k.endswith(".sink")}
    nums = verify.numbers(model_mod, params, other, sample)
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
    spec = cell.cell_spec(tiny_mimo.manifest(), tiny_mimo.CELL,
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
@pytest.fixture(scope="module")
def published():
    return cell.load_json(os.path.join(
        tiny.ROOT, "benchmarks", "configs", "mimo-v2.5-ep16-d7.json"))


def test_layer_counts(published):
    assert roofline_mimo.layers_of(published, True) == 5
    assert roofline_mimo.layers_of(published, False) == 2
    assert roofline_mimo.expert_layers(published) == 6


def test_expert_bytes(published):
    # gate, up, down: 3 x 4096 x 2048 values of 2 bytes
    assert roofline_mimo.expert_bytes(published) == 50_331_648


def test_kv_bytes_per_token(published):
    assert roofline_mimo.kv_bytes_per_token(published, True) \
        == 8 * (192 + 128) * 2 == 5120
    assert roofline_mimo.kv_bytes_per_token(published, False) \
        == 4 * (192 + 128) * 2 == 2560


def test_weight_bytes_outside_experts(published):
    full = 4096 * (64 * 192 + 4 * 192 + 4 * 128) + 64 * 128 * 4096
    window = 4096 * (64 * 192 + 8 * 192 + 8 * 128) + 64 * 128 * 4096 + 64
    assert full == 89_128_960 and window == 94_371_904
    by_hand = 2 * (2 * (full + 2 * 4096) + 5 * (window + 2 * 4096)
                   + 3 * 4096 * 16384               # the dense FFN
                   + 6 * (4096 * 256 + 256)         # routers and biases
                   + 4096 + 4096 * 19072)           # final norm, head
    assert roofline_mimo.weight_bytes_outside_experts(published) == by_hand
    assert by_hand == pytest.approx(1.872e9, rel=1e-3)
    # with every held expert hit, the issue's 6.70 GB a tick
    assert by_hand + 96 * 50_331_648 == pytest.approx(6.70e9, rel=1e-3)


def test_attention_and_tick_bytes(published):
    assert roofline_mimo.window_attention_bytes(published, 64 * 128) \
        == 64 * 128 * 5 * 5120
    assert roofline_mimo.full_attention_bytes(published, 64 * 1600) \
        == 64 * 1600 * 2 * 2560
    outside = roofline_mimo.weight_bytes_outside_experts(published)
    assert roofline_mimo.tick_bytes(published, 10, 800, 81_920, 1_024_000) \
        == (10 * outside + 800 * 50_331_648 + 81_920 * 25_600
            + 1_024_000 * 5120)
