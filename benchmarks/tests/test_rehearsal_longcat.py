"""LongCat-Flash's rehearsal at tiny widths on the CPU, kernels in
interpret mode: its cell through ``cell.run_cell`` and the real client
child, and what decides ``correct`` shown to fail: the int8 control, a
token altered where it is produced. Then each count of
``harness/roofline_longcat.py`` against one done by hand at the
published widths."""
import os
import time

import numpy as np
import pytest

from benchmarks.harness import cell, roofline_longcat, verify
from benchmarks.tests import tiny, tiny_longcat

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# the accepted `.sat` metrics the committed manifest also lists the new
# cell under, whose readers need no scope the recorded Qwen trace lacks
GENERIC = {"tick_commit_ms.sat", "tick_dispatch_ms.sat", "tick_emit_ms.sat",
           "idle_unnamed_share.sat", "tick_unscoped_share.sat",
           "tick_attn_ms.sat"}


@pytest.fixture(scope="module")
def interpret():
    old = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    yield
    if old is None:
        del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
    else:
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = old


def run(trace=False, tamper=None, seed=2**31 + 13):
    return cell.run_cell(tiny_longcat.manifest(), tiny_longcat.CELL, seed,
                         10.0, trace, time.monotonic(), data_dir=tiny.DATA,
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
    """Against the recorded, scoped trace of a Qwen run: the two counter
    metrics are read from this run's own counters, and the accepted
    ``.sat`` metrics the committed manifest lists the cell under read as
    they do on the chip. The metrics of the expert scopes find no
    ``experts`` op in that trace, return nothing and raise nothing, as
    on a program that lacks the scopes."""
    from benchmarks.harness import peaks, trace
    fixture = os.path.join(tiny.DATA, "v5e_scoped.xplane.pb")
    monkeypatch.setattr(trace, "find_xplane", lambda logdir: fixture)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    traced = run(trace=True)
    assert traced["correct"] is True
    got = traced["metrics"]
    # 4 of the 12 router columns are zero-compute, 3 choices a token
    assert 0 < got["zero_expert_choice_share.sat"]["value"] < 100
    assert 0 < got["experts_hit_share.sat"]["value"] <= 100
    assert GENERIC | {"batch_occupancy", "tick_runahead_share.sat"} \
        <= set(got)
    assert not set(got) & {
        "tick_experts_ms.sat", "scmoe_experts_membw_roofline.sat",
        "experts_membw_roofline.sat", "mla_attn_roofline.sat",
        "moe_tick_membw_roofline.sat", "tick_membw_roofline.sat"}


def test_another_familys_run_gives_the_new_readers_nothing():
    """What the driver's traced runs of the parent see: a program with
    no such counters and a configuration without the family's keys."""
    from benchmarks.harness import readers_longcat
    src = {"config": {"kv_lora_rank": 512, "n_routed_experts": 16},
           "snaps": {"w0": {"engines": [{}]}, "w1": {"engines": [{}]}}}
    for read in (readers_longcat.zero_expert_choice_share,
                 readers_longcat.experts_membw_roofline,
                 readers_longcat.mla_attn_roofline,
                 readers_longcat.tick_membw_roofline):
        assert read(src) is None


def test_the_reference_agrees_and_the_control_does_not(interpret):
    import jax
    from paddle_tpu.generation.paged import PagedEngine
    config = cell.load_json(os.path.join(
        tiny.DATA, "configs", "longcat-flash-tiny.json"))
    model_mod = cell.load_model(config)
    model = model_mod.build(config, 5, jax.devices()[0])
    engine = PagedEngine(model, **config["engine"])
    assert engine.decode_route() == "ragged"
    assert len(engine.pools) == 2 * config["num_layers"]
    rng = np.random.default_rng(3)
    sample = []
    for i, n in enumerate((5, 19, 40)):
        prompt = rng.integers(1, 256, n).tolist()
        engine.submit(f"r{i}", prompt, max_new_tokens=12)
        engine.run()
        sample.append({"prompt": prompt, "tokens": engine.results[f"r{i}"],
                       "lps": engine.logprobs[f"r{i}"]})
    nums = verify.numbers(model_mod, engine.params, config, sample)
    assert nums["tokens"] == 36 and nums["finite"]
    assert verify.judge(nums, config["limits"]) == []
    control = verify.control_numbers(model_mod, engine.params, config,
                                     sample)
    assert control["logprob_rms"] > 3 * config["limits"]["logprob_rms"]
    assert verify.judge(dict(nums, **{k: control[k] for k in (
        "argmax_gap_max", "logprob_rms")}), config["limits"])
    # weights are the benchmark's own, a pure function of the seed, the
    # selection bias at this family's deviation
    again = model_mod.build(config, 5, jax.devices()[0]).functional()[1]
    other = model_mod.build(config, 6, jax.devices()[0]).functional()[1]
    for k in ("model.layers.1.moe.expert_bias", "model.layers.1.moe.w_up",
              "model.layers.0.halves.1.mlp.up_proj.weight"):
        assert np.array_equal(engine.params[k], again[k])
        assert not np.array_equal(engine.params[k], other[k])
    assert engine.params["model.layers.1.moe.w_up"].shape == (4, 64, 32)
    assert engine.params["model.layers.1.moe.gate"].shape == (64, 12)
    bias = np.concatenate([np.asarray(v) for k, v in engine.params.items()
                           if k.endswith("expert_bias")])
    assert 0.3 * model_mod.BIAS_STD < bias.std() < 2 * model_mod.BIAS_STD


def test_new_weights_in_place_are_the_seeds_and_trace_nothing_again(
        interpret):
    """``fill_weights`` is how ``chip_limits.py`` reads many seeds in one
    process (test_rehearsal_moe.py says what it must keep)."""
    import jax
    spec = cell.cell_spec(tiny_longcat.manifest(), tiny_longcat.CELL,
                          data_dir=tiny.DATA)
    model_mod = cell.load_model(spec["config"])
    engine = cell.build_engine(model_mod, spec, 5, jax.devices()[0], False)
    before = cell.jit_cache_sizes([engine])
    old = engine.params
    engine.params = model_mod.fill_weights(engine.params, 6)
    assert type(engine.params) is type(old)
    assert list(engine.params) == list(old)
    engine.submit("r", list(range(1, 20)), max_new_tokens=4)
    engine.run()
    assert cell.jit_cache_sizes([engine]) == before
    built = model_mod.build(spec["config"], 6,
                            jax.devices()[0]).functional()[1]
    assert all(np.array_equal(engine.params[k], built[k]) for k in built)


# ---------------------------------------------------------------- the counts
@pytest.fixture(scope="module")
def published():
    return cell.load_json(os.path.join(
        tiny.ROOT, "benchmarks", "configs",
        "longcat-flash-omni-ep32-d4.json"))


def test_expert_bytes(published):
    # gate, up, down: 3 x 6144 x 2048 values of 2 bytes
    assert roofline_longcat.expert_bytes(published) == 75_497_472


def test_weight_bytes_outside_experts(published):
    attention = (6144 * 1536 + 1536         # q_a and its norm
                 + 1536 * 64 * 192          # q_b
                 + 6144 * 576 + 512         # kv_a and the latent's norm
                 + 512 * 64 * (128 + 128)   # kv_b
                 + 64 * 128 * 6144)         # o
    assert attention == 90_572_800
    half = attention + 2 * 6144 + 3 * 6144 * 12288      # norms, dense FFN
    by_hand = 2 * (8 * half
                   + 4 * (6144 * 768 + 768)             # router and bias
                   + 6144 + 6144 * 16384)               # final norm, head
    assert roofline_longcat.weight_bytes_outside_experts(published) == by_hand
    # with every held expert hit, the issue's 10.14 GB a tick
    assert by_hand + 64 * 75_497_472 == pytest.approx(10.14e9, rel=1e-3)


def test_latent_bytes_and_flops_per_token(published):
    assert roofline_longcat.attentions(published) == 8
    assert roofline_longcat.latent_bytes_per_token(published) \
        == 8 * 576 * 2 == 9216
    assert roofline_longcat.latent_attention_flops_per_token(published) \
        == 8 * 64 * 2 * (576 + 512)


def test_latent_attention_floor_s(published):
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    tokens = 64 * 320
    got = roofline_longcat.latent_attention_floor_s(published, tokens, peak)
    assert got == pytest.approx(tokens * 9216 / 819e9)     # memory-bound
    assert roofline_longcat.latent_attention_floor_s(
        published, tokens, dict(peak, bf16_flops=1e12)) == pytest.approx(
            tokens * 1_114_112 / 1e12)


def test_tick_bytes(published):
    outside = roofline_longcat.weight_bytes_outside_experts(published)
    assert roofline_longcat.tick_bytes(published, 10, 400, 204_800) == \
        10 * outside + 400 * 75_497_472 + 204_800 * 9216
