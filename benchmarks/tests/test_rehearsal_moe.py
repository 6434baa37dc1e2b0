"""The DeepSeek-V3 family's rehearsal at tiny widths on the CPU, kernels
in interpret mode: its cell through ``cell.run_cell`` and the real
client child, and what decides ``correct`` shown to fail: the int8
control, the selection bias dropped, a token altered where it is
produced. Then each count of ``harness/roofline_moe_mla.py`` against one
done by hand at the published widths."""
import os
import time

import numpy as np
import pytest

from benchmarks.harness import cell, roofline_moe_mla, verify
from benchmarks.tests import tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
REAL = {"gigachat3.1-702b-ep16-d6.batch-decode": "tiny-moe.batch",
        "qwen2-7b-d16.batch-decode": "tiny.batch"}
# the accepted `.sat` metrics the committed manifest also lists the new
# cell under: its layers run them
GENERIC = {"tick_commit_ms.sat", "tick_dispatch_ms.sat", "tick_emit_ms.sat",
           "idle_unnamed_share.sat", "tick_unscoped_share.sat",
           "tick_attn_ms.sat"}


def manifest() -> dict:
    """``tiny.tiny_manifest`` plus the new family's tiny configuration
    and cell. Each saturated metric lists the tiny twins of the cells
    the COMMITTED ``BENCHMARK.json`` lists it under, so the rehearsal
    runs the manifest that is checked in, at tiny widths."""
    m = tiny.tiny_manifest()
    m["configs"].append({
        "name": "tiny-moe",
        "file": "benchmarks/tests/data/configs/deepseek-v3-tiny.json"})
    m["workloads"].append({"name": "tiny-moe.batch", "config": "tiny-moe",
                           "traffic": "tiny-batch", "chips": 1})
    real = tiny.real_manifest()
    listed = {x["name"]: x.get("workloads")
              for x in real["end_to_end"] + real["per_layer"]}
    for metric in m["end_to_end"] + m["per_layer"]:
        cells = listed[metric["name"]]
        if cells is not None and set(cells) & set(REAL):
            metric["workloads"] = [REAL[c] for c in cells if c in REAL]
    return m


@pytest.fixture(scope="module")
def interpret():
    old = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    yield
    if old is None:
        del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
    else:
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = old


def run(trace=False, tamper=None, seed=2**31 + 11):
    return cell.run_cell(manifest(), "tiny-moe.batch", seed, 10.0, trace,
                         time.monotonic(), data_dir=tiny.DATA,
                         require_tpu=False, tamper=tamper)


def test_the_cell_runs_and_is_correct(interpret):
    result = run()
    assert set(result) == KEYS and result["correct"] is True
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    # 36 requests of 12 tokens: a fast machine serves most of them in
    # the half second before the window opens
    assert result["failed"] == 0 and result["attempted"] >= 1


def test_a_token_altered_where_it_is_produced_is_not_correct(interpret):
    def tamper(records):
        for r in records:
            if r["tokens"]:
                r["tokens"][-1] = r["final_tokens"][-1] = \
                    (r["tokens"][-1] + 101) % 256
    assert run(tamper=tamper)["correct"] is False


def test_the_traced_run_reads_the_counters_and_finds_no_scope(
        interpret, monkeypatch):
    """Against the recorded, scoped trace of a Qwen run: the counter
    metric is read from this run's own counters, and the accepted
    ``.sat`` metrics the committed manifest lists the cell under read
    as they do on the chip. The metrics of the expert scopes find no
    ``router`` / ``experts`` op in that trace, return nothing and raise
    nothing, as on a program that lacks the scopes (``attn`` that trace
    has, so the ones that read it find something)."""
    from benchmarks.harness import peaks, trace
    fixture = os.path.join(tiny.DATA, "v5e_scoped.xplane.pb")
    monkeypatch.setattr(trace, "find_xplane", lambda logdir: fixture)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    traced = run(trace=True)
    assert traced["correct"] is True
    got = set(traced["metrics"])
    assert "experts_hit_share.sat" in got and "batch_occupancy" in got
    assert 0 < traced["metrics"]["experts_hit_share.sat"]["value"] <= 100
    assert GENERIC <= got
    assert not got & {"tick_membw_roofline.sat", "ragged_attn_roofline.sat",
                      "tick_kv_layout_ms.sat"}
    assert not got & {"tick_moe_ms.sat", "tick_experts_ms.sat",
                      "experts_membw_roofline.sat"}


def test_the_reference_agrees_and_the_controls_do_not(interpret):
    import jax
    from paddle_tpu.generation.paged import PagedEngine
    config = cell.load_json(os.path.join(
        tiny.DATA, "configs", "deepseek-v3-tiny.json"))
    model_mod = cell.load_model(config)
    model = model_mod.build(config, 5, jax.devices()[0])
    engine = PagedEngine(model, **config["engine"])
    assert engine.decode_route() == "ragged"
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
    # the selection bias is drawn wide enough to matter: a reference
    # without it parts from the program
    unbiased = {k: v * 0 if k.endswith("expert_bias") else v
                for k, v in engine.params.items()}
    assert verify.judge(verify.numbers(model_mod, unbiased, config, sample),
                        config["limits"])
    # weights are the benchmark's own, a pure function of the seed
    again = model_mod.build(config, 5, jax.devices()[0]).functional()[1]
    other = model_mod.build(config, 6, jax.devices()[0]).functional()[1]
    for k in ("model.layers.1.mlp.expert_bias", "model.layers.2.mlp.w_up",
              "model.layers.0.mlp.up_proj.weight"):
        assert np.array_equal(engine.params[k], again[k])
        assert not np.array_equal(engine.params[k], other[k])
    assert engine.params["model.layers.1.mlp.w_up"].shape == (4, 64, 32)
    assert engine.params["model.layers.1.mlp.gate"].shape == (64, 16)


def test_new_weights_in_place_are_the_seeds_and_trace_nothing_again(
        interpret):
    """``fill_weights`` is how ``chip_limits.py`` reads many seeds in one
    process: it gives what ``build`` gives for the seed, and hands the
    engine the mapping type, key order and placement it had, so that the
    chunk and tick programs are not traced again inside the next
    window."""
    import jax
    spec = cell.cell_spec(manifest(), "tiny-moe.batch", data_dir=tiny.DATA)
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
        "gigachat3.1-702b-ep16-d6.json"))


def test_expert_bytes(published):
    # gate, up, down: 3 x 7168 x 2048 values of 2 bytes
    assert roofline_moe_mla.expert_bytes(published) == 88_080_384


def test_expert_layers(published):
    assert roofline_moe_mla.expert_layers(published) == 5   # 6 less 1 dense


def test_attention_weight_params(published):
    by_hand = (7168 * 1536 + 1536          # q_a and its norm
               + 1536 * 64 * 192           # q_b
               + 7168 * 576 + 512          # kv_a and the latent's norm
               + 512 * 64 * (128 + 192)    # kv_b
               + 64 * 192 * 7168)          # o
    assert by_hand == 132_581_376
    assert roofline_moe_mla.attention_weight_params(published) == by_hand


def test_weight_bytes_outside_experts(published):
    layer = 132_581_376 + 2 * 7168                  # attention, two norms
    by_hand = 2 * (6 * layer
                   + 3 * 7168 * 18432               # the dense layer's FFN
                   + 5 * (7168 * 256 + 256          # router and bias
                          + 3 * 7168 * 2048)        # shared expert
                   + 7168 + 7168 * 16032)           # final norm, head
    assert roofline_moe_mla.weight_bytes_outside_experts(published) == by_hand
    assert 3.0e9 < by_hand < 3.2e9


def test_latent_bytes_per_token(published):
    assert roofline_moe_mla.latent_bytes_per_token(published) \
        == 6 * 576 * 2 == 6912


def test_latent_attention_flops_per_token(published):
    assert roofline_moe_mla.latent_attention_flops_per_token(published) \
        == 6 * 64 * 2 * (576 + 512)


def test_latent_attention_floor_s(published):
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    tokens = 64 * 320
    got = roofline_moe_mla.latent_attention_floor_s(published, tokens, peak)
    assert got == pytest.approx(tokens * 6912 / 819e9)     # memory-bound
    slow_memory = dict(peak, bf16_flops=1e12)
    assert roofline_moe_mla.latent_attention_floor_s(
        published, tokens, slow_memory) == pytest.approx(
            tokens * 835_584 / 1e12)


def test_tick_bytes(published):
    outside = roofline_moe_mla.weight_bytes_outside_experts(published)
    assert roofline_moe_mla.tick_bytes(published, 10, 700, 204_800) == \
        10 * outside + 700 * 88_080_384 + 204_800 * 6912
