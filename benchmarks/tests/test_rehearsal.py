"""The harness end to end at ``qwen2_tiny`` widths on the CPU, kernels
in interpret mode: the code path of ``run.py`` with its look for a chip
skipped, through the real client child. And what decides ``correct``,
shown to fail: the reference against the engine (tied and untied), the
lower-precision control, a token altered where it is produced."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks.harness import cell, verify
from benchmarks.tests import tiny

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def interpret():
    old = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    yield
    if old is None:
        del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
    else:
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = old


def run(workload, trace=False, tamper=None, seed=2**31 + 7):
    return cell.run_cell(tiny.tiny_manifest(), workload, seed, 5.0, trace,
                         time.monotonic(), data_dir=tiny.DATA,
                         require_tpu=False, tamper=tamper)


def records_of(workload):
    return cell.load_json(os.path.join(
        tiny.ROOT, ".bench_out", workload, "client.json"))


@pytest.fixture(scope="module")
def sessions_run(interpret):
    result = run("tiny.sessions")
    return result, records_of("tiny.sessions")


def test_a_rate_cell_runs_through_the_client_child(sessions_run):
    result, client = sessions_run
    assert set(result) == KEYS and result["correct"] is True
    assert set(result["metrics"]) == {"ttft_p50_ms", "gap_p95_ms",
                                      "setup_s"}
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in result["metrics"].values())
    assert result["failed"] == 0 and result["attempted"] >= 10
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    json.loads(json.dumps(result))
    recs = client["records"]
    # lead-in requests were sent and answered, and judged in nothing
    assert any(r["due"] < client["w0"] for r in recs)
    assert result["attempted"] == sum(
        client["w0"] <= r["due"] < client["w1"] for r in recs)
    # later turns carry the history, and the prefix cache adopted it
    later = [r for r in recs if r["turn"] > 0]
    assert later and all(len(r["prompt"]) > 32 for r in later)
    assert {r["greedy"] for r in recs} == {True, False}


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_a_token_altered_where_it_is_produced_is_not_correct(interpret,
                                                             greedy):
    """The rest of a run driven with the timed path broken underneath:
    the last served token of each greedy request swapped for another
    breaks ``argmax_gap_max``; the sampled requests' tokens swapped,
    as a sampler that forgot its filters would give them (one alone has
    one chance in five of lying in a top 50 of 256), break
    ``sampled_set_gap_max``."""
    def tamper(records):
        for r in records:
            if r["greedy"] == greedy and r["tokens"]:
                for i in range(1 if greedy else len(r["tokens"])):
                    r["tokens"][-1 - i] = r["final_tokens"][-1 - i] = \
                        (r["tokens"][-1 - i] + 101) % 256
    result = run("tiny.sessions", tamper=tamper)
    assert set(result) == KEYS and result["correct"] is False


def test_a_saturated_cell_and_its_traced_run(interpret, monkeypatch):
    result = run("tiny.batch")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    # the traced run: every per-layer metric of the cell, read from the
    # run's own counters and spans and from the recorded TPU trace (the
    # CPU's own trace has no device plane)
    from benchmarks.harness import peaks, trace
    fixture = os.path.join(tiny.DATA, "v5e_ticks.xplane.pb")
    monkeypatch.setattr(trace, "find_xplane", lambda logdir: fixture)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    traced = run("tiny.batch", trace=True)
    assert set(traced) == KEYS | {"breakdown"}
    want = {m["name"] for m in tiny.tiny_manifest()["per_layer"]
            if m["moves"] in ("tokens_per_s", "setup_s")}
    assert set(traced["metrics"]) == want
    assert traced["device"]["busy_s"] > 0
    assert traced["device"]["window_s"] >= traced["device"]["busy_s"]
    for key in ("device_ops", "idle_gaps"):
        assert 1 <= len(traced["breakdown"][key]) <= 10
    assert 60 <= traced["metrics"]["batch_occupancy"]["value"] <= 100


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_the_reference_agrees_with_the_engine_and_the_control_does_not(
        interpret, tied):
    """Engine against reference at tiny widths, straight (no gateway):
    greedy tokens and their logprobs. Then the control: the reference
    with int8 weights in the program's place misses both limits."""
    import jax
    from paddle_tpu.generation.paged import PagedEngine
    config = cell.load_json(os.path.join(
        tiny.DATA, "configs", "qwen2-tiny.json"))
    config["tie_word_embeddings"] = tied
    model_mod = cell.load_module(os.path.join(
        tiny.ROOT, "benchmarks", "models", "qwen2.py"), "t_model")
    model = model_mod.build(config, 5, jax.devices()[0])
    engine = PagedEngine(model, **config["engine"])
    rng = np.random.default_rng(3)
    sample = []
    for i, n in enumerate((5, 19, 40)):
        prompt = rng.integers(1, 256, n).tolist()
        engine.submit(f"r{i}", prompt, max_new_tokens=12)
        engine.run()
        sample.append({"prompt": prompt, "tokens": engine.results[f"r{i}"],
                       "lps": engine.logprobs[f"r{i}"]})
    kw = {"temperature": 0.8, "top_k": 50, "top_p": 0.95}
    drawn = []
    for i, n in enumerate((7, 33)):
        prompt = rng.integers(1, 256, n).tolist()
        engine.submit(f"s{i}", prompt, max_new_tokens=12, seed=11 + i, **kw)
        engine.run()
        drawn.append({"prompt": prompt, "tokens": engine.results[f"s{i}"],
                      "lps": engine.logprobs[f"s{i}"]})
    assert drawn[0]["tokens"] != drawn[1]["tokens"]
    nums = verify.numbers(model_mod, engine.params, config, sample, drawn, kw)
    assert nums["tokens"] == 36 and nums["sampled_tokens"] == 24
    assert nums["finite"] and nums["sampled_outside"] == 0
    assert verify.judge(nums, config["limits"]) == []
    # a number read without a limit is a failure, not a pass
    assert verify.judge(nums, {k: v for k, v in config["limits"].items()
                               if k != "sampled_set_gap_max"})
    # drawn with the filters forgotten (top_k and top_p off, a high
    # temperature): some token falls outside the reference's set
    engine.submit("loose", drawn[1]["prompt"], max_new_tokens=12, seed=5,
                  temperature=3.0)
    engine.run()
    loose = [{"prompt": drawn[1]["prompt"],
              "tokens": engine.results["loose"],
              "lps": engine.logprobs["loose"]}]
    wrong = verify.numbers(model_mod, engine.params, config, sample, loose,
                           kw)
    assert wrong["sampled_outside"] > 0
    assert wrong["sampled_set_gap_max"] > 100 * config["limits"][
        "sampled_set_gap_max"]
    control = verify.control_numbers(model_mod, engine.params, config, sample)
    assert control["logprob_rms"] > 3 * config["limits"]["logprob_rms"]
    broken = dict(nums, **{k: control[k] for k in
                           ("argmax_gap_max", "logprob_rms")})
    assert verify.judge(broken, config["limits"])
    # weights are the benchmark's own, a pure function of the seed
    again = model_mod.build(config, 5, jax.devices()[0]).functional()[1]
    other = model_mod.build(config, 6, jax.devices()[0]).functional()[1]
    k = "model.layers.0.self_attn.q_proj.bias"
    assert np.array_equal(engine.params[k], again[k])
    assert not np.array_equal(engine.params[k], other[k])
    assert float(np.std(np.asarray(engine.params[k]))) > 0.1


def test_run_py_refuses_the_cpu_and_the_interpreter():
    """No result line, non-zero exit: no TPU, or interpret mode on."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_PALLAS_INTERPRET", None)
    cmd = [sys.executable, os.path.join(tiny.ROOT, "benchmarks", "run.py"),
           "--workload", tiny.real_manifest()["workloads"][0]["name"],
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == "" and "TPU" in p.stderr
    p = subprocess.run(cmd, env=dict(env, PADDLE_TPU_PALLAS_INTERPRET="1"),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "PADDLE_TPU_PALLAS_INTERPRET" in p.stderr
