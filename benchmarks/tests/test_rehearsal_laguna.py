"""Laguna's rehearsal at tiny widths on the CPU, kernels in interpret
mode: its cell through ``cell.run_cell`` and the real client child (a
mix of prompts four to six windows deep, three to five chunks each,
answers of unequal lengths), and what decides ``correct`` shown to fail:
the int8 control, a token altered where it is produced, and the
reference told of a model that differs from the served one by the gate,
by one position of the band, by the rotary scheme, by the router. Then
each count of ``harness/roofline_laguna.py`` against one done by hand at
the published widths, and the new readers on a fixture."""
import copy
import os
import time

import numpy as np
import pytest

from benchmarks.harness import cell, readers_laguna, roofline_laguna, verify
from benchmarks.tests import tiny, tiny_laguna

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
GENERIC = {"tick_commit_ms.sat", "tick_dispatch_ms.sat", "tick_emit_ms.sat",
           "idle_unnamed_share.sat", "tick_unscoped_share.sat"}
NEW_TRACED = {"gqamoe_window_attn_ms.sat", "gqamoe_full_attn_ms.sat",
              "tick_attn_gate_ms.sat", "gqamoe_window_attn_roofline.sat",
              "gqamoe_full_attn_roofline.sat",
              "gqamoe_experts_membw_roofline.sat",
              "gqamoe_tick_membw_roofline.sat", "chunk_full_attn_ms.sat",
              "chunk_full_attn_flops_roofline.sat"}
READERS = (readers_laguna.window_attn_ms, readers_laguna.full_attn_ms,
           readers_laguna.attn_gate_ms, readers_laguna.window_attn_roofline,
           readers_laguna.full_attn_roofline,
           readers_laguna.experts_membw_roofline,
           readers_laguna.tick_membw_roofline,
           readers_laguna.window_blocks_per_row,
           readers_laguna.chunk_full_attn_ms,
           readers_laguna.chunk_full_attn_flops_roofline,
           readers_laguna.chunk_live_share)


@pytest.fixture(scope="module")
def interpret():
    old = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
    yield
    if old is None:
        del os.environ["PADDLE_TPU_PALLAS_INTERPRET"]
    else:
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = old


def run(trace=False, tamper=None, seed=2**31 + 46):
    return cell.run_cell(tiny_laguna.manifest(), tiny_laguna.CELL, seed,
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
    """Against the recorded, scoped trace of a Qwen run: the counter
    metrics are read from this run's own counters, and the accepted
    ``.sat`` metrics the committed manifest lists the cell under read as
    they do on the chip. The metrics of the window, gate and expert
    scopes find no such op in that trace, return nothing and raise
    nothing, as on a program that lacks the scopes."""
    from benchmarks.harness import peaks, trace
    fixture = os.path.join(tiny.DATA, "v5e_scoped.xplane.pb")
    monkeypatch.setattr(trace, "find_xplane", lambda logdir: fixture)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    traced = run(trace=True)
    assert traced["correct"] is True
    got = traced["metrics"]
    # window 12 over pages of 8: a band is 2 or 3 pages, the ring 5
    assert 1 < got["gqamoe_window_blocks_per_row.sat"]["value"] <= 3
    assert 0 < got["experts_hit_share.sat"]["value"] <= 100
    assert 0 < got["experts_read_share.sat"]["value"] <= 100
    assert got["chunk_pack_rows.sat"]["value"] == 1.0   # prompts over a chunk
    # a table of 16 pages and a ring of 5 are one run each: a chunk
    # scores all 128 + 2 x 40 positions whatever is live
    assert 20 < got["chunk_live_share.sat"]["value"] < 100
    assert GENERIC | {"batch_occupancy", "tick_runahead_share.sat"} \
        <= set(got)
    # the recorded trace's `attn` ops are read as the full layers'
    # calls, and its tick modules' time by the whole tick's share
    assert set(got) & NEW_TRACED == {"gqamoe_full_attn_ms.sat",
                                     "gqamoe_full_attn_roofline.sat",
                                     "gqamoe_tick_membw_roofline.sat"}
    assert not set(got) & {"tick_attn_ms.sat", "tick_membw_roofline.sat",
                           "ragged_attn_roofline.sat",
                           "tick_window_attn_ms.sat",
                           "window_blocks_per_row.sat"}


def test_another_familys_run_gives_the_new_readers_nothing():
    """What the driver's traced runs of the parent see: a program with
    no such counters and a configuration without the family's keys."""
    src = {"config": {"kv_lora_rank": 512, "n_routed_experts": 16},
           "snaps": {"w0": {"engines": [{}]}, "w1": {"engines": [{}]}}}
    for read in READERS:
        assert read(src) is None


def test_the_parents_program_gives_the_counter_readers_nothing():
    """This family's configuration over a program without the chunk
    attention's counters (the parent commit under the driver's traced
    runs of the accepted cells never sees this; a later revert would)."""
    config = cell.load_json(os.path.join(
        tiny.ROOT, "benchmarks", "configs", "laguna-s-2.1-ep16-d9.json"))
    src = {"config": config,
           "snaps": {"w0": {"engines": [{"prefill_chunks": 0}]},
                     "w1": {"engines": [{"prefill_chunks": 9}]}}}
    assert readers_laguna.chunk_live_share(src) is None


def test_the_readers_on_a_fixture(monkeypatch):
    """One traced tick of 2 ms under ``attn_window``, 1 ms under ``attn``
    and 0.5 ms under ``attn_gate``; two traced prompt calls of 3 ms
    under ``chunk_attn`` in all; the window's counters of 10 ticks."""
    from benchmarks.harness import spans
    config = cell.load_json(os.path.join(
        tiny.ROOT, "benchmarks", "configs", "laguna-s-2.1-ep16-d9.json"))
    ms = {"attn_window": 2.0, "attn": 1.0, "attn_gate": 0.5, "experts": 4.0}
    monkeypatch.setattr(spans, "scope_ms",
                        lambda src, *s: sum(ms[x] for x in s))
    monkeypatch.setattr(spans, "spans_of", lambda src: {"ticks": 1})
    end = {"moe_experts_hit": 1120, "moe_layer_ticks": 80,
           "kv_window_blocks": 2 * 6 * 33 * 10, "active_slot_steps": 20,
           "kv_window_tokens": 10 * 2 * 6 * 512,
           "kv_context_tokens": 10 * 3 * (5000 + 6000),
           "decode_steps": 10, "prefill_chunks": 11,
           "chunk_attn_positions_live": 900,
           "chunk_attn_positions_scored": 1000}
    src = {"config": config, "device_kind": "TPU v5 lite",
           "window": (0.0, 51.0),
           "_chunk_spans": {"calls": 2, "by_scope": {"chunk_attn": 3e-3}},
           "trace": {"modules": {"_fused_tick_greedy": {"n": 1,
                                                        "s": 0.020}}},
           "records": [{"due": 1.0, "prompt": [1] * 5000},
                       {"due": 2.0, "prompt": [1] * 6000}],
           "snaps": {"w0": {"engines": [dict.fromkeys(end, 0)]},
                     "w1": {"engines": [end]}}}
    assert readers_laguna.window_attn_ms(src) == 2.0
    assert readers_laguna.full_attn_ms(src) == 1.0
    assert readers_laguna.attn_gate_ms(src) == 0.5
    band = 2 * 6 * 512 * 4096               # rows x layers x tokens x bytes
    whole = 3 * 11000 * 4096
    assert readers_laguna.window_attn_roofline(src) == pytest.approx(
        100 * band / 819e9 / 2e-3)
    assert readers_laguna.full_attn_roofline(src) == pytest.approx(
        100 * whole / 819e9 / 1e-3)
    expert = 3 * 3072 * 1024 * 2
    assert readers_laguna.experts_membw_roofline(src) == pytest.approx(
        100 * 14 * 8 * expert / 819e9 / 4e-3)
    outside = roofline_laguna.weight_bytes_outside_experts(config)
    assert readers_laguna.tick_membw_roofline(src) == pytest.approx(
        100 * (outside + 14 * 8 * expert + band + whole) / 819e9 / 20e-3)
    assert readers_laguna.window_blocks_per_row(src) == 33.0
    assert readers_laguna.chunk_full_attn_ms(src) == 1.5
    pairs = (5000 * 5001 + 6000 * 6001) / 2 * 2 / 11   # two traced calls
    assert readers_laguna.chunk_full_attn_flops_roofline(src) == \
        pytest.approx(100 * pairs * 144 * 4 * 128 / 197e12 / 3e-3)
    assert readers_laguna.chunk_live_share(src) == 90.0


@pytest.fixture(scope="module")
def served(interpret):
    """A tiny engine's own tokens and logprobs at contexts several
    windows deep, with the benchmark's seeded weights."""
    import jax
    from paddle_tpu.generation.paged import PagedEngine
    config = cell.load_json(os.path.join(
        tiny.DATA, "configs", "laguna-tiny.json"))
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
    # weights are the benchmark's own, a pure function of the seed; the
    # router has no selection bias
    again = model_mod.build(config, 5, jax.devices()[0]).functional()[1]
    other = model_mod.build(config, 6, jax.devices()[0]).functional()[1]
    for k in ("model.layers.1.mlp.gate", "model.layers.1.mlp.w_up",
              "model.layers.2.self_attn.g_proj.weight",
              "model.layers.1.mlp.shared_up_proj",
              "model.layers.0.mlp.up_proj.weight"):
        assert np.array_equal(engine.params[k], again[k])
        assert not np.array_equal(engine.params[k], other[k])
    assert engine.params["model.layers.1.mlp.w_up"].shape == (4, 64, 32)
    assert engine.params["model.layers.1.mlp.gate"].shape == (64, 8)
    assert engine.params["model.layers.0.self_attn.q_proj.weight"].shape \
        == (64, 96)
    assert engine.params["model.layers.1.self_attn.q_proj.weight"].shape \
        == (64, 160)
    bias = np.concatenate([np.asarray(v) for k, v in engine.params.items()
                           if k.endswith("expert_bias")])
    assert bias.size == 16 and not bias.any()


def _told(config, told):
    other = copy.deepcopy(config)
    other["rope_parameters"]["full_attention"].update(
        told.pop("rope_full", {}))
    other.update(told)
    return other


@pytest.mark.parametrize("told", [
    {"gating": False},                          # a dropped gate
    {"sliding_window": 13},                     # a band off by one
    {"sliding_window": 11},
    {"rope_full": {"rope_type": "default"}},    # plain rotary for YaRN
    {"rope_full": {"attention_factor": 1.0}},   # its factor left out
    {"moe_routed_scaling_factor": 1.0},         # an unscaled routed sum
], ids=lambda t: "-".join(f"{k}={v}" for k, v in t.items())[:50])
def test_a_model_that_differs_from_the_served_one_is_not_correct(served,
                                                                 told):
    """The comparison that decides ``correct``, with the reference told
    of a model one mechanism away from what was served: each fails at
    least one of the configuration's limits."""
    config, model_mod, engine, sample = served
    nums = verify.numbers(model_mod, engine.params,
                          _told(config, dict(told)), sample)
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
    continuation's position and its live length are arguments, not
    shapes."""
    import jax
    spec = cell.cell_spec(tiny_laguna.manifest(), tiny_laguna.CELL,
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
        tiny.ROOT, "benchmarks", "configs", "laguna-s-2.1-ep16-d9.json"))


def test_the_configuration_holds_every_published_number(published):
    """The catalog row's ``config`` key by key: every top-level value
    is here unchanged unless ``reduced`` names it, nested groups whole."""
    import json
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Laguna-S-2.1"' in line) \
        if os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl") else None
    if row is None:
        pytest.skip("the catalog is not on this machine")
    differs = {k for k, v in row["config"].items() if published.get(k) != v}
    assert differs == set(published["reduced"])
    assert published["source"] == row["source_url"]
    for k in ("layer_types", "mlp_layer_types", "gating_types",
              "num_attention_heads_per_layer"):
        assert published[k] == row["config"][k][:9]


def test_layer_counts(published):
    assert roofline_laguna.layers_of(published, True) == 6
    assert roofline_laguna.layers_of(published, False) == 3
    assert roofline_laguna.query_heads(published, True) == 6 * 72
    assert roofline_laguna.query_heads(published, False) == 3 * 48
    assert roofline_laguna.expert_layers(published) == 8


def test_expert_and_kv_bytes(published):
    # gate, up, down: 3 x 3072 x 1024 values of 2 bytes
    assert roofline_laguna.expert_bytes(published) == 18_874_368
    assert roofline_laguna.kv_bytes_per_token(published) \
        == 2 * 8 * 128 * 2 == 4096


def test_weight_bytes_outside_experts(published):
    full = 3072 * (48 + 16) * 128 + 48 * 128 * 3072 + 3072 * 48
    window = 3072 * (72 + 16) * 128 + 72 * 128 * 3072 + 3072 * 72
    assert full == 44_187_648 and window == 63_135_744    # ISSUE 46's
    by_hand = 2 * (3 * (full + 2 * 3072) + 6 * (window + 2 * 3072)
                   + 3 * 3072 * 12288               # the dense FFN
                   + 8 * (3072 * 256 + 3 * 3072 * 1024)  # routers, shared
                   + 3072 + 3072 * 12544)           # final norm, head
    assert roofline_laguna.weight_bytes_outside_experts(published) == by_hand
    # with every held expert hit, the issue's 3.9 GB a tick (its 1.99 B
    # parameters less the embedding's 38.5 M rows a tick does not read)
    assert by_hand + 128 * 18_874_368 == pytest.approx(3.90e9, rel=5e-3)


def test_attention_tick_and_chunk_counts(published):
    assert roofline_laguna.window_attention_bytes(published, 6 * 64 * 512) \
        == 6 * 64 * 512 * 4096
    assert roofline_laguna.full_attention_bytes(published, 3 * 64 * 5700) \
        == 3 * 64 * 5700 * 4096
    outside = roofline_laguna.weight_bytes_outside_experts(published)
    assert roofline_laguna.tick_bytes(published, 10, 800, 1000, 2000) \
        == 10 * outside + 800 * 18_874_368 + 3000 * 4096
    assert roofline_laguna.causal_pairs(4) == 10
    # one prompt of 1,024 tokens: 524,800 pairs x 144 heads x 512 FLOP
    assert roofline_laguna.chunk_full_attention_flops(
        published, roofline_laguna.causal_pairs(1024)) \
        == 524_800 * 144 * 512
