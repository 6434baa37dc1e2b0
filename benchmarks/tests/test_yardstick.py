"""The arithmetic of the yardstick, with no engine: the generator, the
percentiles and due times, the byte count, the manifest and its files."""
import glob
import json
import os
import re

import numpy as np
import pytest

from benchmarks.harness import cell, roofline, stats, traffic
from benchmarks.tests import tiny

BENCH = os.path.join(tiny.ROOT, "benchmarks")
MIXES = sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json")))


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_a_mix_is_a_pure_function_of_the_seed_and_keeps_its_caps(path):
    mix = cell.load_json(path)
    kw = dict(rate=2.5) if mix["loop"] == "open" else dict(clients=16)
    a = traffic.plan(2**31 + 11, mix, 151936, 40, **kw)
    b = traffic.plan(2**31 + 11, mix, 151936, 40, **kw)
    c = traffic.plan(12, mix, 151936, 40, **kw)
    assert a == b and a != c
    for p in (a, c):
        for s in p["sessions"]:
            used = len(p["systems"][s["tenant"]]) \
                if s["tenant"] is not None else 0
            for t in s["turns"]:
                m, g = len(t["message"]), t["max_new_tokens"]
                spec = mix["message_tokens"]
                assert spec.get("min", spec.get("lo", m)) <= m \
                    <= spec.get("cap", spec.get("hi", m))
                assert g <= mix["new_tokens"].get(
                    "cap", mix["new_tokens"].get("value", g))
                assert all(1 <= x < 151936 for x in t["message"])
                used += m + g
            assert used <= 2048

    def schedule(p):
        return [(s["arrival"], s["tenant"], s["greedy"],
                 [(len(t["message"]), t["max_new_tokens"], t["think_s"])
                  for t in s["turns"]]) for s in p["sessions"]]

    def content(p):
        return [(s["seed"], [t["message"] for t in s["turns"]])
                for s in p["sessions"]]
    # a mix with a schedule seed sends one fixed trace, and the run's
    # seed changes what the requests hold; without one, both change
    assert (schedule(a) == schedule(c)) == ("schedule_seed" in mix)
    assert content(a) != content(c)
    # a shorter run sends a prefix of the longer one's schedule
    short = traffic.plan(12, mix, 151936, 15, **kw)
    if mix["loop"] == "open":
        assert schedule(short) == schedule(c)[:len(short["sessions"])]
        arr = [s["arrival"] for s in a["sessions"]]
        horizon = mix["lead_in_s"] + 40
        assert arr == sorted(arr) and 0 <= arr[0] and arr[-1] < horizon
        # a Poisson count: within four deviations of its mean
        assert abs(len(arr) - 2.5 * horizon) <= 4 * (2.5 * horizon) ** 0.5
        gaps = np.diff(arr)
        assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.35)
        assert sum(s["greedy"] for s in a["sessions"]) == pytest.approx(
            len(arr) * (1 - mix["sampled_share"]), abs=3 * len(arr) ** 0.5)


def test_draw_gives_independent_values_of_the_distribution():
    rng = np.random.default_rng(0)
    x = traffic.draw(rng, {"dist": "exponential", "mean": 2.0}, 4000)
    assert x.mean() == pytest.approx(2.0, rel=0.06)
    assert x.std() == pytest.approx(2.0, rel=0.1)
    assert abs(np.corrcoef(x[:-1], x[1:])[0, 1]) < 0.06     # no evening out
    x = traffic.draw(rng, {"dist": "lognormal", "median": 96, "sigma": 0.7,
                           "cap": 512}, 4001)
    assert np.median(x) == pytest.approx(96, rel=0.06) and x.max() <= 512
    x = traffic.draw(rng, {"dist": "geometric", "mean": 3, "cap": 6}, 3000)
    assert set(x) == {1, 2, 3, 4, 5, 6}
    assert (x == 1).mean() == pytest.approx(1 / 3, abs=0.03)
    with pytest.raises(ValueError):
        traffic.draw(rng, {"dist": "zipf"}, 3)


def test_percentile_is_the_interpolated_order_statistic():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    for q in (0, 10, 50, 90, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)))
    assert stats.percentile([], 90) is None
    assert stats.percentile([7.0], 90) == 7.0


def rec(id, due, sent, times, status=200, reason="stop", **kw):
    return dict(id=id, due=due, sent=sent, token_times=times,
                tokens=list(range(len(times))), status=status,
                finish_reason=reason, greedy=True, prompt=[1, 2], turn=0,
                **kw)


def test_requests_are_timed_from_when_they_were_due_even_if_stalled():
    """A hand-made schedule against a server that stalls for a second:
    the request the stall delayed is charged the stall, a request due
    before the window is judged in nothing but its tokens count where
    they arrived, and a shed or unfinished request misses."""
    w0, w1, give_up = 10.0, 20.0, 25.0
    records = [
        rec("lead", 9.0, 9.0, [9.5, 10.5, 11.5]),          # due before w0
        rec("ok", 10.0, 10.001, [10.1, 10.15, 10.2, 10.25]),
        # due at 12.0 but the generator (or the socket) got to it at 13.0
        rec("stalled", 12.0, 13.0, [13.2, 13.25, 13.3]),
        rec("shed", 14.0, 14.0, [], status=429, reason=None),
        rec("unfinished", 19.0, 19.0, [19.5, 19.6], reason=None,
            cancelled=True),
        rec("late", 20.0, 20.0, [20.1]),                    # due at w1: out
    ]
    slo = {"ttft_ms": 500.0, "mean_gap_ms": 60.0}
    m = stats.client_metrics(records, w0, w1, give_up, slo)
    assert m["attempted"]["value"] == 4
    assert m["failed"]["value"] == 2       # shed, and cut at the deadline
    closed = stats.client_metrics(records, w0, w1, give_up, slo, "closed")
    assert closed["failed"]["value"] == 1  # a closed loop's cut is by design
    ttfts = sorted([100.0, 1200.0, (give_up - 14.0) * 1e3, 500.0])
    assert m["ttft_p90_ms"]["value"] == pytest.approx(
        float(np.percentile(ttfts, 90)))
    assert m["ttft_p90_ms"]["n"] == 4
    # gaps of the window's requests only: 3 x 50, 2 x 50, 1 x 100
    assert m["gap_p95_ms"]["n"] == 6
    assert m["gap_p95_ms"]["value"] == pytest.approx(87.5)
    # tokens by arrival: lead's 2 inside, ok 4, stalled 3, unfinished 2
    assert m["tokens_per_s"]["value"] == pytest.approx(11 / 10.0)
    assert m["loadgen_late_p95_ms"]["value"] == pytest.approx(
        float(np.percentile([1.0, 1000.0, 0.0, 0.0], 95)))
    # only "ok" met both limits: the stall broke TTFT for "stalled"
    assert m["slo_met_share"]["value"] == pytest.approx(25.0)


def test_tick_bytes_counts_weights_once_and_kv_per_token():
    cfg = cell.load_json(os.path.join(BENCH, "configs",
                                      "qwen2-7b-d16.json"))
    assert roofline.kv_bytes_per_token(cfg) == 32768
    w = roofline.weight_bytes_per_tick(cfg)
    assert 8.4e9 < w < 8.7e9        # 16 layers + the head, bfloat16;
    # the embedding (1.09 GB more in memory) is not read by a tick
    assert roofline.tick_bytes(cfg, 3, 1000) == 3 * w + 1000 * 32768
    small = cell.load_json(os.path.join(BENCH, "configs", "qwen2-1.5b.json"))
    assert roofline.kv_bytes_per_token(small) == 28672
    assert 2.9e9 < roofline.weight_bytes_per_tick(small) < 3.2e9


def test_set_floor_is_the_lowest_logit_a_sampler_keeps():
    from benchmarks.harness import verify
    top = np.log(np.array([[0.5, 0.3, 0.15, 0.05], [0.97, 0.01, 0.01, 0.01]]))
    # at temperature 1 the masses are the numbers above: 0.95 keeps the
    # first three of row 0 (0.5 + 0.3 = 0.8 is still under it) and the
    # first of row 1
    assert np.allclose(verify.set_floor(top, 1.0, 0.95),
                       [top[0, 2], top[1, 0]])
    assert np.allclose(verify.set_floor(top, 1.0, 1.0), top[:, 3])
    # a low temperature sharpens: row 0's best alone passes 0.95
    assert np.allclose(verify.set_floor(top, 0.1, 0.95), top[:, 0])


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_the_manifest_keeps_to_the_contract():
    m = tiny.real_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in m[k]}) == len(m[k])
    metrics = m["end_to_end"] + m["per_layer"]
    assert len({x["name"] for x in metrics}) == len(metrics)
    assert all(UNIT.match(x["unit"]) for x in metrics)
    assert all(x["better"] in ("lower", "higher") for x in metrics)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) <= 5
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in m["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 4)
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
    used = {w["config"] for w in cells.values()}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and c["file"].startswith("benchmarks/")
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        on_disk = cell.load_json(os.path.join(tiny.ROOT, c["file"]))
        assert sorted(on_disk["reduced"]) == sorted(c["reduced"])
    assert len(json.dumps(m)) < 64 * 1024
    for name in cells:
        spec = cell.cell_spec(m, name)
        reported = {x["name"] for x in spec["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert spec["per_layer"], f"{name} reports no per-layer metric"
        assert ("rate_per_s" in spec["cell"]) == \
            (spec["mix"]["loop"] == "open")


def test_every_per_layer_metric_is_a_file_that_moves_a_reported_metric():
    m = tiny.real_manifest()
    e2e = {x["name"]: x for x in m["end_to_end"]}
    cells = [w["name"] for w in m["workloads"]]
    layers = set()
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        mod = cell.load_module(
            os.path.join(BENCH, "layer_metrics", x["name"] + ".py"),
            "t_" + x["name"].replace(".", "_"))
        assert (mod.NAME, mod.UNIT, mod.MOVES, mod.LAYER, mod.SOURCE) == \
            (x["name"], x["unit"], x["moves"], x["layer"], x["source"])
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert callable(mod.reduce) and x["moves"] in e2e
        layers.add(x["layer"])
        # reported only in cells that report the metric it moves
        moved = e2e[x["moves"]].get("workloads", cells)
        for name in x.get("workloads", moved):
            assert name in moved
        if x["name"].endswith("_roofline"):
            assert x["unit"] == "%"
    perf = open(os.path.join(tiny.ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
    on_disk = {os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(BENCH, "layer_metrics", "*.py"))}
    assert {x["name"] for x in m["per_layer"]} <= on_disk
