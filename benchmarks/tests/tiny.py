"""A manifest of tiny cells over the tests' own data files, with the
real manifest's metrics: what the rehearsals run."""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
ROOT = os.path.dirname(os.path.dirname(HERE))


def real_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny_manifest() -> dict:
    m = real_manifest()
    rate = {"tiny.sessions"}
    m["configs"] = [{"name": "tiny",
                     "file": "benchmarks/tests/data/configs/qwen2-tiny.json"}]
    m["workloads"] = [
        {"name": "tiny.sessions", "config": "tiny",
         "traffic": "tiny-sessions", "chips": 1},
        {"name": "tiny.batch", "config": "tiny", "traffic": "tiny-batch",
         "chips": 1}]
    for metric in m["end_to_end"] + m["per_layer"]:
        if "workloads" in metric:
            moves = metric.get("moves", metric["name"])
            metric["workloads"] = sorted(rate) if moves != "tokens_per_s" \
                else ["tiny.batch"]
    return m
