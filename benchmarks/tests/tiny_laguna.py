"""``tiny.tiny_manifest`` plus Laguna's tiny configuration, mix and
cell: what ``test_rehearsal_laguna.py`` runs."""
from benchmarks.tests import tiny

CELL = "tiny-laguna.code-batch"
REAL = {"laguna-s-2.1-ep16-d9.code-batch": CELL,
        "qwen2-7b-d16.batch-decode": "tiny.batch"}


def manifest() -> dict:
    """Each saturated metric lists the tiny twins of the cells the
    COMMITTED ``BENCHMARK.json`` lists it under, so the rehearsal runs
    the manifest that is checked in, at tiny widths."""
    m = tiny.tiny_manifest()
    m["configs"].append({
        "name": "tiny-laguna",
        "file": "benchmarks/tests/data/configs/laguna-tiny.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-laguna",
                           "traffic": "tiny-code-batch", "chips": 1})
    real = tiny.real_manifest()
    listed = {x["name"]: x.get("workloads")
              for x in real["end_to_end"] + real["per_layer"]}
    for metric in m["end_to_end"] + m["per_layer"]:
        cells = listed[metric["name"]]
        if cells is not None and set(cells) & set(REAL):
            metric["workloads"] = [REAL[c] for c in cells if c in REAL]
    return m
