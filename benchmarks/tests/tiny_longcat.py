"""``tiny.tiny_manifest`` plus LongCat-Flash's tiny configuration and
cell: what ``test_rehearsal_longcat.py`` runs."""
from benchmarks.tests import tiny

CELL = "tiny-longcat.batch"
REAL = {"longcat-flash-omni-ep32-d4.batch-decode": CELL,
        "qwen2-7b-d16.batch-decode": "tiny.batch"}


def manifest() -> dict:
    """Each saturated metric lists the tiny twins of the cells the
    COMMITTED ``BENCHMARK.json`` lists it under, so the rehearsal runs
    the manifest that is checked in, at tiny widths."""
    m = tiny.tiny_manifest()
    m["configs"].append({
        "name": "tiny-longcat",
        "file": "benchmarks/tests/data/configs/longcat-flash-tiny.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-longcat",
                           "traffic": "tiny-batch", "chips": 1})
    real = tiny.real_manifest()
    listed = {x["name"]: x.get("workloads")
              for x in real["end_to_end"] + real["per_layer"]}
    for metric in m["end_to_end"] + m["per_layer"]:
        cells = listed[metric["name"]]
        if cells is not None and set(cells) & set(REAL):
            metric["workloads"] = [REAL[c] for c in cells if c in REAL]
    return m
