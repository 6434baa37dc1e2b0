"""``tiny.tiny_manifest`` plus Ling's tiny configuration, cell and mix
(answers of DIFFERENT lengths, prompts of one or two chunks): what
``test_rehearsal_ling.py`` runs."""
from benchmarks.tests import tiny

CELL = "tiny-ling.reason-batch"
REAL = {"ling-3.0-flash-ep16-d14.reason-batch": CELL,
        "qwen2-7b-d16.batch-decode": "tiny.batch"}


def manifest() -> dict:
    """Each saturated metric lists the tiny twins of the cells the
    COMMITTED ``BENCHMARK.json`` lists it under, so the rehearsal runs
    the manifest that is checked in, at tiny widths."""
    m = tiny.tiny_manifest()
    m["configs"].append({
        "name": "tiny-ling",
        "file": "benchmarks/tests/data/configs/ling-hybrid-tiny.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-ling",
                           "traffic": "tiny-reason-batch", "chips": 1})
    real = tiny.real_manifest()
    listed = {x["name"]: x.get("workloads")
              for x in real["end_to_end"] + real["per_layer"]}
    for metric in m["end_to_end"] + m["per_layer"]:
        cells = listed[metric["name"]]
        if cells is not None and set(cells) & set(REAL):
            metric["workloads"] = [REAL[c] for c in cells if c in REAL]
    return m
