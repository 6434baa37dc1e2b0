#!/usr/bin/env python3
"""Read, on the chip, the numbers the limits of ``correct`` are set
from: for a dozen seeds the program's own, for a few the control's.

    python3 benchmarks/tests/chip_limits.py --workload <cell> \
        --seeds 12 --control 3 --seconds 16 [--first 5000]

One process, one set-up. For each seed the weights are made anew in
place (``fill_weights``), the cell's mix is run for a short window at
the cell's own load through a fresh gateway and client child, and the
samples a benchmark run would compare are compared. The control is the
reference computed W8A8 in int8 put in the program's place, at the same
prompts and tokens. Where the mix samples, the control's seeds also
read the fault the sampled requests' set gap is there to catch: one
served token of each sampled request replaced by a uniformly drawn
one. One JSON line per seed, and a summary.
"""
from __future__ import annotations

import argparse
import asyncio
import copy
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import cell, verify  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=16.0)
    ap.add_argument("--first", type=int, default=5000)
    args = ap.parse_args(argv)
    manifest = cell.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    spec = cell.cell_spec(manifest, args.workload)
    import jax
    from paddle_tpu.utils import compile_cache
    if jax.devices()[0].platform != "tpu":
        print("chip_limits: needs a TPU", file=sys.stderr)
        return 1
    compile_cache.enable(min_compile_time_s=0.0)
    config = spec["config"]
    model_mod = cell.load_model(config)
    engines = cell.build_engines(model_mod, spec, args.first,
                                 jax.devices()[:1], False)
    engine = engines[0]
    out_dir = os.path.join(ROOT, ".bench_out", args.workload + ".limits")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for i in range(args.seeds):
        seed = args.first + 7919 * i
        if i:
            engine.params = model_mod.fill_weights(engine.params, seed)
        src = asyncio.run(cell.serve(spec, engines, seed, args.seconds,
                                     False, out_dir, lambda: 0))
        c = src["client"]
        bad = verify.exact_checks(c["records"])
        row = {"seed": seed, "exact_failures": bad[:3],
               "program": cell.compare(model_mod, engine.params, spec, c,
                                       seed)}
        if i < args.control and row["program"]:
            n = int(spec["mix"].get("verify_requests", 8))
            greedy, sampled = (verify.choose_sample(
                c["records"], c["w0"], c["w1"], seed, n, greedy=g)
                for g in (True, False))
            row["control_int8"] = verify.control_numbers(
                model_mod, engine.params, config, greedy)
            if sampled:
                broken = copy.deepcopy(sampled)
                rng = np.random.default_rng(seed)
                for r in broken:
                    r["tokens"][-1] = int(rng.integers(
                        1, config["vocab_size"]))
                row["fault_uniform_token"] = verify.numbers(
                    model_mod, engine.params, config, greedy, broken,
                    spec["mix"]["sampling"])["sampled_set_gap_max"]
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = [r["program"] for r in rows if r["program"]]
    ctrl = [r["control_int8"] for r in rows if "control_int8" in r]
    summary = {"workload": args.workload, "seeds": len(prog)}
    for k in verify.NUMBERS:
        if k not in prog[0]:
            continue
        summary[k] = {"program_largest": max(p[k] for p in prog),
                      "program_all": [round(p[k], 5) for p in prog],
                      "control_smallest": min(c[k] for c in ctrl)
                      if ctrl and k in ctrl[0] else None}
    faults = [r["fault_uniform_token"] for r in rows
              if "fault_uniform_token" in r]
    if faults:
        summary["sampled_set_gap_max"]["fault_smallest"] = min(faults)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
