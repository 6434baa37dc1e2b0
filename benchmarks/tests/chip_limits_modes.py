#!/usr/bin/env python3
"""``chip_limits.py`` for a configuration with MORE than one control:
the program's own numbers for some seeds and, for the first few, the
reference put in the program's place in each of ``--modes`` (``int8``:
W8A8; ``bf16_state``: every product float32 and the recurrent STATE
rounded to bfloat16 after each position, for a family whose reference
has that mode).

    python3 benchmarks/tests/chip_limits_modes.py --workload <cell> \
        --seeds 6 --control 2 --modes int8,bf16_state --seconds 16

One process, one set-up; for each seed the weights are made anew in
place, the cell's mix is run for a short window through a fresh gateway
and client child, and the sample a benchmark run would compare is
compared. One JSON line per seed, and a summary.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import cell, verify  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control", type=int, default=2)
    ap.add_argument("--modes", default="int8,bf16_state")
    ap.add_argument("--seconds", type=float, default=16.0)
    ap.add_argument("--first", type=int, default=5000)
    args = ap.parse_args(argv)
    modes = [m for m in args.modes.split(",") if m]
    manifest = cell.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    spec = cell.cell_spec(manifest, args.workload)
    import jax
    from paddle_tpu.utils import compile_cache
    if jax.devices()[0].platform != "tpu":
        print("chip_limits_modes: needs a TPU", file=sys.stderr)
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
        t0 = time.perf_counter()
        row = {"seed": seed,
               "exact_failures": verify.exact_checks(c["records"])[:3],
               "program": cell.compare(model_mod, engine.params, spec, c,
                                       seed)}
        row["compare_s"] = round(time.perf_counter() - t0, 1)
        if i < args.control and row["program"]:
            greedy = verify.choose_sample(
                c["records"], c["w0"], c["w1"], seed,
                int(spec["mix"].get("verify_requests", 8)), greedy=True)
            for mode in modes:
                row["control_" + mode] = verify.control_numbers(
                    model_mod, engine.params, config, greedy, mode=mode)
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = [r["program"] for r in rows if r["program"]]
    summary = {"workload": args.workload, "seeds": len(prog)}
    for k in ("argmax_gap_max", "logprob_rms"):
        summary[k] = {"program_largest": max(p[k] for p in prog),
                      "program_all": [round(p[k], 5) for p in prog]}
        for mode in modes:
            got = [r["control_" + mode][k] for r in rows
                   if "control_" + mode in r]
            summary[k]["control_%s_all" % mode] = [round(v, 5) for v in got]
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
