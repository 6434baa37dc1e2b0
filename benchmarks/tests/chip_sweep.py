#!/usr/bin/env python3
"""Find a rate cell's knee, once, on the chip (benchmarks/README.md).

    python3 benchmarks/tests/chip_sweep.py --workload <cell> \
        --rates 1,2,3,4 --seconds 20 [--seed 1]

One process, one set-up: the cell's engines are built and warmed once
and its mix is run at each rate in turn through a fresh gateway and
client child, as a benchmark run does it. One JSON line per rate.
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

from benchmarks.harness import cell, stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    manifest = cell.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    spec = cell.cell_spec(manifest, args.workload)
    import jax
    from paddle_tpu.utils import compile_cache
    if jax.devices()[0].platform != "tpu":
        print("chip_sweep: needs a TPU", file=sys.stderr)
        return 1
    compile_cache.enable(min_compile_time_s=0.0)
    replicas = int(spec["cell"].get("replicas", spec["workload"]["chips"]))
    config = spec["config"]
    model_mod = cell.load_model(config)
    engines = cell.build_engines(model_mod, spec, args.seed,
                                 jax.devices()[:replicas], False)
    out_dir = os.path.join(ROOT, ".bench_out", args.workload + ".sweep")
    os.makedirs(out_dir, exist_ok=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        spec["cell"]["rate_per_s"] = rate
        t = time.monotonic()
        src = asyncio.run(cell.serve(spec, engines, args.seed + i,
                                     args.seconds, False, out_dir,
                                     lambda: 0))
        c = src["client"]
        m = stats.client_metrics(c["records"], c["w0"], c["w1"],
                                 c["give_up"], spec["cell"].get("slo"))
        win = [r for r in c["records"]
               if stats.in_window(r, c["w0"], c["w1"])]
        gaps = [g for g in (stats.mean_gap_ms(r) for r in win)
                if g is not None]
        # the backlog: requests due in the window and not yet finished
        # when it closed, against those unfinished when it opened
        def backlog(at):
            return sum(1 for r in c["records"] if r["due"] < at
                       and (r.get("end") or float("inf")) > at)
        h0, h1 = (src["snaps"][k]["health"] for k in ("w0", "w1"))
        row = {"rate_per_s": rate, "sent": len(win),
               "backlog_at_start": backlog(c["w0"]),
               "backlog_at_end": backlog(c["w1"]),
               "queued_at_end": sum(
                   rep["scheduler"]["queued"] + rep["engine"]["queued"]
                   for rep in h1["replicas"].values()),
               "shed": h1["shed"] - h0["shed"],
               "failed": m["failed"]["value"],
               "mean_gap_p50_ms": stats.percentile(gaps, 50),
               "mean_gap_p90_ms": stats.percentile(gaps, 90),
               "took_s": round(time.monotonic() - t, 1)}
        for k in ("ttft_p50_ms", "ttft_p90_ms", "gap_p50_ms", "gap_p95_ms",
                  "tokens_per_s", "slo_met_share", "loadgen_late_p95_ms"):
            if k in m:
                row[k] = round(m[k]["value"], 3)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
