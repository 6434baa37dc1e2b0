#!/usr/bin/env python3
"""One run of one benchmark cell on the chip this process is started on.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``. Everything else goes to stderr. Without a TPU, with
fewer chips than the cell asks for, or with the Pallas interpreter on,
it exits non-zero and prints no result: it never falls back to the CPU.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()        # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if os.environ.get("PADDLE_TPU_PALLAS_INTERPRET"):
        print("bench: FAIL: PADDLE_TPU_PALLAS_INTERPRET is set; a measured "
              "run sends every kernel through the real compiler",
              file=sys.stderr)
        return 1
    from benchmarks.harness import cell
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    try:
        result = cell.run_cell(manifest, args.workload, args.seed,
                               args.seconds, bool(args.trace), T_PROCESS)
    except cell.BenchFailure as e:
        print(f"bench: FAIL: {e}", file=sys.stderr)
        return 1
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
