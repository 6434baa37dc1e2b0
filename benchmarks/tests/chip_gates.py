#!/usr/bin/env python3
"""Read, on the chip, the spread of the decays and write strengths that
a configuration's seeded weights give its linear-attention layers: what
its ``assumed.weights`` quotes (with every decay 0 or 1 no limit
separates a sound program from a broken one).

    python3 benchmarks/tests/chip_gates.py --config <name> --seeds 3 \
        [--tokens 512] [--first 5000]

For each seed the weights are made as a benchmark run makes them and one
sequence of seeded ids goes through the family's plain reference, whose
``gate_spread`` reports by layer. One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--tokens", type=int, default=512)
    ap.add_argument("--first", type=int, default=5000)
    args = ap.parse_args(argv)
    manifest = cell.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(c for c in manifest["configs"] if c["name"] == args.config)
    config = cell.load_json(os.path.join(ROOT, entry["file"]))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chip_gates: needs a TPU", file=sys.stderr)
        return 1
    model_mod = cell.load_model(config)
    params = None
    for i in range(args.seeds):
        seed = args.first + 7919 * i
        if params is None:
            params = model_mod.build(config, seed,
                                     jax.devices()[0]).functional()[1]
        else:
            params = model_mod.fill_weights(params, seed)
        ids = np.random.default_rng(seed).integers(
            1, config["vocab_size"], args.tokens).tolist()
        print(json.dumps({"seed": seed, "layers": model_mod.gate_spread(
            dict(params), config, ids)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
