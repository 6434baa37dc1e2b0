#!/usr/bin/env python3
"""Time the ragged kernel's LATENT mode alone on the chip, beside the
dense whole-table gather (PERF.md section 5, "The kernel alone").

    python3 tests/chip_latent_timing.py [--contexts 64,320,576,2040]

At the gigachat3.1-702b-ep16-d6 cell's geometry (64 rows, 128 blocks of
16 tokens, 64 query heads over one 640-column row a token, values its
first 512 columns, bf16, an 8193-block pool) every row holds the same
context; one JSON line per context with the time of ONE call, the bytes
the call must read (576 live columns a token) and its share of 819 GB/s.
A call's time is the two-point fit of tests/chip_ragged_timing.py: one
jitted program chains ``n`` calls, each call's output (padded back to
the query's width) the next one's query, and (t(24) - t(8)) / 16 leaves
out the dispatch. Each output is compared with the dense gather's
first. Not a pytest file; it refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

R, M, B, H, W, DV, P = 64, 128, 16, 64, 640, 512, 8193
SCALE = 192 ** -0.5
CHAINS = (8, 24)
REPEATS = 10


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--contexts", default="64,320,576,2040")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("chip_latent_timing: needs a TPU", file=sys.stderr)
        return 1
    from paddle_tpu.ops.paged_cache import (PagedKV, paged_latent_attention,
                                            paged_latent_attention_dense)
    routes = {"ragged": paged_latent_attention,       # the chip's route
              "dense": paged_latent_attention_dense}

    def attend(route):
        def fn(q, kp, tbl, lens):
            out = routes[route](
                q[:, None], PagedKV(kp, None, tbl, lens), DV, SCALE)
            return jnp.pad(out[:, 0], ((0, 0), (0, 0), (0, W - DV)))
        return fn

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(R, H, W) * 0.3, jnp.bfloat16)
    kp = jnp.asarray(rs.randn(P, B, W), jnp.bfloat16)
    tbl = jnp.asarray(1 + rs.permutation(P - 1)[:R * M].reshape(R, M),
                      jnp.int32)

    def chain(fn, n):
        def run(q, kp, tbl, lens):
            for _ in range(n):
                q = fn(q, kp, tbl, lens)
            return q
        return jax.jit(run)

    def seconds(prog, lens):
        prog(q, kp, tbl, lens).block_until_ready()          # compile
        best = float("inf")
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(REPEATS):
                out = prog(q, kp, tbl, lens)
            out.block_until_ready()
            best = min(best, (time.perf_counter() - t) / REPEATS)
        return best

    progs = {name: [chain(attend(name), n) for n in (1,) + CHAINS]
             for name in ("ragged", "dense")}
    rows = []
    for ctx in (int(c) for c in args.contexts.split(",")):
        lens = jnp.full((R,), ctx - 1, jnp.int32)   # ctx tokens attended
        ref = np.asarray(progs["dense"][0](q, kp, tbl, lens), np.float32)
        need = R * ctx * 576 * 2
        row = {"context": ctx, "rows": R, "bytes": need,
               "device_kind": jax.devices()[0].device_kind}
        for name, (once, short, long) in progs.items():
            if name != "dense":
                got = np.asarray(once(q, kp, tbl, lens), np.float32)
                row[name + "_max_err"] = float(np.abs(got - ref).max())
            t0, t1 = seconds(short, lens), seconds(long, lens)
            call = (t1 - t0) / (CHAINS[1] - CHAINS[0])
            row[name + "_us_a_call"] = round(call * 1e6, 2)
            row[name + "_share_of_819GBs"] = round(
                100 * need / 819e9 / call, 2)
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "latent_timing.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
