#!/usr/bin/env python3
"""Time the held experts' part of an expert layer alone on the chip: the
weight-streaming kernel (``ops/pallas/expert_mlp.py``) beside the
einsums over all held experts, both through
``ExpertShareMLP.routed`` with its gate held open or shut (PERF.md
section 5, "The kernels alone"; the cut by token count,
``expert_mlp.MAX_TOKENS``, is read off this table).

    python3 tests/chip_experts_timing.py [--hidden 7168,6144,4096]
        [--tokens 64,128,256] [--hit 16,13,10,5] [--weights-mb 48]

16 held experts of width 2048 of a 256-column router, 8 choices a token
(bf16), at the hidden sizes of the three expert configurations. Every
token chooses ``min(8, hit)`` of the first ``hit`` held experts in
rotation and fills up with experts the rank does not hold, so exactly
``hit`` of the 16 get a token. One JSON line per (hidden, tokens, hit):
the time of ONE call of each route, the bytes of the experts hit (3 x
hidden x 2048 x 2 B each) and of all 16, and each route's share of 819
GB/s on the bytes it has to read (the kernel: the experts hit; the
einsums: all held). A call's time is the two-point fit of
tests/chip_ragged_timing.py: one jitted program chains ``n`` calls,
each call's output the next one's tokens, and (t(24) - t(8)) / 16
leaves out the dispatch. ``--weights-mb`` sets the kernel's budget for
its six weight buffers, which fixes its column tile. Not a pytest
file; it refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

N, M, E, K, FIRST = 16, 2048, 256, 8, 16
CHAINS = (8, 24)
REPEATS = 5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", default="7168,6144,4096")
    ap.add_argument("--tokens", default="64,128,256")
    ap.add_argument("--hit", default="16,13,10,5")
    ap.add_argument("--weights-mb", type=int, default=None)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("chip_experts_timing: needs a TPU", file=sys.stderr)
        return 1
    import paddle_tpu as pt
    from paddle_tpu.ops.pallas import expert_mlp
    from paddle_tpu.parallel import moe
    if args.weights_mb:
        expert_mlp._VMEM_WEIGHTS = args.weights_mb << 20

    def choices(T, hit):
        """ids [T, K]: exactly ``hit`` of the held experts get a token."""
        ids = np.empty((T, K), np.int32)
        for t in range(T):
            for c in range(K):
                ids[t, c] = FIRST + (t * K + c) % hit if c < hit \
                    else (t + c) % FIRST            # an expert not held
        return ids

    def seconds(prog, *a):
        prog(*a).block_until_ready()                        # compile
        best = float("inf")
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(REPEATS):
                out = prog(*a)
            out.block_until_ready()
            best = min(best, (time.perf_counter() - t) / REPEATS)
        return best

    rows = []
    rs = np.random.RandomState(0)
    for h in (int(v) for v in args.hidden.split(",")):
        pt.seed(0)
        layer = moe.ExpertShareMLP(h, M, E, K, FIRST, N)
        params = {k: v.astype(jnp.bfloat16)
                  for k, v in layer.named_parameters()}
        expert = 3 * h * M * 2
        for T in (int(v) for v in args.tokens.split(",")):
            x = jnp.asarray(rs.randn(T, h), jnp.bfloat16)
            gates = jnp.asarray(rs.rand(T, K) * 0.2, jnp.float32)
            einsums = None          # their time does not read the hits
            for hit in (int(v) for v in args.hit.split(",")):
                ids = jnp.asarray(choices(T, hit))
                row = {"hidden": h, "tokens": T, "hit": hit,
                       "column_tile": expert_mlp._column_tile(h, M, 2),
                       "bytes_hit": hit * expert, "bytes_held": N * expert,
                       "device_kind": jax.devices()[0].device_kind}
                outs = {}
                for route, flag in (("kernel", True), ("einsums", False)):
                    expert_mlp.use_expert_kernel = lambda *_, flag=flag: flag

                    def fn(params, x, ids, gates, n):
                        with layer.bound(params):
                            for _ in range(n):
                                x = layer.routed(x, ids, gates)
                        return x
                    once, short, long = (
                        jax.jit(functools.partial(fn, n=n))
                        for n in (1,) + CHAINS)
                    a = (params, x, ids, gates)
                    outs[route] = np.asarray(once(*a), np.float32)
                    if flag or einsums is None:
                        call = (seconds(long, *a) - seconds(short, *a)) \
                            / (CHAINS[1] - CHAINS[0])
                        if not flag:
                            einsums = call
                    else:
                        call = einsums
                    need = (hit if flag else N) * expert
                    row[route + "_us_a_call"] = round(call * 1e6, 1)
                    row[route + "_share_of_819GBs"] = round(
                        100 * need / 819e9 / call, 1)
                row["max_err"] = float(np.abs(outs["kernel"]
                                              - outs["einsums"]).max())
                row["max_abs"] = float(np.abs(outs["einsums"]).max())
                rows.append(row)
                print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "experts_timing.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        f.writelines(json.dumps(row) + "\n" for row in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
