#!/usr/bin/env python3
"""Time the held experts' part of an expert layer alone on the chip: the
two kernels of ``ops/pallas/expert_mlp.py`` (a tick's rows through the
experts hit; a prompt call's sorted pairs through the grouped product)
beside the einsums over all held experts, all through
``ExpertShareMLP.routed`` with its gates held open or shut (PERF.md
section 5, "The held experts' part alone").

    python3 tests/chip_experts_timing.py
        [--shapes 3072x1024x16x10,2560x768x32x8,4096x2048x16x8,7168x2048x16x8]
        [--tokens 64,256,1024] [--hit 16,10] [--weights-mb 48] [--ops]

A shape is hidden x expert width x experts held x choices a token, of a
256-column router (bf16). Rows up to ``expert_mlp.MAX_TOKENS`` take the
tick's kernel, at ``--hit`` experts hit: every token chooses ``min(k,
hit)`` of the first ``hit`` held experts in rotation and fills up with
experts the rank does not hold. Rows above it take the grouped product,
at two routings: ``uniform`` (every token draws its k columns from all
256 without repeat, so n / 256 of the choices are held: 640 pairs of
1,024 x 10 over 16 held) and ``all-hit`` (every token's first choice a
held expert in rotation, the others not held: T pairs, every expert
hit). One JSON line per (shape, tokens, routing): the time of ONE call
of each route, the bytes of the experts hit and of all held, and each
route's share of 819 GB/s on the bytes it has to read (a kernel: the
experts hit; the einsums: all held). A call's time is the two-point fit
of tests/chip_ragged_timing.py: one jitted program chains ``n`` calls,
each call's output the next one's tokens and its routing the one
before's moved on a token, and (t(12) - t(4)) / 8 leaves out the
dispatch. ``--weights-mb`` sets the kernels' budget for
their six weight buffers, which fixes their column tile. ``--ops`` adds,
for the grouped product, the device time of a call by XLA op (one
profiled run of the chain of 12; each op's own time, what is nested
inside it taken out). Not a pytest file; it refuses to run without a
TPU.
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

E, FIRST = 256, 16
CHAINS = (4, 12)
REPEATS = 5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="3072x1024x16x10,2560x768x32x8,"
                    "4096x2048x16x8,7168x2048x16x8")
    ap.add_argument("--tokens", default="64,256,1024")
    ap.add_argument("--hit", default="16,10")
    ap.add_argument("--weights-mb", type=int, default=None)
    ap.add_argument("--ops", action="store_true")
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
    gates_of = {name: getattr(expert_mlp, name)
                for name in ("use_expert_kernel", "use_grouped_kernel")}
    rs = np.random.RandomState(0)

    def rotation(T, K, hit):
        """ids [T, K]: exactly ``hit`` of the held experts get a token."""
        ids = np.empty((T, K), np.int32)
        for t in range(T):
            for c in range(K):
                ids[t, c] = FIRST + (t * K + c) % hit if c < min(K, hit) \
                    else (t + c) % FIRST            # an expert not held
        return ids

    def uniform(T, K):
        return np.stack([rs.permutation(E)[:K] for _ in range(T)]
                        ).astype(np.int32)

    def all_hit(T, K, N):
        ids = (np.arange(T)[:, None] + np.arange(K)) % FIRST
        ids[:, 0] = FIRST + np.arange(T) % N
        return ids.astype(np.int32)

    def ops_of(prog, *a):
        """{op: us a call} of one profiled run of the chain ``prog``."""
        import tempfile
        from benchmarks.harness import spans, trace
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                prog(*a).block_until_ready()
            planes = trace.read_planes(trace.find_xplane(d))
        own = {}
        for p in planes.values():
            for raw, _, s in spans.self_times(p["ops"]):
                key = trace.op_key(raw)
                own[key] = own.get(key, 0.0) + s
        return {k: round(v * 1e6 / CHAINS[1], 1)
                for k, v in trace.top(own, 12)}

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
    for h, M, N, K in (tuple(int(v) for v in s.split("x"))
                       for s in args.shapes.split(",")):
        pt.seed(0)
        layer = moe.ExpertShareMLP(h, M, E, K, FIRST, N)
        params = {k: v.astype(jnp.bfloat16)
                  for k, v in layer.named_parameters()}
        expert = 3 * h * M * 2
        for T in (int(v) for v in args.tokens.split(",")):
            x = jnp.asarray(rs.randn(T, h), jnp.bfloat16)
            gates = jnp.asarray(rs.rand(T, K) * 0.2, jnp.float32)
            few = T <= expert_mlp.MAX_TOKENS
            routings = [(f"hit{v}", rotation(T, K, int(v)))
                        for v in args.hit.split(",")] if few else \
                [("uniform", uniform(T, K)), ("all-hit", all_hit(T, K, N))]
            einsums = None          # their time does not read the routing
            for routing, ids in routings:
                held = (ids >= FIRST) & (ids < FIRST + N)
                hit = len(set(ids[held].tolist()))
                row = {"hidden": h, "width": M, "held": N, "choices": K,
                       "tokens": T, "routing": routing,
                       "pairs": int(held.sum()), "hit": hit,
                       "column_tile": expert_mlp._column_tile(h, M, 2),
                       "bytes_hit": hit * expert, "bytes_held": N * expert,
                       "device_kind": jax.devices()[0].device_kind}
                ids = jnp.asarray(ids)
                outs = {}
                for route, flag in (("kernel", True), ("einsums", False)):
                    for name, gate in gates_of.items():
                        setattr(expert_mlp, name, gate if flag
                                else lambda *_: False)

                    def fn(params, x, ids, gates, n):
                        with layer.bound(params):
                            for i in range(n):
                                # its own routing a call, or XLA sorts
                                # the choices once for the whole chain
                                x = layer.routed(x, jnp.roll(ids, i, 0),
                                                 jnp.roll(gates, i, 0))
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
                        elif args.ops and not few:
                            row["ops_us_a_call"] = ops_of(long, *a)
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
