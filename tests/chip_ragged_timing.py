#!/usr/bin/env python3
"""Time the bare ragged decode-attention kernel on the chip, outside
the benchmark (PERF.md section 5, "The kernel alone").

    python3 tests/chip_ragged_timing.py [--parent DIR] [--contexts 64,320]

At the serving cell's geometry (8 rows, 128 blocks of 16 tokens, 4 kv
heads, group 7, head 128, bf16, a 2049-block pool) every row holds the
same context; one JSON line per context with the time of ONE kernel
call. ``--parent DIR`` (a checkout of another commit, e.g. `git archive`
into a directory `.gitignore` lists; PR 27 or later, whose kernel takes
the [P, B, kvh*d] pools and the head count) times that commit's kernel
beside this one in the same process, and the dense whole-table gather
(`paged_decode_attention_dense`) is timed too: it reads all M*B
positions whatever the context.

A call's time is a two-point fit: one jitted program chains `n` calls
(each call's output is the next one's query, so none is deduplicated)
and (t(40) - t(8)) / 32 leaves out the dispatch. Each kernel's output is
compared with the dense gather's before it is timed. Not a pytest file;
it refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

R, M, B, KVH, GROUP, D, P = 8, 128, 16, 4, 7, 128, 2049
CHAINS = (8, 40)
REPEATS = 20


def load_kernel(checkout):
    """``ragged_paged_attention_pallas`` of another checkout, loaded as
    a sibling module of this tree's `paddle_tpu.ops.pallas` package."""
    import paddle_tpu.ops.pallas  # noqa: F401  (the package it joins)
    path = os.path.join(checkout, "paddle_tpu", "ops", "pallas",
                        "ragged_paged_attention.py")
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.ops.pallas._other_ragged", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.ragged_paged_attention_pallas


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--contexts", default="64,320,576,1024,2040")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("chip_ragged_timing: needs a TPU", file=sys.stderr)
        return 1
    from paddle_tpu.ops.paged_cache import (PagedKV,
                                            paged_decode_attention_dense)
    from paddle_tpu.ops.pallas.ragged_paged_attention import \
        ragged_paged_attention_pallas

    def dense(q, kp, vp, tbl, lens, scale, kv_heads):
        pk = PagedKV(kp, vp, tbl, lens, kv_heads)
        return paged_decode_attention_dense(q[:, None], pk, scale)[:, 0]

    kernels = {"change": ragged_paged_attention_pallas, "dense": dense}
    if args.parent:
        kernels["parent"] = load_kernel(args.parent)

    rs = np.random.RandomState(0)
    h = KVH * GROUP
    q = jnp.asarray(rs.randn(R, h, D), jnp.bfloat16)
    kp = jnp.asarray(rs.randn(P, B, KVH * D), jnp.bfloat16)
    vp = jnp.asarray(rs.randn(P, B, KVH * D), jnp.bfloat16)
    tbl = jnp.asarray(1 + rs.permutation(P - 1)[:R * M].reshape(R, M),
                      jnp.int32)

    def chain(fn, n):
        def run(q, kp, vp, tbl, lens):
            for _ in range(n):
                q = fn(q, kp, vp, tbl, lens, D ** -0.5, KVH)
            return q
        return jax.jit(run)

    def seconds(prog, lens):
        prog(q, kp, vp, tbl, lens).block_until_ready()      # compile
        best = float("inf")
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(REPEATS):
                out = prog(q, kp, vp, tbl, lens)
            out.block_until_ready()
            best = min(best, (time.perf_counter() - t) / REPEATS)
        return best

    progs = {name: [chain(fn, n) for n in (1,) + CHAINS]
             for name, fn in kernels.items()}
    dev = jax.devices()[0]
    rows = []
    for ctx in (int(c) for c in args.contexts.split(",")):
        lens = jnp.full((R,), ctx - 1, jnp.int32)   # ctx tokens attended
        ref = np.asarray(progs["dense"][0](q, kp, vp, tbl, lens),
                         np.float32)
        row = {"context": ctx, "rows": R, "device_kind": dev.device_kind}
        for name, (once, short, long) in progs.items():
            if name != "dense":
                got = np.asarray(once(q, kp, vp, tbl, lens), np.float32)
                row[name + "_max_err"] = float(np.abs(got - ref).max())
            t0, t1 = seconds(short, lens), seconds(long, lens)
            row[name + "_us_a_call"] = round(
                (t1 - t0) / (CHAINS[1] - CHAINS[0]) * 1e6, 2)
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "ragged_timing.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
