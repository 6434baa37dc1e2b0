#!/usr/bin/env python3
"""On the chip, outside the benchmark (ISSUE 46; PERF.md section 5):

    python3 tests/chip_chunk_attention_timing.py [--only kernel|chunk]

1. THE RAGGED KERNEL at Laguna-S-2.1's shapes against the dense gather
   (``paged_decode_attention_dense``): 8 kv heads of 128 columns under
   query groups of 6 (a whole table of 448 pages) and of 9 (a band of
   512 positions over a ring of 97 pages), rows of 4k-7k tokens, block
   boundaries and a full slot included; then the kernel alone at the
   cell's 64 rows, against its bytes.
2. A PROMPT CHUNK'S ATTENTION by the path ISSUE 46 replaced (row 0's
   WHOLE table gathered, one dense attention under the position mask:
   kept here as the yardstick) and by the walk over live runs of pages
   (``paged_chunk_attention_walk``) and, where ``chunk_attn_route``
   says so, by the kernel over tiles of the chunk's queries (ISSUE 48:
   ``paged_chunk_attention``), at Laguna's slot (48 and 72 heads, a
   chunk of 1,024, 448 pages / a ring of 97), MiMo-V2's (64 heads, keys
   192 / values 128, a chunk of 256, 128 pages / a ring of 25 with a
   sink: the walk alone), Qwen2-7B's (28 heads over 4) and
   Olmo-Hybrid's (30 over 30), at several live lengths: the walk and the
   kernel each compared with the gather (and the kernel with the walk),
   then timed. The gate's shape rule is read from this table.

One JSON line a case on stdout and all of them in
``chiprun_out/chunk_attention_timing.json``. Not a pytest file; it
refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

B = 16
REPEATS = 10


def timed(fn, *args):
    """Median seconds of one call (the call's own dispatch included:
    these calls take milliseconds)."""
    import jax
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def kernel_cases(emit):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.paged_cache import (PagedKV, paged_decode_attention,
                                            paged_decode_attention_dense,
                                            paged_decode_route)
    rng = np.random.default_rng(46)
    kvh, d = 8, 128
    for name, group, M, window, ring in (("full-g6", 6, 448, None, False),
                                         ("window-g9", 9, 97, 512, True)):
        h = kvh * group
        for R, lens in ((8, [4095, 4096, 4480, 5119, 5700, 6143, 6911,
                             7167]),
                        (64, rng.integers(4096, 6912, 64).tolist())):
            P = R * M + 1
            kp, vp = (jax.random.normal(jax.random.PRNGKey(i),
                                        (P, B, kvh * d), jnp.bfloat16)
                      for i in (R, R + 1))
            tables = jnp.asarray(1 + rng.permutation(R * M).reshape(R, M),
                                 jnp.int32)
            q = jnp.asarray(rng.normal(size=(R, 1, h, d)), jnp.bfloat16)
            pk = PagedKV(kp, vp, tables, jnp.asarray(lens, jnp.int32), kvh,
                         ring)
            assert paged_decode_route(q, kp, kvh) == "ragged"
            kernel = jax.jit(lambda q, pk: paged_decode_attention(
                q, pk, window=window))
            row = {"case": "kernel", "layer": name, "rows": R,
                   "kernel_ms": 1e3 * timed(kernel, q, pk)}
            seen = sum(min(n + 1, window) if window else n + 1
                       for n in lens)
            row["bytes_floor_ms"] = 1e3 * seen * 2 * kvh * d * 2 / 819e9
            row["roofline_pct"] = 100 * row["bytes_floor_ms"] \
                / row["kernel_ms"]
            if R == 8:      # the dense gather fits at 8 rows
                dense = jax.jit(lambda q, pk: paged_decode_attention_dense(
                    q, pk, None, window))
                got = np.asarray(kernel(q, pk), np.float32)
                want = np.asarray(dense(q, pk), np.float32)
                row["max_abs_err"] = float(np.abs(got - want).max())
                row["max_abs"] = float(np.abs(want).max())
                row["dense_ms"] = 1e3 * timed(dense, q, pk)
                # bf16 results of magnitude <= 0.2 over thousands of
                # keys: one bf16 step of the largest is 1e-3
                assert row["max_abs_err"] < 4e-3, row
            emit(row)


def dense_gather():
    """The parent's path: the yardstick tests/test_chunk_attention.py
    keeps, loaded from there so that there is one copy of it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chunk_yardstick", os.path.join(ROOT, "tests",
                                        "test_chunk_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.dense_gather


CHUNK_CASES = (
    # name, heads, kvh, dk, dv, chunk, pages, window, ring, sink, lives
    ("laguna-full", 48, 8, 128, 128, 1024, 448, None, False, False,
     (2048, 4096, 6144, 7168)),
    ("laguna-window", 72, 8, 128, 128, 1024, 97, 512, True, False,
     (2048, 6144)),
    ("laguna-full-c512", 48, 8, 128, 128, 512, 448, None, False, False,
     (4096, 7168)),
    ("mimo-full", 64, 4, 192, 128, 256, 128, None, False, False,
     (512, 1024, 1536, 2048)),
    ("mimo-window", 64, 8, 192, 128, 256, 25, 128, True, True,
     (512, 1536)),
    ("qwen2-7b", 28, 4, 128, 128, 256, 128, None, False, False,
     (512, 1024, 2048)),
    ("olmo-full", 30, 30, 128, 128, 256, 128, None, False, False,
     (512, 1536)),
)


def chunk_cases(emit):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.paged_cache import (PagedKV, chunk_attn_route,
                                            paged_chunk_attention,
                                            paged_chunk_attention_walk)
    rng = np.random.default_rng(47)
    gather = dense_gather()
    for (name, h, kvh, dk, dv, chunk, M, window, ring, has_sink,
         lives) in CHUNK_CASES:
        P = 2 * M + 1
        kp = jax.random.normal(jax.random.PRNGKey(M), (P, B, kvh * dk),
                               jnp.bfloat16)
        vp = jax.random.normal(jax.random.PRNGKey(M + 1), (P, B, kvh * dv),
                               jnp.bfloat16)
        table = jnp.asarray(1 + rng.permutation(P - 1)[:M][None], jnp.int32)
        q = jnp.asarray(rng.normal(size=(1, chunk, h, dk)), jnp.bfloat16)
        sink = jnp.asarray(rng.normal(size=(h,)), jnp.float32) \
            if has_sink else None
        walk = jax.jit(lambda q, pk, pos, sink: paged_chunk_attention_walk(
            q, pk, pos, window, sink))
        kernel = jax.jit(lambda q, pk, pos, sink: paged_chunk_attention(
            q, pk, pos, window=window, sink=sink)) \
            if chunk_attn_route(q, kp, kvh) == "kernel" else None
        dense = jax.jit(lambda q, pk, pos, sink: gather(
            q, pk, pos, window=window, sink=sink))
        for live in lives:
            pk = PagedKV(kp, vp, table, jnp.asarray([live], jnp.int32),
                         kvh, ring, "chunk")
            pos = jnp.asarray(live - chunk + np.arange(chunk))[None]
            got = np.asarray(walk(q, pk, pos, sink), np.float32)
            want = np.asarray(dense(q, pk, pos, sink), np.float32)
            pairs = chunk * (live - chunk) + chunk * (chunk + 1) // 2 \
                if not ring else None
            row = {"case": "chunk", "layer": name, "live": live,
                   "slot": M * B, "chunk": chunk,
                   "max_abs_err": float(np.abs(got - want).max()),
                   "max_abs": float(np.abs(want).max()),
                   "dense_ms": 1e3 * timed(dense, q, pk, pos, sink),
                   "walk_ms": 1e3 * timed(walk, q, pk, pos, sink)}
            if kernel is not None:
                out = np.asarray(kernel(q, pk, pos, sink), np.float32)
                row["kernel_ms"] = 1e3 * timed(kernel, q, pk, pos, sink)
                row["kernel_err_vs_walk"] = float(np.abs(out - got).max())
                row["kernel_err_vs_dense"] = float(np.abs(out - want).max())
                assert row["kernel_err_vs_dense"] \
                    < 2e-2 * max(row["max_abs"], 1), row
            if pairs:
                row["flops_floor_ms"] = 1e3 * pairs * h * 2 * (dk + dv) \
                    / 197e12
                row["walk_flops_pct"] = 100 * row["flops_floor_ms"] \
                    / row["walk_ms"]
                if kernel is not None:
                    row["kernel_flops_pct"] = 100 * row["flops_floor_ms"] \
                        / row["kernel_ms"]
            # bf16 probabilities and values: the dense path rounds its
            # normalised probabilities, the walk its unnormalised ones
            assert row["max_abs_err"] < 2e-2 * max(row["max_abs"], 1), row
            emit(row)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=("kernel", "chunk"), default=None)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("chip_chunk_attention_timing: needs a TPU", file=sys.stderr)
        return 1
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    if args.only != "chunk":
        kernel_cases(emit)
    if args.only != "kernel":
        chunk_cases(emit)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "chunk_attention_timing.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
