#!/usr/bin/env python3
"""Time a prompt chunk's delta rule at a decay a key channel alone on
the chip: the one Pallas kernel a layer (``ops/pallas/delta_chunk.py``)
beside the fusions of ``ops.delta_rule._chunk_channel``, both through
``gated_delta_chunk`` with its gate held open or shut (PERF.md section
5, "The kernels alone").

    python3 tests/chip_chunk_rule_timing.py [--live 128,192,256]
        [--side-by-side 4]

The Ling cell's geometry: 32 heads of 128 x 128, a call of 256
positions in sub-chunks of 64, float32, one segment. ``--live``: the
call's real positions; those behind them are padded as the model pads
(``beta`` 0, ``g`` 0), so at 192 one sub-chunk of four is dead and at
128 two are, which the kernel skips and the fusions compute. One JSON
line per count: the time of ONE call (one layer) of each route, and the
share of 197 TFLOP/s its live positions' 3 x 2 x 128 x 128 FLOP a
position and head come to (the count of ``harness/roofline_ling.py``).
A call's time is the two-point fit of tests/chip_state_step_timing.py:
one jitted program chains ``n`` calls, each call's state and output the
next one's state and values, and (t(12) - t(4)) / 8 leaves out the
dispatch (three elementwise passes over q, k and g ride in either
route's call: they tie the next call's operands to this one's output).
``--side-by-side`` sets how many heads the kernel traces interleaved.
Not a pytest file; it refuses to run without a TPU.
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

H, D, T = 32, 128, 256
CHAINS = (4, 12)
REPEATS = 5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", default="128,192,256")
    ap.add_argument("--side-by-side", type=int, default=None)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("chip_chunk_rule_timing: needs a TPU", file=sys.stderr)
        return 1
    from paddle_tpu.ops import delta_rule as dr
    from paddle_tpu.ops.pallas import delta_chunk
    if args.side_by_side:
        delta_chunk._HEADS_A_TIME = args.side_by_side

    def seconds(prog, *a):
        prog(*a)[0].block_until_ready()                     # compile
        best = float("inf")
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(REPEATS):
                o = prog(*a)[0]
            o.block_until_ready()
            best = min(best, (time.perf_counter() - t) / REPEATS)
        return best

    rs = np.random.RandomState(0)
    f = lambda *s: jnp.asarray(rs.standard_normal(s), jnp.float32)  # noqa: E731
    q = dr.l2_normalize(f(T, H, D)) * D ** -0.5
    k = dr.l2_normalize(f(T, H, D))
    v, S0 = f(T, H, D), f(H, D, D)
    rows = []
    for live in (int(x) for x in args.live.split(",")):
        real = jnp.arange(T) < live
        beta = jnp.where(real[:, None], jnp.asarray(
            rs.uniform(0.01, 0.99, (T, H)), jnp.float32), 0.0)
        g = jnp.where(real[:, None, None],
                      -5.0 * jax.nn.sigmoid(3.0 * f(T, H, D)), 0.0)
        row = {"live": live, "positions": T,
               "side_by_side": delta_chunk._HEADS_A_TIME,
               "device_kind": jax.devices()[0].device_kind}
        outs = {}
        for route, flag in (("kernel", True), ("fusions", False)):
            delta_chunk.use_chunk_kernel = lambda *a, flag=flag: flag

            def fn(q, k, v, g, beta, S, n):
                for _ in range(n):
                    v, S = dr.gated_delta_chunk(q, k, v, g, beta, S)
                    S = S[0]
                    # every operand of the next call hangs on this one:
                    # XLA would else compute what q, k and g alone decide
                    # (most of the fusions' work) once for the chain
                    s = 1.0 + 1e-12 * v[0, 0, 0]
                    q, k, g = q * s, k * s, g * s
                return v, S
            once, short, long = (jax.jit(functools.partial(fn, n=n))
                                 for n in (1,) + CHAINS)
            a = (q, k, v, g, beta, S0)
            outs[route] = [np.asarray(x) for x in once(*a)]
            call = (seconds(long, *a) - seconds(short, *a)) \
                / (CHAINS[1] - CHAINS[0])
            row[route + "_us_a_call"] = round(call * 1e6, 1)
            row[route + "_share_of_197TFLOPs"] = round(
                100 * live * H * 3 * 2 * D * D / 197e12 / call, 3)
        row["o_max_err"] = float(np.abs(outs["kernel"][0][:live]
                                        - outs["fusions"][0][:live]).max())
        row["state_max_err"] = float(np.abs(outs["kernel"][1]
                                            - outs["fusions"][1]).max())
        row["o_max_abs"] = float(np.abs(outs["fusions"][0][:live]).max())
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "chunk_rule_timing.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as log:
        log.writelines(json.dumps(row) + "\n" for row in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
