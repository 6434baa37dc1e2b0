#!/usr/bin/env python3
"""Time a linear-attention layer's decode step alone on the chip: the
one-pass Pallas kernel (``ops/pallas/delta_state.py``) beside the two
XLA fusions of ``ops.delta_rule.delta_state_step``'s jnp body, both
through ``delta_state_step`` with its gate held open or shut (PERF.md
section 5, "The kernels alone").

    python3 tests/chip_state_step_timing.py [--rows 8,32,64]
        [--state-mb 24]

The hybrid cell's geometry: 30 heads, keys 96 and values 192 wide, the
state stored two heads to a 384-lane row, float32, every row live. One
JSON line per row count: the time of ONE call of each route, the bytes
of one read and one write of the state, and each route's share of 819
GB/s on those bytes (the count of ``harness/roofline_olmo_hybrid.py``).
A call's time is the two-point fit of tests/chip_ragged_timing.py: one
jitted program chains ``n`` calls over a DONATED state, each call's
state and output the next one's state and values, and (t(24) - t(8)) /
16 leaves out the dispatch. ``--state-mb`` sets the kernel's budget for
its four state buffers, which fixes the slots a grid step holds. Not a
pytest file; it refuses to run without a TPU.
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

H, DK, DV = 30, 96, 192
CHAINS = (8, 24)
REPEATS = 5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="8,32,64")
    ap.add_argument("--state-mb", type=int, default=None)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("chip_state_step_timing: needs a TPU", file=sys.stderr)
        return 1
    from paddle_tpu.ops import delta_rule as dr
    from paddle_tpu.ops.pallas import delta_state
    if args.state_mb:
        delta_state._VMEM_STATE = args.state_mb << 20
    hp = dr.state_lane_heads(H, DV)

    def seconds(prog, S, *a):
        S, o = prog(S, *a)                                  # compile
        o.block_until_ready()
        best = float("inf")
        for _ in range(3):
            t = time.perf_counter()
            for _ in range(REPEATS):
                S, o = prog(S, *a)
            o.block_until_ready()
            best = min(best, (time.perf_counter() - t) / REPEATS)
        return best, S

    rows = []
    rs = np.random.RandomState(0)
    for R in (int(v) for v in args.rows.split(",")):
        f = lambda *s: jnp.asarray(rs.standard_normal(s), jnp.float32)  # noqa: E731
        q = dr.l2_normalize(f(R, H, DK)) * DK ** -0.5
        k = dr.l2_normalize(f(R, H, DK))
        v = f(R, H, DV)
        beta = jnp.asarray(rs.uniform(0.01, 1.99, (R, H)), jnp.float32)
        alpha = jnp.asarray(np.exp(-np.exp(rs.uniform(-9, 1.5, (R, H)))),
                            jnp.float32)
        live = jnp.ones((R,), bool)
        S0 = dr.pack_state(f(R, H, DK, DV), hp)
        state = R * H * DK * DV * 4
        row = {"rows": R, "state_bytes": state,
               "slots_a_step": delta_state._rows_per_step(R, state // R),
               "device_kind": jax.devices()[0].device_kind}
        outs = {}
        for route, flag in (("kernel", True), ("fusions", False)):
            delta_state.use_state_kernel = lambda _S, flag=flag: flag

            def fn(S, q, k, v, alpha, beta, live, n):
                for _ in range(n):
                    S, v = dr.delta_state_step(S, q, k, v, alpha, beta,
                                               live)
                return S, v
            once, short, long = (
                jax.jit(functools.partial(fn, n=n), donate_argnums=(0,))
                for n in (1,) + CHAINS)
            a = (q, k, v, alpha, beta, live)
            S1, o1 = once(S0 + 0.0, *a)
            outs[route] = (np.asarray(S1), np.asarray(o1))
            t_long, S = seconds(long, S0 + 0.0, *a)
            t_short, S = seconds(short, S, *a)
            call = (t_long - t_short) / (CHAINS[1] - CHAINS[0])
            row[route + "_us_a_call"] = round(call * 1e6, 1)
            row[route + "_share_of_819GBs"] = round(
                100 * 2 * state / 819e9 / call, 1)
        row["state_max_err"] = float(np.abs(outs["kernel"][0]
                                            - outs["fusions"][0]).max())
        row["o_max_err"] = float(np.abs(outs["kernel"][1]
                                        - outs["fusions"][1]).max())
        row["o_max_abs"] = float(np.abs(outs["fusions"][1]).max())
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "state_step_timing.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        f.writelines(json.dumps(row) + "\n" for row in rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
