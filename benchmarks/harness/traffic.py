"""The one traffic generator. No jax.

A mix is a data file (``benchmarks/traffic/<mix>.json``) of parameters;
``plan()`` turns it, a seed and a rate or client count into the
sessions a run sends. A session is a tenant's system prompt (shared or
none) and one or more turns; a single request is a session of one turn.

Traffic is what independent users send: the sessions of an open loop
arrive as a Poisson process (exponential gaps at the cell's rate), and
every size, turn count and think time is drawn independently from its
distribution. Nothing is evened out.

Two generators feed a plan. The SCHEDULE (arrival times, sizes, turn
counts, think times, tenants, which requests sample) comes from the
mix's ``schedule_seed`` where it has one: one fixed trace of the
process above, the same in every run of the cell, because on a window
of a minute the trace itself decides the tails (where three long
answers fall into one burst of arrivals, p90 TTFT is tenfold what it
is where they do not: PERF.md, Findings), and a run-to-run comparison
has to hold the offered work still. The CONTENT (token ids, the
per-request sampling seeds; the weights, elsewhere) comes from
``--seed``. A mix without ``schedule_seed`` draws its schedule from
``--seed`` too.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

_NORMAL = NormalDist()


def draw(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` independent values of the distribution ``spec`` (by its
    inverse at ``n`` uniform draws), clipped to its ``min`` and ``cap``."""
    u = np.clip(rng.random(n), 1e-9, 1 - 1e-9)
    kind = spec["dist"]
    if kind == "const":
        x = np.full(n, float(spec["value"]))
    elif kind == "uniform":
        x = spec["lo"] + u * (spec["hi"] - spec["lo"])
    elif kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(v)) for v in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif kind == "exponential":
        x = -spec["mean"] * np.log1p(-u)
    elif kind == "geometric":
        p = 1.0 / spec["mean"]
        x = 1 + np.floor(np.log1p(-u) / math.log1p(-p))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = spec.get("min"), spec.get("cap")
    if lo is not None or hi is not None:
        x = np.clip(x, lo, hi)
    return x


def _ints(rng, spec, n) -> List[int]:
    return [int(round(v)) for v in draw(rng, spec, n)]


def plan(seed: int, mix: dict, vocab: int, seconds: float,
         rate: Optional[float] = None, clients: Optional[int] = None,
         max_context: int = 2048) -> Dict:
    """The sessions of one run: ``lead_in_s + seconds`` of traffic.

    open loop   — sessions arrive as a Poisson process of ``rate`` a second;
    closed loop — ``clients`` callers, each sending its next request when
                  the last one completed.
    """
    content = np.random.default_rng([int(seed), 0])
    base = int(mix["schedule_seed"]) if "schedule_seed" in mix \
        else [int(seed), 1]

    def stream(k: int) -> np.random.Generator:
        # one generator per quantity, so that a shorter run sends a
        # prefix of the longer run's schedule
        return np.random.default_rng([k] + np.atleast_1d(base).tolist())
    horizon = float(mix.get("lead_in_s", 0.0)) + float(seconds)
    loop = mix["loop"]
    if loop == "open":
        if not rate:
            raise ValueError("an open-loop mix needs the cell's rate")
        gaps = stream(1).exponential(1.0 / rate,
                                     int(rate * horizon * 2) + 64)
        arrivals = np.cumsum(gaps)
        arrivals = arrivals[arrivals < horizon]
        n = len(arrivals)
        owners = [None] * n
    elif loop == "closed":
        if not clients:
            raise ValueError("a closed-loop mix needs the cell's clients")
        per = int(mix["requests_per_client"])
        n = clients * per
        arrivals = [0.0] * n
        owners = [i % clients for i in range(n)]
    else:
        raise ValueError(f"unknown loop {loop!r}")

    tenants = int(mix.get("tenants", 0))
    system_len = int(mix.get("system_prompt_tokens", 0))
    systems = [content.integers(1, vocab, system_len).tolist()
               for _ in range(tenants)]
    turns_n = _ints(stream(2), mix.get("turns", {"dist": "const", "value": 1}),
                    n)
    total_turns = int(sum(turns_n))
    msg_len = _ints(stream(3), mix["message_tokens"], total_turns)
    new_len = _ints(stream(4), mix["new_tokens"], total_turns)
    think = draw(stream(5),
                 mix.get("think_s", {"dist": "const", "value": 0.0}),
                 total_turns)
    sampled = stream(6).random(n) < float(mix.get("sampled_share", 0.0))
    tenant_of = stream(7).integers(0, tenants, n) if tenants else None

    sessions, k = [], 0
    for i in range(n):
        used = system_len
        turns = []
        for _ in range(turns_n[i]):
            m, g, th = msg_len[k], new_len[k], float(think[k])
            k += 1
            if used + m + g > max_context:
                continue        # the session has outgrown its context
            turns.append({"message": content.integers(1, vocab, m).tolist(),
                          "max_new_tokens": g, "think_s": th})
            used += m + g
        if not turns:
            continue
        sessions.append({
            "index": i, "arrival": float(arrivals[i]), "client": owners[i],
            "tenant": int(tenant_of[i]) if tenants else None,
            "greedy": not bool(sampled[i]),
            "seed": int(content.integers(1, 2**31 - 1)),
            "turns": turns})
    return {"loop": loop, "lead_in_s": float(mix.get("lead_in_s", 0.0)),
            "drain_s": float(mix.get("drain_s", 0.0)),
            "sampling": mix.get("sampling", {}), "systems": systems,
            "sessions": sessions}
