"""Percentile and request arithmetic of the client side. No jax.

Every time here is a ``time.monotonic()`` reading of the client child,
and every request is timed from when it was DUE to be sent, so a stall
that delays a send is charged to the server and not hidden by it.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics, as ``numpy.percentile`` gives it. None for no
    sample."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def in_window(rec: dict, w0: float, w1: float) -> bool:
    """A request belongs to the window if it was due inside it."""
    return w0 <= rec["due"] < w1


def ttft_ms(rec: dict, give_up: float) -> float:
    """Due send to first streamed token. A request that never showed a
    token is charged the whole time until the client gave up on it."""
    first = rec["token_times"][0] if rec["token_times"] else give_up
    return (first - rec["due"]) * 1e3


def gaps_ms(rec: dict) -> List[float]:
    t = rec["token_times"]
    return [(b - a) * 1e3 for a, b in zip(t, t[1:])]


def mean_gap_ms(rec: dict) -> Optional[float]:
    t = rec["token_times"]
    if len(t) < 2:
        return None
    return (t[-1] - t[0]) * 1e3 / (len(t) - 1)


def finished(rec: dict) -> bool:
    return rec.get("status") == 200 and rec.get("finish_reason") == "stop" \
        and not rec.get("error")


def meets_slo(rec: dict, ttft_limit_ms: float, gap_limit_ms: float,
              give_up: float) -> bool:
    """TTFT and the mean gap between tokens both inside their limits.
    Failed, shed and unfinished requests miss."""
    if not finished(rec):
        return False
    if ttft_ms(rec, give_up) > ttft_limit_ms:
        return False
    g = mean_gap_ms(rec)
    return g is None or g <= gap_limit_ms


def cut_by_client(rec: dict, loop: str) -> bool:
    """A closed-loop client stops at the window's end by design: what
    it then had in flight was cut, not failed."""
    return loop == "closed" and bool(rec.get("cancelled"))


def client_metrics(records: List[dict], w0: float, w1: float,
                   give_up: float, slo: Optional[dict],
                   loop: str = "open") -> Dict[str, dict]:
    """Every client-side number of one run. ``records`` are all requests
    the child sent (lead-in included); only those due in ``[w0, w1)``
    are judged, but tokens count by when they ARRIVED: a token received
    inside the window counts whichever request it belongs to."""
    win = [r for r in records if in_window(r, w0, w1)]
    tokens_in = sum(1 for r in records for t in r["token_times"]
                    if w0 <= t < w1)
    out: Dict[str, dict] = {
        "tokens_per_s": {"value": tokens_in / (w1 - w0), "n": tokens_in},
        "attempted": {"value": len(win)},
        # failed: shed, errored, or (open loop) still unfinished when
        # the client gave up on it
        "failed": {"value": sum(1 for r in win if not finished(r)
                                and not cut_by_client(r, loop))},
    }
    ttfts = [ttft_ms(r, give_up) for r in win]
    gaps = [g for r in win for g in gaps_ms(r)]
    late = [(r["sent"] - r["due"]) * 1e3 for r in win if r.get("sent")]
    mean_gaps = [g for g in map(mean_gap_ms, win) if g is not None]
    for name, xs, q in (("ttft_p90_ms", ttfts, 90), ("ttft_p50_ms", ttfts, 50),
                        ("ttft_p75_ms", ttfts, 75), ("ttft_p95_ms", ttfts, 95),
                        ("gap_p95_ms", gaps, 95), ("gap_p50_ms", gaps, 50),
                        ("mean_gap_p90_ms", mean_gaps, 90),
                        ("loadgen_late_p95_ms", late, 95)):
        v = percentile(xs, q)
        if v is not None:
            out[name] = {"value": v, "n": len(xs)}
    if ttfts:
        out["ttft_mean_ms"] = {"value": sum(ttfts) / len(ttfts),
                               "n": len(ttfts)}
    if slo and win:
        met = sum(meets_slo(r, slo["ttft_ms"], slo["mean_gap_ms"], give_up)
                  for r in win)
        out["slo_met_share"] = {"value": 100.0 * met / len(win),
                                "n": len(win)}
    return out
