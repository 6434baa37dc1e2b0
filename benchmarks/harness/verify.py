"""What decides ``correct``: the served tokens of the timed path against
the plain reference, plus the exact checks on the run's bookkeeping.

After the window has closed, two samples of the requests that it
finished (due in it, or ended in it) are teacher-forced through the
family's reference, once per request (prompt + served tokens): one of
the GREEDY requests and, where the mix samples, one of the SAMPLED
ones. Each holds the longest and the shortest context of its kind,
later turns of sessions where the mix has any, the rest drawn from the
seed. Each number has a limit of its own (in the configuration's file,
with the readings it was set from in PERF.md).

Greedy requests:

- ``argmax_gap_max``: the widest gap by which a served token's
  reference logit lies below the reference's best logit at that
  position (0 where the program chose the reference's own first token);
- ``logprob_rms``: the root mean square, over the sample's tokens, of
  the logprob the gateway streamed minus the reference's log-softmax at
  that token. Steady from seed to seed where the first is a maximum.

Sampled requests (the engine streams the served token's logprob under
the unfiltered softmax, so the same reading applies):

- ``sampled_logprob_rms``: as ``logprob_rms``, over the sampled tokens;
- ``sampled_set_gap_max``: the widest gap by which a served token's
  reference logit lies below the LOWEST logit of the set the reference
  itself would sample from at that position: its ``top_k`` best,
  cut where their renormalised mass at the request's temperature
  reaches ``top_p``. 0 where every served token lies inside that set;
  a token outside it by less than the program's own rounding reads a
  small gap, a sampler that ignores its filters reads whole logits.

The control (``control_numbers``) is the reference itself put in the
program's place in the nearest precision below the configuration's
(int8 for bfloat16). It need not decode: at each position of
the same prompts and tokens it reads the gap of the token the lower
precision puts first, and the lower precision's logprob of the served
token. Benchmark runs do not run it; ``benchmarks/tests`` do.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

from . import stats

NUMBERS = ("argmax_gap_max", "logprob_rms", "sampled_logprob_rms",
           "sampled_set_gap_max")


def choose_sample(records: List[dict], w0: float, w1: float, seed: int,
                  n: int, greedy: bool = True) -> List[dict]:
    pool = [r for r in records
            if r["greedy"] == greedy and stats.finished(r) and r["tokens"]
            and (stats.in_window(r, w0, w1) or w0 <= r["end"] < w1)]
    if not pool:
        return []
    size = lambda r: len(r["prompt"]) + len(r["tokens"])   # noqa: E731
    pool.sort(key=lambda r: r["id"])
    picked = [max(pool, key=size), min(pool, key=size)]
    later = sorted((r for r in pool if r["turn"] > 0), key=size,
                   reverse=True)
    picked += later[:2]
    rng = np.random.default_rng([int(seed), 0x5EED, int(greedy)])
    rest = [r for r in pool if all(r is not p for p in picked)]
    rng.shuffle(rest)
    out, seen = [], set()
    for r in picked + rest:
        if r["id"] not in seen:
            seen.add(r["id"])
            out.append(r)
    return out[:max(n, 1)]


def _rows_per_block(config: dict, longest: int) -> int:
    """Rows of one reference pass: attention scores of a block stay
    under about 400 MB of float32."""
    L = -(-longest // 256) * 256
    per_row = config["num_attention_heads"] * L * L * 4
    rows = 4
    while rows > 1 and rows * per_row > 400e6:
        rows //= 2
    return rows


def _pass(model, params, config, sample, read, mode=None, top=0):
    seqs = [r["prompt"] + r["tokens"] for r in sample]
    return model.reference_rows(
        params, config, seqs, [len(r["prompt"]) for r in sample], read,
        mode=mode, top=top,
        rows_per_block=_rows_per_block(config, max(map(len, seqs))))


def _logprob_err(sample, ref) -> np.ndarray:
    return np.concatenate([np.asarray(r["lps"], np.float64)
                           - (o["at"] - o["lse"])
                           for r, o in zip(sample, ref)])


def set_floor(top: np.ndarray, temperature: float,
              top_p: float) -> np.ndarray:
    """The lowest logit of the set a sampler draws from, given its
    ``top_k`` best logits in falling order (``top`` [n, top_k]): those
    whose renormalised mass at ``temperature``, summed over the better
    ones, is still under ``top_p``. The best is always kept."""
    lt = (top - top[:, :1]) / max(float(temperature), 1e-6)
    p = np.exp(lt)
    p /= p.sum(-1, keepdims=True)
    kept = ((np.cumsum(p, -1) - p) < float(top_p)).sum(-1)
    return top[np.arange(len(top)), np.maximum(kept, 1) - 1]


def numbers(model, params, config: dict, greedy: List[dict],
            sampled: Optional[List[dict]] = None,
            sampling: Optional[dict] = None) -> Dict:
    """The numbers of the program's served tokens: two of the greedy
    sample and, where there is a sampled one, two of that."""
    ref = _pass(model, params, config, greedy, [r["tokens"] for r in greedy])
    gap = np.concatenate([o["best"] - o["at"] for o in ref])
    err = _logprob_err(greedy, ref)
    out = {"argmax_gap_max": float(gap.max()),
           "logprob_rms": float(np.sqrt(np.mean(err ** 2))),
           "tokens": int(gap.size), "requests": len(greedy),
           "longest": max(len(r["prompt"]) + len(r["tokens"])
                          for r in greedy + (sampled or [])),
           "finite": bool(np.all(np.isfinite(gap))
                          and np.all(np.isfinite(err)))}
    if sampled:
        k = int(sampling.get("top_k", 0))
        if k <= 0:
            raise ValueError("the sampled requests' set is read from the "
                             "reference's top_k best logits: the mix's "
                             "sampling needs a top_k")
        ref = _pass(model, params, config, sampled,
                    [r["tokens"] for r in sampled], top=k)
        err = _logprob_err(sampled, ref)
        floor = np.concatenate([set_floor(
            o["top"], sampling.get("temperature", 1.0),
            sampling.get("top_p", 1.0)) for o in ref])
        out_of = np.maximum(floor - np.concatenate([o["at"] for o in ref]),
                            0.0)
        out.update(sampled_logprob_rms=float(np.sqrt(np.mean(err ** 2))),
                   sampled_set_gap_max=float(out_of.max()),
                   sampled_tokens=int(err.size),
                   sampled_requests=len(sampled),
                   sampled_outside=int((out_of > 0).sum()),
                   finite=bool(out["finite"] and np.all(np.isfinite(err))
                               and np.all(np.isfinite(out_of))))
    return out


def control_numbers(model, params, config: dict, sample: List[dict],
                    mode: str = "int8") -> Dict:
    """The greedy sample's two numbers for the reference computed in
    ``mode``."""
    served = [r["tokens"] for r in sample]
    low = _pass(model, params, config, sample, served, mode=mode)
    ref = _pass(model, params, config, sample,
                [o["best_token"].tolist() for o in low])
    ref_served = _pass(model, params, config, sample, served)
    gap = np.concatenate([o["best"] - o["at"] for o in ref])
    err = np.concatenate([(lo["at"] - lo["lse"]) - (o["at"] - o["lse"])
                          for lo, o in zip(low, ref_served)])
    return {"argmax_gap_max": float(gap.max()),
            "logprob_rms": float(np.sqrt(np.mean(err ** 2))),
            "tokens": int(gap.size)}


def exact_checks(records: List[dict]) -> List[str]:
    """Bookkeeping that must hold exactly; returns what does not."""
    bad = []
    for r in records:
        if stats.finished(r):
            if r["tokens"] != r.get("final_tokens"):
                bad.append(f"{r['id']}: streamed tokens differ from the "
                           f"final list")
            elif r["lps"] != r.get("final_lps"):
                bad.append(f"{r['id']}: streamed logprobs differ from the "
                           f"final list")
            elif len(r["tokens"]) != r["max_new_tokens"]:
                bad.append(f"{r['id']}: asked for {r['max_new_tokens']} "
                           f"tokens, got {len(r['tokens'])}")
            elif not all(lp is not None and math.isfinite(lp) and lp <= 0
                         for lp in r["lps"]):
                bad.append(f"{r['id']}: a logprob is missing, not finite "
                           f"or positive")
        elif r.get("cancelled") or r.get("status") in (429, 503):
            # cut by the client itself at the window's end (closed loop)
            # or at its deadline, or shed: accounted for, and counted
            # among the failed where it was due in the window
            continue
        else:
            bad.append(f"{r['id']}: neither finished, shed nor cut by the "
                       f"client: status {r.get('status')} "
                       f"{r.get('finish_reason')!r} {r.get('error')!r}")
    return bad


def judge(nums: Optional[Dict], limits: Dict[str, float]) -> List[str]:
    """Each number beside its limit; returns the failures."""
    if nums is None:
        return ["no finished request of each kind (greedy, and sampled where "
                "the mix samples) in the window to compare"]
    bad = []
    if not nums["finite"]:
        bad.append("a compared number is not finite")
    for k in NUMBERS:
        if k not in nums:
            continue
        if k not in limits:
            bad.append(f"{k} was read and the configuration gives it no "
                       f"limit")
        elif not nums[k] <= limits[k]:
            bad.append(f"{k} {nums[k]:.5f} over its limit {limits[k]}")
    return bad
