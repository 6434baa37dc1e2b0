#!/usr/bin/env python
"""Train-step microbenchmark for bisecting `bench.py` regressions on CPU
(ISSUE 4 satellite): synthetic batches through the REAL `Trainer` hot
path — jitted step, AOT warmup, `DevicePrefetcher` — N timed steps, one
JSON line per arm on stdout:

    {"prefetch": "on", "steps": 30, "step_ms": 8.1,
     "tokens_per_sec": 31600.0, "mfu": 1.1e-4, ...}

`--feed-delay-ms` injects a per-batch host-side delay (tokenization /
host-copy stand-in), which is the workload where the async prefetch
pipeline pays: `--prefetch on` overlaps that delay with step compute,
`--prefetch off` serializes it. `--prefetch both` (default) runs the A/B
in one process so a regression bisect is a single command:

    python tools/bench_step.py --steps 30 --feed-delay-ms 5

Runs on whatever backend jax selects (`JAX_PLATFORMS=cpu` for a CPU
bisect) — CPU numbers are meaningless in absolute terms, but the on/off
RATIO and step-to-step drift are what a bisect needs. `mfu` is null on
a device with no published peak (the CPU).
"""
import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


class SlowFeed:
    """List-backed loader whose every batch costs `delay_ms` of host time
    (sleep, so it overlaps with compute when prefetched — exactly like a
    tokenizer or host copy that releases the GIL)."""

    def __init__(self, batches, delay_ms: float):
        self._batches = batches
        self._delay_s = delay_ms / 1000.0

    def __iter__(self):
        for b in self._batches:
            if self._delay_s:
                time.sleep(self._delay_s)
            yield b

    def __len__(self):
        return len(self._batches)


def run_arm(prefetch_on: bool, ns: argparse.Namespace) -> dict:
    import numpy as np
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    from paddle_tpu.trainer import Trainer, TrainingArguments

    rng = np.random.RandomState(0)
    batches = [jnp.asarray(rng.randint(0, 256, (ns.batch, ns.seq)))
               for _ in range(8)]
    feed = SlowFeed(batches, ns.feed_delay_ms)
    with tempfile.TemporaryDirectory() as tmp:
        args = TrainingArguments(
            output_dir=tmp, max_steps=ns.steps,
            logging_steps=max(ns.steps // 3, 1),
            resume_from_checkpoint=False, save_steps=0,
            prefetch_depth=ns.depth if prefetch_on else 0,
            aot_warmup=True)   # compile lands before step 0, outside the timer
        tr = Trainer(LlamaForCausalLM(llama_tiny()),
                     pt.optimizer.AdamW(learning_rate=1e-4), args,
                     train_dataloader=feed)
        t0 = time.perf_counter()
        tr.train()
        wall_s = time.perf_counter() - t0
        timer = tr.step_timer
        feed_obj = tr._data_feed
        return {
            "prefetch": "on" if prefetch_on else "off",
            "depth": ns.depth if prefetch_on else 0,
            "steps": ns.steps,
            "batch": ns.batch,
            "seq": ns.seq,
            "feed_delay_ms": ns.feed_delay_ms,
            "step_ms": round(timer.avg_step_s * 1e3, 3),
            "tokens_per_sec": round(timer.tokens_per_sec, 1),
            "mfu": timer.mfu,
            "wall_s": round(wall_s, 2),
            "sync_fallbacks": getattr(feed_obj, "sync_fallbacks", 0),
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--prefetch", choices=("on", "off", "both"),
                    default="both")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--depth", type=int, default=2,
                    help="prefetch buffer depth for the `on` arm")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--feed-delay-ms", type=float, default=5.0,
                    help="host-side cost per batch (slow-feed workload)")
    ns = ap.parse_args(argv)

    arms = {"on": [True], "off": [False], "both": [False, True]}[ns.prefetch]
    results = []
    for on in arms:
        try:
            res = run_arm(on, ns)
        except Exception as e:   # one JSON line even on failure
            res = {"prefetch": "on" if on else "off", "error": repr(e)}
        results.append(res)
        print(json.dumps(res), flush=True)
    if len(results) == 2 and all("error" not in r for r in results):
        off, on_ = results
        print(json.dumps({
            "speedup_on_vs_off": round(
                on_["tokens_per_sec"] / max(off["tokens_per_sec"], 1e-9), 3),
        }), flush=True)
    return 1 if any("error" in r for r in results) else 0


if __name__ == "__main__":
    sys.exit(main())
