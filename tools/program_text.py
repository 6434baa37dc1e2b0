#!/usr/bin/env python
"""Digests of the serving programs' lowered text, a tiny engine a family.

    python tools/program_text.py [checkout [chunk]] > a.txt
    python tools/program_text.py <other checkout> > b.txt ; diff a.txt b.txt

For each model family served through ``PagedEngine`` a tiny engine's
programs (the two fused ticks, the speculative ticks, the packed and the
lone prompt chunk, the whole-prompt prefill, the host reference's tick)
are lowered on the CPU, nothing compiled or run, and one line a program
is printed: its name, a digest of its StableHLO text, the text's length.
Two checkouts that print the same lines hand XLA the same programs for
those families: the check a PR makes that adds a family or a mechanism
beside them (PR 33, PR 38, PR 41). A family the checkout lacks is left out, so
the older checkout's lines are a subset. ``chunk`` (16 unless given)
is the positions of a prompt call: above a tick's 128 rows, and with
``PADDLE_TPU_PALLAS_INTERPRET=1`` so that the kernels are taken, the
expert families' prompt calls are the ones whose path differs from a
tick's (PR 47).
"""
import hashlib
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# (imported, as tests/test_delta_rule_chunk_kernel.py does: this checkout)
ROOT = os.path.abspath(sys.argv[1]) \
    if __name__ == "__main__" and len(sys.argv) > 1 else \
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu import models  # noqa: E402
from paddle_tpu.generation.paged import PagedEngine  # noqa: E402

FAMILIES = {
    "llama": ("LlamaForCausalLM", "llama_tiny", {}, True),
    "qwen2": ("Qwen2ForCausalLM", "qwen2_tiny", {}, True),
    "deepseek": ("DeepseekV2ForCausalLM", "deepseek_v2_tiny",
                 dict(scoring="sigmoid", experts_held=4), True),
    "longcat": ("LongcatFlashForCausalLM", "longcat_flash_tiny",
                dict(experts_held=4), True),
    "mimo": ("MiMoV2ForCausalLM", "mimo_v2_tiny", dict(experts_held=4),
             True),
    "olmo_hybrid": ("OlmoHybridForCausalLM", "olmo_hybrid_tiny", {}, False),
    "ling_hybrid": ("LingHybridForCausalLM", "ling_hybrid_tiny",
                    dict(experts_held=4), False),
    "laguna": ("LagunaForCausalLM", "laguna_tiny", dict(experts_held=4),
               True),
}
CHUNK = int(sys.argv[2]) if __name__ == "__main__" and len(sys.argv) > 2 \
    else 16
GEOMETRY = dict(max_slots=4, num_blocks=4 * CHUNK, block_size=4,
                max_blocks_per_seq=CHUNK, chunk_prefill_tokens=CHUNK)


def programs(model, spec: int):
    """(name, lowered) of one engine's programs."""
    eng = PagedEngine(model, spec_tokens=spec, **GEOMETRY)
    eng._refresh_dev()
    state = (eng.params, eng.pools, eng.seen, eng._dev)
    if spec:
        yield "tick_spec", eng._tick_spec_jit.lower(*state)
        yield "tick_spec_greedy", eng._tick_spec_greedy_jit.lower(*state)
        return
    yield "tick", eng._tick_jit.lower(*state)
    yield "tick_greedy", eng._tick_greedy_jit.lower(*state)
    eng.submit(0, list(range(1, CHUNK + 14)), max_new_tokens=4)
    eng._try_admit()
    call, _ = eng._pack_call([0])
    yield "packed", eng._chunk_jit.packed.lower(
        eng.params, eng.pools, eng.seen, eng._put(call))
    row, key = eng._put(eng.block_tables[0]), eng._put(eng.slots[0].key)
    sampling = (np.float32(0), np.int32(0), np.float32(1), np.float32(1))
    yield "alone", eng._chunk_jit.alone.lower(
        eng.params, eng.pools, row,
        eng._put(np.zeros((1, CHUNK), np.int32)), np.int32(CHUNK),
        np.int32(CHUNK + 13), key, *sampling, eng.seen[0], np.int32(0),
        bucket=CHUNK)
    yield "prefill", eng._prefill_jit.lower(
        eng.params, eng.pools, row, eng._put(np.zeros((1, 32), np.int32)),
        np.int32(29), key, *sampling, np.int32(0), bucket=32)
    yield "host_greedy", eng._decode_greedy_jit.lower(
        eng.params, eng.pools, eng._put(eng.block_tables),
        eng._put(eng.seq_lens), eng._put(np.zeros((4,), np.int32)),
        eng.seen, eng._put(eng.reps), eng._put(np.ones((4,), bool)))


def main() -> int:
    for family, (cls, tiny, flags, speculates) in FAMILIES.items():
        if not hasattr(models, cls):
            continue
        pt.seed(0)
        model = getattr(models, cls)(getattr(models, tiny)(**flags))
        for spec in (0, 2) if speculates else (0,):
            for name, lowered in programs(model, spec):
                text = lowered.as_text()
                print(f"{family}.{name}",
                      hashlib.sha256(text.encode()).hexdigest()[:16],
                      len(text))
    return 0


if __name__ == "__main__":
    sys.exit(main())
