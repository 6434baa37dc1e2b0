"""Standalone gateway replica process (ISSUE 13): the unit the fleet
manager spawns and the autoscaler scales.

    python -m paddle_tpu.serving.fleet.replica_main \\
        --port 0 --model stub --chunk-tokens 8

Builds N engines (negligible-compute stub for harness runs, tiny
llama for real decode), WARMS them before announcing readiness (a
cold first dispatch reads as a hang to sub-second fleet probes — the
compile-before-traffic rule the chaos harness taught, ISSUE 12),
prints one ``FLEET_REPLICA_READY host=... port=...`` line to stdout,
then serves until SIGTERM drains it (``run_until_shutdown``). The
engine geometry here is the single source of truth the fleet
loadgen's bitwise replay gate rebuilds its reference engine from
(:func:`stub_engine_kw`).
"""
from __future__ import annotations

import argparse
import asyncio
import sys
from typing import Any, Dict

__all__ = ["stub_engine_kw", "build_engine", "main", "READY_LINE"]

READY_LINE = "FLEET_REPLICA_READY"


def stub_engine_kw(chunk_tokens: int = 8) -> Dict[str, Any]:
    """The stub-model engine geometry every fleet replica runs (and
    the loadgen's reference replay must match bit-for-bit)."""
    return dict(max_slots=4, num_blocks=128, block_size=8,
                max_blocks_per_seq=16, prefill_buckets=(16,),
                chunk_prefill_tokens=int(chunk_tokens),
                enable_prefix_cache=True)


def tiny_engine_kw(chunk_tokens: int = 32) -> Dict[str, Any]:
    return dict(max_slots=4, num_blocks=128, block_size=16,
                max_blocks_per_seq=16, prefill_buckets=(32,),
                chunk_prefill_tokens=int(chunk_tokens),
                enable_prefix_cache=True)


def build_engine(model: str, chunk_tokens: int):
    """One warmed engine (compile-before-traffic: the executable
    build happens HERE, before the readiness line)."""
    from paddle_tpu.generation.paged import PagedEngine
    if model == "stub":
        from paddle_tpu.generation.stub import TickStubModel
        eng = PagedEngine(TickStubModel(),
                          **stub_engine_kw(chunk_tokens))
    else:
        from paddle_tpu.models import LlamaForCausalLM
        from paddle_tpu.models.llama import llama_tiny
        eng = PagedEngine(LlamaForCausalLM(llama_tiny()),
                          **tiny_engine_kw(chunk_tokens))
    eng.submit("warmup", list(range(1, 5)), max_new_tokens=4)
    eng.run()
    eng.results.pop("warmup", None)
    eng.logprobs.pop("warmup", None)
    return eng


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--model", default="stub",
                    choices=("stub", "tiny"))
    ap.add_argument("--chunk-tokens", type=int, default=8)
    ap.add_argument("--engines", type=int, default=1,
                    help="replica engines inside this gateway")
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--name", default=None)
    ap.add_argument("--watchdog-timeout-s", type=float, default=30.0)
    ap.add_argument("--run-dir", default=None,
                    help="observability run dir: the gateway dumps "
                         "its request-trace rings (and its "
                         "series_<gw>.json trajectory, ISSUE 15) "
                         "here on drain")
    ap.add_argument("--slo-window-scale", type=float, default=1.0,
                    help="scale the burn-rate alert windows "
                         "(loadgen --slo-windows pass-through; "
                         "<1 lets a CI-length run fire real alerts)")
    ap.add_argument("--telemetry", default="on",
                    choices=("on", "off"),
                    help="off = no sampler, no burn-rate alerting "
                         "(the pre-ISSUE-15 gateway, the A/B "
                         "reference)")
    ap.add_argument("--spill-mb", type=int, default=0,
                    help="host-RAM KV spill arena capacity (MiB); "
                         "0 = no arena (ISSUE 17). An arena also "
                         "makes this replica's spilled spans "
                         "fleet-fetchable over GET /kvz (ISSUE 18)")
    ap.add_argument("--migrate", default="off",
                    choices=("on", "off"),
                    help="on = SIGTERM drain CUTS live requests over "
                         "to the fleet (terminal migrated events + "
                         "resume_kv spans) instead of finishing "
                         "them here; requires --spill-mb > 0")
    ns = ap.parse_args(argv)

    import paddle_tpu as pt
    from paddle_tpu.serving import Gateway
    from paddle_tpu.utils import compile_cache
    from paddle_tpu.utils import observability as obs
    # replica processes share one persistent compile cache: a scale-up's
    # cold start deserializes executables instead of recompiling
    compile_cache.enable(min_compile_time_s=0.1)
    pt.seed(0)
    if ns.run_dir:
        obs.configure(ns.run_dir)

    def factory():
        return build_engine(ns.model, ns.chunk_tokens)

    engines = [factory() for _ in range(max(ns.engines, 1))]
    telemetry_kw = dict(slo_window_scale=ns.slo_window_scale) \
        if ns.telemetry == "on" else \
        dict(sample_interval_s=None, slo_alerting=False)
    spill_kw: Dict[str, Any] = {}
    if ns.spill_mb > 0:
        from paddle_tpu.serving.kvspill import KVSpillArena
        spill_kw["spill_arena"] = KVSpillArena(
            ns.spill_mb << 20, name=ns.name or "replica")
        spill_kw["migrate_on_drain"] = ns.migrate == "on"
    gw = Gateway(engines, host=ns.host, port=ns.port,
                 max_queue=ns.max_queue, name=ns.name,
                 engine_factory=factory,
                 watchdog_timeout_s=ns.watchdog_timeout_s,
                 **spill_kw, **telemetry_kw)

    async def serve():
        await gw.start()
        # the manager's readiness contract: one line, then serve
        print(f"{READY_LINE} host={gw.host} port={gw.port}",
              flush=True)
        await gw.run_until_shutdown()

    asyncio.run(serve())
    return 0


if __name__ == "__main__":
    sys.exit(main())
