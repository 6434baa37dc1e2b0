"""Arithmetic the per-layer metric files share. Each file under
``benchmarks/layer_metrics/`` names one metric and calls one of these
with the sources ``cell.run_cell`` gathered once for the traced run:

``client``   the client child's numbers (``stats.client_metrics``)
``records``  its per-request records, ``window`` their ``(w0, w1)``
``snaps``    the program's counters at the window's start and end
``reqtrace`` the gateway's per-request timelines (``dump_traces``)
``trace``    the reduced device trace (``trace.reduce_trace``), with
``trace_times`` the host clock at its start and end and the counters
             either side of it
``config``   the configuration file, ``device_kind``, ``replicas``

A reader that finds nothing to read returns None, and the metric is
left out of the result line.
"""
from __future__ import annotations

from typing import Optional

from . import peaks, roofline, stats

TICK_PREFIX = "_fused_tick"
CHUNK_PREFIX = "_chunk_prefill"


def engine_delta(src, key: str) -> int:
    a, b = src["snaps"]["w0"]["engines"], src["snaps"]["w1"]["engines"]
    return sum(y[key] - x[key] for x, y in zip(a, b))


def health_delta(src, *path) -> float:
    def get(h):
        for p in path:
            h = h[p]
        return h
    return get(src["snaps"]["w1"]["health"]) - get(src["snaps"]["w0"]["health"])


def window_records(src):
    w0, w1 = src["window"]
    return [r for r in src["records"] if stats.in_window(r, w0, w1)]


def queue_wait_p50_ms(src) -> Optional[float]:
    ids = {r["id"] for r in window_records(src)}
    waits = [e["queue_wait_ms"] for e in src["reqtrace"]
             if e["request_id"] in ids and e.get("queue_wait_ms") is not None]
    return stats.percentile(waits, 50)


def shed_share(src) -> Optional[float]:
    sent = len(window_records(src))
    return 100.0 * health_delta(src, "shed") / sent if sent else None


def route_prefix_hit_share(src) -> Optional[float]:
    if src["replicas"] < 2:
        return None
    hits = health_delta(src, "router", "prefix_route_hits")
    total = hits + health_delta(src, "router", "prefix_route_misses")
    return 100.0 * hits / total if total else None


def prefix_hit_token_share(src) -> Optional[float]:
    prompt = sum(len(r["prompt"]) for r in window_records(src))
    return 100.0 * engine_delta(src, "prefix_hit_tokens") / prompt \
        if prompt else None


def batch_occupancy(src) -> Optional[float]:
    a, b = src["snaps"]["w0"]["engines"], src["snaps"]["w1"]["engines"]
    slots = src["engine"]["max_slots"]
    steps = sum(y["decode_steps"] - x["decode_steps"] for x, y in zip(a, b))
    return 100.0 * engine_delta(src, "active_slot_steps") / (steps * slots) \
        if steps else None


def tick_host_share(src) -> Optional[float]:
    """Everything of a tick's wall that is not the wait for the device:
    host staging, uploads, dispatch and the drain of the token ring."""
    a, b = src["snaps"]["w0"], src["snaps"]["w1"]
    if not a["tick_phase_ms"] or a["tick_phase_ms"][0] is None:
        return None
    wall = sum(y - x for x, y in zip(a["tick_wall_ms"], b["tick_wall_ms"]))
    device = sum(y["device"] - x["device"] for x, y in
                 zip(a["tick_phase_ms"], b["tick_phase_ms"]))
    return 100.0 * (wall - device) / wall if wall > 0 else None


def _modules(src, prefix):
    ms = [m for name, m in src["trace"]["modules"].items()
          if name.startswith(prefix)]
    return sum(m["n"] for m in ms), sum(m["s"] for m in ms)


def tick_device_ms(src) -> Optional[float]:
    n, s = _modules(src, TICK_PREFIX)
    return 1e3 * s / n if n else None


def prefill_device_share(src) -> Optional[float]:
    _, s = _modules(src, CHUNK_PREFIX)
    busy = src["trace"]["busy_s"] * src["trace"]["chips"]
    return 100.0 * s / busy if busy > 0 else None


def _traced_context_tokens(src) -> int:
    """Context the traced ticks' live rows held, summed over ticks: a
    token that arrived inside the traced span as the j-th of its
    request was decoded by a tick that read ``prompt + j`` tokens of K
    and V for that row (the first token comes from the prefill)."""
    ta, tb = src["trace_times"]["ta"], src["trace_times"]["tb"]
    return sum(len(r["prompt"]) + j
               for r in src["records"]
               for j, t in enumerate(r["token_times"])
               if j > 0 and ta <= t < tb)


def ragged_attn_roofline(src) -> Optional[float]:
    """The decode attention kernel (the one Pallas kernel inside the
    tick programs) against its memory floor: the K and V bytes of the
    live rows over the chip's bandwidth, over the kernel's device time
    in the traced ticks."""
    k = src["trace"]["tick_kernels"]
    if not k["n"] or k["s"] <= 0:
        return None
    need = roofline.decode_attention_bytes(src["config"],
                                           _traced_context_tokens(src))
    bw = peaks.peaks(src["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / k["s"]


def tick_membw_roofline(src) -> Optional[float]:
    """Bytes the traced ticks had to read over the chip's bandwidth,
    over the device time they took."""
    n, s = _modules(src, TICK_PREFIX)
    if not n or s <= 0:
        return None
    need = roofline.tick_bytes(src["config"], n,
                               _traced_context_tokens(src))
    bw = peaks.peaks(src["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / s


def device_idle_share(src) -> Optional[float]:
    t = src["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) \
        if t["window_s"] > 0 else None
