"""Arithmetic of the per-layer metrics of the expert and latent-attention
layers (``readers.py`` holds the shared ones). They read the engine's
``moe_*`` counters in the window's snapshots, the device time under the
program's ``router`` / ``experts`` / ``shared_expert`` / ``absorb`` /
``attn`` scopes in the traced ticks, and the counts of
``roofline_moe_mla``. A program without those counters or scopes, or a
configuration of another family (it has an ``attn`` scope too), gives
each reader nothing to read: it returns None and never raises.
"""
from __future__ import annotations

from typing import Optional

from . import peaks, readers, roofline_moe_mla, spans


def _of_the_family(src) -> bool:
    return {"kv_lora_rank", "n_routed_experts"} <= set(src["config"])


def _delta(src, key: str) -> Optional[int]:
    a, b = src["snaps"]["w0"]["engines"], src["snaps"]["w1"]["engines"]
    if not all(key in e for e in a + b):
        return None
    return sum(y[key] - x[key] for x, y in zip(a, b))


def hits_per_layer_tick(src) -> Optional[float]:
    """Held experts that got a token, per expert layer and tick, over
    the window."""
    hit, ticks = _delta(src, "moe_experts_hit"), _delta(src,
                                                        "moe_layer_ticks")
    return hit / ticks if hit is not None and ticks else None


def experts_hit_share(src) -> Optional[float]:
    hits = hits_per_layer_tick(src)
    return None if hits is None \
        else 100.0 * hits / src["config"]["n_routed_experts"]


def _scope_s(src, *scopes: str) -> Optional[float]:
    """Device seconds under the scopes, summed over the traced ticks."""
    ms = spans.scope_ms(src, *scopes)
    return None if ms is None else ms * 1e-3 * spans.spans_of(src)["ticks"]


def experts_membw_roofline(src) -> Optional[float]:
    """The weights of the held experts that got a token, over the chip's
    bandwidth, over the device time under ``experts``."""
    hits, s = hits_per_layer_tick(src), _scope_s(src, "experts")
    if hits is None or not s:
        return None
    cfg = src["config"]
    need = (spans.spans_of(src)["ticks"] * hits
            * roofline_moe_mla.expert_layers(cfg)
            * roofline_moe_mla.expert_bytes(cfg))
    bw = peaks.peaks(src["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / s


def mla_attn_roofline(src) -> Optional[float]:
    """The latent decode kernel against its floor (the larger of bytes
    over bandwidth and operations over the peak), over the device time
    under ``attn`` in the traced ticks."""
    s = _scope_s(src, "attn") if _of_the_family(src) else None
    if not s:
        return None
    floor = roofline_moe_mla.latent_attention_floor_s(
        src["config"], readers._traced_context_tokens(src),
        peaks.peaks(src["device_kind"]))
    return 100.0 * floor / s


def moe_tick_membw_roofline(src) -> Optional[float]:
    """Bytes the traced ticks had to read (weights outside the experts
    once a tick, the experts hit, the live rows' latents) over the
    chip's bandwidth, over the tick modules' device time."""
    n, s = readers._modules(src, readers.TICK_PREFIX)
    hits = hits_per_layer_tick(src)
    if not n or s <= 0 or hits is None:
        return None
    cfg = src["config"]
    need = roofline_moe_mla.tick_bytes(
        cfg, n, n * hits * roofline_moe_mla.expert_layers(cfg),
        readers._traced_context_tokens(src))
    bw = peaks.peaks(src["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / s
