"""Arithmetic of the per-layer metrics of LongCat-Flash's double layer
(``readers.py`` and ``readers_moe.py`` hold the shared ones). They read
the engine's ``moe_*`` counters in the window's snapshots, the device
time under the program's ``experts`` / ``attn`` scopes in the traced
ticks, and the counts of ``roofline_longcat``. A program without those
counters or scopes, or a configuration of another family, gives each
reader nothing to read: it returns None and never raises.
"""
from __future__ import annotations

from typing import Optional

from . import peaks, readers, roofline_longcat, spans
from .readers_moe import _delta, _scope_s, hits_per_layer_tick


def _of_the_family(src) -> bool:
    return {"zero_expert_num", "expert_ffn_hidden_size"} <= set(src["config"])


def zero_expert_choice_share(src) -> Optional[float]:
    """Live rows' choices that fell on a zero-compute column, of all
    their choices, over the window."""
    zero, live = _delta(src, "moe_zero_choices"), _delta(src,
                                                         "moe_live_choices")
    return 100.0 * zero / live if zero is not None and live else None


def _bandwidth(src) -> float:
    return peaks.peaks(src["device_kind"])["hbm_bytes_per_s"]


def experts_membw_roofline(src) -> Optional[float]:
    """The weights of the held experts that got a token, over the chip's
    bandwidth, over the device time under ``experts``."""
    if not _of_the_family(src):
        return None
    hits, s = hits_per_layer_tick(src), _scope_s(src, "experts")
    if hits is None or not s:
        return None
    cfg = src["config"]
    need = (spans.spans_of(src)["ticks"] * hits * cfg["num_layers"]
            * roofline_longcat.expert_bytes(cfg))
    return 100.0 * (need / _bandwidth(src)) / s


def mla_attn_roofline(src) -> Optional[float]:
    """The latent decode kernel, two calls a layer, against its floor
    (the larger of bytes over bandwidth and operations over the peak),
    over the device time under ``attn`` in the traced ticks."""
    s = _scope_s(src, "attn") if _of_the_family(src) else None
    if not s:
        return None
    floor = roofline_longcat.latent_attention_floor_s(
        src["config"], readers._traced_context_tokens(src),
        peaks.peaks(src["device_kind"]))
    return 100.0 * floor / s


def tick_membw_roofline(src) -> Optional[float]:
    """Bytes the traced ticks had to read (weights outside the experts
    once a tick, the experts hit, the live rows' latents) over the
    chip's bandwidth, over the tick modules' device time."""
    if not _of_the_family(src):
        return None
    n, s = readers._modules(src, readers.TICK_PREFIX)
    hits = hits_per_layer_tick(src)
    if not n or s <= 0 or hits is None:
        return None
    cfg = src["config"]
    need = roofline_longcat.tick_bytes(
        cfg, n, n * hits * cfg["num_layers"],
        readers._traced_context_tokens(src))
    return 100.0 * (need / _bandwidth(src)) / s
