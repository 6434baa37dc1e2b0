"""Arithmetic of the per-layer metrics of MiMo-V2's two layer kinds
(``readers.py`` and ``readers_moe.py`` hold the shared ones). They read
the engine's ``moe_*`` and ``kv_*`` counters in the window's snapshots,
the device time under the program's ``attn_window`` / ``attn`` /
``experts`` scopes in the traced ticks, and the counts of
``roofline_mimo``. A program without those counters or scopes, or a
configuration of another family, gives each reader nothing to read: it
returns None and never raises.
"""
from __future__ import annotations

from typing import Optional

from . import readers, roofline_mimo, spans
from .readers_longcat import _bandwidth
from .readers_moe import _delta, _scope_s, hits_per_layer_tick


def _of_the_family(src) -> bool:
    return {"hybrid_layer_pattern", "swa_num_key_value_heads",
            "sliding_window"} <= set(src["config"])


def _traced_band_tokens(src) -> int:
    """Tokens inside their window the traced ticks' live rows held,
    summed over ticks (``readers._traced_context_tokens`` with each row
    cut to the window)."""
    ta, tb = src["trace_times"]["ta"], src["trace_times"]["tb"]
    w = src["config"]["sliding_window"]
    return sum(min(len(r["prompt"]) + j, w)
               for r in src["records"]
               for j, t in enumerate(r["token_times"])
               if j > 0 and ta <= t < tb)


def window_attn_ms(src) -> Optional[float]:
    """Device ms a tick under ``attn_window``: the window layers'
    kernel calls, sink and all."""
    return spans.scope_ms(src, "attn_window") if _of_the_family(src) \
        else None


def full_attn_ms(src) -> Optional[float]:
    """Device ms a tick under ``attn``: in this family the full layers'
    kernel calls alone."""
    return spans.scope_ms(src, "attn") if _of_the_family(src) else None


def window_attn_roofline(src) -> Optional[float]:
    """The window layers' kernel calls against their memory floor: the
    live rows' in-band K and V over the chip's bandwidth, over the
    device time under ``attn_window``."""
    s = _scope_s(src, "attn_window") if _of_the_family(src) else None
    if not s:
        return None
    need = roofline_mimo.window_attention_bytes(src["config"],
                                                _traced_band_tokens(src))
    return 100.0 * (need / _bandwidth(src)) / s


def full_attn_roofline(src) -> Optional[float]:
    """The full layers' kernel calls against theirs: the live rows'
    whole-context K and V."""
    s = _scope_s(src, "attn") if _of_the_family(src) else None
    if not s:
        return None
    need = roofline_mimo.full_attention_bytes(
        src["config"], readers._traced_context_tokens(src))
    return 100.0 * (need / _bandwidth(src)) / s


def experts_membw_roofline(src) -> Optional[float]:
    """The weights of the held experts that got a token, over the chip's
    bandwidth, over the device time under ``experts``."""
    if not _of_the_family(src):
        return None
    hits, s = hits_per_layer_tick(src), _scope_s(src, "experts")
    if hits is None or not s:
        return None
    cfg = src["config"]
    need = (spans.spans_of(src)["ticks"] * hits
            * roofline_mimo.expert_layers(cfg)
            * roofline_mimo.expert_bytes(cfg))
    return 100.0 * (need / _bandwidth(src)) / s


def tick_membw_roofline(src) -> Optional[float]:
    """Bytes the traced ticks had to read (weights outside the experts
    once a tick, the experts hit, both layer kinds' K and V) over the
    chip's bandwidth, over the tick modules' device time."""
    if not _of_the_family(src):
        return None
    n, s = readers._modules(src, readers.TICK_PREFIX)
    hits = hits_per_layer_tick(src)
    if not n or s <= 0 or hits is None:
        return None
    cfg = src["config"]
    need = roofline_mimo.tick_bytes(
        cfg, n, n * hits * roofline_mimo.expert_layers(cfg),
        _traced_band_tokens(src), readers._traced_context_tokens(src))
    return 100.0 * (need / _bandwidth(src)) / s


def window_blocks_per_row(src) -> Optional[float]:
    """Pages a live row holds in ONE window layer, over the window: the
    engine's ``kv_window_blocks`` (summed over live rows, window layers
    and ticks) over live row-ticks and window layers."""
    if not _of_the_family(src):
        return None
    blocks = _delta(src, "kv_window_blocks")
    rows = _delta(src, "active_slot_steps")
    layers = roofline_mimo.layers_of(src["config"], True)
    return blocks / (rows * layers) if blocks is not None and rows \
        and layers else None
