"""Arithmetic of the per-layer metrics of Laguna's layer kinds
(``readers.py`` and ``readers_moe.py`` hold the shared ones). They read
the engine's ``moe_*``, ``kv_*``, ``chunk_attn_positions_*``,
``prefill_chunks``, ``decode_steps`` and ``active_slot_steps`` counters
in the window's snapshots, the device time under the program's
``attn_window`` / ``attn`` / ``attn_gate`` / ``experts`` scopes in the
traced ticks and under ``chunk_attn`` in the traced prompt calls, and
the counts of ``roofline_laguna``. A program without those counters or
scopes, or a configuration of another family, gives each reader nothing
to read: it returns None and never raises.

What the traced ticks' live rows held is the trace's tick modules times
the window's mean a tick of the engine's own counters (the cell is
saturated; the counters do not depend on when a token reached its
client, which under the tracer is late).
"""
from __future__ import annotations

from typing import Optional

from . import peaks, readers, roofline_laguna, spans
from .readers_ling import chunk_spans_of
from .readers_moe import _delta, _scope_s, hits_per_layer_tick


def _of_the_family(src) -> bool:
    return {"layer_types", "num_attention_heads_per_layer", "gating",
            "shared_expert_intermediate_size",
            "num_experts_published"} <= set(src["config"])


def _peak(src) -> dict:
    return peaks.peaks(src["device_kind"])


def _traced(src, counter: str) -> Optional[float]:
    """``counter`` summed over the traced ticks: their number times the
    window's mean of it a decode tick."""
    n, _ = readers._modules(src, readers.TICK_PREFIX)
    total, ticks = _delta(src, counter), _delta(src, "decode_steps")
    return n * total / ticks if n and total is not None and ticks else None


def window_attn_ms(src) -> Optional[float]:
    """Device ms a tick under ``attn_window``: the window layers' kernel
    calls at a query group of 9."""
    return spans.scope_ms(src, "attn_window") if _of_the_family(src) \
        else None


def full_attn_ms(src) -> Optional[float]:
    """Device ms a tick under ``attn``: in this family the full layers'
    kernel calls alone, at a query group of 6."""
    return spans.scope_ms(src, "attn") if _of_the_family(src) else None


def attn_gate_ms(src) -> Optional[float]:
    """Device ms a tick under ``attn_gate``: every layer's gate
    projection, its sigmoid and the multiply a head."""
    return spans.scope_ms(src, "attn_gate") if _of_the_family(src) else None


def window_attn_roofline(src) -> Optional[float]:
    """The window layers' kernel calls against their memory floor: the
    live rows' in-band K and V over the chip's bandwidth, over the
    device time under ``attn_window``."""
    s = _scope_s(src, "attn_window") if _of_the_family(src) else None
    band = _traced(src, "kv_window_tokens") if s else None
    if not band:
        return None
    need = roofline_laguna.window_attention_bytes(src["config"], band)
    return 100.0 * (need / _peak(src)["hbm_bytes_per_s"]) / s


def full_attn_roofline(src) -> Optional[float]:
    """The full layers' kernel calls against theirs: the live rows'
    whole-context K and V."""
    s = _scope_s(src, "attn") if _of_the_family(src) else None
    ctx = _traced(src, "kv_context_tokens") if s else None
    if not ctx:
        return None
    need = roofline_laguna.full_attention_bytes(src["config"], ctx)
    return 100.0 * (need / _peak(src)["hbm_bytes_per_s"]) / s


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
            * roofline_laguna.expert_layers(cfg)
            * roofline_laguna.expert_bytes(cfg))
    return 100.0 * (need / _peak(src)["hbm_bytes_per_s"]) / s


def tick_membw_roofline(src) -> Optional[float]:
    """Bytes the traced ticks had to read (weights outside the experts
    once a tick, the experts hit, both layer kinds' K and V) over the
    chip's bandwidth, over the tick modules' device time: the share of
    the whole step."""
    if not _of_the_family(src):
        return None
    n, s = readers._modules(src, readers.TICK_PREFIX)
    hits = hits_per_layer_tick(src)
    band, ctx = _traced(src, "kv_window_tokens"), _traced(
        src, "kv_context_tokens")
    if not n or s <= 0 or hits is None or band is None or ctx is None:
        return None
    cfg = src["config"]
    need = roofline_laguna.tick_bytes(
        cfg, n, n * hits * roofline_laguna.expert_layers(cfg), band, ctx)
    return 100.0 * (need / _peak(src)["hbm_bytes_per_s"]) / s


def window_blocks_per_row(src) -> Optional[float]:
    """Pages a live row holds in ONE window layer, over the window: the
    engine's ``kv_window_blocks`` over live row-ticks and window
    layers."""
    if not _of_the_family(src):
        return None
    blocks = _delta(src, "kv_window_blocks")
    rows = _delta(src, "active_slot_steps")
    layers = roofline_laguna.layers_of(src["config"], True)
    return blocks / (rows * layers) if blocks is not None and rows \
        and layers else None


def _chunk_full_s(src) -> Optional[float]:
    r = chunk_spans_of(src) if _of_the_family(src) else None
    return r["by_scope"].get("chunk_attn") if r and r["calls"] else None


def chunk_full_attn_ms(src) -> Optional[float]:
    """Device ms a prompt call under ``chunk_attn``: the full layers'
    attention of a chunk, the walk over its row's live runs of pages (a
    first chunk's: over the call's own rows)."""
    s = _chunk_full_s(src)
    return None if not s else 1e3 * s / chunk_spans_of(src)["calls"]


def chunk_full_attn_flops_roofline(src) -> Optional[float]:
    """The operations causal attention needs for the traced prompt
    calls (the calls in the trace times the window's mean of causal
    pairs a call: every prompt of the window's requests is L (L + 1) / 2
    pairs a full layer however it is chunked, over the engine's
    ``prefill_chunks``) over the chip's bf16 peak, over the device time
    under ``chunk_attn``."""
    s = _chunk_full_s(src)
    calls = _delta(src, "prefill_chunks") if s else None
    pairs = sum(roofline_laguna.causal_pairs(len(r["prompt"]))
                for r in readers.window_records(src)) if calls else 0
    if not pairs:
        return None
    need = roofline_laguna.chunk_full_attention_flops(
        src["config"], chunk_spans_of(src)["calls"] * pairs / calls)
    return 100.0 * (need / _peak(src)["bf16_flops"]) / s


def chunk_live_share(src) -> Optional[float]:
    """Positions of their rows' tables that were LIVE, of the positions
    the window's prompt chunks' attention scored: the engine's
    ``chunk_attn_positions_live`` over ``chunk_attn_positions_scored``.
    A dense gather over a 7,168-token slot would read about 50 at a
    mean context of 3,600 behind a chunk; a walk over runs of 512 within
    one run of 100."""
    live = _delta(src, "chunk_attn_positions_live")
    scored = _delta(src, "chunk_attn_positions_scored")
    return 100.0 * live / scored if live is not None and scored else None
