"""Arithmetic of the per-layer metrics of Ling 3.0's layer kinds
(``readers.py`` holds the shared ones). They read the engine's ``moe_*``,
``state_*``, ``prefill_chunks`` and ``active_slot_steps`` counters in the
window's snapshots, the device
time under the program's ``conv`` / ``decay_gate`` / ``delta_state`` /
``gate_norm`` / ``experts`` / ``attn`` scopes in the traced ticks and
under ``chunk_delta_state`` in the traced prompt calls, and the counts
of ``roofline_ling``. A program without those counters or scopes, or a
configuration of another family, gives each reader nothing to read: it
returns None and never raises.
"""
from __future__ import annotations

import bisect
from typing import Optional

from . import peaks, readers, roofline_ling, spans, trace
from .readers_moe import _delta, _scope_s, hits_per_layer_tick

KDA_SCOPES = ("conv", "decay_gate", "delta_state", "gate_norm")


def _of_the_family(src) -> bool:
    return {"kda_lower_bound", "layer_group_size", "kv_lora_rank",
            "num_experts_published"} <= set(src["config"])


def _peak(src) -> dict:
    return peaks.peaks(src["device_kind"])


def _traced_row_ticks(src) -> Optional[float]:
    """Live rows the traced ticks advanced, summed over ticks: the tick
    modules of the trace times the window's mean of live rows a tick
    (``state_rows_updated`` over ``state_layer_ticks``: the cell is
    saturated, and the engine's counters do not depend on when a token
    reached its client, which under the tracer is late by seconds)."""
    n, _ = readers._modules(src, readers.TICK_PREFIX)
    rows, ticks = _delta(src, "state_rows_updated"), _delta(
        src, "state_layer_ticks")
    return n * rows / ticks if n and rows is not None and ticks else None


def kda_ms(src) -> Optional[float]:
    """Device ms a tick under the linear layers' own scopes: the
    convolutions, the decay gate, the state step, the gated norm (their
    other projections are under ``qkv`` with the latent layers')."""
    return spans.scope_ms(src, *KDA_SCOPES) if _of_the_family(src) else None


def kda_state_ms(src) -> Optional[float]:
    return spans.scope_ms(src, "delta_state") if _of_the_family(src) \
        else None


def kda_state_membw_roofline(src) -> Optional[float]:
    """The state the traced ticks' live rows had to read and write over
    the chip's bandwidth, over the device time under ``delta_state``."""
    s = _scope_s(src, "delta_state") if _of_the_family(src) else None
    rows = _traced_row_ticks(src) if s else None
    if not rows:
        return None
    need = roofline_ling.state_bytes(src["config"], rows)
    return 100.0 * (need / _peak(src)["hbm_bytes_per_s"]) / s


def chunk_spans_of(src) -> Optional[dict]:
    """Device time of the traced prompt calls' ops by the program's
    scope (``spans.reduce_spans`` reads the tick modules only): the
    ``_chunk_prefill*`` modules of the run's own trace, their count."""
    if "_chunk_spans" in src:
        return src["_chunk_spans"]
    path = spans.find_trace()
    out = None
    if path:
        names, known = spans.op_names(path), spans.scopes()
        out = {"calls": 0, "by_scope": {}}
        for plane, p in trace.read_planes(path).items():
            mods = sorted((s, s + d) for raw, s, d in p["modules"]
                          if trace.module_name(raw).startswith(
                              readers.CHUNK_PREFIX))
            out["calls"] += len(mods)
            starts = [m[0] for m in mods]
            for raw, start, own in spans.self_times(p["ops"]):
                i = bisect.bisect_right(starts, start) - 1
                if i < 0 or start >= mods[i][1]:
                    continue
                scope = spans.scope_of(names.get(plane, {}).get(raw), known)
                out["by_scope"][scope] = out["by_scope"].get(scope, 0.0) + own
    src["_chunk_spans"] = out
    return out


def _chunk_delta_s(src) -> Optional[float]:
    r = chunk_spans_of(src) if _of_the_family(src) else None
    return r["by_scope"].get("chunk_delta_state") if r and r["calls"] \
        else None


def kda_chunk_ms(src) -> Optional[float]:
    """Device ms a prompt call under ``chunk_delta_state``: the
    chunkwise delta rule of every linear layer."""
    s = _chunk_delta_s(src)
    return None if not s else 1e3 * s / chunk_spans_of(src)["calls"]


def _traced_prompt_positions(src) -> Optional[float]:
    """Prompt positions the traced prompt calls served: the trace's
    calls times the window's mean of prompt tokens a call (the prompts
    of the window's requests over the engine's ``prefill_chunks``)."""
    calls = _delta(src, "prefill_chunks")
    prompt = sum(len(r["prompt"]) for r in readers.window_records(src))
    if not calls or not prompt:
        return None
    return chunk_spans_of(src)["calls"] * prompt / calls


def kda_chunk_flops_roofline(src) -> Optional[float]:
    """The operations the recurrence itself needs for the traced prompt
    positions over the chip's bf16 peak, over the device time under
    ``chunk_delta_state``."""
    s = _chunk_delta_s(src)
    positions = _traced_prompt_positions(src) if s else None
    if not positions:
        return None
    need = roofline_ling.chunk_delta_flops(src["config"], positions)
    return 100.0 * (need / _peak(src)["bf16_flops"]) / s


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
            * roofline_ling.expert_layers(cfg)
            * roofline_ling.expert_bytes(cfg))
    return 100.0 * (need / _peak(src)["hbm_bytes_per_s"]) / s


def mla_attn_roofline(src) -> Optional[float]:
    """The latent layers' decode kernel against its floor, over the
    device time under ``attn`` in the traced ticks."""
    s = _scope_s(src, "attn") if _of_the_family(src) else None
    if not s:
        return None
    floor = roofline_ling.latent_attention_floor_s(
        src["config"], readers._traced_context_tokens(src), _peak(src))
    return 100.0 * floor / s


def tick_membw_roofline(src) -> Optional[float]:
    """Bytes the traced ticks had to move (weights outside the experts
    once a tick, the experts hit, the live rows' states read and
    written, their latent rows) over the chip's bandwidth, over the tick
    modules' device time."""
    if not _of_the_family(src):
        return None
    n, s = readers._modules(src, readers.TICK_PREFIX)
    hits, rows = hits_per_layer_tick(src), _traced_row_ticks(src)
    if not n or s <= 0 or hits is None or not rows:
        return None
    cfg = src["config"]
    need = roofline_ling.tick_bytes(
        cfg, n, rows,
        n * hits * roofline_ling.expert_layers(cfg),
        readers._traced_context_tokens(src))
    return 100.0 * (need / _peak(src)["hbm_bytes_per_s"]) / s


def rows_routed_here_share(src) -> Optional[float]:
    """Live rows of which at least one chosen expert is held, of all
    live rows, a layer and tick, over the window: the engine's
    ``moe_rows_routed_here`` over ``active_slot_steps`` x expert
    layers."""
    if not _of_the_family(src):
        return None
    here, rows = _delta(src, "moe_rows_routed_here"), _delta(
        src, "active_slot_steps")
    if here is None or not rows:
        return None
    return 100.0 * here / (rows * roofline_ling.expert_layers(src["config"]))
