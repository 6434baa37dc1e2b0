"""Arithmetic of the per-layer metrics of a prompt call's expert layers
(``readers.py`` holds the shared ones): the device time under the
program's ``experts`` scope inside the traced ``_chunk_prefill*``
modules (``readers_ling.chunk_spans_of``), and the engine's
``chunk_experts_*`` counters in the window's snapshots. A program
without the scope or the counters, a configuration without expert
layers, or a traced span that holds no prompt call gives each reader
nothing to read: it returns None and never raises.
"""
from __future__ import annotations

from typing import Optional

from .readers_ling import chunk_spans_of
from .readers_moe import _delta


def chunk_experts_ms(src) -> Optional[float]:
    """Device ms a prompt call under ``experts``: the held experts' part
    of every expert layer (``ExpertShareMLP.routed``: the sort of the
    choices, the gather of the pairs' rows and the grouped product; at
    the parent the einsums over every held expert)."""
    r = chunk_spans_of(src)
    s = r["by_scope"].get("experts") if r and r["calls"] else None
    return None if not s else 1e3 * s / r["calls"]


def chunk_experts_grouped_share(src) -> Optional[float]:
    """Expert layers of the window's prompt calls whose forward took the
    grouped product, of all of them: the engine's
    ``chunk_experts_grouped_calls`` over ``chunk_experts_layer_calls``
    (counted on the host as a prompt call is dispatched)."""
    grouped = _delta(src, "chunk_experts_grouped_calls")
    calls = _delta(src, "chunk_experts_layer_calls")
    return 100.0 * grouped / calls if grouped is not None and calls else None
