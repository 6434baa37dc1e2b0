"""Arithmetic of the per-layer metrics of a prompt chunk's attention
over its row's pages (``readers.py`` holds the shared ones): the device
time under the program's ``chunk_attn_window`` scope inside the traced
``_chunk_prefill*`` modules (``readers_ling.chunk_spans_of``; the full
layers' ``chunk_attn`` is ``readers_laguna.chunk_full_attn_ms``), and
the engine's ``chunk_attn_layer_calls`` / ``chunk_attn_kernel_calls`` in
the window's snapshots. A program without the scope or the counters, a
configuration without band-keeping layers, or a traced span that holds
no prompt call gives each reader nothing to read: it returns None and
never raises.
"""
from __future__ import annotations

from typing import Optional

from .readers_ling import chunk_spans_of
from .readers_moe import _delta


def chunk_window_attn_ms(src) -> Optional[float]:
    """Device ms a prompt call under ``chunk_attn_window``: the
    band-keeping layers' attention of a chunk over their rings (the
    kernel over tiles of the chunk's queries with the transposes around
    it; the walk over every run of the ring at a program without it; a
    first chunk's over the call's own rows)."""
    r = chunk_spans_of(src)
    s = r["by_scope"].get("chunk_attn_window") if r and r["calls"] else None
    return None if not s else 1e3 * s / r["calls"]


def chunk_attn_kernel_share(src) -> Optional[float]:
    """K/V layers of the window's prompt chunks with cached context
    behind them whose attention took the kernel, of all of them: the
    engine's ``chunk_attn_kernel_calls`` over ``chunk_attn_layer_calls``
    (counted on the host as such a call is dispatched)."""
    kernel = _delta(src, "chunk_attn_kernel_calls")
    calls = _delta(src, "chunk_attn_layer_calls")
    return 100.0 * kernel / calls if kernel is not None and calls else None
