"""Arithmetic of the per-layer metrics of Olmo-Hybrid's two layer kinds
(``readers.py`` holds the shared ones). They read the engine's
``state_*`` counters in the window's snapshots, the device time under
the program's ``conv`` / ``delta_state`` / ``gate_norm`` / ``attn``
scopes in the traced ticks, and the counts of ``roofline_olmo_hybrid``.
A program without those counters or scopes, or a configuration of
another family, gives each reader nothing to read: it returns None and
never raises.
"""
from __future__ import annotations

from typing import Optional

from . import readers, roofline_olmo_hybrid, spans
from .readers_longcat import _bandwidth
from .readers_moe import _delta, _scope_s

LINEAR_SCOPES = ("conv", "delta_state", "gate_norm")


def _of_the_family(src) -> bool:
    return {"layer_types", "linear_key_head_dim",
            "linear_value_head_dim"} <= set(src["config"])


def _traced_row_ticks(src) -> int:
    """Live rows the traced ticks advanced, summed over ticks: a token
    that arrived inside the traced span as the j-th of its request (j >
    0: the first comes from the prefill) was one row of one tick, as
    ``readers._traced_context_tokens`` counts its context."""
    ta, tb = src["trace_times"]["ta"], src["trace_times"]["tb"]
    return sum(1 for r in src["records"]
               for j, t in enumerate(r["token_times"])
               if j > 0 and ta <= t < tb)


def linattn_ms(src) -> Optional[float]:
    """Device ms a tick under the linear layers' own scopes: the
    convolutions, the state step, the gated norm (their projections are
    under ``qkv`` with the full layers')."""
    return spans.scope_ms(src, *LINEAR_SCOPES) if _of_the_family(src) \
        else None


def delta_state_ms(src) -> Optional[float]:
    """Device ms a tick under ``delta_state``: the recurrence's decode
    step with its state read and write."""
    return spans.scope_ms(src, "delta_state") if _of_the_family(src) \
        else None


def delta_state_membw_roofline(src) -> Optional[float]:
    """The state the traced ticks' live rows had to read and write over
    the chip's bandwidth, over the device time under ``delta_state``."""
    s = _scope_s(src, "delta_state") if _of_the_family(src) else None
    if not s:
        return None
    need = roofline_olmo_hybrid.delta_state_bytes(src["config"],
                                                  _traced_row_ticks(src))
    return 100.0 * (need / _bandwidth(src)) / s


def attn_roofline(src) -> Optional[float]:
    """The full layers' kernel calls (a query group of one) against
    their memory floor: the live rows' whole-context K and V."""
    s = _scope_s(src, "attn") if _of_the_family(src) else None
    if not s:
        return None
    need = roofline_olmo_hybrid.attention_bytes(
        src["config"], readers._traced_context_tokens(src))
    return 100.0 * (need / _bandwidth(src)) / s


def tick_membw_roofline(src) -> Optional[float]:
    """Bytes the traced ticks had to move (weights outside the embedding
    once a tick, the live rows' states read and written, their K and V)
    over the chip's bandwidth, over the tick modules' device time."""
    if not _of_the_family(src):
        return None
    n, s = readers._modules(src, readers.TICK_PREFIX)
    if not n or s <= 0:
        return None
    need = roofline_olmo_hybrid.tick_bytes(
        src["config"], n, _traced_row_ticks(src),
        readers._traced_context_tokens(src))
    return 100.0 * (need / _bandwidth(src)) / s


def delta_carry_share(src) -> Optional[float]:
    """Prompt segments that started from the state the chunk before
    them left, of all segments, over the window: the engine's
    ``state_carries`` over it plus ``state_resets``."""
    if not _of_the_family(src):
        return None
    carries, resets = _delta(src, "state_carries"), _delta(src,
                                                           "state_resets")
    if carries is None or resets is None or not carries + resets:
        return None
    return 100.0 * carries / (carries + resets)
