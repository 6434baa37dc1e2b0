"""Arithmetic of the metrics that read the PROGRAM's own names for the
two sides of a tick (``paddle_tpu.utils.observability``): the
``jax.named_scope`` of each device op (``TICK_SCOPES``), the
``tick/<phase>`` spans the tick thread writes into the profiler's trace
(``TICK_PHASES``, ``LOOP_PHASES``), and the tick profiler's phase totals
in the window's snapshots. A program without them (the parent of the PR
that added them) gives every reader here nothing to read: it returns
None, or for the two shares 100, and never raises.

Where the names are, in a real trace (a v5e, jax 0.9.0; looked at by
hand): a device op's scope is in the ``tf_op`` stat of its EVENT
METADATA, ``jit(_fused_tick_greedy)/attn/kv_layout/reshape:``;
``jax.profiler.ProfileData`` shows an event's own stats only, so the
metadata is read from the file's bytes by the small protobuf reader
below. A fusion carries the ``tf_op`` of its root. The host's spans are
events named ``tick`` (stat ``n``, the tick's index) and
``tick/<phase>`` on the tick thread's line of the ``/host:CPU`` plane,
among the Python tracer's own; device and host events share one clock.

``sources`` carries no path to the trace, so the run's own file is
found as ``cell.run_cell`` finds it: the newest ``.xplane.pb`` under
``.bench_out/*/trace``. It is read once per run (kept in ``sources``),
and both whole tables go to stderr then.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterator, List, Optional, Tuple

from . import trace
from .cell import ROOT, note
from .readers import TICK_PREFIX

def scopes() -> Tuple[str, ...]:
    """The program's own ``TICK_SCOPES``, names only (as the loader and
    the tokenizer are the program's); none where it has no scopes."""
    from paddle_tpu.utils import observability
    return tuple(getattr(observability, "TICK_SCOPES", ()))


# host spans under which an idle device is still unexplained: the host
# is itself waiting for the device, or has nothing to serve
NOT_WORK = ("device", "idle")
Interval = Tuple[float, float]


# ------------------------------------------------------ the file's bytes
def pb_fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one protobuf message: an int
    for a varint, bytes for a length-delimited or fixed-width field."""
    def varint(i: int):
        v = shift = 0
        while True:
            b = buf[i]
            i += 1
            v |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                return v, i

    i = 0
    while i < len(buf):
        tag, i = varint(i)
        num, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = varint(i)
        elif wt == 2:
            size, i = varint(i)
            v, i = buf[i:i + size], i + size
        elif wt in (1, 5):
            size = 8 if wt == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wt}")
        yield num, wt, v


def pb_first(buf: bytes, num: int, default=None):
    return next((v for n, _, v in pb_fields(buf) if n == num), default)


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {an op's event name: its ``tf_op``}}, the
    ``op_name`` XLA kept for it. tsl/profiler/protobuf/xplane.proto:
    XSpace.planes=1; XPlane.name=2, event_metadata=4 (map: value=2),
    stat_metadata=5 (map: value=2); XEventMetadata.name=2, stats=5;
    XStatMetadata.id=1, name=2; XStat.metadata_id=1, str_value=5."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for num, _, plane in pb_fields(space):
        if num != 1:
            continue
        name = pb_first(plane, 2, b"").decode()
        if not name.startswith("/device:TPU:"):
            continue
        metas, tf_op = [], None
        for n, _, v in pb_fields(plane):
            if n == 4:
                metas.append(pb_first(v, 2, b""))
            elif n == 5:
                sm = pb_first(v, 2, b"")
                if pb_first(sm, 2) == b"tf_op":
                    tf_op = pb_first(sm, 1)
        ops = out.setdefault(name, {})
        if tf_op is None:
            continue
        for meta in metas:
            for n, _, stat in pb_fields(meta):
                if n == 5 and pb_first(stat, 1) == tf_op:
                    ops[pb_first(meta, 2, b"").decode()] = \
                        pb_first(stat, 5, b"").decode()
    return out


def scope_of(op_name: Optional[str],
             known: Optional[Tuple[str, ...]] = None) -> Optional[str]:
    """The innermost of the program's scopes in an ``op_name``."""
    if not op_name:
        return None
    if known is None:
        known = scopes()
    found = [p for p in op_name.rstrip(":").split("/") if p in known]
    return found[-1] if found else None


# ------------------------------------------------------- the host's line
def host_lines(path: str) -> List[List[Tuple[str, float, float]]]:
    """One list per tick thread of its ``(phase, start s, end s)``
    spans, ``tick`` itself as phase "tick", on the trace's clock."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            spans = []
            for ev in line.events:
                name = ev.name
                if name == "tick" or name.startswith(("tick/", "tick#")):
                    phase = name[5:] if name.startswith("tick/") else "tick"
                    start = ev.start_ns * 1e-9
                    spans.append((phase, start,
                                  start + ev.duration_ns * 1e-9))
            if spans:
                out.append(spans)
    return out


def innermost(spans) -> List[Tuple[float, float, str]]:
    """Nested spans of one thread flattened to disjoint, sorted
    ``(start, end, phase)`` stretches, each under the innermost span
    open then; a stretch of ``tick`` that no phase covers is "host",
    the tick profiler's residual."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []         # (phase, end)
    at = 0.0

    def emit(until: float):
        nonlocal at
        if stack and until > at:
            phase = stack[-1][0]
            out.append((at, until, "host" if phase == "tick" else phase))
        at = max(at, until)

    for phase, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= start:
            emit(stack[-1][1])
            stack.pop()
        emit(start)
        stack.append((phase, end))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


# ---------------------------------------------------------- the reduction
def self_times(ops) -> List[Tuple[str, float, float]]:
    """``(name, start, seconds of its own)``: an op's duration less what
    the ops nested inside it on the same line took."""
    out, stack = [], []         # stack of [name, start, end, inner]
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= start:
            n, s, e, inner = stack.pop()
            out.append((n, s, max(e - s - inner, 0.0)))
        if stack:
            stack[-1][3] += dur
        stack.append([name, start, start + dur, 0.0])
    for n, s, e, inner in stack:
        out.append((n, s, max(e - s - inner, 0.0)))
    return out


def busy_intervals(events) -> List[Interval]:
    out: List[List[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], start + dur)
        else:
            out.append([start, start + dur])
    return [(a, b) for a, b in out]


def overlap(a: Interval, stretches, starts) -> Dict[str, float]:
    """Seconds of ``a`` under each phase of the sorted stretches."""
    out: Dict[str, float] = {}
    i = max(bisect.bisect_right(starts, a[0]) - 1, 0)
    while i < len(stretches) and stretches[i][0] < a[1]:
        s, e, phase = stretches[i]
        got = min(e, a[1]) - max(s, a[0])
        if got > 0:
            out[phase] = out.get(phase, 0.0) + got
        i += 1
    return out


def under(gaps: List[Interval], stretches, into=None) -> Dict[str, float]:
    """Seconds of all the gaps under each phase of the stretches."""
    out = {} if into is None else into
    starts = [s[0] for s in stretches]
    for gap in gaps:
        for phase, sec in overlap(gap, stretches, starts).items():
            out[phase] = out.get(phase, 0.0) + sec
    return out


def reduce_spans(path: str) -> dict:
    """What the metrics below read from one trace: device time of the
    tick modules' ops by scope, and the device's idle time by the host
    phase it fell under."""
    planes = trace.read_planes(path)
    names = op_names(path)
    every = [e for p in planes.values() for k in ("modules", "ops")
             for e in p[k]]
    t0 = min(s for _, s, _ in every)
    t1 = max(s + d for _, s, d in every)
    by_scope: Dict[Optional[str], float] = {}
    unscoped: Dict[str, float] = {}
    ticks = 0
    idle: List[Interval] = []
    known = scopes()
    for plane, p in planes.items():
        mods = sorted((s, s + d) for raw, s, d in p["modules"]
                      if trace.module_name(raw).startswith(TICK_PREFIX))
        ticks += len(mods)
        starts = [m[0] for m in mods]
        for raw, start, own in self_times(p["ops"]):
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start >= mods[i][1]:
                continue
            scope = scope_of(names.get(plane, {}).get(raw), known)
            by_scope[scope] = by_scope.get(scope, 0.0) + own
            if scope is None:
                key = trace.op_key(raw)
                unscoped[key] = unscoped.get(key, 0.0) + own
        at = t0
        for a, b in busy_intervals(p["ops"] or p["modules"]):
            if a > at:
                idle.append((at, a))
            at = max(at, b)
        if t1 > at:
            idle.append((at, t1))
    lines = [innermost(spans) for spans in host_lines(path)]
    # with several tick threads (replicas) each chip's idle time is set
    # against every thread: the trace does not say which drives which
    by_phase: Dict[str, float] = {}
    for line in lines:
        under(idle, line, into=by_phase)
    work = busy_intervals([(ph, s, e - s) for line in lines
                           for s, e, ph in line
                           if ph not in NOT_WORK + ("host",)])
    named = under(idle, [(a, b, "work") for a, b in work]).get("work", 0.0)
    idle_s = sum(b - a for a, b in idle)
    if len(lines) == 1:
        by_phase["(no span)"] = idle_s - sum(by_phase.values())
    return {"ticks": ticks, "by_scope": by_scope, "unscoped_ops": unscoped,
            "idle_s": idle_s, "idle_named_s": named,
            "idle_by_phase": by_phase, "tick_threads": len(lines)}


def find_trace() -> Optional[str]:
    """The newest trace a run of this checkout left."""
    found = [trace.find_xplane(d) for d in glob.glob(
        os.path.join(ROOT, ".bench_out", "*", "trace"))]
    found = [f for f in found if f]
    return max(found, key=os.path.getmtime) if found else None


def print_tables(r: dict):
    n = max(r["ticks"], 1)
    total = sum(r["by_scope"].values())
    note(f"device time of a tick by scope ({r['ticks']} tick modules, "
         f"{1e3 * total / n:.3f} ms of ops a tick):")
    for scope, sec in sorted(r["by_scope"].items(), key=lambda kv: -kv[1]):
        note(f"  {scope or '(no scope)':<12s} {1e3 * sec / n:8.3f} ms "
             f"{100 * sec / total if total else 0:5.1f}%")
    for key, sec in trace.top(r["unscoped_ops"], 5):
        note(f"    no scope: {key}  {1e3 * sec / n:.3f} ms")
    note(f"the device's idle time by the host phase it fell under "
         f"({r['idle_s']:.4f} s idle, {1e3 * r['idle_s'] / n:.3f} ms a "
         f"tick, {r['tick_threads']} tick thread(s)):")
    for phase, sec in sorted(r["idle_by_phase"].items(),
                             key=lambda kv: -kv[1]):
        note(f"  {phase:<12s} {1e3 * sec / n:8.3f} ms "
             f"{100 * sec / r['idle_s'] if r['idle_s'] else 0:5.1f}%")


def spans_of(src) -> Optional[dict]:
    """The run's reduction, made on first use and kept in ``src``."""
    if "_spans" not in src:
        path = find_trace()
        src["_spans"] = reduce_spans(path) if path else None
        if src["_spans"]:
            print_tables(src["_spans"])
    return src["_spans"]


# ------------------------------------------------------------ the metrics
def scope_ms(src, *scopes: str) -> Optional[float]:
    """Device ms a tick under these scopes; None where no op of the
    trace's ticks carries them."""
    r = spans_of(src)
    if not r or not r["ticks"]:
        return None
    sec = [r["by_scope"][s] for s in scopes if s in r["by_scope"]]
    return 1e3 * sum(sec) / r["ticks"] if sec else None


def unscoped_share(src) -> Optional[float]:
    r = spans_of(src)
    total = sum(r["by_scope"].values()) if r else 0.0
    return 100.0 * r["by_scope"].get(None, 0.0) / total if total > 0 \
        else None


def idle_unnamed_share(src) -> Optional[float]:
    r = spans_of(src)
    if not r or r["idle_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["idle_named_s"] / r["idle_s"])


def phase_ms(src, phases, per: str = "decode_ticks") -> Optional[float]:
    """The tick profiler's total of ``phases`` between the window's two
    snapshots, over the engines' count of ``per`` between them.

    In a traced run the snapshots lie either side of ``capture_trace``,
    and its ``stop_trace`` works the Python tracer's events into the
    file for about 19 s while the server runs (PERF.md section 5): the
    tick thread is slowed for most of the window, so these per-tick
    figures read up to twice the untraced ones and compare only with
    other traced runs, until the harness snapshots clear of it."""
    a, b = src["snaps"]["w0"], src["snaps"]["w1"]
    ms = n = 0.0
    for pa, pb, sa, sb in zip(a["tick_phase_ms"], b["tick_phase_ms"],
                              a["engines"], b["engines"]):
        if pa is None or pb is None or per not in sb \
                or not all(p in pb for p in phases):
            return None
        ms += sum(pb[p] - pa[p] for p in phases)
        n += sb[per] - sa[per]
    return ms / n if n > 0 else None
