"""From a profiler trace (``.xplane.pb``) to device numbers. Reads the
file with nothing but JAX (``jax.profiler.ProfileData``).

A TPU's plane (``/device:TPU:<n>``) carries one line of XLA modules —
one event per execution of a compiled program, named after the jitted
function — and one of XLA ops. Busy time is the union of the op
intervals (of the module intervals where a trace has no op line); the
window is from the first to the last device event of the trace over
all chips, so a chip that sat idle while another worked is charged.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]        # name, start s, duration s

LINES = {"XLA Modules": "modules", "XLA Ops": "ops"}


def find_xplane(logdir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def module_name(raw: str) -> str:
    """``jit__fused_tick_greedy(123456)`` -> ``_fused_tick_greedy``."""
    name = re.sub(r"\(.*\)$", "", raw.strip())
    return name[4:] if name.startswith("jit_") else name


_OP = re.compile(r"^%?([\w.\-]+) = (\(?[a-z0-9]+\[[0-9,]*\])?.*? "
                 r"([a-z][\w\-]*)\(")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def is_kernel(raw: str) -> bool:
    """A Pallas (Mosaic) kernel: the one custom call the TPU compiler
    names so. Stable whatever the enclosing jit is called."""
    return KERNEL_TARGET in raw


def op_key(raw: str) -> str:
    """An op's line of HLO text cut to what tells ops apart and stays
    the same from layer to layer: its opcode and the type and shape of
    its (first) result, ``fusion bf16[8,18944]``. The programs carry no
    ``named_scope`` yet (PERF.md), so an op's own name says nothing."""
    m = _OP.match(raw)
    if not m:
        return raw[:60]
    kind = "pallas_kernel" if is_kernel(raw) else m.group(3)
    return f"{kind} {m.group(2).lstrip('(')}" if m.group(2) else kind


def read_planes(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{device plane: {"modules": [...], "ops": [...]}} with times in
    seconds on the trace's own clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = {"modules": [], "ops": []}
        for line in plane.lines:
            kind = LINES.get(line.name)
            if kind is None:
                continue
            for ev in line.events:
                dev[kind].append((ev.name, ev.start_ns * 1e-9,
                                  ev.duration_ns * 1e-9))
        if dev["modules"] or dev["ops"]:
            out[plane.name] = dev
    return out


def union_seconds(events: List[Event]) -> float:
    """Total length of the union of the events' intervals."""
    total, end = 0.0, float("-inf")
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def _inside(intervals: List[Tuple[float, float]], t: float) -> bool:
    """Whether ``t`` lies in one of the sorted, disjoint intervals."""
    import bisect
    i = bisect.bisect_right(intervals, (t, float("inf"))) - 1
    return i >= 0 and intervals[i][0] <= t < intervals[i][1]


def top(pairs: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(pairs.items(),
                                      key=lambda kv: -kv[1])[:n]]


def reduce_trace(path: str) -> dict:
    """Everything the per-layer metrics read from one trace."""
    planes = read_planes(path)
    if not planes:
        raise ValueError(f"{path}: no TPU plane with device events")
    every = [e for p in planes.values() for k in ("modules", "ops")
             for e in p[k]]
    t0 = min(s for _, s, _ in every)
    t1 = max(s + d for _, s, d in every)
    busy, modules, op_time, gaps = {}, {}, {}, {}
    kernel = {"n": 0, "s": 0.0}     # Pallas kernels inside decode ticks
    for name, p in planes.items():
        busy[name] = union_seconds(p["ops"] or p["modules"])
        for raw, _, dur in p["modules"]:
            m = modules.setdefault(module_name(raw), {"n": 0, "s": 0.0})
            m["n"] += 1
            m["s"] += dur
        ticks = sorted((s0, s0 + d) for raw, s0, d in p["modules"]
                       if module_name(raw).startswith("_fused_tick"))
        for raw, start, dur in p["ops"]:
            key = op_key(raw)
            op_time[key] = op_time.get(key, 0.0) + dur
            if is_kernel(raw) and _inside(ticks, start):
                kernel["n"] += 1
                kernel["s"] += dur
        # idle gaps, named by the programs on either side of them: all
        # the device can say about what the host was doing meanwhile
        ms = sorted(p["modules"], key=lambda e: e[1])
        for (a, sa, da), (b, sb, _) in zip(ms, ms[1:]):
            gap = sb - (sa + da)
            if gap > 0:
                key = f"{module_name(a)} -> {module_name(b)}"
                gaps[key] = gaps.get(key, 0.0) + gap
    n = len(planes)
    return {"window_s": t1 - t0, "busy_s": sum(busy.values()) / n,
            "busy_s_per_chip": busy, "chips": n, "modules": modules,
            "tick_kernels": kernel,
            "device_ops": top(op_time), "idle_gaps": top(gaps)}
