#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to a fixture that keeps what the
program's names are read from (``harness/spans.py``): the TPU planes'
"XLA Modules" and "XLA Ops" lines for the first few decode ticks WITH
each op's ``tf_op`` stat (its ``op_name``, where the scope lives), and
from the host plane the tick thread's ``tick`` (with its index ``n``)
and ``tick/<phase>`` spans over the same stretch of the trace's clock.

    python3 benchmarks/tests/make_span_fixture.py <in.xplane.pb> <out> [ticks]

``data/v5e_scoped.xplane.pb`` was cut from the trace of PR 24's first
traced run of ``qwen2-7b-d16.batch-decode`` on a TPU v5e. Unlike
``make_trace_fixture.py`` this works on the file's own bytes (a
protobuf reader and writer for the fields of
tsl/profiler/protobuf/xplane.proto named below), because
``jax.profiler.ProfileData`` does not show an event metadata's stats.
Every other stat goes; strings over 160 characters (the ops' HLO text)
are cut to that; a Pallas kernel keeps its ``custom_call_target``.

XSpace.planes=1; XPlane.id=1 name=2 lines=3 event_metadata=4 (a map:
key=1 value=2) stat_metadata=5 stats=6; XLine.id=1 name=2 timestamp_ns=3
events=4; XEvent.metadata_id=1 offset_ps=2 duration_ps=3 stats=4;
XEventMetadata.id=1 name=2 metadata=3 display_name=4 stats=5;
XStatMetadata.id=1 name=2; XStat.metadata_id=1 str_value=5."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks.harness.spans import pb_fields as fields  # noqa: E402
from benchmarks.harness.spans import pb_first as first  # noqa: E402
from benchmarks.tests.make_trace_fixture import varint  # noqa: E402

KERNEL = b'custom_call_target="tpu_custom_call"'
DEVICE_LINES = (b"XLA Modules", b"XLA Ops")
KEEP_STATS = (b"tf_op", b"n")
CUT = 160


def field(num: int, wt: int, v) -> bytes:
    if wt == 0:
        return varint(num << 3) + varint(v)
    if wt == 2:
        return varint(num << 3 | 2) + varint(len(v)) + v
    return varint(num << 3 | wt) + v


def cut_text(v: bytes) -> bytes:
    if len(v) <= CUT:
        return v
    keep = v[:CUT]
    return keep + b" ... " + KERNEL + b")" \
        if KERNEL in v and KERNEL not in keep else keep


def cut_stats(msg: bytes, stats_field: int, keep: set, drop=()) -> bytes:
    """An XEvent or XEventMetadata with only the stats ``keep`` names
    (by stat metadata id), its strings cut, the fields ``drop`` gone."""
    out = b""
    for n, wt, v in fields(msg):
        if n in drop or (n == stats_field and first(v, 1) not in keep):
            continue
        if n == stats_field:
            v = b"".join(field(sn, swt, cut_text(sv) if sn == 5 else sv)
                         for sn, swt, sv in fields(v))
        elif wt == 2:
            v = cut_text(v)
        out += field(n, wt, v)
    return out


def events_of(line: bytes):
    """(start ps on the trace's clock, duration ps, metadata id, bytes)"""
    t0 = first(line, 3, 0) * 1000
    for n, _, v in fields(line):
        if n == 4:
            yield (t0 + first(v, 2, 0), first(v, 3, 0), first(v, 1, 0), v)


def cut_plane(plane: bytes, keep_line, t0: int, t1: int) -> bytes:
    """The plane with only the lines ``keep_line(name)`` accepts, of
    those the events ``keep_event(metadata name)`` accepts that start
    in [t0, t1] ps, and only the event metadata those use."""
    names, keep = {}, set()
    for n, _, v in fields(plane):
        if n == 4:
            meta = first(v, 2, b"")
            names[first(meta, 1, first(v, 1, 0))] = first(meta, 2, b"")
        elif n == 5 and first(first(v, 2, b""), 2) in KEEP_STATS:
            keep.add(first(v, 1))
    out, used = b"", set()
    for n, wt, v in fields(plane):
        if n == 5 and first(v, 1) not in keep:
            continue
        if n == 3:
            keep_event = keep_line(first(v, 2, b""))
            if keep_event is None:
                continue
            line = b""
            for ln, lwt, lv in fields(v):
                if ln != 4:
                    line += field(ln, lwt, lv)
            for start, _, mid, ev in events_of(v):
                if t0 <= start <= t1 and keep_event(names.get(mid, b"")):
                    used.add(mid)
                    line += field(4, 2, cut_stats(ev, 4, keep))
            out += field(3, 2, line)
        elif n not in (4, 6):           # 6: the plane's own stats
            out += field(n, wt, v)
    for n, wt, v in fields(plane):
        if n == 4 and first(v, 1, 0) in used:
            # 3: the op's serialized payload
            out += field(4, 2, b"".join(
                field(mn, mwt, cut_stats(mv, 5, keep, drop=(3,))
                      if mn == 2 else mv) for mn, mwt, mv in fields(v)))
    return out


def is_tick_span(name: bytes) -> bool:
    return name == b"tick" or name.startswith((b"tick/", b"tick#"))


def main(argv) -> int:
    src, dst = argv[1], argv[2]
    ticks = int(argv[3]) if len(argv) > 3 else 3
    with open(src, "rb") as f:
        space = f.read()
    planes = [v for n, _, v in fields(space) if n == 1]
    # the stretch to keep: the first ``ticks`` modules of the first chip
    t0 = t1 = None
    for plane in planes:
        if not first(plane, 2, b"").startswith(b"/device:TPU:"):
            continue
        for n, _, v in fields(plane):
            if n == 3 and first(v, 2) == b"XLA Modules":
                mods = sorted(events_of(v))[:ticks]
                t0, t1 = mods[0][0], mods[-1][0] + mods[-1][1]
        break
    if t0 is None:
        raise SystemExit(f"{src}: no TPU plane with an XLA Modules line")
    lead = 30_000_000_000       # 30 ms before: the host's first dispatch
    out = b""
    for plane in planes:
        name = first(plane, 2, b"")
        if name.startswith(b"/device:TPU:"):
            out += field(1, 2, cut_plane(
                plane, lambda ln: (lambda ev: True)
                if ln in DEVICE_LINES else None, t0, t1))
        elif name.startswith(b"/host:CPU"):
            cut = cut_plane(plane, lambda ln: is_tick_span, t0 - lead, t1)
            # host lines the cut left empty (every thread but the tick
            # threads) go
            kept = b""
            for n, wt, v in fields(cut):
                if n == 3 and first(v, 4) is None:
                    continue
                kept += field(n, wt, v)
            out += field(1, 2, kept)
    with open(dst, "wb") as f:
        f.write(out)
    print(f"{dst}: {len(out)} bytes, {ticks} ticks", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
