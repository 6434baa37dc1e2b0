#!/usr/bin/env python3
"""Cut a recorded ``.xplane.pb`` down to a fixture a repository can
carry: the TPU planes' "XLA Modules" and "XLA Ops" lines for the first
few decode ticks, with the ops' HLO text cut to 160 characters (a Pallas kernel keeps
its ``custom_call_target``).

    python3 benchmarks/tests/make_trace_fixture.py <in.xplane.pb> <out> [ticks]

``data/v5e_ticks.xplane.pb`` was cut from the trace of PR 23's first
traced run of ``qwen2-7b-d16.batch-decode`` on a TPU v5e. The writer is
a minimal protobuf encoder for the XSpace fields the reader uses
(tsl/profiler/protobuf/xplane.proto: XSpace.planes=1; XPlane.name=2,
lines=3, event_metadata=4; XLine.name=2, timestamp_ns=3, events=4;
XEvent.metadata_id=1, offset_ps=2, duration_ps=3; XEventMetadata.id=1,
name=2)."""
import sys


KERNEL = 'custom_call_target="tpu_custom_call"'


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def main(argv) -> int:
    from jax.profiler import ProfileData
    src, dst = argv[1], argv[2]
    ticks = int(argv[3]) if len(argv) > 3 else 3
    planes = b""
    for plane in ProfileData.from_file(src).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: list(ln.events) for ln in plane.lines
                 if ln.name in ("XLA Modules", "XLA Ops")}
        mods = lines["XLA Modules"][:ticks]
        t0 = int(mods[0].start_ns)
        t1 = int(mods[-1].start_ns + mods[-1].duration_ns)
        meta, body, out_lines = {}, b"", b""
        for name, events in lines.items():
            evs = b""
            for e in events:
                if not t0 <= e.start_ns <= t1:
                    continue
                key = e.name[:160]
                if KERNEL in e.name and KERNEL not in key:
                    key += " ... " + KERNEL + ")"
                mid = meta.setdefault(key, len(meta) + 1)
                evs += field(4, field(1, mid)
                             + field(2, int((e.start_ns - t0) * 1000))
                             + field(3, int(e.duration_ns * 1000)))
            out_lines += field(3, field(2, name) + field(3, t0) + evs)
        for key, mid in meta.items():
            body += field(4, field(1, mid)
                          + field(2, field(1, mid) + field(2, key)))
        planes += field(1, field(2, plane.name) + out_lines + body)
    with open(dst, "wb") as f:
        f.write(planes)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
