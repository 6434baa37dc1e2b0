"""ISSUE 20: tick-phase profiler — dispatch/device/host attribution.

Contracts pinned here:

- PHASE SUM == WALL: under an injected clock the in-tick phases
  (``obs.TICK_PHASES``) sum EXACTLY to the measured tick wall — host
  is the residual of the bracketed phases, so there is no unexplained
  remainder for ``phase_breakdown``/``phase_decompose`` to
  mis-attribute. Brackets nest: an upload inside ``stage`` or a
  program call inside ``chunk`` takes its time out of the outer one.
- A CHUNK IS NOT HOST TIME (ISSUE 24): the prefill chunk's program
  call lands in ``dispatch`` and the blocking read of its first token
  in ``device``; before, both fell into the ``host`` residual.
- LOOP PHASES (ISSUE 24): what the worker thread does between steps
  (``engine.loop_phase``: ``obs.LOOP_PHASES``) feeds the totals, the
  histograms and the thread's wall, and no tick record; the serving
  gateway's worker reports all four.
- CPU BESIDE WALL (ISSUE 35): a bracket reads the thread's CPU clock
  inside its wall readings; per-phase CPU totals follow the wall's
  nesting and residual rules, never exceed the wall, and come out in
  ``tick_profile_summary()`` and, as whole microseconds, in
  ``PagedEngine.stats``; with the profiler off ``stats`` has the
  parent's keys.
- BITWISE OFF==ON: profile-on greedy+sampled streams are bit-identical
  to profile-off across the engine's paths (the served fused tick,
  its speculative dispatches, its run-ahead across block growth,
  chunked prefill, and the unfused host reference) — the profiler reads
  clocks and calls ``block_until_ready`` on arrays the next statement
  would block on anyway; it never changes what the device computes.
- STEADY CONTRACT UNTOUCHED: with the profiler ON, steady decode
  ticks keep the ISSUE 19 pins — one dispatch per tick, zero uploads,
  zero upload bytes.
- RING BOUND: the per-tick ring holds at most ``profile_ring_len``
  records with strictly increasing tick counters; the ``tickphase/1``
  doc round-trips ``obs.validate_tickphase_doc``.
- FLUSH ON RESET: ``obs.reset()`` (and the gateway drain that calls
  it) writes ``tickphase_<engine>.json`` into the still-configured
  run dir via the registered flusher.
- REQUEST WATERFALL: tick trace events carry the completed tick's
  phase split; ``decode_phase_share`` folds them into per-request
  fractions and the trace ring banks them as ``phase_share``.

The ``/profilez`` HTTP capture e2e (gateway + fleet-frontend
federation) rides behind ``slow`` (``tools/marker_audit.py``
``test_tick_profile.py.*profilez.*e2e``).
"""
import asyncio
import glob
import json
import os
import sys

import numpy as np
import pytest

from paddle_tpu.generation.paged import PagedEngine, _TickPhaseProfile
from paddle_tpu.generation.stub import TickStubModel
from paddle_tpu.serving.reqtrace import (RequestTrace, RequestTraceRing,
                                         decode_phase_share)
from paddle_tpu.utils import observability as obs


def _cyc(n, start=0):
    return (np.arange(n) % 5 + 1 + start)[None]


def _engine(**kw):
    base = dict(max_slots=4, num_blocks=32, block_size=8,
                max_blocks_per_seq=8, prefill_buckets=(16,))
    base.update(kw)
    return PagedEngine(TickStubModel(), **base)


# greedy + sampled + stop-sequence + eos: the mixed workload the
# bitwise pins replay across the mode matrix
SUBS = [
    ("g", _cyc(6), dict(max_new_tokens=12)),
    ("s", _cyc(8, 2), dict(max_new_tokens=10, temperature=0.8,
                           top_k=20, seed=5)),
    ("st", _cyc(9, 1), dict(max_new_tokens=14,
                            stop_sequences=[[3, 4]])),
    ("e", _cyc(5, 3), dict(max_new_tokens=10, eos_token_id=2)),
]


def _drain(eng):
    for rid, ids, kw in SUBS:
        eng.submit(rid, ids, **kw)
    res = eng.run()
    return res, dict(eng.logprobs)


class FakeClock:
    """Deterministic profiler clock: +1 ms per call, so every
    bracketed phase costs exactly the number of clock reads its code
    path makes and the phase math is pinned to exact floats."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.001
        return self.t


# ========================================================= phase math
def test_phase_sum_equals_wall_injected_clock():
    eng = _engine(tick_profile=True, profile_clock=FakeClock())
    _drain(eng)
    prof = eng._prof
    assert prof.ticks > 0
    # the residual construction: the in-tick phases sum to the wall
    # EXACTLY, nested brackets included
    assert sum(prof.totals[p] for p in obs.TICK_PHASES) \
        == pytest.approx(prof.wall_total_ms, rel=1e-9)
    # every bracketed phase this engine reaches ran under the fake
    # clock (it prefills whole prompts: no chunk)
    for p in obs.TICK_PHASES:
        assert (prof.totals[p] > 0.0) == (p != "chunk"), p
    assert all(prof.totals[p] == 0.0 for p in obs.LOOP_PHASES)
    # per-entry exactness too, and the engine-facing aggregates agree
    doc = eng.tick_profile_doc()
    assert obs.validate_tickphase_doc(doc) == []
    for rec in doc["entries"]:
        assert sum(rec[f"{p}_ms"] for p in obs.TICK_PHASES) \
            == pytest.approx(rec["wall_ms"], rel=1e-9)
    assert eng.tick_phase_totals == prof.totals
    assert eng.tick_wall_ms_total == prof.wall_total_ms
    assert doc["phase_totals_ms"] == pytest.approx(
        {p: prof.totals[p] for p in obs.TICK_PHASES}, abs=1e-3)
    # the thread's wall covers the ticks and the gaps between them
    assert doc["thread_wall_ms"] >= doc["wall_total_ms"]


# ===================================================== CPU beside wall
class HandClock:
    """A clock the test moves by hand."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_cpu_totals_follow_the_walls_nesting_and_residual():
    wall, cpu = HandClock(), HandClock()

    def spend(wall_ms, cpu_ms):
        wall.t += wall_ms * 1e-3
        cpu.t += cpu_ms * 1e-3

    prof = _TickPhaseProfile({"engine": "t-cpu"}, clock=wall,
                             cpu_clock=cpu)
    prof.begin()
    spend(2, 1)                         # no bracket: the host residual
    with prof.span("stage"):
        spend(4, 4)
        with prof.span("h2d"):          # taken out of ``stage``
            spend(3, 1)
    with prof.span("device"):
        spend(10, 0)                    # a wait: wall and no CPU
    with prof.span("commit"):
        spend(1, 0.5)
    with prof.span("expire"):
        spend(1, 10)                    # a stepping CPU clock's step,
                                        # whole in the bracket that reads it
    prof.end(dispatches=1, uploads=1, nbytes=8, patches=0, active=1)
    with prof.span("emit"):             # a loop phase, outside a tick
        spend(5, 2)
    want_wall = dict(host=2, stage=4, h2d=3, device=10, commit=1, expire=1,
                     emit=5)
    want_cpu = dict(host=1, stage=4, h2d=1, device=0, commit=0.5, expire=10,
                    emit=2)
    for p in prof.PHASES:
        assert prof.totals[p] == pytest.approx(want_wall.get(p, 0)), p
        assert prof.cpu_totals[p] == pytest.approx(want_cpu.get(p, 0)), p
    assert sum(prof.totals[p] for p in obs.TICK_PHASES) \
        == pytest.approx(prof.wall_total_ms, rel=1e-9) == 21.0
    summ = prof.summary()
    assert summ["phase_cpu_ms"] == pytest.approx(
        {p: want_cpu.get(p, 0) for p in obs.TICK_PHASES})
    assert summ["loop_phase_cpu_ms"] == pytest.approx(
        {p: want_cpu.get(p, 0) for p in obs.LOOP_PHASES})
    # what the thread wanted a CPU for and did not get: the wall less
    # the CPU of the phases that are not waits by design
    work = [p for p in prof.PHASES
            if p not in ("device", "idle", "lock", "expire")]
    assert sum(prof.totals[p] - prof.cpu_totals[p] for p in work) \
        == pytest.approx(6.5)


class Ticking(FakeClock):
    def __init__(self, step_s):
        super().__init__()
        self.step_s = step_s

    def __call__(self):
        self.t += self.step_s
        return self.t


def test_engine_cpu_never_exceeds_wall_and_reaches_stats():
    eng = _engine(tick_profile=True, profile_clock=FakeClock())
    eng._prof.cpu_clock = Ticking(0.00025)
    with eng.loop_phase("sched"):
        pass
    _drain(eng)
    prof = eng._prof
    assert sum(prof.totals[p] for p in obs.TICK_PHASES) \
        == pytest.approx(prof.wall_total_ms, rel=1e-9)
    for p in prof.PHASES:
        assert 0.0 <= prof.cpu_totals[p] <= prof.totals[p], p
        assert (prof.cpu_totals[p] > 0.0) == (prof.totals[p] > 0.0), p
    # a quarter of the wall, by the two clocks' steps
    assert prof.cpu_totals["sched"] == pytest.approx(0.25)
    assert prof.cpu_totals["device"] == pytest.approx(
        prof.totals["device"] / 4)
    stats = eng.stats
    for p in prof.PHASES:
        assert stats["phase_cpu_us." + p] == int(prof.cpu_totals[p] * 1e3)
    summ = eng.tick_profile_summary()
    assert set(summ["phase_cpu_ms"]) == set(obs.TICK_PHASES)
    assert set(summ["loop_phase_cpu_ms"]) == set(obs.LOOP_PHASES)
    # the wall's dict stays the wall's: serve_loadgen and fleet_dash
    # sum it
    assert set(eng.tick_phase_totals) == set(prof.PHASES)
    doc = eng.tick_profile_doc()
    assert obs.validate_tickphase_doc(doc) == []
    assert doc["switch_interval_s"] == sys.getswitchinterval()


def test_a_cpu_clock_that_steps_loses_nothing_in_the_brackets():
    """The benchmark's machines advance a thread's CPU clock in 10 ms
    steps: a step lands whole in the bracket that reads it, so a small
    phase may hold more CPU than wall, and the phases together hold
    every step that fell inside a tick or a loop bracket."""
    wall = FakeClock()

    def stepped():                      # half the wall, in 10 ms steps
        return int(wall.t * 0.5 / 0.010) * 0.010

    eng = _engine(tick_profile=True, profile_clock=wall)
    eng._prof.cpu_clock = stepped
    _drain(eng)
    prof = eng._prof
    held = sum(prof.cpu_totals.values())
    # only a step read between two ticks is under no name
    assert 20.0 <= held <= stepped() * 1e3 + 1e-6
    assert held >= 0.7 * stepped() * 1e3
    assert all(v >= 0.0 and round(v, 6) % 10 == 0
               for v in prof.cpu_totals.values())
    assert sum(prof.totals[p] for p in obs.TICK_PHASES) \
        == pytest.approx(prof.wall_total_ms, rel=1e-9)


def test_profile_off_stats_keys_are_the_parents():
    off, on = _engine(), _engine(tick_profile=True)
    _drain(off)
    _drain(on)
    cpu_keys = {"phase_cpu_us." + p for p in _TickPhaseProfile.PHASES}
    assert not any(k.startswith("phase_cpu_us") for k in off.stats)
    assert set(on.stats) == set(off.stats) | cpu_keys
    assert {k: v for k, v in on.stats.items() if k not in cpu_keys} \
        == off.stats
    assert (on.dispatch_count, on.h2d_uploads, on.h2d_upload_bytes) \
        == (off.dispatch_count, off.h2d_uploads, off.h2d_upload_bytes)
    assert "stats" not in off.health() and all(
        k in on.health() for k in cpu_keys)


class SlowCalls(FakeClock):
    """A FakeClock on which the chunk program's call takes 100 ms and
    every wait for the device 50 ms."""

    def install(self, eng, monkeypatch):
        from paddle_tpu.generation import paged
        ready = paged.jax.block_until_ready

        def slow(program):
            def slow_chunk(*a, **kw):
                self.t += 0.100
                return program(*a, **kw)
            return slow_chunk

        def slow_ready(x):
            self.t += 0.050
            return ready(x)
        # both chunk programs: the call that starts at 0 and the one
        # that continues a prompt
        eng._chunk_jit = paged._ChunkPrograms(
            slow(eng._chunk_jit.packed), slow(eng._chunk_jit.alone))
        monkeypatch.setattr(paged.jax, "block_until_ready", slow_ready)


def test_chunk_call_and_read_are_not_host_time(monkeypatch):
    clock = SlowCalls()
    eng = _engine(tick_profile=True, profile_clock=clock,
                  chunk_prefill_tokens=8)
    clock.install(eng, monkeypatch)
    eng.submit("c", _cyc(12), max_new_tokens=3)     # two chunks
    eng.step()          # admit + first chunk: a call, no read
    eng.step()          # last chunk: a call and the first token's read
    first, last = list(eng._prof.ring)
    assert first["dispatch_ms"] == pytest.approx(101.0)
    assert first["device_ms"] == 0.0
    # the last chunk's tick also dispatches the first decode tick
    assert last["dispatch_ms"] == pytest.approx(102.0)
    assert last["device_ms"] == pytest.approx(51.0)
    for rec in (first, last):
        assert rec["chunk_ms"] > 0.0 and rec["h2d_ms"] > 0.0
        assert rec["host_ms"] < 10.0        # the slow calls are not here
        assert sum(rec[f"{p}_ms"] for p in obs.TICK_PHASES) \
            == pytest.approx(rec["wall_ms"], rel=1e-9)
    assert eng.stats["decode_ticks"] == 1
    assert eng.stats["prefill_chunks"] == 2
    eng.run()
    h = obs.registry().snapshot()
    key = f"paged_prefill_chunk_ms{{engine=\"{eng._obs_labels['engine']}\"}}"
    assert h[key]["count"] == 2


def test_loop_phases_feed_totals_and_no_tick_record():
    eng = _engine(tick_profile=True, profile_clock=FakeClock())
    with eng.loop_phase("sched"):
        eng.submit("a", _cyc(6), max_new_tokens=4)
    eng.step()
    with eng.loop_phase("emit"):
        pass
    with eng.loop_phase("idle"):
        with pytest.raises(KeyError):       # not a phase: the bracket
            eng.loop_phase("nap")           # around it still closes
    summ = eng.tick_profile_summary()
    assert summ["ticks"] == 1 and len(eng._prof.ring) == 1
    assert summ["loop_totals_ms"] == {"sched": 1.0, "lock": 0.0,
                                      "emit": 1.0, "idle": 1.0}
    assert set(eng._prof.ring[0]) >= {f"{p}_ms" for p in obs.TICK_PHASES}
    assert not any(f"{p}_ms" in eng._prof.ring[0] for p in obs.LOOP_PHASES)
    # every clock tick from the first bracket to the last is under a
    # name but the ones BETWEEN brackets: 3 gaps of 1 ms here
    named = sum(eng.tick_phase_totals.values())
    assert summ["thread_wall_ms"] - named == pytest.approx(3.0)
    assert obs.validate_tickphase_doc(eng.tick_profile_doc()) == []
    # profiler off: one shared no-op, no summary
    off = _engine()
    assert off.loop_phase("emit") is off.loop_phase("idle")
    with off.loop_phase("emit"):
        pass
    assert off.tick_profile_summary() is None


def test_gateway_worker_reports_every_loop_phase():
    """The serving gateway's tick thread through real HTTP: all four
    loop phases show up, and with the ticks they cover the thread."""
    from paddle_tpu.serving import Gateway
    from test_gateway import _sse
    eng = _engine(tick_profile=True, chunk_prefill_tokens=8)

    async def run():
        gw = Gateway(eng, name="t-loop")
        await gw.start()
        await asyncio.sleep(0.05)                   # an idle stretch
        await _sse(gw.port, {"prompt": list(range(1, 10)),
                             "max_new_tokens": 6, "temperature": 0.0})
        await gw.drain()
    asyncio.run(run())
    summ = eng.tick_profile_summary()
    loop = summ["loop_totals_ms"]
    assert all(loop[p] > 0.0 for p in ("sched", "emit", "idle")), loop
    assert loop["lock"] >= 0.0
    named = summ["wall_total_ms"] + sum(loop.values())
    assert 0.9 * summ["thread_wall_ms"] < named \
        <= summ["thread_wall_ms"] * (1 + 1e-6)


def test_dash_letters_follow_the_vocabulary():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "fleet_dash", os.path.join(os.path.dirname(__file__), "..",
                                   "tools", "fleet_dash.py"))
    dash = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(dash)
    assert tuple(dash.PHASE_LETTERS) == obs.TICK_PHASES + obs.LOOP_PHASES
    letters = list(dash.PHASE_LETTERS.values())
    assert len(set(letters)) == len(letters)


def test_real_clock_sum_within_validator_tolerance():
    eng = _engine(tick_profile=True)
    _drain(eng)
    doc = eng.tick_profile_doc()
    assert doc["ticks"] > 0
    assert obs.validate_tickphase_doc(doc) == []
    # snapshot surface carries the same numbers
    snap = eng.debug_snapshot()["tick_profile"]
    assert snap["enabled"] and snap["ticks"] == doc["ticks"]
    assert _engine().debug_snapshot()["tick_profile"] \
        == {"enabled": False}


# ====================================================== bitwise pins
@pytest.mark.parametrize("mode_kw", [
    {},                                # the served fused tick
    {"spec_tokens": 2},                # speculative tick
    {"fused_tick": False},             # the host reference
    {"block_size": 4, "max_blocks_per_seq": 16},   # growth under lag
    {"chunk_prefill_tokens": 8},       # chunked prefill (ISSUE 24)
], ids=["fused-ring", "spec", "unfused", "run-ahead-growth", "chunked"])
def test_profile_on_off_bitwise(mode_kw):
    res_off, lp_off = _drain(_engine(**mode_kw))
    res_on, lp_on = _drain(_engine(tick_profile=True, **mode_kw))
    assert res_on == res_off
    for rid in lp_off:
        assert lp_on[rid] == lp_off[rid]


def test_steady_tick_contract_with_profiler_on():
    """ISSUE 19 steady pins stay green with the profiler running:
    1 dispatch per tick, 0 uploads, 0 bytes."""
    eng = _engine(block_size=64, max_blocks_per_seq=2,
                  tick_profile=True)
    for i in range(4):
        eng.submit(f"r{i}", _cyc(6), max_new_tokens=100)
    for _ in range(6):
        eng.step()
    d0, u0 = eng.dispatch_count, eng.h2d_uploads
    b0 = eng.h2d_upload_bytes
    t0 = eng._prof.ticks
    for _ in range(20):
        eng.step()
    assert eng.dispatch_count - d0 == 20
    assert eng.h2d_uploads - u0 == 0
    assert eng.h2d_upload_bytes - b0 == 0
    # and the ring saw exactly those ticks, each recording 1 dispatch
    assert eng._prof.ticks - t0 == 20
    steady = list(eng._prof.ring)[-20:]
    assert all(r["dispatches"] == 1 and r["uploads"] == 0
               and r["bytes"] == 0 for r in steady)


# ========================================================== ring bound
def test_ring_bounded_and_monotonic():
    eng = _engine(tick_profile=True, profile_ring_len=4)
    _drain(eng)
    doc = eng.tick_profile_doc()
    assert eng._prof.ticks > 4          # the run outgrew the ring
    assert len(doc["entries"]) == 4     # ...which stayed bounded
    assert doc["capacity"] == 4
    assert obs.validate_tickphase_doc(doc) == []
    ticks = [r["tick"] for r in doc["entries"]]
    assert ticks == sorted(ticks) and len(set(ticks)) == 4
    # totals keep full-run accounting even after ring eviction
    assert doc["wall_total_ms"] >= sum(
        r["wall_ms"] for r in doc["entries"]) - 1e-6


# ======================================================== reset flush
def test_reset_flushes_tickphase_ring(tmp_path):
    obs.reset()                     # drop flushers stale engines left
    obs.configure(str(tmp_path))
    try:
        eng = _engine(tick_profile=True, profile_clock=FakeClock())
        _drain(eng)
        assert glob.glob(str(tmp_path / "tickphase_*.json")) == []
    finally:
        obs.reset()                 # the flush under test
    files = glob.glob(str(tmp_path / "tickphase_*.json"))
    assert len(files) == 1
    with open(files[0]) as f:
        doc = json.load(f)
    assert obs.validate_tickphase_doc(doc) == []
    assert doc["ticks"] == eng._prof.ticks > 0
    # a second reset must not re-run the (cleared) flusher
    os.remove(files[0])
    obs.reset()
    assert glob.glob(str(tmp_path / "tickphase_*.json")) == []


# ================================================== request waterfall
def test_trace_events_carry_phase_and_share():
    eng = _engine(tick_profile=True)
    events = []
    eng.trace_sink = lambda rid, kind, **f: events.append(
        (rid, kind, f))
    eng.submit("a", _cyc(6), max_new_tokens=8)
    eng.run()
    ticks = [f for rid, kind, f in events
             if rid == "a" and kind == "tick"]
    assert ticks
    with_phase = [f["phase"] for f in ticks if "phase" in f]
    assert with_phase                # at least the post-first ticks
    for ph in with_phase:
        # the tick's index is the ``n`` of its span in a profiler trace
        assert set(ph) == {"tick", "wall_ms"} | {
            f"{p}_ms" for p in obs.TICK_PHASES}

    # profiler OFF: tick events stay phase-free (no schema surprise)
    eng2 = _engine()
    ev2 = []
    eng2.trace_sink = lambda rid, kind, **f: ev2.append((kind, f))
    eng2.submit("a", _cyc(6), max_new_tokens=8)
    eng2.run()
    assert all("phase" not in f for k, f in ev2 if k == "tick")


def test_decode_phase_share_math_and_ring_entry():
    t = RequestTrace("req-1")
    t.ev("queue_enter", slo="interactive")
    t.ev("tick", n=1, phase={"wall_ms": 4.0, "host_ms": 1.0,
                             "h2d_ms": 0.0, "dispatch_ms": 2.0,
                             "device_ms": 0.5, "drain_ms": 0.5})
    t.ev("tick", n=2, phase={"wall_ms": 6.0, "host_ms": 2.0,
                             "h2d_ms": 1.0, "dispatch_ms": 1.0,
                             "device_ms": 1.5, "drain_ms": 0.5})
    t.ev("tick", n=3)                # no phase: skipped, not crashed
    share = decode_phase_share(t)
    assert share["ticks"] == 2 and share["wall_ms"] == 10.0
    assert share["host_frac"] == pytest.approx(0.3)
    assert share["dispatch_frac"] == pytest.approx(0.3)
    assert share["device_frac"] == pytest.approx(0.2)
    assert share["drain_frac"] == pytest.approx(0.1)
    assert share["h2d_frac"] == pytest.approx(0.1)
    # the ring banks it on finish
    ring = RequestTraceRing(capacity=4, labels={"gateway": "t"})
    entry = ring.finish(t, "stop", tokens=2)
    assert entry["phase_share"] == share
    # and a phase-free trace yields no key at all
    t2 = RequestTrace("req-2")
    t2.ev("queue_enter", slo="interactive")
    assert decode_phase_share(t2) is None
    assert "phase_share" not in ring.finish(t2, "stop")


# ==================================================== /profilez e2e
@pytest.mark.slow
def test_profilez_capture_e2e(tmp_path):
    """The capture layer over real HTTP: a gateway ``/profilez``
    returns windowed per-replica phase totals + dumps validating
    tickphase files into the run dir; the fleet frontend federates the
    same capture to a named peer; concurrent captures 409."""
    from paddle_tpu.serving import Gateway
    from paddle_tpu.serving.fleet import FleetFrontend, RemoteReplica
    from test_gateway import _http, _poll, _sse
    obs.reset()
    obs.configure(str(tmp_path))

    async def run():
        gw = Gateway(_engine(tick_profile=True,
                             chunk_prefill_tokens=8,
                             prefill_buckets=(16,)),
                     name="t-pz")
        await gw.start()
        rep = RemoteReplica("p0", "127.0.0.1", gw.port,
                            probe_interval_s=0.05)
        fe = FleetFrontend([rep], chunk_tokens=8, name="t-pz-fe")
        await fe.start()
        await _poll(rep.healthy, 5)
        await _sse(gw.port, {"prompt": list(range(1, 10)),
                             "max_new_tokens": 6, "temperature": 0.0})
        cap, c409 = await asyncio.gather(
            _http(gw.port, "GET", "/profilez?duration_s=0.3"),
            _http(gw.port, "GET", "/profilez?duration_s=0.3"))
        fed = await _http(fe.port, "GET",
                          "/profilez?duration_s=0.1&replica=p0")
        miss = await _http(fe.port, "GET",
                           "/profilez?duration_s=0.1&replica=nope")
        await fe.drain()
        await gw.drain()
        return cap, c409, fed, miss

    cap, c409, fed, miss = asyncio.run(run())
    assert sorted((cap[0], c409[0])) == [200, 409]
    body = json.loads(cap[2] if cap[0] == 200 else c409[2])
    assert body["gateway"] == "t-pz"
    assert body["duration_s"] == pytest.approx(0.3)
    assert body["tickphase_files"]
    rep0 = body["replicas"]["r0"]
    assert rep0["enabled"]
    assert set(rep0["phase_ms_in_window"]) == set(obs.TICK_PHASES)
    for path in body["tickphase_files"]:
        with open(path) as f:
            assert obs.validate_tickphase_doc(json.load(f)) == []
    st, _, fb = fed
    assert st == 200
    fdoc = json.loads(fb)
    assert fdoc["fleet"] == "t-pz-fe" and fdoc["replica"] == "p0"
    assert fdoc["report"]["gateway"] == "t-pz"
    assert fdoc["report"]["replicas"]["r0"]["enabled"]
    assert miss[0] == 404
    # drain re-dumped the rings into the run dir beside the traces
    assert glob.glob(str(tmp_path / "tickphase_t-pz_*.json"))
    obs.reset()
