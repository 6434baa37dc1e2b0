"""ISSUE 12: fault-tolerant serving fleet — replica supervision,
in-flight request failover, circuit-breaker rejoin, seeded chaos.

Contracts pinned here:

- BREAKER: the closed -> open -> half-open -> closed state machine,
  exponential backoff (doubling per reopen), and the AT-MOST-ONE
  in-flight probe rule.
- ROUTER REJOIN: evict -> probe -> rejoin through the breaker folded
  into the warm/sticky/least-loaded ladder (eviction is no longer
  one-way), and a half-open replica receives at most one probe at a
  time.
- RESUME: ``PagedEngine.export_resumable()`` descriptors resubmitted
  as ``prompt + committed tokens`` continue a greedy stream BITWISE
  identically to the uninterrupted reference — no duplicated and no
  missing token across the kill boundary (tokens AND logprobs).
- FAILOVER E2E: a replica killed (crash / silent drop / hung
  dispatch) mid-stream hands its live requests to a surviving
  replica; the client's SSE stream stays bitwise the no-failure
  stream (the ``_fail_all``-hardening satellite: the bare 500 is gone
  when survivors exist).
- BUDGET: ``failover_budget`` caps resubmissions (counted in
  ``gateway_retry_budget_exhausted_total``), and a DRAINING replica
  never accepts failover traffic.
- CHAOS (slow): the ``serve_loadgen --chaos`` harness — 3-replica
  gateway, seeded mid-run kills — finishes with zero corrupted
  streams and errors within the retry-budget bound.

Everything tier-1 runs the negligible-compute stub with sub-second
watchdog/breaker knobs; the open-loop chaos sweep rides behind
``slow`` (``tools/marker_audit.py`` chaos patterns).
"""
import asyncio
import time

import pytest

from paddle_tpu.serving import (CircuitBreaker, Gateway,
                                PrefixAffinityRouter, ServeRequest)
from paddle_tpu.serving.supervisor import (BREAKER_CLOSED, BREAKER_OPEN,
                                           BREAKER_HALF_OPEN)

from test_gateway import _engine, _http, _load_loadgen, _poll, _sse

PROMPT = list(range(1, 13))


def _direct(prompt=PROMPT, max_new=24, **kw):
    eng = _engine()
    eng.submit("ref", [prompt], max_new_tokens=max_new, **kw)
    eng.run()
    return eng.results["ref"], eng.logprobs["ref"]


# ================================================================= breaker
def test_breaker_state_machine():
    t = [0.0]
    states = []
    b = CircuitBreaker(probes_to_close=2, backoff_s=1.0,
                       backoff_factor=2.0, on_state=states.append,
                       clock=lambda: t[0])
    assert b.state == BREAKER_CLOSED
    b.record_failure()
    assert b.state == BREAKER_OPEN
    assert not b.try_probe()            # backoff (1.0s) not elapsed
    t[0] = 1.1
    assert b.try_probe()                # promotes half-open + claims slot
    assert b.state == BREAKER_HALF_OPEN
    assert not b.try_probe()            # AT MOST one probe in flight
    b.probe_done(True)
    assert b.state == BREAKER_HALF_OPEN  # needs 2 successes
    assert b.try_probe()
    b.probe_done(False)                 # failed probe reopens...
    assert b.state == BREAKER_OPEN
    t[0] = 2.5
    assert not b.try_probe()            # ...with DOUBLED backoff (2.0s)
    t[0] = 3.2
    assert b.try_probe()
    b.probe_done(None)                  # inconclusive: slot released,
    assert b.state == BREAKER_HALF_OPEN  # state unchanged
    assert b.try_probe()
    b.probe_done(True)
    assert b.try_probe()
    b.probe_done(True)                  # 2nd success closes
    assert b.state == BREAKER_CLOSED
    assert b.snapshot()["opens"] == 0   # reset for the next episode
    assert states == [BREAKER_OPEN, BREAKER_HALF_OPEN, BREAKER_OPEN,
                      BREAKER_HALF_OPEN, BREAKER_CLOSED]


def test_breaker_rearm_defers_probation():
    """The supervisor re-arms after a slow rebuild: the probation
    window must not open while the replica is still being rebuilt."""
    t = [0.0]
    b = CircuitBreaker(backoff_s=0.1, clock=lambda: t[0])
    b.record_failure()
    t[0] = 0.5                          # rebuild finished late
    b.rearm()
    assert not b.try_probe()            # backoff restarted from 0.5
    t[0] = 0.65
    assert b.try_probe()


# ================================================================== router
class _FakeReplica:
    def __init__(self, name, load=0.0):
        self.name, self._load, self._healthy = name, load, True
        self.breaker = None

    def healthy(self):
        return self._healthy

    def mark(self, h):
        self._healthy = h

    def has_prefix(self, d):
        return False

    def load(self):
        return self._load


def test_router_evict_probe_rejoin():
    """Satellite pin: eviction is no longer one-way — the breaker
    folds into the ladder as evict -> probe -> rejoin, and a half-open
    replica receives at most ONE probe request at a time."""
    t = [0.0]
    a, b = _FakeReplica("a"), _FakeReplica("b", load=5)
    a.breaker = CircuitBreaker(backoff_s=1.0, clock=lambda: t[0],
                               on_state=lambda s:
                               a.mark(s == BREAKER_CLOSED))
    r = PrefixAffinityRouter([a, b], labels={"gateway": "t-rejoin"})
    assert r.route(None) is a           # least loaded, both healthy
    a.breaker.record_failure()          # replica failed: evicted
    assert not a.healthy()
    assert r.route(None) is b           # out of rotation
    t[0] = 1.5                          # backoff elapsed
    assert r.route(None) is a           # the ONE probation probe
    assert r.route(None) is b           # probe in flight: ladder only
    assert r.route(None, allow_probe=False) is b   # gateway race-retry
    a.breaker.probe_done(True)          # probe succeeded: rejoined
    assert a.healthy()
    assert r.route(None) is a           # back in the ladder
    assert r.snapshot()["breakers"] == {"a": BREAKER_CLOSED}


# ============================================================ engine resume
def test_export_resumable_resume_offset_bitwise():
    """Resume pin: committed tokens exported off a mid-stream engine
    and resubmitted as prompt + committed continue the greedy stream
    BITWISE — the boundary duplicates nothing and drops nothing,
    tokens and logprobs both."""
    full, full_lps = _direct(max_new=16)
    eng = _engine()
    eng.submit("a", [PROMPT], max_new_tokens=16,
               stop_sequences=[[9, 9, 9]])
    for _ in range(7):                  # mid-stream (ring drains lag 1)
        eng.step()
    desc = eng.export_resumable()["a"]
    committed = desc["committed"]
    assert 0 < len(committed) < 16
    assert committed == full[:len(committed)]     # prefix-exact so far
    eng2 = _engine()
    eng2.submit("a", [desc["prompt"]],
                max_new_tokens=desc["remaining"],
                stop_sequences=desc["stop"],
                resume_tokens=desc["committed"],
                resume_lps=desc["committed_lps"])
    eng2.run()
    assert eng2.results["a"] == full              # no dup, no gap
    assert eng2.logprobs["a"] == pytest.approx(full_lps)


def test_export_resumable_rejects_non_tail_resume():
    eng = _engine()
    with pytest.raises(ValueError, match="tail of input_ids"):
        eng.submit("x", [PROMPT], max_new_tokens=4,
                   resume_tokens=[999])


def test_hard_reset_engine_reusable():
    """The supervisor's rebuild-in-place: after hard_reset a mid-run
    engine is empty (all blocks free, no slots/queue) and serves the
    same request bitwise like a fresh engine — compiled executables
    survive, state does not."""
    eng = _engine()
    eng.submit("a", [PROMPT], max_new_tokens=6)
    ref = dict(eng.run())["a"]
    eng.submit("b", [list(range(20, 29))], max_new_tokens=50)
    for _ in range(4):
        eng.step()                      # mid-flight state to destroy
    eng.hard_reset()
    h = eng.health()
    assert h["active_slots"] == 0 and h["queued"] == 0
    assert h["free_blocks"] == eng.P - 1
    assert eng.results == {} and not eng.prefix_cache
    eng.submit("c", [PROMPT], max_new_tokens=6)
    assert eng.run()["c"] == ref


# ============================================================ failover e2e
def _warm_engine():
    """Compile-before-traffic: a cold engine's first step pays the
    executable build — far over the sub-second test watchdog deadline
    — so every fleet engine serves one request before it can take
    watched traffic (what a real fleet's readiness probe guarantees;
    the chaos loadgen's factory does the same). The warm-up prompt is
    two chunks long, as the tests' is: the program of a chunk with
    cached context behind it is built here too, not under the watchdog
    (other tokens than the tests' prompt, so nothing of it is adopted)."""
    e = _engine()
    e.submit("warmup", [list(range(101, 113))], max_new_tokens=4)
    e.run()
    e.results.pop("warmup", None)
    e.logprobs.pop("warmup", None)
    return e


def _fleet_gw(n=2, name="t-fo", **kw):
    # 1s watchdog: far above a warmed stub step (~ms) even on a
    # contended full-suite CPU, far below the test budget
    base = dict(watchdog_timeout_s=1.0, watchdog_interval_s=0.02,
                breaker_backoff_s=0.05, name=name)
    base.update(kw)
    return Gateway([_warm_engine() for _ in range(n)], **base)


async def _kill_serving(gw, kind):
    w = next(w for w in gw._workers if w._live)
    w.inject_fault(kind)
    return w.replica.name


@pytest.mark.parametrize("kind", ["crash", "drop", "hang"])
def test_failover_stream_bitwise_vs_uninterrupted(kind, monkeypatch):
    """Acceptance pin: a replica killed mid-stream (tick crash, silent
    thread drop, or hung dispatch caught by the watchdog) hands its
    live request to the surviving replica and the client's SSE stream
    stays BITWISE the uninterrupted reference — tokens, final token
    list and logprobs. Also the ``_fail_all`` hardening satellite: no
    bare 500 when survivors exist."""
    monkeypatch.setenv("PADDLE_TPU_FAULT_DISPATCH_HANG_S", "2.5")
    killed = {}

    async def run():
        gw = _fleet_gw(name=f"t-fo-{kind}")
        await gw.start()
        try:
            async def kill():
                killed["replica"] = await _kill_serving(gw, kind)

            st, _, toks, fin = await _sse(
                gw.port, dict(prompt=PROMPT, max_new_tokens=24),
                on_first=kill)
        finally:
            await gw.drain()
        return st, toks, fin, gw.health(), gw.debugz()

    st, toks, fin, health, dbz = asyncio.run(run())
    direct, direct_lps = _direct()
    assert st == 200 and fin["finish_reason"] == "stop"
    assert toks == direct, f"{kind}: streamed tokens diverged"
    assert fin["tokens"] == direct
    assert fin["logprobs"] == pytest.approx(direct_lps)
    assert health["failovers"] >= 1
    assert health["retry_budget_exhausted"] == 0
    assert "replica" in killed
    if kind == "hang":
        assert dbz["supervisor"]["watchdog_fires"] >= 1


def test_breaker_rejoins_replica_after_crash():
    """Evict -> probe -> rejoin, end to end: after a crash the replica
    is out of rotation (breaker open), the supervisor rebuilds it, a
    later request probes it, and the fleet is back to full strength —
    permanent eviction is gone."""
    async def run():
        gw = _fleet_gw(name="t-rejoin-e2e")
        await gw.start()
        try:
            st, _, toks, fin = await _sse(
                gw.port, dict(prompt=PROMPT, max_new_tokens=16),
                on_first=lambda: _kill_serving(gw, "crash"))
            assert st == 200 and fin["finish_reason"] == "stop"

            async def recovered():
                # traffic drives the probe: keep sending until the
                # probe lands and the breaker closes (a request racing
                # the rebuild may error — that's what the NEXT one is
                # for, so don't assert on individual outcomes)
                st2, _, _, fin2 = await _sse(
                    gw.port, dict(prompt=PROMPT, max_new_tokens=2))
                if st2 != 200 or (fin2 or {}).get(
                        "finish_reason") != "stop":
                    return False
                snap = gw.health()["router"]
                return snap["replicas_up"] == 2 and all(
                    s == BREAKER_CLOSED
                    for s in snap.get("breakers", {}).values())

            ok = False
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not ok:
                ok = await recovered()
                await asyncio.sleep(0.05)
            return ok, gw.health()
        finally:
            await gw.drain()

    ok, health = asyncio.run(run())
    assert ok, "crashed replica never rejoined rotation"
    assert health["router"]["replicas_up"] == 2


def test_retry_budget_exhaustion_errors_cleanly():
    """Budget pin: ``failover_budget=0`` turns the first failover into
    a clean client error (no retry storm, counter incremented) while
    the fleet itself recovers."""
    async def run():
        gw = _fleet_gw(name="t-budget", failover_budget=0)
        await gw.start()
        try:
            st, _, toks, fin = await _sse(
                gw.port, dict(prompt=PROMPT, max_new_tokens=24),
                on_first=lambda: _kill_serving(gw, "crash"))
        finally:
            await gw.drain()
        return st, fin, gw.health()

    st, fin, health = asyncio.run(run())
    assert st == 200 and fin.get("error")
    assert "budget" in fin["error"]
    assert health["retry_budget_exhausted"] == 1
    assert health["failovers"] == 0


def test_draining_replica_never_accepts_failover():
    """Drain/breaker composition satellite: failover target selection
    skips draining replicas — SIGTERM drain composes with an open
    breaker instead of dumping failed traffic onto an exiting
    worker."""
    gw = Gateway([_engine(), _engine()], name="t-drainfo")
    w1, w2 = gw._workers
    for w in (w1, w2):                  # threads never started: fake
        w.is_alive = lambda: True       # liveness for the filter
    req = ServeRequest("r1", PROMPT, {"max_new_tokens": 4})
    req.owner = w1
    w2.draining = True
    gw._resubmit(req, None, w1)
    assert w2.sched.depth() == 0        # draining survivor refused it
    assert int(gw._c_failovers.value) == 0
    req2 = ServeRequest("r2", PROMPT, {"max_new_tokens": 4})
    req2.owner = w1
    w2.draining = False
    gw._resubmit(req2, None, w1)
    assert w2.sched.depth() == 1        # healthy survivor takes it
    assert int(gw._c_failovers.value) == 1


def test_failover_trace_events_and_retention():
    """Reqtrace satellite: a failed-over request's ring entry carries
    the typed failure events (replica_fail, resubmit, resume_offset,
    breaker_open) with ``failovers`` counted top-level, and is
    RETAINED even though it finished fast and clean."""
    async def run():
        gw = _fleet_gw(name="t-fo-trace")
        await gw.start()
        try:
            st, _, _, fin = await _sse(
                gw.port, dict(prompt=PROMPT, max_new_tokens=16,
                              request_id="fo-req"),
                on_first=lambda: _kill_serving(gw, "crash"))
            assert st == 200 and fin["finish_reason"] == "stop"
            await _poll(lambda: any(
                e["request_id"] == "fo-req"
                for w in gw._workers if w.ring is not None
                for e in w.ring.snapshot()))
            entries = [e for w in gw._workers if w.ring is not None
                       for e in w.ring.snapshot()
                       if e["request_id"] == "fo-req"]
        finally:
            await gw.drain()
        return entries

    entries = asyncio.run(run())
    assert len(entries) == 1
    e = entries[0]
    assert e["outcome"] == "stop"
    assert e["failovers"] == 1
    assert e["retained"] and e["events"]
    kinds = [k for _, k, _ in e["events"]]
    for k in ("replica_fail", "breaker_open", "resubmit",
              "resume_offset"):
        assert k in kinds, f"missing {k} in {kinds}"
    ro = next(f for _, k, f in e["events"] if k == "resume_offset")
    assert ro["committed"] >= ro["offset"] >= 0


def test_debugz_exposes_breaker_and_supervisor():
    async def run():
        gw = _fleet_gw(name="t-fo-dbz")
        await gw.start()
        try:
            st, _, _, fin = await _sse(
                gw.port, dict(prompt=PROMPT, max_new_tokens=16),
                on_first=lambda: _kill_serving(gw, "crash"))
            assert st == 200 and fin["finish_reason"] == "stop"
            import json
            st2, _, payload = await _http(gw.port, "GET", "/debugz")
            return st2, json.loads(payload)
        finally:
            await gw.drain()

    st, dbz = asyncio.run(run())
    assert st == 200
    assert dbz["failover_budget"] == 2 and dbz["failovers"] >= 1
    assert dbz["supervisor"]["alive"]
    states = {r["breaker"]["state"] for r in dbz["replicas"].values()
              if r["breaker"] is not None}
    assert states & {BREAKER_CLOSED, BREAKER_OPEN, BREAKER_HALF_OPEN}


def test_expired_probe_releases_breaker_slot():
    """Regression: a probation probe that dies in the scheduler queue
    (expiry / queue flush) must still report to the breaker — a leaked
    probe slot would freeze the replica half-open forever (the silent
    one-way eviction this PR removes)."""
    gw = Gateway([_engine()], name="t-probeleak", supervise=True)
    w = gw._workers[0]
    b = CircuitBreaker(backoff_s=0.0)
    w.replica.breaker = b
    b.record_failure()
    assert b.try_probe()                    # the slot our probe holds
    req = ServeRequest("p1", PROMPT, {"max_new_tokens": 2},
                       deadline=time.monotonic() - 1.0)
    req.probe = True
    w.sched.enqueue(req)
    w.flush_queue(503, "dead worker")       # reaps the expired probe
    assert not b.snapshot()["probe_inflight"]
    assert b.try_probe()                    # slot reusable again


# ================================================================== chaos
def _chaos_ns(**kw):
    import types
    base = dict(requests=24, rate=60.0, share_frac=0.5, sys_tokens=8,
                tail_tokens=4, max_new=8, interactive_frac=0.7,
                ttft_slo_ms=5000.0, timeout_s=60.0, tenants=2,
                replicas=3, policy="prefix", max_queue=256,
                model="stub", seed=0, url=None, out="",
                chaos=True, chaos_kills=2, chaos_mode="mix",
                failover_budget=2, watchdog_timeout_s=0.5,
                goodput_floor=0.95)
    base.update(kw)
    return types.SimpleNamespace(**base)


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_loadgen_zero_corruption():
    """The ISSUE 12 acceptance run: 3-replica gateway under open-loop
    load with >=2 seeded mid-run replica kills (crash + hung
    dispatch). Every finished greedy stream must replay bitwise
    against a fresh reference engine, errors must stay within the
    retry-budget bound (kills <= budget ==> zero 5xx), and the
    completed fraction must clear the goodput floor — across seeds."""
    slg = _load_loadgen()
    for seed in (0, 3):
        rung = asyncio.run(slg.run_loadgen(_chaos_ns(seed=seed)))
        ch = rung["chaos"]
        assert ch["kills"] == 2
        assert ch["corrupted_streams"] == 0, ch
        assert ch["errors_5xx"] == 0, ch
        assert ch["failovers"] >= 1
        assert ch["completed_frac"] >= 0.95
        assert ch["ok"], ch
