"""ISSUE 9: continuous-batching serving gateway over PagedEngine.

Contracts pinned here:

- STREAM PARITY: gateway SSE token streams are BIT-IDENTICAL to direct
  ``PagedEngine`` streams for the same requests/seeds (the gateway's
  dispatch mirrors ``stream()``'s stop hold-back, so a yielded token is
  never retracted).
- SCHEDULING: interactive beats batch, EDF within class, queue-age
  promotion un-starves batch, per-tenant fair share interleaves, and a
  queued request whose deadline expired is cancelled (timeouts counter)
  BEFORE it ever takes a slot.
- ROUTING: prefix-affinity routes same-digest requests to the replica
  holding the warm blocks (router-key == prefix-cache-key, pinned),
  with least-loaded fallback and health eviction; affinity measurably
  raises ``prefix_hit_tokens`` over round-robin on a shared-system-
  prompt workload.
- THE LOOP'S HALF OF A ROUND (ISSUE 35): behind a profiled engine
  every streamed token is counted once in ``health()["stream"]``, its
  way from the tick thread's push to the socket and the loop's own
  stretch are summed (they grow with an injected ``stream_stall``),
  ``/metrics`` holds the same sums, and with the profiler off the token
  event, the SSE bytes and the counters are what they were.
- ONE HAND-OVER A TICK (ISSUE 36): what a tick made crosses to the
  event loop in one ``call_soon_threadsafe`` whatever the rows, an emit
  outside a tick reaches its client without one, a request's order
  (tokens, then ``done``) holds across hand-overs and beside a stalled
  sibling, and ``stream_batches`` / ``stream_batch_events`` count the
  hand-overs and their events with the profiler on or off.
- LIFECYCLE: SIGTERM drains (finish in-flight, 503 new work, flush
  metrics); an SSE client dropping mid-stream frees its slot/blocks
  via ``PagedEngine.cancel`` (no stranded slots); saturation sheds
  with 429 + Retry-After.

Everything runs the negligible-compute stub CausalLM so these tests
measure the serving machinery, not model FLOPs; full open-loop sweeps
and the subprocess loadgen CLI e2e ride behind ``slow`` (see
``tools/marker_audit.py``).
"""
import asyncio
import importlib.util
import json
import os
import signal
import time
import types

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.generation.paged import PagedEngine
from paddle_tpu.serving import (Gateway, NoReplicaError,
                                PrefixAffinityRouter, ServeRequest,
                                ShedError, SLOScheduler)
from paddle_tpu.utils import observability as obs
from paddle_tpu.utils.shutdown import GracefulShutdown


# --------------------------------------------------------------- stub model
# the shared reference stub: negligible compute, so these tests time
# the serving machinery itself; one copy serves tests AND the loadgen
from paddle_tpu.generation.stub import TickStubModel as StubModel  # noqa: E402


def _engine(**kw):
    base = dict(max_slots=4, num_blocks=64, block_size=8,
                max_blocks_per_seq=8, prefill_buckets=(16,),
                chunk_prefill_tokens=8, enable_prefix_cache=True)
    base.update(kw)
    return PagedEngine(StubModel(), **base)


# ------------------------------------------------------------- HTTP client
async def _http(port, method, path, body=b""):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write((f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode()
                     + body)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        headers = {}
        while True:
            ln = await reader.readline()
            if ln in (b"\r\n", b"\n", b""):
                break
            k, _, v = ln.decode().partition(":")
            headers[k.strip().lower()] = v.strip()
        n = int(headers.get("content-length", "0") or 0)
        payload = await reader.readexactly(n) if n else b""
        return status, headers, payload
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except Exception:
            pass


async def _sse(port, payload, break_after=None, on_first=None):
    """SSE request; returns (status, headers, tokens, final_event).
    ``break_after=N``: abruptly close the connection after N tokens
    (the disconnect test). ``on_first``: awaited callback after the
    first token arrives."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps(payload).encode()
    try:
        writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode()
                     + body)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        headers = {}
        while True:
            ln = await reader.readline()
            if ln in (b"\r\n", b"\n", b""):
                break
            k, _, v = ln.decode().partition(":")
            headers[k.strip().lower()] = v.strip()
        if status != 200:
            n = int(headers.get("content-length", "0") or 0)
            extra = await reader.readexactly(n) if n else b""
            return status, headers, [], (json.loads(extra)
                                         if extra else None)
        toks, final = [], None
        while True:
            ln = await reader.readline()
            if not ln:
                break
            ln = ln.strip()
            if not ln.startswith(b"data: "):
                continue
            ev = json.loads(ln[6:])
            if ev.get("done"):
                final = ev
                break
            toks.append(ev["token"])
            if len(toks) == 1 and on_first is not None:
                await on_first()
            if break_after is not None and len(toks) >= break_after:
                break
        return status, headers, toks, final
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except Exception:
            pass


async def _poll(cond, timeout=10.0, every=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        await asyncio.sleep(every)
    return False


# ============================================================ prefix digest
def test_prefix_digest_matches_cache_key():
    """Satellite pin: router key == prefix-cache key, byte for byte."""
    eng = _engine()
    prompt = list(range(1, 25))         # 24 tokens, chunk grid = 8
    d = eng.prefix_digest(prompt)
    assert isinstance(d, str) and len(d) == 64
    # the longest span the cache could file for this prompt is the same
    # one prefix_digest reports: k*8 <= 23 -> [0, 16)
    assert bytes.fromhex(d) == eng._chunk_digests(prompt, 23)[-1]
    assert not eng.has_prefix(d)        # nothing cached yet
    eng.submit("a", np.asarray([prompt], np.int32), max_new_tokens=2)
    eng.run()
    assert eng.has_prefix(d)            # the span is now warm
    assert bytes.fromhex(d) in eng.prefix_cache
    # deterministic across engines with the same chunk grid (what makes
    # it a ROUTING key), and invariant to the unique tail
    assert _engine().prefix_digest(prompt) == d
    assert _engine().prefix_digest(prompt[:16] + [99, 98, 97]) == d
    # short prompts have no grid-aligned span
    assert eng.prefix_digest([1, 2, 3]) == ""
    # the full CHAIN: every span digest is itself a live cache key
    # after the prompt cached (what lets the router probe a request
    # whose unique tail crosses a chunk boundary)
    chain = eng.prefix_digests(prompt, max_tokens=len(prompt))
    assert len(chain) == 3 and chain[-1] != d   # spans 8, 16, 24
    for hx in chain:
        assert bytes.fromhex(hx) in eng.prefix_cache
    # a boundary-crossing tail shares the head of the chain only
    other = eng.prefix_digests(prompt[:16] + list(range(200, 212)))
    assert other[:2] == chain[:2] and other[2] != chain[2]


def test_prefix_digest_requires_chunk():
    eng = PagedEngine(StubModel(), max_slots=2, num_blocks=16,
                      block_size=8, max_blocks_per_seq=4,
                      prefill_buckets=(16,))
    with pytest.raises(ValueError, match="chunk_prefill_tokens"):
        eng.prefix_digest(list(range(20)))


# ================================================================ scheduler
def _req(rid, slo="interactive", tenant="t", priority=0, deadline=None):
    return ServeRequest(rid, [1, 2, 3], {"max_new_tokens": 4}, slo=slo,
                        tenant=tenant, priority=priority,
                        deadline=deadline)


def test_scheduler_slo_classes_fair_share_priority():
    s = SLOScheduler(max_queue=16, promote_after_ms=60_000,
                     labels={"gateway": "t-slo"})
    s.enqueue(_req("b1", slo="batch", tenant="A"))
    s.enqueue(_req("b2", slo="batch", tenant="A"))
    s.enqueue(_req("b3", slo="batch", tenant="B"))
    s.enqueue(_req("i1", slo="interactive", tenant="A"))
    s.enqueue(_req("hi", slo="interactive", tenant="A", priority=5))
    # interactive first; priority beats EDF within the tenant
    assert s.pop().request_id == "hi"
    assert s.pop().request_id == "i1"
    # batch drains fair-share across tenants: A served twice already,
    # so B goes first, then A FIFO
    assert s.pop().request_id == "b3"
    assert s.pop().request_id == "b1"
    assert s.pop().request_id == "b2"
    assert s.pop() is None


def test_scheduler_queue_age_promotion():
    s = SLOScheduler(max_queue=16, promote_after_ms=30.0,
                     interactive_ttft_ms=500.0,
                     labels={"gateway": "t-promote"})
    s.enqueue(_req("old-batch", slo="batch"))
    time.sleep(0.05)                    # past the promotion age
    s.enqueue(_req("fresh-inter", slo="interactive"))
    # the promoted batch request's EDF deadline is already in the past,
    # so it beats the fresh interactive one: starvation-free
    pick = s.pop()
    assert pick.request_id == "old-batch" and pick.promoted
    assert s.snapshot()["promotions"] == 1
    assert s.pop().request_id == "fresh-inter"


def test_scheduler_sheds_on_depth_and_engine_backpressure():
    s = SLOScheduler(max_queue=1, labels={"gateway": "t-shed"})
    s.enqueue(_req("a"))
    with pytest.raises(ShedError) as ei:
        s.enqueue(_req("b"))
    assert ei.value.retry_after_s > 0
    # engine-side saturation reuses PagedEngine.health()'s own
    # backpressure fields verbatim
    s2 = SLOScheduler(max_queue=16, labels={"gateway": "t-shed2"})
    with pytest.raises(ShedError):
        s2.enqueue(_req("c"),
                   engine_health={"queued": 8, "queue_capacity": 8})
    assert s.snapshot()["shed"] == 1 and s2.snapshot()["shed"] == 1


def test_expired_queued_request_cancelled_before_slot():
    """Satellite: the deadline threads from submission through the
    scheduler, and an expired QUEUED request is reaped (timeouts
    counter) without ever reaching pop()."""
    s = SLOScheduler(max_queue=16, labels={"gateway": "t-exp"})
    s.enqueue(_req("dead", deadline=time.monotonic() - 0.1))
    s.enqueue(_req("live", deadline=time.monotonic() + 60.0))
    reaped = s.reap()
    assert [r.request_id for r in reaped] == ["dead"]
    assert s.snapshot()["timeouts"] == 1
    assert s.pop().request_id == "live"
    assert s.pop() is None


# =================================================================== router
class _FakeReplica:
    def __init__(self, name, warm=(), load=0.0, healthy=True):
        self.name, self._warm = name, set(warm)
        self._load, self._healthy = load, healthy
        self.engine = None

    def healthy(self):
        return self._healthy

    def mark(self, h):
        self._healthy = h

    def has_prefix(self, d):
        return d in self._warm

    def load(self):
        return self._load


def test_router_prefix_affinity_sticky_and_spill():
    a = _FakeReplica("a", warm={"d1"}, load=1)
    b = _FakeReplica("b", load=0)
    r = PrefixAffinityRouter([a, b], spill_margin=4,
                             labels={"gateway": "t-router"})
    assert r.route("d1") is a           # warm wins over lighter load
    assert r.route(None) is b           # no digest: least loaded
    assert r.route("d2") is b           # miss: least loaded + sticky
    b._load = 3
    assert r.route("d2") is b           # sticky holds within the margin
    a._load = 99
    assert r.route("d1") is b           # warm overload spills
    snap = r.snapshot()
    assert snap["prefix_route_hits"] == 2
    assert snap["prefix_route_misses"] == 2   # d2 miss + d1 spill


def test_router_probes_digest_chain_longest_first():
    """A unique tail crossing a chunk boundary changes the LONGEST
    digest; the router must still find the replica warm on the shared
    shorter span (and prefer the longest warm span when both hit)."""
    a = _FakeReplica("a", warm={"shared"}, load=1)
    b = _FakeReplica("b", warm={"longer", "shared"}, load=1)
    c = _FakeReplica("c", load=0)
    r = PrefixAffinityRouter([a, b, c], labels={"gateway": "t-chain"})
    # longest span "uniq" is cold everywhere; "shared" is warm on a
    assert r.route(["uniq", "shared"]) is a
    # longest warm span wins over a shorter one warm elsewhere
    assert r.route(["longer", "shared"]) is b
    # full miss remembers ALL spans: a later sibling sharing only the
    # short span follows the sticky choice
    assert r.route(["x2", "x1"]) is c
    assert r.route(["y2", "x1"]) is c
    snap = r.snapshot()
    assert snap["prefix_route_hits"] == 3 and \
        snap["prefix_route_misses"] == 1


def test_router_health_eviction():
    a = _FakeReplica("a", warm={"d"}, load=0)
    b = _FakeReplica("b", load=5)
    r = PrefixAffinityRouter([a, b], labels={"gateway": "t-evict"})
    assert r.route("d") is a
    a.mark(False)
    assert r.route("d") is b            # evicted from consideration
    r.evict_unhealthy()
    assert r.snapshot()["sticky_entries"] == 1   # only d->b survives
    b.mark(False)
    with pytest.raises(NoReplicaError):
        r.route(None)


def test_router_round_robin_policy():
    a, b = _FakeReplica("a"), _FakeReplica("b")
    r = PrefixAffinityRouter([a, b], policy="round_robin",
                             labels={"gateway": "t-rr"})
    assert [r.route("d") for _ in range(4)] == [a, b, a, b]


# ============================================================== gateway e2e
def test_gateway_sse_streams_match_direct_engine():
    """Acceptance: concurrent SSE streams are bit-identical to direct
    PagedEngine streams for the same requests (greedy, seeded
    sampling, and stop-sequence trimming)."""
    reqs = [
        dict(prompt=list(range(1, 13)), max_new_tokens=8),
        dict(prompt=[5, 9, 2, 7, 7, 1, 3, 8, 4], max_new_tokens=10,
             temperature=0.9, top_k=20, seed=7),
        dict(prompt=list(range(40, 52)), max_new_tokens=12,
             stop=[[0]]),
        dict(prompt=[3, 1, 4, 1, 5, 9, 2, 6], max_new_tokens=5),
    ]

    async def gateway_run():
        gw = Gateway(_engine(), name="t-parity")
        await gw.start()
        try:
            outs = await asyncio.gather(
                *[_sse(gw.port, dict(r, stream=True)) for r in reqs])
        finally:
            await gw.drain()
        return outs

    outs = asyncio.run(gateway_run())
    eng = _engine()
    for i, r in enumerate(reqs):
        kw = {k: v for k, v in r.items()
              if k not in ("prompt", "stop")}
        if "stop" in r:
            kw["stop_sequences"] = r["stop"]
        eng.submit(i, np.asarray([r["prompt"]], np.int32), **kw)
    direct = eng.run()
    for i, (status, _, toks, fin) in enumerate(outs):
        assert status == 200
        assert fin["finish_reason"] == "stop"
        assert toks == direct[i], f"request {i} streamed tokens differ"
        assert fin["tokens"] == direct[i]
        assert fin["logprobs"] == pytest.approx(eng.logprobs[i])


def test_gateway_nonstream_healthz_metrics_pinned():
    async def run():
        gw = Gateway(_engine(), name="t-pin")
        await gw.start()
        try:
            body = json.dumps(dict(prompt=list(range(1, 10)),
                                   max_new_tokens=6,
                                   stream=False)).encode()
            st, _, payload = await _http(gw.port, "POST",
                                         "/v1/generate", body)
            resp = json.loads(payload)
            st2, _, hz = await _http(gw.port, "GET", "/healthz")
            st3, _, prom = await _http(gw.port, "GET", "/metrics")
        finally:
            await gw.drain()
        return st, resp, st2, json.loads(hz), st3, prom.decode()

    st, resp, st2, health, st3, prom = asyncio.run(run())
    assert st == 200 and st2 == 200 and st3 == 200
    assert len(resp["tokens"]) == 6 and resp["finish_reason"] == "stop"
    assert health["completed"] == 1 and health["tokens"] == 6
    # health() and the /metrics scrape read the SAME registry objects
    line = next(ln for ln in prom.splitlines()
                if ln.startswith('gateway_tokens_total{')
                and 'gateway="t-pin"' in ln)
    assert float(line.split()[-1]) == health["tokens"]
    assert health["replicas"]["r0"]["engine"]["prefills"] == 1
    assert 'gateway_ttft_ms_bucket' in prom


async def _raw_sse(port, payload):
    """(the response's bytes after the HTTP head, as they came; when its
    first event had arrived, on the monotonic clock)."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps(payload).encode()
    writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    await writer.drain()
    raw = await reader.readuntil(b"\n\n")      # the head and one event
    first = time.monotonic()
    raw += await reader.read()
    writer.close()
    return raw.partition(b"\r\n\r\n")[2], first


STREAM_REQS = [
    dict(prompt=list(range(1, 13)), max_new_tokens=8),
    dict(prompt=[5, 9, 2, 7, 7, 1, 3, 8, 4], max_new_tokens=10,
         temperature=0.9, top_k=20, seed=7),
    dict(prompt=[3, 1, 4, 1, 5, 9, 2, 6], max_new_tokens=5),
]


def _stream_run(name, stall_s=None, stall="stream_stall@0+", **engine_kw):
    """STREAM_REQS through a gateway over real HTTP: the raw SSE bodies,
    ``health()`` and the ``/metrics`` text after them, and when each
    body's first token arrived and when the body ended (seconds after
    the requests were sent)."""
    async def run():
        gw = Gateway(_engine(**engine_kw), name=name)
        await gw.start()
        first, ended = {}, {}
        t0 = time.monotonic()

        async def one(i, r):
            raw, arrived = await _raw_sse(gw.port, dict(r, stream=True))
            first[i], ended[i] = arrived - t0, time.monotonic() - t0
            return raw
        try:
            raws = await asyncio.gather(
                *[one(i, r) for i, r in enumerate(STREAM_REQS)])
            _, _, prom = await _http(gw.port, "GET", "/metrics")
            health = dict(gw.health(), first=first, ended=ended)
            return raws, health, prom.decode()
        finally:
            await gw.drain()
    if stall_s is None:
        return asyncio.run(run())
    from paddle_tpu.utils import faults
    os.environ[faults.STREAM_STALL_ENV_VAR] = str(stall_s)
    try:
        with faults.scoped(stall):
            return asyncio.run(run())
    finally:
        del os.environ[faults.STREAM_STALL_ENV_VAR]


TIMED_KEYS = {"stream_tokens", "emit_to_wire_us", "loop_write_us",
              "event_loop_cpu_us"}


def _prom_value(prom, name, gateway):
    line = next(ln for ln in prom.splitlines()
                if ln.startswith(name + "{") and f'gateway="{gateway}"' in ln)
    return float(line.split()[-1])


@pytest.mark.parametrize("stall_s", [None, 0.02], ids=["plain", "stalled"])
def test_stream_times_count_each_token_once_and_grow_with_a_stall(stall_s):
    name = f"t-wire-{stall_s}"
    raws, health, prom = _stream_run(name, stall_s, tick_profile=True)
    tokens = sum(r["max_new_tokens"] for r in STREAM_REQS)
    assert sum(raw.count(b'data: {"token"') for raw in raws) == tokens
    st = health["stream"]
    assert set(st) == TIMED_KEYS | {"stream_batches", "stream_batch_events"}
    assert all(isinstance(v, int) for v in st.values())
    assert st["stream_tokens"] == tokens == health["tokens"]
    # a token is pushed before the loop takes it up
    assert st["emit_to_wire_us"] >= st["loop_write_us"] > 0
    assert st["event_loop_cpu_us"] > 0
    if stall_s is None:
        assert st["loop_write_us"] < tokens * 0.02e6
    else:           # the stall lies between the dequeue and the write
        assert st["loop_write_us"] >= tokens * stall_s * 1e6
        assert st["emit_to_wire_us"] >= tokens * stall_s * 1e6
    # health() and the scrape hold the same sums
    assert _prom_value(prom, "gateway_emit_to_wire_ms_count", name) \
        == st["stream_tokens"]
    assert _prom_value(prom, "gateway_emit_to_wire_ms_sum", name) \
        == pytest.approx(st["emit_to_wire_us"] / 1e3, abs=0.01 * tokens)
    assert "gateway_emit_to_wire_ms_bucket" in prom


def test_profile_off_the_token_event_the_bytes_and_the_counters_are_the_parents():
    raws_off, health, prom = _stream_run("t-wire-off")
    raws_on, _, _ = _stream_run("t-wire-on", tick_profile=True)
    assert raws_on == raws_off          # the stamp never reaches the wire
    assert {health["stream"][k] for k in TIMED_KEYS} == {0}
    assert _prom_value(prom, "gateway_emit_to_wire_ms_count",
                       "t-wire-off") == 0
    # the bytes, spelt out: what the parent's writer wrote
    eng = _engine()
    for i, r in enumerate(STREAM_REQS):
        eng.submit(i, np.asarray([r["prompt"]], np.int32),
                   **{k: v for k, v in r.items() if k != "prompt"})
    direct = eng.run()
    for i, raw in enumerate(raws_off):
        want = b"".join(
            b"data: " + json.dumps({"token": int(t), "lp": float(lp)}
                                   ).encode() + b"\n\n"
            for t, lp in zip(direct[i], eng.logprobs[i]))
        assert raw.startswith(want)
        assert json.loads(raw[len(want):][6:])["done"] is True
    # and the event that crosses the threads
    for profiled, width in ((False, 3), (True, 4)):
        gw = Gateway(_engine(tick_profile=profiled),
                     name=f"t-wire-ev-{profiled}")
        worker, got = gw._workers[0], []
        worker._emit = lambda req, ev: got.append(ev)
        t0 = time.perf_counter()
        worker._token_out(_req("a"), 5, time.monotonic(), lp=-0.5)
        assert got[0][:3] == ("token", 5, -0.5) and len(got[0]) == width
        if profiled:
            assert t0 <= got[0][3] <= time.perf_counter()


class _RecordingLoop:
    """Stands where the gateway's event loop would: keeps what the tick
    thread hands over; ``run()`` is the loop's turn."""

    def __init__(self):
        self.calls = []

    def call_soon_threadsafe(self, fn, *args):
        self.calls.append((fn, args))

    def run(self):
        calls, self.calls = self.calls, []
        for fn, args in calls:
            fn(*args)


def _streamed(rid, prompt=(1, 2, 3, 4, 5), **gen):
    from paddle_tpu.serving.gateway import _Stream
    return ServeRequest(rid, list(prompt), dict({"max_new_tokens": 4}, **gen),
                        sink=_Stream(), stream=True)


@pytest.mark.parametrize("profiled", [False, True], ids=["off", "profiled"])
@pytest.mark.parametrize("rows", [1, 8])
def test_a_dispatch_hands_a_tick_over_once_whatever_the_rows(rows, profiled):
    gw = Gateway(_engine(max_slots=rows, tick_profile=profiled),
                 name=f"t-once-{rows}-{profiled}")
    loop = gw._loop = _RecordingLoop()
    worker, eng = gw._workers[0], gw._workers[0].engine
    reqs = [_streamed(f"r{i}", prompt=range(1 + i, 7 + i))
            for i in range(rows)]
    for req in reqs:
        worker._admit(req, time.monotonic())
    seen = {r.request_id: [] for r in reqs}
    ticks = 0
    while worker._live:
        eng.step()
        worker._dispatch()
        # ONE crossing a dispatch that made anything, none for one that
        # made nothing
        assert len(loop.calls) <= 1
        for fn, (batch, timed) in loop.calls:
            assert fn == gw._deliver and timed is profiled
            ticks += 1
            if ticks > 2:       # past the prefill steps every row decodes
                assert sum(ev[0] == "token" for _, ev in batch) >= rows - 1
            for req, ev in batch:
                seen[req.request_id].append(ev)
        before = gw.health()["stream"]
        loop.run()
        after = gw.health()["stream"]
        assert after["stream_batches"] - before["stream_batches"] <= 1
    for req in reqs:
        evs = seen[req.request_id]
        # a request's order: its tokens, then one done that lists them
        assert [e[0] for e in evs] == ["token"] * 4 + ["done"]
        assert [e[1] for e in evs[:4]] == evs[4][1]["tokens"]
        assert all(len(e) == (4 if profiled else 3) for e in evs[:4])
        # a stream whose coroutine has not started holds them, in order
        assert [ev for ev, _ in req.sink.pending] == evs
    st = gw.health()["stream"]
    assert st["stream_batches"] == ticks
    assert st["stream_batch_events"] == 5 * rows
    assert st["stream_tokens"] == 0         # nothing was written
    if rows == 8:
        assert st["stream_batch_events"] / st["stream_batches"] > 4


def test_concurrent_emitters_lose_and_reorder_nothing():
    """The buffer's lock: a failover may emit from the supervisor's
    thread beside a dispatch. Many threads emit and flush through ONE
    worker: every event crosses once, and each thread's events, and the
    hand-overs that carry them, keep their order."""
    import sys
    import threading
    gw = Gateway(_engine(), name="t-emit-race")
    loop = gw._loop = _RecordingLoop()
    worker = gw._workers[0]
    n_threads, n_events = 16, 400
    reqs = [_streamed(f"e{i}") for i in range(n_threads)]

    def emitter(i):
        for k in range(n_events):
            worker._emit(reqs[i], ("token", k, None))
            if k % 7 == i % 7:
                worker._flush()
        worker._flush()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=emitter, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert worker._out == []
    seen = {r.request_id: [] for r in reqs}
    for fn, (batch, _) in loop.calls:
        assert batch                        # no empty hand-over
        for req, ev in batch:
            seen[req.request_id].append(ev[1])
    assert all(v == list(range(n_events)) for v in seen.values())


def test_an_emit_outside_a_tick_reaches_its_client_without_one():
    """An admission the engine refuses and a dead worker's queue flush
    emit outside ``_dispatch``: the client is answered though no tick
    runs."""
    async def refused():
        eng = _engine()
        gw = Gateway(eng, name="t-notick")
        await gw.start()
        try:
            out = await asyncio.wait_for(_sse(
                gw.port, dict(prompt=[1, 2, 3], max_new_tokens=4,
                              repetition_penalty=0.0)), 10)
            return out, eng.stats["decode_steps"], gw.health()["stream"]
        finally:
            await gw.drain()

    (status, _, toks, fin), steps, st = asyncio.run(refused())
    assert status == 200 and toks == [] and fin["done"] and fin["error"]
    assert steps == 0
    assert st["stream_batches"] == 1 and st["stream_batch_events"] == 1

    async def flushed():
        gw = Gateway(_engine(), name="t-notick-flush")
        gw._loop = asyncio.get_running_loop()     # its workers never run
        worker = gw._workers[0]
        reqs = [_streamed("q0"), _streamed("q1")]
        for req in reqs:
            worker.sched.enqueue(req)
        worker.flush_queue(503, "dead worker")
        await asyncio.sleep(0)
        return [list(r.sink.pending) for r in reqs], gw.health()["stream"]

    pending, st = asyncio.run(flushed())
    assert pending == [[(("error", 503, "dead worker"), False)]] * 2
    assert st["stream_batches"] == 1 and st["stream_batch_events"] == 2


def test_a_stalled_stream_holds_no_sibling_back_and_keeps_its_bytes():
    """ONE token draws a ``stream_stall``, the first its stream has: the
    stream waits it out, its siblings have ended by the time it lets that
    token go, and every stream's bytes, the stalled one's too, are what
    they are with no stall."""
    stall_s = 2.0
    plain, _, _ = _stream_run("t-stall-plain")
    raws, health, _ = _stream_run("t-stall-one", stall_s,
                                  stall="stream_stall@0")
    assert raws == plain
    # order against the stall's release as the client sees it, and no
    # distance between two ends: how long the siblings take to decode
    # is the machine's load, not the gateway
    first, ended = health["first"], health["ended"]
    stalled = max(first, key=first.get)
    assert first[stalled] >= stall_s
    assert all(ended[i] < first[stalled] for i in ended if i != stalled)
    for raw in raws:            # tokens, then done, on every stream
        kinds = [b'"done": true' in ln for ln in raw.split(b"\n\n") if ln]
        assert kinds == [False] * (len(kinds) - 1) + [True]


@pytest.mark.parametrize("profiled", [False, True], ids=["off", "profiled"])
def test_hand_overs_and_their_events_are_counted_profiler_on_or_off(profiled):
    name = f"t-batches-{profiled}"
    raws, health, prom = _stream_run(name, tick_profile=profiled)
    st = health["stream"]
    tokens = sum(r["max_new_tokens"] for r in STREAM_REQS)
    # every event crossed in a hand-over: the tokens and a done a stream
    assert st["stream_batch_events"] == tokens + len(STREAM_REQS)
    # and a hand-over is a tick's, not a token's: three streams decode
    # side by side, so there are far fewer hand-overs than events
    longest = max(r["max_new_tokens"] for r in STREAM_REQS)
    assert longest <= st["stream_batches"] <= longest + 6
    assert _prom_value(prom, "gateway_emit_batches_total", name) \
        == st["stream_batches"]
    assert st["stream_tokens"] == (tokens if profiled else 0)


def test_the_benchmarks_reader_divides_events_by_hand_overs():
    """``benchmarks/layer_metrics/emit_batch_events.sat.py`` over the
    window's two snapshots; nothing, and no raise, for a program whose
    ``health()["stream"]`` lacks the counters (the parent)."""
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    spec = importlib.util.spec_from_file_location(
        "emit_batch_events_sat", os.path.join(
            root, "benchmarks", "layer_metrics", "emit_batch_events.sat.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    def sources(a, b):
        return {"snaps": {"w0": {"health": {"stream": a}},
                          "w1": {"health": {"stream": b}}}}
    old = {"stream_tokens": 5, "emit_to_wire_us": 0, "loop_write_us": 0,
           "event_loop_cpu_us": 0}
    assert reader.reduce(sources(old, old)) is None
    assert reader.reduce({"snaps": {"w0": {"health": {}},
                                    "w1": {"health": {}}}}) is None
    a = dict(old, stream_batches=10, stream_batch_events=100)
    assert reader.reduce(sources(a, a)) is None       # no hand-over
    b = dict(old, stream_batches=110, stream_batch_events=6400)
    assert reader.reduce(sources(a, b)) == pytest.approx(63.0)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == reader.NAME)
    assert (entry["name"], entry["unit"], entry["better"], entry["source"],
            entry["layer"], entry["moves"]) == (
        reader.NAME, reader.UNIT, "higher", reader.SOURCE, reader.LAYER,
        reader.MOVES)
    assert entry["workloads"] == [
        w["name"] for w in manifest["workloads"]
        if w["name"] != "qwen2-7b-d16.chat"]


def test_gateway_sheds_429_with_retry_after():
    async def run():
        gw = Gateway(_engine(), name="t-429", max_queue=0)
        await gw.start()
        try:
            return await _sse(gw.port, dict(prompt=list(range(1, 10)),
                                            max_new_tokens=4))
        finally:
            await gw.drain()

    status, headers, _, body = asyncio.run(run())
    assert status == 429
    assert int(headers["retry-after"]) >= 1
    assert body["retry_after_s"] > 0


def test_cancel_on_disconnect_frees_slot():
    """Satellite: a dropped SSE stream cancels the request on the tick
    thread — slot and blocks free immediately, nothing is stranded,
    and the replica keeps serving."""
    async def run():
        eng = _engine(max_slots=2)
        gw = Gateway(eng, name="t-disc")
        await gw.start()
        try:
            st, _, toks, _ = await _sse(
                gw.port, dict(prompt=list(range(1, 10)),
                              max_new_tokens=50), break_after=2)
            assert st == 200 and len(toks) == 2
            freed = await _poll(
                lambda: eng.health()["active_slots"] == 0
                and eng.stats["cancellations"] == 1)
            assert freed, "dropped stream stranded its slot"
            # capacity recycled: a follow-up request completes
            st2, _, toks2, fin2 = await _sse(
                gw.port, dict(prompt=list(range(1, 10)),
                              max_new_tokens=4))
            assert st2 == 200 and fin2["finish_reason"] == "stop"
            assert len(toks2) == 4
            return gw.health()
        finally:
            await gw.drain()

    health = asyncio.run(run())
    assert health["disconnects"] == 1


def test_half_close_client_still_gets_full_stream():
    """A legal HTTP half-close (shutdown write side after the POST
    body, still reading) must NOT be treated as a disconnect: the
    stream completes and nothing is cancelled."""
    async def run():
        eng = _engine()
        gw = Gateway(eng, name="t-halfclose")
        await gw.start()
        try:
            reader, writer = await asyncio.open_connection("127.0.0.1",
                                                           gw.port)
            body = json.dumps(dict(prompt=list(range(1, 10)),
                                   max_new_tokens=6)).encode()
            writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                          f"Content-Length: {len(body)}\r\n\r\n"
                          ).encode() + body)
            await writer.drain()
            writer.write_eof()            # half-close: EOF on the read
            status = int((await reader.readline()).split()[1])
            toks, fin = [], None
            while True:
                ln = (await reader.readline()).strip()
                if ln.startswith(b":"):   # SSE comment (the probe)
                    continue
                if not ln.startswith(b"data: "):
                    continue
                ev = json.loads(ln[6:])
                if ev.get("done"):
                    fin = ev
                    break
                toks.append(ev["token"])
            writer.close()
            return status, toks, fin, gw.health(), eng.stats
        finally:
            await gw.drain()

    status, toks, fin, health, stats = asyncio.run(run())
    assert status == 200 and fin["finish_reason"] == "stop"
    assert len(toks) == 6 and toks == fin["tokens"]
    assert health["disconnects"] == 0
    assert stats["cancellations"] == 0


def test_sigterm_drain_finishes_inflight_rejects_new(tmp_path):
    """Acceptance: SIGTERM -> stop admitting (503 + Retry-After) ->
    in-flight SSE completes bit-identically -> metrics flushed ->
    run_until_shutdown returns."""
    obs.configure(str(tmp_path))

    async def run():
        gw = Gateway(_engine(), name="t-drain",
                     shutdown=GracefulShutdown(signals=(signal.SIGTERM,)))
        await gw.start()
        runner = asyncio.ensure_future(gw.run_until_shutdown())
        rejected = {}

        async def fire_sigterm():
            os.kill(os.getpid(), signal.SIGTERM)
            # probe WHILE the in-flight stream is still running (after
            # drain completes the listener closes, which is the same
            # "not admitting" outcome but not the 503 under test)
            st2, h2, _, _ = await _sse(gw.port,
                                       dict(prompt=[1, 2, 3],
                                            max_new_tokens=2))
            rejected.update(status=st2, headers=h2)

        st, _, toks, fin = await _sse(
            gw.port, dict(prompt=list(range(1, 10)),
                          max_new_tokens=40),
            on_first=fire_sigterm)
        # in-flight request ran to completion THROUGH the drain
        assert st == 200 and fin["finish_reason"] == "stop"
        assert len(toks) == 40
        assert rejected["status"] == 503
        assert "retry-after" in rejected["headers"]
        await asyncio.wait_for(runner, timeout=30)
        return gw.health()

    health = asyncio.run(run())
    assert health["draining"]
    assert health["completed"] == 1
    assert os.path.exists(os.path.join(str(tmp_path), "metrics.prom"))


def test_prefix_affinity_raises_hit_tokens_vs_round_robin():
    """Acceptance: on a shared-system-prompt workload, prefix-affinity
    routing lands same-digest requests on the replica with the warm
    blocks and measurably beats round-robin on prefix_hit_tokens."""
    sysp = list(range(1, 17))           # 16 tokens = 2 chunk spans

    async def serve(policy):
        engines = [_engine(), _engine()]
        gw = Gateway(engines, name=f"t-aff-{policy}", routing=policy)
        await gw.start()
        try:
            for i in range(8):
                st, _, _, fin = await _sse(
                    gw.port, dict(prompt=sysp + [100 + i, 50 + i],
                                  max_new_tokens=2))
                assert st == 200 and fin["finish_reason"] == "stop"
        finally:
            await gw.drain()
        return (sum(e.stats["prefix_hit_tokens"] for e in engines),
                gw.health()["router"])

    hits_aff, router_aff = asyncio.run(serve("prefix"))
    hits_rr, _ = asyncio.run(serve("round_robin"))
    # prefix policy: 1 cold miss, 7 warm hits of the 16-token span;
    # round-robin alternates replicas -> 2 cold misses
    assert hits_aff == 7 * 16
    assert hits_rr == 6 * 16
    assert hits_aff > hits_rr
    assert router_aff["prefix_route_hits"] == 7
    assert router_aff["prefix_route_misses"] == 1


def test_gateway_queue_timeout_never_takes_engine_slot():
    """Gateway-level satellite e2e: a request whose deadline expires
    while queued behind a busy engine is answered 504 and NEVER
    submitted (engine prefill count unchanged)."""
    async def run():
        eng = _engine(max_slots=1)
        gw = Gateway(eng, name="t-qto")
        await gw.start()
        try:
            long = asyncio.ensure_future(_sse(
                gw.port, dict(prompt=list(range(1, 10)),
                              max_new_tokens=50)))
            await _poll(lambda: eng.health()["active_slots"] == 1)
            body = json.dumps(dict(prompt=[4, 5, 6], max_new_tokens=4,
                                   timeout_s=0.05,
                                   stream=False)).encode()
            st, _, payload = await _http(gw.port, "POST",
                                         "/v1/generate", body)
            st1, _, toks, _ = await long
            return st, json.loads(payload), st1, len(toks), gw.health()
        finally:
            await gw.drain()

    st, resp, st_long, n_long, health = asyncio.run(run())
    assert st == 504 and resp["finish_reason"] == "timeout"
    assert st_long == 200 and n_long == 50
    rep = health["replicas"]["r0"]
    assert rep["scheduler"]["timeouts"] == 1
    assert rep["engine"]["prefills"] == 1     # the expired one never ran
    assert rep["engine"]["timeouts"] == 0     # nor reached engine expiry


# ================================================================= loadgen
def _load_loadgen():
    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        "serve_loadgen.py")
    spec = importlib.util.spec_from_file_location("serve_loadgen", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _loadgen_ns(**kw):
    base = dict(requests=6, rate=100.0, share_frac=0.5, sys_tokens=8,
                tail_tokens=4, max_new=6, interactive_frac=0.7,
                ttft_slo_ms=5000.0, timeout_s=60.0, tenants=2,
                replicas=1, policy="prefix", max_queue=256,
                model="stub", seed=0, url=None, out="")
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_loadgen_inprocess_smoke():
    """The bench rung contract: one run emits every key bench.py's
    gateway ingestion promotes, with sane values, and records the
    engines' ring drains."""
    slg = _load_loadgen()
    rung = asyncio.run(slg.run_loadgen(_loadgen_ns()))
    for key in ("gateway_tokens_per_sec", "gateway_p50_ttft_ms",
                "gateway_p99_ttft_ms", "gateway_p50_tpot_ms",
                "gateway_p99_tpot_ms", "goodput_tokens_per_sec",
                "prefix_hit_tokens"):
        assert key in rung, key
    assert rung["completed"] == 6 and rung["shed"] == 0
    assert rung["gateway_tokens_per_sec"] > 0
    assert rung["gateway_p99_ttft_ms"] >= rung["gateway_p50_ttft_ms"]
    assert rung["ring_drains"] > 0 and rung["patches_fused"] > 0


@pytest.mark.slow
def test_open_loop_rate_sweep_and_goodput():
    """Open-loop sweep: pushing the offered rate up cannot LOWER p99
    TTFT (queueing delay is visible, not hidden by a closed loop)."""
    slg = _load_loadgen()
    p99 = {}
    for rate in (4.0, 200.0):
        rung = asyncio.run(slg.run_loadgen(
            _loadgen_ns(requests=24, rate=rate, max_new=12)))
        assert rung["completed"] == 24
        p99[rate] = rung["gateway_p99_ttft_ms"]
    assert p99[200.0] >= p99[4.0]


@pytest.mark.slow
def test_loadgen_cli_multi_replica_e2e(tmp_path):
    """Subprocess e2e of the CLI: multi-replica prefix routing, rung
    file written where bench.py ingests it."""
    import subprocess
    import sys
    out = os.path.join(str(tmp_path), "rung.json")
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "serve_loadgen.py"),
         "--model", "stub", "--replicas", "2", "--requests", "16",
         "--rate", "50", "--sys-tokens", "8", "--tail-tokens", "4",
         "--max-new", "6", "--out", out],
        cwd=root, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("LOADGEN_JSON "))
    rung = json.loads(line[len("LOADGEN_JSON "):])
    assert rung["completed"] == 16 and rung["replicas"] == 2
    with open(out) as f:
        banked = json.load(f)
    assert banked["gateway"]["gateway_p99_ttft_ms"] == \
        rung["gateway_p99_ttft_ms"]


@pytest.mark.slow
def test_gateway_llama_stream_parity():
    """Real-model twin of the stub parity test (the stub pin is the
    tier-1 representative). TWO replicas share ONE model object — the
    shared-layer-tree case whose concurrent ticks must serialize on
    the per-model lock (regression: UnexpectedTracerError when two
    tick threads traced through the shared tree simultaneously)."""
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.models.llama import llama_tiny
    pt.seed(0)
    model = LlamaForCausalLM(llama_tiny())

    def eng():
        return PagedEngine(model, max_slots=2, num_blocks=32,
                           block_size=8, max_blocks_per_seq=8,
                           prefill_buckets=(16,),
                           chunk_prefill_tokens=8,
                           enable_prefix_cache=True)

    reqs = [dict(prompt=list(range(1, 12)), max_new_tokens=8),
            dict(prompt=[7, 3, 9, 2, 5], max_new_tokens=8,
                 temperature=0.8, seed=3)]

    async def run():
        gw = Gateway([eng(), eng()], name="t-llama")
        await gw.start()
        try:
            return await asyncio.gather(
                *[_sse(gw.port, dict(r, stream=True)) for r in reqs])
        finally:
            await gw.drain()

    outs = asyncio.run(run())
    direct = eng()
    for i, r in enumerate(reqs):
        kw = {k: v for k, v in r.items() if k != "prompt"}
        direct.submit(i, np.asarray([r["prompt"]], np.int32), **kw)
    res = direct.run()
    for i, (st, _, toks, fin) in enumerate(outs):
        assert st == 200 and toks == res[i] == fin["tokens"]
