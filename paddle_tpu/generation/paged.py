"""Paged KV cache + continuous batching (reference: PaddleNLP llm
predictor's block attention / paged KV serving path, vLLM's PagedAttention
scheduling).

TPU-native design — everything the XLA program sees is STATIC:

- The KV cache is a fixed pool of ``num_blocks`` physical blocks of
  ``block_size`` tokens per layer (``[P, B, kvh*d]``: a page is one
  contiguous ``(B, kvh*d)`` slab, the form the kernels fetch, so no
  program copies the pool). A request owns a row of the ``[R, M]``
  block table mapping its logical blocks to physical ones. Memory per
  request grows in block quanta, so one long request no longer pins a
  whole max-length buffer and the pool holds as many mixed-length
  requests as actually fit.
- One jitted ``decode_step`` advances EVERY active slot one token:
  per-row scatter-write of the new K/V into the row's current block,
  gather of the row's blocks ``kp[block_tables]``, masked attention up
  to each row's length. One jitted ``prefill`` per bucket writes a new
  request's prompt K/V into its blocks. Shapes never change, so both
  executables compile once per bucket.
- Scheduling (admission, block allocation, eviction) is HOST-side
  bookkeeping between jitted calls — numpy lists, no recompiles. New
  requests are admitted mid-decode the moment a slot and blocks free
  up: the bucketed Predictor's whole-batch barrier is gone.
- The served decode tick is DEVICE-RESIDENT (``fused_tick=True``, the
  default): block tables, seq lens, per-row sampling params, PRNG keys,
  token budgets and the active mask live on device as engine state
  advanced INSIDE the one compiled tick program (staged transitions →
  attention → repetition penalty → sampling → eos/budget done flags →
  token-ring append). It is one path, always all of it:

  * TRANSITIONS ARE STAGED. Each slot transition — admit, finish,
    chunked-prefill advance, preempt, cancel, expiry, block growth —
    packs ONE per-slot descriptor (``_pack_descriptor``: row index,
    table row, lens/budget/eos, sampling params, PRNG key, spec state);
    the pending descriptors, coalesced per slot, go to a device-resident
    queue of ``max_slots`` rows in one plain upload (``_flush_patches``)
    and the NEXT tick's program scatters them into its state before it
    computes (``_apply_patch_queue``). Churn costs no dispatch of its
    own; a steady tick uploads nothing.
  * TOKENS RIDE A RING. The program appends what it commits to a
    device-resident ring ([R, ring_len] with per-slot monotone write
    cursors carried in the tick state) and the host reads a
    dispatch's slice one step later (``_drain_oldest``). Stream
    writes, stop matching, finishes and trace events are driven off
    drained entries, one step behind the device. An out-of-band cancel
    or expiry drains only its own row (``_drain_row``), from every
    outstanding dispatch, so the mirrors a transition reads are never
    stale.
  * WHAT IS IN FLIGHT WHEN. Between steps ONE dispatch is outstanding.
    A step with something to decide (a free slot, a row mid-prefill, a
    finished, cancelled or expired row, pool pressure) drains it
    FIRST, so every transition reads current mirrors, then expires,
    admits, chunks, stages and dispatches the next tick: the device
    waits for the host in between. A step with nothing to decide
    (``_may_run_ahead``: every slot holds a decoding request, the
    pool serves the next blocks) dispatches tick N+1 FIRST and drains
    tick N after it: TWO are outstanding inside such a step, the chip
    always has its next program queued behind the running one, and
    the host's whole round (the wait for N, drain, commit, the
    caller's emit and scheduling, stage, call) runs under tick N+1.
    The only transition such a step makes is block growth, one
    position past the undrained tick's; its patch carries the table
    row alone (``_DESC_TABLE_ONLY``), because the other mirrors lag
    the device by that tick. What the device commits for a row after
    the host ended its request (a stop matched at the drain, an eos
    the host could not foresee) is never read: the drained cursor
    steps over it and the K/V write dies with the released blocks.
    ``runahead_ticks`` counts the decode dispatches made with one
    undrained; ``health()["outstanding_dispatches"]`` says how many
    are in flight.
  * A FULL REBUILD of the device state from the host mirrors
    (``_refresh_dev``) happens at the first dispatch, after
    ``hard_reset`` and when a ring cursor nears the end of int32
    (``_RING_CURSOR_LIMIT``); ``full_rebuilds`` counts them.

- ``fused_tick=False`` is the REFERENCE, not a served path: the plain
  per-tick host loop (``_decode_host``) that uploads every mirror each
  tick and does stop/eos/budget bookkeeping in Python. It is the one
  thing the fused engine's streams are compared with: greedy and seeded
  sampled streams are bitwise equal per request (tests/
  test_fused_tick.py::TestFusedTickParity).
- ``spec_tokens=k`` (ISSUE 7) turns each fused tick into a speculative
  MULTI-token tick: a device-resident prompt-lookup proposer (shared
  with ``ngram_speculative_generate``) drafts up to k tokens per slot
  from that request's own committed stream, one forward verifies all
  k+1 positions through the multi-query paged attention, and the
  accepted length commits in-program — still one dispatch per tick,
  with eos/stop/budget honored inside the accepted window. Per-request
  adaptive k (device-resident accept-rate EMA) and per-row headroom
  checks fall individual rows back to the 1-token tick without
  leaving the program.
- The verify is REJECTION-SAMPLED (ISSUE 11, Leviathan-style): every
  active row is spec-eligible, not just greedy+penalty-free ones.
  Greedy rows keep the bitwise longest-argmax-prefix rule; sampled
  rows accept each drafted token with probability p(token) under their
  own filtered distribution and resample rejections from the residual
  (per-row PRNG keys split once per tick, folded per position), so
  per-request output DISTRIBUTIONS are preserved exactly while
  repetitive sampled traffic commits multiple tokens per forward;
  penalized rows compose — the repetition penalty is applied to each
  verify position over the window's own committed prefix (a
  sequential in-program scan over the k+1 positions).

Padded prompt positions scatter into a reserved GARBAGE block (physical
block 0) so they can never corrupt a live block; it is never allocated.
"""
from __future__ import annotations

import contextlib
import functools
import itertools
import json
import os
import sys
import time
import types
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.paged_cache import (CacheLayer, PagedKV, SlotState, StateLayer,
                               chunk_attention_positions, chunk_attn_route,
                               chunk_experts_route, chunk_rule_route,
                               paged_decode_route, state_step_route)
from ..parallel.moe import ExpertShareMLP
from ..utils import observability as obs
from ..utils.faults import BackpressureError

__all__ = ["PagedKV", "PagedEngine"]

# unique per-process engine label: every engine's counters live in the
# global observability registry (scrapeable), while `stats`/`health()`
# keep their per-instance semantics
_engine_ids = itertools.count()
_NO_COUNTS = types.SimpleNamespace(total=None)   # a model with no counters

# --- adaptive-k policy for the fused speculative tick (ISSUE 7). Per
# request, an EMA of the accepted-draft fraction decides how hard to
# speculate; it lives ON DEVICE (advanced inside the tick program) with
# a host mirror carried on the request, so adapting k costs zero
# steady-state uploads. Below the floor a row falls back to the 1-token
# tick, re-probing with a single draft every PROBE-th active tick so a
# stream that turns repetitive mid-request can recover.
_SPEC_EMA_ALPHA = 0.3      # EMA step toward this tick's accept fraction
_SPEC_EMA_FLOOR = 0.25     # below: stop drafting (probes only)
_SPEC_PROBE_EVERY = 16     # collapsed rows re-probe with k=1 this often

# The token ring's write cursors are int32 and only a full rebuild of
# the device state zeroes them: once a row has drained this many tokens
# the next transition rebuilds (`_flush_patches`), long before the wrap.
_RING_CURSOR_LIMIT = 2 ** 30

# flags of a staged descriptor (its word 6): the PRNG key it carries is
# authoritative; it patches the block-table row and nothing else
_DESC_KEY_OVERRIDE = 1
_DESC_TABLE_ONLY = 2

# a packed prefill call's words a segment, behind its block-table row
# (``_pack_call``): length, slot, the call's index of its last live
# position, last-chunk flag, top_k; temperature, top_p, repetition
# penalty as raw bits; the PRNG key
_SEG_WORDS = 10


def _home_device(params):
    """Where an engine over ``params`` lives: the one device every
    weight is on; the current default device for a weightless stub;
    None when the weights span several devices (a sharded model keeps
    JAX's own placement)."""
    devs = set()
    for leaf in jax.tree_util.tree_leaves(params):
        if isinstance(leaf, jax.Array):
            devs |= leaf.devices()
    if not devs:
        devs = jnp.zeros((), jnp.int32).devices()
    return devs.pop() if len(devs) == 1 else None


def _on_device(method):
    """Run an engine entry point inside the engine's own default-device
    scope, whatever the calling thread's is. jit keys its caches on the
    THREAD-LOCAL default device, so without one fixed scope every
    program compiled while an engine is warmed up inside
    ``jax.default_device(dev)`` (how its weights get to ``dev``) is
    traced and loaded a second time by the gateway's tick thread, which
    inherits no scope — seconds per program at real widths, under the
    watchdog's deadline (PR 21: the first chip runs paid ~10 s of this
    while serving). The eager helper ops between programs land on the
    engine's device for the same reason."""
    @functools.wraps(method)
    def scoped(self, *args, **kwargs):
        with jax.default_device(self.device):
            return method(self, *args, **kwargs)
    return scoped


# what an engine over state layers adds up inside its programs beside
# the model's own tick counters: state layers x decode ticks, those of
# them whose step took the one-pass kernel (``state_step_route``), live
# rows x state layers, and the prompt segments that started from zero /
# from the state the chunk before them left (counted in the chunk
# programs into ``pools[-1]`` and moved to the token ring by the next
# tick)
_STATE_COUNTERS = ("state_layer_ticks", "state_kernel_ticks",
                   "state_rows_updated", "state_resets", "state_carries")


# and what it adds up on the host as it dispatches a prompt call: state
# layers x prompt calls, and those of them whose chunk rule took the
# kernel (``chunk_rule_route``)
_CHUNK_RULE_COUNTERS = ("chunk_rule_layer_calls", "chunk_rule_kernel_calls")


# and, for a model with expert layers (``ExpertShareMLP``): expert
# layers x prompt calls, and those of them whose forward took the
# grouped product over the sorted (position, expert) pairs
# (``chunk_experts_route`` of the call's positions)
_CHUNK_EXPERTS_COUNTERS = ("chunk_experts_layer_calls",
                           "chunk_experts_grouped_calls")


# and, as it dispatches a prompt chunk that has cached context behind
# it, over the call's K/V layers: the positions the chunk's attention
# scored (whole runs of pages) and those of them the row's table held
# live (``ops.paged_cache.chunk_attention_positions``); and those K/V
# layers, with how many of them took the kernel over tiles of the
# chunk's queries (``chunk_attn_route``)
_CHUNK_ATTN_COUNTERS = ("chunk_attn_positions_scored",
                        "chunk_attn_positions_live",
                        "chunk_attn_layer_calls",
                        "chunk_attn_kernel_calls")


# what a band-keeping engine adds up inside a tick, over the live rows
# and the layers of each kind, beside the model's own tick counters:
# pages inside the band / of the whole context, the tokens the two kinds
# of kernel call read, and the pages that fell behind a band
_BAND_COUNTERS = ("kv_window_blocks", "kv_full_blocks", "kv_window_tokens",
                  "kv_context_tokens", "kv_window_blocks_released")


class _Request:
    """Queued/running request state. Sampling params are per-request and
    ride into the jitted step as row arrays; ``key`` is the row's PRNG
    stream — each emitted token consumes exactly one split, whether it
    was sampled at prefill or at a decode tick, so a preempted request
    that re-prefills continues the SAME stream (sampled outputs stay
    reproducible under preemption, like the greedy recompute path)."""
    __slots__ = ("request_id", "prompt", "max_new", "eos", "tokens",
                 "blocks", "prefix", "prefix_lps", "admit_seq",
                 "temperature", "top_k", "top_p", "key", "lps",
                 "prefill_pos", "stop", "trim", "rep", "deadline",
                 "t_submit", "spec_ema")

    def __init__(self, request_id, prompt, max_new, eos, temperature,
                 top_k, top_p, key, prefix=None, prefix_lps=None,
                 stop=(), rep=1.0, deadline=None):
        self.request_id = request_id
        self.prompt = prompt            # ids the prefill runs over
        self.max_new = max_new          # tokens still to emit
        self.eos = eos
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.key = key                  # [2] uint32 PRNG state
        self.stop = stop                # token-id stop sequences
        self.trim = 0                   # matched stop length to cut
        self.rep = rep                  # repetition penalty (1.0 = off)
        self.deadline = deadline        # monotonic() cutoff (None = no cap)
        self.prefix = prefix or []      # tokens emitted before preemption
        self.prefix_lps = prefix_lps or []
        self.admit_seq = 0              # preemption picks the youngest
        self.tokens: List[int] = []
        self.lps: List[float] = []      # chosen-token logprobs
        self.blocks: List[int] = []
        self.prefill_pos = 0            # prompt tokens already cached
        self.t_submit = time.monotonic()   # queue-wait histogram anchor
        # accept-rate EMA for the speculative tick's adaptive k (host
        # mirror of the device copy; optimistic start so new requests
        # draft immediately). Carried across preemptions.
        self.spec_ema = 1.0


class _NoPhase:
    """``PagedEngine._phase`` with the profiler off: one shared object
    that measures nothing."""
    __slots__ = ()
    on = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def switch(self, phase: str):
        pass


_NO_PHASE = _NoPhase()


class _PhaseSpan:
    """One open bracket of the profiler, as a ``with`` block
    (``PagedEngine._phase``). ``switch`` closes the bracket and opens
    the next in its place, for two phases that abut; leaving the block
    closes whichever is open then, an exception's way out included.
    ``on`` lets a caller add what only a profiled tick does (the
    ``block_until_ready`` that splits the device's wait from the
    drain)."""
    __slots__ = ("_prof", "_phase")
    on = True

    def __init__(self, prof: "_TickPhaseProfile", phase: str):
        self._prof, self._phase = prof, phase

    def __enter__(self):
        self._prof.open(self._phase)
        return self

    def __exit__(self, *exc):
        self._prof.close()
        return False

    def switch(self, phase: str):
        self._prof.close()
        self._prof.open(phase)


class _TickPhaseProfile:
    """Where the time of the thread that owns the engine goes, under
    the names of ``obs.TICK_PHASES`` (inside ``PagedEngine.step``) and
    ``obs.LOOP_PHASES`` (between two steps; the serving gateway's
    worker loop reports those through ``PagedEngine.loop_phase``).

    A phase is a BRACKET, ``with engine._phase(name):`` (a
    ``_PhaseSpan`` over ``open`` / ``close``). Brackets nest, and a
    bracket's time is its own: what an inner bracket took
    (the uploads inside ``stage``, the program call inside ``chunk``)
    is taken out of the outer one. ``host`` is never bracketed: it is
    the RESIDUAL of a tick, its wall minus every bracket, so the
    in-tick phases sum to the tick wall EXACTLY (pinned under an
    injected clock in tests/test_tick_profile.py) and
    ``serve_loadgen``'s ``phase_breakdown`` and ``obs_report
    phase_decompose`` split tok/s with no unexplained remainder.
    Brackets outside a tick (the loop phases; the scoped drain a
    cancel runs between steps) feed the totals and histograms but no
    tick record. ``thread_wall_ms`` runs from the first bracket or tick
    to the last, so the share of the thread's time under no name is
    ``1 - (sum(totals)) / thread_wall_ms``.

    While a bracket is open it also holds a
    ``jax.profiler.TraceAnnotation("tick/<phase>")``, and a tick one
    ``TraceAnnotation("tick", n=<tick index>)``: inside a profiler
    trace the thread's phases lie on a host line of the same
    ``.xplane.pb`` as the device's ops, on its clock. The k-th
    ``tick/dispatch`` span of a decode tick is the k-th ``_fused_tick*``
    module on the device.

    Host-side bookkeeping only: phases land in registry histograms
    (``paged_tick_phase_ms{phase=...}`` on the SERVING_MS_BUCKETS grid,
    so the fleet sampler/dash pick them up for free) plus a bounded
    per-tick ring of records (phase times, dispatches, uploads, bytes,
    fused patches, active slots). Nothing here touches the device
    beyond a ``block_until_ready`` on arrays the very next statement
    would block on anyway — profile-on streams are pinned bitwise
    identical to profile-off, and the steady-tick 1-dispatch/0-upload
    contract is untouched.

    Beside the clock a bracket reads the thread's CPU time
    (``time.thread_time``), inside its clock readings, and
    ``cpu_totals`` keeps each phase's CPU milliseconds beside its wall
    (nesting and the ``host`` residual as for the wall). Wall less
    CPU, over the phases that are not waits by design (``device``,
    ``idle``, ``lock``), is the time the thread wanted a CPU and had none: the interpreter
    lock, which the event loop's thread holds while it writes tokens,
    or the scheduler. Nothing holds a bracket's CPU under its wall: a
    sandboxed kernel may advance a thread's CPU clock in 10 ms steps,
    sampled (the benchmark's machines do), and a step then lands whole
    in whichever bracket reads it. One bracket's CPU means nothing
    there; the totals over a stretch of seconds are an estimate whose
    noise is the step times the root of the steps counted.

    ``clock`` and ``cpu_clock`` are injectable (tests pin the phase math
    deterministically the way ``MetricsTimeSeries(clock=...)`` does)."""

    PHASES = obs.TICK_PHASES + obs.LOOP_PHASES

    def __init__(self, labels: Dict[str, str], clock=None,
                 capacity: int = 1024, cpu_clock=None):
        self.clock = clock if clock is not None else time.perf_counter
        self.cpu_clock = cpu_clock if cpu_clock is not None \
            else time.thread_time
        self.capacity = max(int(capacity), 1)
        self.ring: deque = deque(maxlen=self.capacity)
        self.totals = {p: 0.0 for p in self.PHASES}
        self.cpu_totals = {p: 0.0 for p in self.PHASES}
        self.wall_total_ms = 0.0
        self.ticks = 0
        reg = obs.registry()
        self._hists = {
            p: reg.histogram("paged_tick_phase_ms",
                             buckets=obs.SERVING_MS_BUCKETS,
                             phase=p, **labels)
            for p in self.PHASES}
        self._h_wall = reg.histogram("paged_tick_wall_ms",
                                     buckets=obs.SERVING_MS_BUCKETS,
                                     **labels)
        self._span_names = {p: "tick/" + p for p in self.PHASES}
        # open brackets, outermost first: [phase, start, seconds its
        # inner brackets took, the trace annotation it holds, CPU at
        # the start, CPU seconds of its inner brackets]
        self._stack: List[list] = []
        self._acc: Optional[Dict[str, float]] = None
        self._acc_cpu: Optional[Dict[str, float]] = None
        self._tick_ann = None
        self._t0 = 0.0
        self._c0 = 0.0
        self._t_first: Optional[float] = None
        self._t_last = 0.0
        self._last: Optional[Dict[str, float]] = None

    @property
    def thread_wall_ms(self) -> float:
        """From the first bracket or tick to the end of the last."""
        return 0.0 if self._t_first is None \
            else max(self._t_last - self._t_first, 0.0) * 1e3

    def span(self, phase: str) -> _PhaseSpan:
        if phase not in self._span_names:
            raise KeyError(phase)
        return _PhaseSpan(self, phase)

    def open(self, phase: str):
        ann = obs._trace_annotation(self._span_names[phase])
        if ann is not None:
            ann.__enter__()
        t = self.clock()
        if self._t_first is None:
            self._t_first = t
        self._stack.append([phase, t, 0.0, ann, self.cpu_clock(), 0.0])

    def close(self):
        phase, t0, inner, ann, c0, cpu_inner = self._stack.pop()
        c1 = self.cpu_clock()
        t1 = self.clock()
        if ann is not None:
            ann.__exit__(None, None, None)
        self._t_last = t1
        if self._stack:
            self._stack[-1][2] += t1 - t0
            self._stack[-1][5] += c1 - c0
        dt_ms = max((t1 - t0 - inner) * 1e3, 0.0)
        cpu_ms = max((c1 - c0 - cpu_inner) * 1e3, 0.0)
        if self._acc is not None and phase in self._acc:
            self._acc[phase] += dt_ms
            self._acc_cpu[phase] += cpu_ms
        else:
            self.totals[phase] += dt_ms
            self.cpu_totals[phase] += cpu_ms
            self._hists[phase].observe(dt_ms)

    def begin(self):
        """Open a tick window (top of ``PagedEngine.step``)."""
        self._tick_ann = obs._trace_annotation("tick", n=self.ticks)
        if self._tick_ann is not None:
            self._tick_ann.__enter__()
        self._acc = {p: 0.0 for p in obs.TICK_PHASES if p != "host"}
        self._acc_cpu = dict(self._acc)
        self._t0 = self.clock()
        self._c0 = self.cpu_clock()
        if self._t_first is None:
            self._t_first = self._t0

    def end(self, *, dispatches: int, uploads: int, nbytes: int,
            patches: int, active: int):
        """Close the tick: host = wall - bracketed phases (clamped at
        0), in CPU as in wall; observe histograms, append the ring
        record."""
        c1 = self.cpu_clock()
        t1 = self.clock()
        self._t_last = t1
        if self._tick_ann is not None:
            self._tick_ann.__exit__(None, None, None)
            self._tick_ann = None
        wall = max((t1 - self._t0) * 1e3, 0.0)
        phases = self._acc or {}
        self._acc = None
        phases["host"] = max(wall - sum(phases.values()), 0.0)
        cpu = self._acc_cpu or {}
        self._acc_cpu = None
        cpu["host"] = max((c1 - self._c0) * 1e3 - sum(cpu.values()), 0.0)
        for p, v in cpu.items():
            self.cpu_totals[p] += v
        rec: Dict[str, Any] = {
            "tick": self.ticks, "t": round(float(t1), 6),
            "wall_ms": round(wall, 4),
        }
        for p in obs.TICK_PHASES:
            v = phases.get(p, 0.0)
            rec[f"{p}_ms"] = round(v, 4)
            self.totals[p] += v
            self._hists[p].observe(v)
        rec.update(dispatches=int(dispatches), uploads=int(uploads),
                   bytes=int(nbytes), patches=int(patches),
                   active=int(active))
        self._h_wall.observe(wall)
        self.wall_total_ms += wall
        self.ticks += 1
        self.ring.append(rec)
        self._last = {k: rec[k] for k in
                      ("tick", "wall_ms") + tuple(
                          f"{p}_ms" for p in obs.TICK_PHASES)}

    def last_phases(self) -> Optional[Dict[str, float]]:
        """Most recent COMPLETED tick's index and phase split — what a
        drained tick trace event attaches as its per-request decode
        share context (the drain commits tokens one dispatch behind).
        The index is the ``n`` of that tick's ``tick`` span in a
        profiler trace: request id -> tick -> device program."""
        return dict(self._last) if self._last is not None else None

    def summary(self) -> Dict[str, Any]:
        """Lifetime totals: the tick side, the loop side, each in wall
        and in the thread's CPU, and the thread's wall they are shares
        of."""
        return {"ticks": self.ticks,
                "wall_total_ms": round(self.wall_total_ms, 4),
                "phase_totals_ms": {p: round(self.totals[p], 4)
                                    for p in obs.TICK_PHASES},
                "loop_totals_ms": {p: round(self.totals[p], 4)
                                   for p in obs.LOOP_PHASES},
                "phase_cpu_ms": {p: round(self.cpu_totals[p], 4)
                                 for p in obs.TICK_PHASES},
                "loop_phase_cpu_ms": {p: round(self.cpu_totals[p], 4)
                                      for p in obs.LOOP_PHASES},
                "thread_wall_ms": round(self.thread_wall_ms, 4)}

    def to_doc(self, engine: str) -> Dict[str, Any]:
        """The ``tickphase/1`` document
        (``obs.validate_tickphase_doc`` checks it). The interpreter's
        switch interval is how long a thread that wants the lock may
        wait for the one that holds it: the scale of a phase's wall
        less its CPU."""
        return dict(self.summary(), schema=obs.TICKPHASE_SCHEMA,
                    engine=engine, dumped_wall=time.time(),
                    clock_now=float(self.clock()),
                    switch_interval_s=sys.getswitchinterval(),
                    capacity=self.capacity, entries=list(self.ring))


class _ChunkPrograms:
    """The engine's two prompt-chunk programs under its one attribute
    ``_chunk_jit``: ``packed`` serves the prompts that start at
    position 0, several a call (``_chunk_prefill_packed``), ``alone``
    one slot's chunk that has cached context behind it
    (``_chunk_prefill``). ``_cache_size`` is what a "nothing was traced
    again" check reads: both programs' traces."""

    def __init__(self, packed, alone):
        self.packed, self.alone = packed, alone

    def _cache_size(self) -> int:
        return self.packed._cache_size() + self.alone._cache_size()


class PagedEngine:
    """Continuous-batching serving engine for causal LMs whose attention
    takes a ``PagedKV``: the Llama family (a K and a V pool, kv heads x
    head_dim a token) and the DeepSeek-V2/V3 family (one latent pool,
    ``kv_lora_rank + qk_rope_head_dim`` columns a token padded to whole
    lanes). The model says what a cached row is (``paged_cache_rows``)
    and, where a layer has more than one attention, how many of them a
    token has (``paged_cache_layers``: LongCat-Flash's two latent
    attentions a layer are two cache layers), or, where its layers
    differ, what each is (a list of ``CacheLayer``: MiMo-V2's full
    layers cache 4 kv heads, its window layers 8, keys wider than
    values, and a window layer keeps its band only), and its forward
    hands each layer's view to ``ops.paged_cache.write_and_attend`` or
    reads ``view.call`` in a mixer of its own;
    allocation, writes, prefix adoption, spill, upload and reset are one
    code path over a cache layer's tuple of pool arrays. A band-keeping
    layer owns no block of the allocator's: its pool is a ring of
    ``_ring_blocks`` pages a slot (window + chunk), so its pages are
    released and reused by position alone (docs/SERVING.md).

    submit() enqueues requests at any time; each step() admits what
    fits (slot + blocks), prefills at most one queued request, and
    advances every active slot one greedy token. Finished requests free
    their blocks immediately, so capacity recycles mid-stream instead
    of at batch boundaries (reference: PaddleNLP block-attention
    predictor; the bucketed ``Predictor`` keeps whole-batch semantics).
    """

    def __init__(self, model, max_slots: int = 8, num_blocks: int = 128,
                 block_size: int = 16, max_blocks_per_seq: int = 16,
                 prefill_buckets=(32, 64, 128),
                 chunk_prefill_tokens: Optional[int] = None,
                 enable_prefix_cache: bool = False,
                 max_queue: Optional[int] = None,
                 default_timeout_s: Optional[float] = None,
                 fused_tick: bool = True,
                 spec_tokens: int = 0,
                 spec_ngram: int = 2,
                 tick_profile: bool = False,
                 profile_clock=None,
                 profile_ring_len: int = 1024):
        cfg = model.config
        self.model = model
        self.fn, self.params = model.functional()
        # placement: the engine lives where its weights live. Params are
        # committed there; pools, masks and every later upload name the
        # device (``_put`` / ``_zeros``) and every entry point runs
        # inside its default scope (``_on_device``) — the process
        # default is thread-local, and a gateway tick thread would
        # otherwise upload to device 0 and hop. None (weights sharded
        # over several devices) keeps JAX's default placement.
        self.device = _home_device(self.params)
        if self.device is not None:
            self.params = jax.device_put(self.params, self.device)
        self.R, self.P, self.B, self.M = (max_slots, num_blocks,
                                          block_size, max_blocks_per_seq)
        self.prefill_buckets = sorted(prefill_buckets)
        # chunked prefill (vLLM-style): prompts enter the cache
        # chunk_prefill_tokens at a time, interleaved with decode ticks,
        # so one long prompt never stalls the active slots for its whole
        # length. None = whole-prompt prefill at admission (one bucketed
        # call). Quantized to block_size so chunk boundaries align with
        # block boundaries and every chunk reuses ONE compiled shape.
        if chunk_prefill_tokens is not None:
            chunk_prefill_tokens = max(
                block_size,
                -(-chunk_prefill_tokens // block_size) * block_size)
        self.chunk = chunk_prefill_tokens
        # a packed prefill call holds up to this many prompts (segments)
        # in its ``chunk`` positions: the engine's geometry decides, a
        # slot each and a block each at the least
        self._pack_segments = None if self.chunk is None \
            else min(max_slots, self.chunk // block_size)
        self._spec_k = int(spec_tokens)
        # what each cache layer is; the windows of those that keep a
        # band only (none in most models: everything below that reads
        # ``_windows`` is then what it was)
        self._layout = self._cache_layout()
        self._windows = tuple(l.window for l in self._layout
                              if l.window is not None)
        # how many layers keep state by slot (``StateLayer``; none in
        # most models, and everything that reads this is then as it was)
        self._n_state = sum(isinstance(l, StateLayer)
                            for l in self._layout)
        # those of them whose prompt chunks take the chunk-rule kernel
        # (``chunk_rule_route`` of the shapes the layer gives)
        self._n_chunk_kernel = sum(
            chunk_rule_route(*(jax.ShapeDtypeStruct(
                (self.chunk or 1,) + shape, jnp.float32)
                for shape in l.rule)) == "kernel"
            for l in self._layout if isinstance(l, StateLayer) and l.rule)
        # the stacked weights of the model's expert layers (none in most
        # models), and by a prompt call's positions how many of them
        # take the grouped product (``_count_chunk_experts``)
        self._expert_stacks = [
            layer.w_gate for _, layer in
            getattr(model, "named_sublayers", tuple)()
            if isinstance(layer, ExpertShareMLP)]
        self._n_experts_grouped: Dict[int, int] = {}
        # the path each K/V layer's continuation chunk takes, asked at
        # the first such call's dispatch (``chunk_attn_routes``)
        self._chunk_attn_routes: Optional[list] = None
        # automatic prefix caching (reference: PaddleNLP CacheKV prefix
        # sharing / vLLM APC): requests whose prompts share a prefix
        # point their block tables at the SAME physical blocks and skip
        # the prefill compute for the shared part. Reuse is quantized to
        # the CHUNK grid, so every registered span was computed by the
        # same chunk executable at the same grid offsets as a borrower
        # would have used — reuse is bit-exact, not just close. Blocks
        # whose last owner finished park in an LRU pool (system prompts
        # stay warm across requests) and are evicted only under block
        # pressure.
        if enable_prefix_cache and self.chunk is None:
            raise ValueError(
                "enable_prefix_cache requires chunk_prefill_tokens: "
                "chunk-grid-aligned recompute is what makes reused and "
                "freshly computed K/V bit-identical")
        if enable_prefix_cache and self._windows:
            raise ValueError(
                "enable_prefix_cache: this model has layers that keep "
                "only their window's band of a sequence; a prefix's "
                "blocks there were released as the prompt advanced, so "
                "there is nothing to adopt (adoption over band-keeping "
                "layers is not built)")
        if enable_prefix_cache and self._n_state:
            raise ValueError(
                "enable_prefix_cache: this model has layers that keep "
                "recurrent state by slot; a prefix's blocks hold no "
                "state to adopt, and the state behind a prefix is kept "
                "nowhere (snapshots of it are not built)")
        if self._spec_k and self._n_state:
            raise ValueError(
                "spec_tokens: this model has layers that keep recurrent "
                "state; a rejected draft's positions cannot be taken "
                "back out of it (verify-then-commit over state layers is "
                "not built)")
        self.prefix_caching = bool(enable_prefix_cache)
        self.prefix_cache: Dict[tuple, tuple] = {}   # key -> block ids
        self._prefix_rev: Dict[int, set] = {}        # block -> keys
        # fleet prefix gossip (ISSUE 13): bumped on every prefix-cache
        # set mutation (register / evict / reset) so a remote poller
        # can skip re-fetching an unchanged digest set. Monotonic for
        # the engine's lifetime — never reset, even by hard_reset().
        self.prefix_generation = 0
        self.block_refs: Dict[int, int] = {}         # live owner count
        self.cached_free: Dict[int, None] = {}       # LRU, insertion order
        # host-RAM spill tier (ISSUE 17): a KVSpillArena attached by the
        # gateway via attach_spill(). Deliberately NOT constructed here —
        # the arena outlives the engine (supervisor rebuilds re-attach
        # it), which is what makes a crashed replica come back warm.
        self._spill = None
        self.pools, self.seen = self._fresh_device_arrays()
        # block 0 is the garbage block: pad scatter lands there
        self.free_blocks = list(range(1, self.P))
        self.block_tables = np.zeros((self.R, self.M), np.int32)
        self.seq_lens = np.zeros((self.R,), np.int32)
        # per-row sampling params (inactive rows: greedy, key unused)
        self.temps = np.zeros((self.R,), np.float32)
        self.top_ks = np.zeros((self.R,), np.int32)
        self.top_ps = np.ones((self.R,), np.float32)
        self.reps = np.ones((self.R,), np.float32)
        self.keys = np.zeros((self.R, 2), np.uint32)
        self.slots: List[Optional[_Request]] = [None] * self.R
        self.queue: List[_Request] = []
        self.results: Dict[Any, List[int]] = {}
        self.logprobs: Dict[Any, List[float]] = {}
        # overload protection (chaos hardening): bounded admission queue
        # + per-request deadlines; aborted requests land here, keyed by
        # request_id, with the reason ("timeout" / "cancelled")
        self.max_queue = max_queue
        self.default_timeout_s = default_timeout_s
        self.cancelled: Dict[Any, str] = {}
        self._admit_counter = 0
        self._submit_counter = 0
        # registry-backed scheduler counters (ISSUE 5): one source of
        # truth for `stats`, `health()`, and a /metrics scrape. The
        # per-instance engine label keeps pre-migration dict semantics —
        # a fresh engine starts every counter at 0.
        self._obs_labels = {"engine": f"paged{next(_engine_ids)}"}
        reg = obs.registry()
        # counters the model's layers add up INSIDE a tick (an expert
        # layer's assignments and experts hit): they ride a spare row of
        # the token ring, so the drain fetches nothing more for them
        # (the host reference has no ring and does not count them)
        self._model_counter_names = tuple(
            getattr(model, "tick_counters", tuple)())
        self._tick_counter_names = self._model_counter_names + (
            _BAND_COUNTERS if self._windows else ()) + (
            _STATE_COUNTERS if self._n_state else ())
        self._tick_counts_seen = np.zeros(
            (len(self._tick_counter_names),), np.int64)
        # runahead_ticks: decode dispatches made while another was
        # undrained (_may_run_ahead); over decode_steps, the share of
        # ticks the device had its next program queued behind
        # spec_proposed/spec_accepted (ISSUE 7): drafted vs accepted
        # draft tokens — `health()` derives the accept rate from the
        # SAME registry objects a /metrics scrape exports
        # full_rebuilds / patches_fused / h2d_upload_bytes (ISSUE 14,
        # 19): what transitions cost — how often the whole device state
        # was rebuilt, how many descriptors rode the staged queue, and
        # the bytes that crossed H2D either way
        self._counters = {
            k: reg.counter(f"paged_{k}_total", **self._obs_labels)
            for k in ("decode_steps", "prefills", "preemptions",
                      "prefill_chunks", "prefill_segments", "slot_steps",
                      "active_slot_steps", "prefix_hit_tokens",
                      "prefix_adopted_blocks", "timeouts",
                      "cancellations", "rejected",
                      "spec_proposed", "spec_accepted",
                      "full_rebuilds", "h2d_upload_bytes",
                      "dispatches", "runahead_ticks", "patches_fused",
                      "ring_cursor_rollovers",
                      "spill_spans", "spill_restores",
                      "spill_restored_tokens",
                      "spill_restore_failures")
            + self._tick_counter_names
            + (_CHUNK_RULE_COUNTERS if self._n_state else ())
            + (_CHUNK_EXPERTS_COUNTERS if self._expert_stacks else ())
            + _CHUNK_ATTN_COUNTERS}
        # paged_decode_step_ms is what the host can see of one decode
        # dispatch: on the host reference path, which reads back in the
        # tick, the program's whole run, call to tokens on the host; the
        # fused tick returns without reading, so the window is
        # the next step's D2H read of the token ring (short when the
        # program finished while the host worked). The program's time
        # on the device is the profiler trace's, on either path.
        self._h_decode = reg.histogram("paged_decode_step_ms",
                                       buckets=obs.SERVING_MS_BUCKETS,
                                       **self._obs_labels)
        # the host's time in one prefill chunk (_advance_chunk): input
        # staging, the program's call and, on a prompt's last chunk,
        # the read of its first token, which waits for the program
        self._h_chunk = reg.histogram("paged_prefill_chunk_ms",
                                      buckets=obs.SERVING_MS_BUCKETS,
                                      **self._obs_labels)
        self._h_wait = reg.histogram("paged_queue_wait_ms",
                                     buckets=obs.SERVING_MS_BUCKETS,
                                     **self._obs_labels)
        self._h_tpf = reg.histogram("paged_tokens_per_forward",
                                    **self._obs_labels)
        # per-upload H2D size distribution (ISSUE 14): a staged queue,
        # a full-state rebuild, one mirror of the host path
        self._h_bytes = reg.histogram("paged_h2d_bytes",
                                      buckets=obs.BYTES_BUCKETS,
                                      **self._obs_labels)
        # request-scoped tracing hook (ISSUE 10): when a front end (the
        # serving gateway) sets this to a callable ``(request_id, kind,
        # **fields)``, the engine reports each request's lifecycle as
        # typed events — queue enter, slot take (with prefix-hit
        # tokens), every prefill chunk, per-tick token batches (with
        # spec proposed/accepted), preemption, finish/abort. Pure
        # host-side bookkeeping on the existing transition paths: no
        # device work, no extra dispatches/uploads (pinned by
        # tests/test_reqtrace.py), and None (the default) keeps the
        # engine entirely trace-free.
        self.trace_sink = None
        # pools (and the seen masks) are donated: XLA aliases input to
        # output so a decode step costs one scatter, not a full copy
        self._decode_jit = jax.jit(self._decode_step,
                                   donate_argnums=(1, 9))
        self._decode_greedy_jit = jax.jit(self._decode_step_greedy,
                                          donate_argnums=(1, 5))
        self._prefill_jit = jax.jit(self._prefill, donate_argnums=(1,),
                                    static_argnames=("bucket",))
        self._chunk_jit = _ChunkPrograms(
            jax.jit(self._chunk_prefill_packed, donate_argnums=(1, 2)),
            jax.jit(self._chunk_prefill, donate_argnums=(1,),
                    static_argnames=("bucket",)))
        # spill_reupload_program (ISSUE 17): one batched H2D scatter
        # landing a restored span's KV into freshly allocated blocks.
        # Pools are donated (alias-in-place like the decode scatters);
        # block indices are padded to a power-of-two bucket with the
        # garbage block 0, so restore sizes share compiled shapes.
        self._spill_upload_jit = jax.jit(self._spill_upload,
                                         donate_argnums=(0,))
        # --- device-resident fused tick (ISSUE 6 tentpole) ------------
        # fused_tick=True (the served path) keeps block tables / seq
        # lens / sampling params / PRNG keys / done-bookkeeping ON
        # DEVICE as engine state mutated by one compiled program per
        # tick; SLOT TRANSITIONS (admit / finish / chunk / preempt / new
        # block) reach it as staged descriptors and tokens leave it
        # through the ring (both below). fused_tick=False is the per-
        # tick host path: the reference the fused streams must match
        # bit-exactly, not a served mode.
        self._fused = bool(fused_tick)
        self._dev: Optional[Dict[str, Any]] = None   # device state dict
        self._dev_dirty = True          # host mirrors changed since build
        self._dev_keys_dirty = False    # device keys advanced since sync
        self._key_overrides: set = set()  # rows host re-keyed (authoritative)
        # instrumentation for the one-dispatch-per-tick contract: jitted
        # engine-program launches and host->device mirror uploads (the
        # transition scatters on `seen` are not counted — they are slot-
        # transition work, not steady-state ticks). h2d_upload_bytes
        # (ISSUE 14 satellite) weighs each upload event by its actual
        # size: a full-state rebuild and a staged queue are both ONE
        # h2d_uploads event.
        self.dispatch_count = 0
        # steps that dispatched a decode program: what a per-tick
        # figure divides by (a chunk figure divides by the
        # ``prefill_chunks`` counter)
        self.decode_ticks = 0
        self.h2d_uploads = 0
        self.h2d_upload_bytes = 0
        self.full_rebuilds = 0
        self.patches_fused = 0
        self.ring_cursor_rollovers = 0
        # NOTE: the small state dict is NOT donated — donating leaves
        # that pass through unchanged (tables, temps, ...) makes XLA
        # emit input->output aliases for them, and executables
        # round-tripped through the persistent compile cache mis-assign
        # those aliased buffers on jax 0.4.37 CPU (cold-compile exact,
        # cache-hit garbage). The arrays are a few hundred bytes; the
        # copies are free. Pools and seen masks keep their donation.
        self._tick_jit = jax.jit(self._fused_tick,
                                 donate_argnums=(1, 2))
        self._tick_greedy_jit = jax.jit(self._fused_tick_greedy,
                                        donate_argnums=(1, 2))
        # --- prompt-lookup speculative ticks (ISSUE 7 tentpole) -------
        # spec_tokens=k > 0: every fused tick drafts up to k tokens per
        # eligible slot from that request's OWN committed stream (no
        # draft model — the n-gram proposer shared with the batch
        # path's ngram_speculative_generate), verifies all k+1
        # positions in ONE forward through the multi-query paged
        # attention, and commits the per-row accepted length in-program
        # — still one dispatch per tick. EVERY active row is eligible
        # (ISSUE 11: greedy rows accept by argmax prefix, sampled rows
        # by the rejection rule, penalized rows via the per-position
        # penalty scan); a row falls back to the 1-token tick
        # per-request (inside the same program) when block headroom is
        # missing or its accept-rate EMA collapses.
        self._spec_ngram = int(spec_ngram)
        if self._spec_k:
            if self._spec_k < 1:
                raise ValueError("spec_tokens must be >= 0")
            if self._spec_ngram < 1:
                raise ValueError("spec_ngram must be >= 1")
            if not self._fused:
                raise ValueError(
                    "spec_tokens requires fused_tick=True: the "
                    "proposer/verify/commit live inside the fused "
                    "device program")
            self._tick_spec_jit = jax.jit(
                functools.partial(self._fused_tick_spec, greedy=False),
                donate_argnums=(1, 2))
            self._tick_spec_greedy_jit = jax.jit(
                functools.partial(self._fused_tick_spec, greedy=True),
                donate_argnums=(1, 2))
        # --- async token ring (ISSUE 11) ------------------------------
        # the fused tick program appends committed (token, logprob)
        # pairs into a device-resident ring carried in the tick state;
        # the host consumes a dispatch's slice one step later
        # (_drain_oldest). The ring must hold every entry the
        # outstanding dispatches can commit with double-buffer slack:
        # twice a dispatch's advance (the spec window k+1; a plain tick
        # commits 1 and at most two of them are outstanding).
        self._ring_len = max(16, 2 * (self._spec_k + 1),
                             len(self._tick_counter_names))
        # the dispatches not yet drained, oldest first: one between
        # steps, two inside a run-ahead step (_may_run_ahead). Each
        # record keeps ITS program's ring / cursor / active outputs
        # (the state dict is not donated, so they stay readable after
        # the next tick took them as input) and the request each of its
        # rows served, so a drain never credits a slot's next tenant
        self._pending: deque = deque()
        self._drained = np.zeros((self.R,), np.int64)   # consumed cursors
        # readback instrumentation: d2h_syncs counts BLOCKING readbacks
        # (every host-path tick; on the fused path only drains that had
        # to wait, also counted in ring_blocking_drains), ring_drains
        # every ring consumption and ring_scoped_drains the per-row
        # out-of-band ones of cancel/expiry
        self.d2h_syncs = 0
        self.ring_drains = 0
        self.ring_blocking_drains = 0
        self.ring_scoped_drains = 0
        # --- staged slot transitions (ISSUE 14, ISSUE 19) -------------
        # a slot transition packs ONE per-slot descriptor
        # (_pack_descriptor); the pending ones are staged into a
        # device-resident queue ([R, desc_len] int32 + count, carried
        # in the tick state) by a plain H2D upload — no dispatch — and
        # the NEXT tick's program applies them all in a masked batched
        # scatter before computing. One executable, one dispatch,
        # whether the tick carries 0 or R transitions: descriptors
        # coalesce per slot, so R rows always suffice.
        # slots awaiting a patch flush -> whether the patch is the
        # whole descriptor. False: the table row grew and nothing else
        # changed, so the patch carries the table row only
        # (_DESC_TABLE_ONLY): a decoding row's other mirrors may lag
        # the device by a tick
        self._delta_rows: Dict[int, bool] = {}
        # descriptor layout (int32 vector; floats/keys ride as raw
        # bits): [0]=row [1]=lens [2]=last [3]=eos [4]=rem [5]=active
        # [6]=flags (_DESC_KEY_OVERRIDE, _DESC_TABLE_ONLY)
        # [7]=temp [8]=top_k [9]=top_p [10]=rep
        # [11:13]=PRNG key [13]=spec ema [14]=spec tick counter
        # [15:15+M]=block-table row [15+M:]=committed-token row (spec)
        self._desc_len = 15 + self.M + (
            (self.M * self.B + self._spec_k + 1) if self._spec_k else 0)
        # --- tick-phase profiler (ISSUE 20 tentpole) ------------------
        # tick_profile=True times each tick's phases (host staging /
        # H2D / dispatch / device wait / D2H drain) into per-phase
        # registry histograms plus a bounded per-tick ring. OFF (the
        # default) costs one None check per bracket and nothing else —
        # the off path is bitwise the pre-profiler engine. ON changes
        # nothing device-visible either (host clocks + one
        # block_until_ready where the next statement blocks anyway):
        # streams are pinned bitwise across the toggle and the
        # steady-tick 1-dispatch/0-upload pins stay green with the
        # profiler running (tests/test_tick_profile.py).
        # profile_clock: injectable clock for deterministic phase-math
        # tests (same idiom as MetricsTimeSeries(clock=...)); they set
        # the profile's ``cpu_clock`` beside it.
        self.tick_profile = bool(tick_profile)
        self._prof: Optional[_TickPhaseProfile] = None
        if self.tick_profile:
            self._prof = _TickPhaseProfile(
                self._obs_labels, clock=profile_clock,
                capacity=profile_ring_len)
            # the reset()-time flush (ISSUE 20 small fix): a SIGTERM'd
            # replica leaves tickphase_<engine>.json in the run dir
            # beside its series/reqtrace files
            obs.register_flusher(self._flush_tick_profile)

    # ---------------------------------------------------------- placement
    # Uploads and allocations are COMMITTED to the engine's device, like
    # its params and like every program output: a program whose inputs
    # go from uncommitted (first call) to committed (its own outputs fed
    # back) is traced twice.
    def _put(self, x):
        return jax.device_put(x, self.device)

    def _zeros(self, shape, dtype):
        return jnp.zeros(shape, dtype, device=self.device)

    def _cache_rows(self):
        """What one cached token is in one layer, asked of the model
        (``paged_cache_rows``): the (heads, width) of each pool array.
        Models without the method cache K and V, kv heads x head_dim
        each."""
        ask = getattr(self.model, "paged_cache_rows", None)
        if ask is not None:
            return ask()
        cfg = self.model.config
        return ((cfg.num_key_value_heads, cfg.head_dim),) * 2

    def _cache_layout(self) -> List[CacheLayer]:
        """The model's cache layers in order, asked of it too
        (``paged_cache_layers``): a count (one per attention sublayer,
        which is one per layer unless the model says otherwise: a layer
        with two attentions presents two) of layers that all cache
        ``_cache_rows``, or, where they differ, what each one is."""
        ask = getattr(self.model, "paged_cache_layers", None)
        layers = ask() if ask is not None \
            else self.model.config.num_hidden_layers
        if isinstance(layers, int):
            return [CacheLayer(tuple(self._cache_rows()))] * layers
        return [layer if isinstance(layer, StateLayer)
                else CacheLayer(*layer) for layer in layers]

    def _ring_blocks(self, window: int) -> int:
        """Pages a slot of a band-keeping layer's ring: the window, what
        one call writes ahead of it (a prompt chunk, a speculative
        tick's positions) and the page the band's front shares; never
        more than a sequence has."""
        ahead = max(self.chunk or 0, self._spec_k + 1)
        return min(self.M, -(-window // self.B) + -(-ahead // self.B) + 1)

    def _band_behind(self, cached: int) -> int:
        """Pages, over the band-keeping layers, that lie wholly behind
        the band of a row's next query when ``cached`` tokens are in."""
        return sum(max(cached + 1 - w, 0) // self.B for w in self._windows)

    def _band_live(self, cached: int) -> int:
        """Pages, over the band-keeping layers, still inside the bands
        of a row that has ``cached`` tokens in."""
        return len(self._windows) * self._blocks_needed(cached) \
            - self._band_behind(cached)

    def _fresh_device_arrays(self):
        """New pools (per cache layer one [P, B, heads*width] array for
        each entry of the model's cached row: a K/V pair, or one latent
        array; the page-slab form the kernels fetch, so that no program
        ever copies a pool) and the per-row seen-token masks for the
        repetition penalty (seeded by the prefill scatter, updated inside the
        jitted decode step). ``hard_reset`` takes fresh ones too: the
        old arrays may be donated into a dead or in-flight program."""
        cfg = self.model.config
        pools = []
        for layer in self._layout:
            if isinstance(layer, StateLayer):   # a slot's, not a block's
                pools.append(tuple(self._zeros((self.R,) + tuple(shape),
                                               dtype)
                                   for shape, dtype in layer.arrays))
                continue
            # a band-keeping layer: a ring a slot and the garbage block
            P = self.P if layer.window is None \
                else self.R * self._ring_blocks(layer.window) + 1
            pools.append(tuple(
                self._zeros((P, self.B, heads * width), cfg.dtype)
                for heads, width in layer.rows))
        if self._n_state:       # ``state_resets``, ``state_carries``
            pools.append((self._zeros((2,), jnp.int32),))
        return pools, self._zeros((self.R, cfg.vocab_size), bool)

    def decode_route(self) -> str:
        """The attention path this engine's decode tick takes
        (``paged_decode_route`` asked with the tick's own q and every
        cache layer's pool shapes): "ragged" is the Pallas kernel,
        "dense" the XLA whole-table gather, which is the answer as soon
        as ONE layer takes it."""
        cfg = self.model.config
        routes = set()
        for layer, pool in zip(self._layout, self.pools):
            if not layer.rows:                  # no K/V to attend over
                continue
            heads, width = layer.rows[0]        # a query is as wide as
            q = jax.ShapeDtypeStruct(           # a cached (key) head
                (self.R, self._spec_k + 1, cfg.num_attention_heads, width),
                cfg.dtype)
            routes.add(paged_decode_route(q, pool[0], heads))
        return "ragged" if routes == {"ragged"} else "dense"

    # ------------------------------------------------------ tick profiler
    @property
    def tick_phase_totals(self) -> Optional[Dict[str, float]]:
        """Cumulative per-phase milliseconds (None with the profiler
        off): the in-tick phases (``obs.TICK_PHASES``, what
        ``serve_loadgen`` sums into ``phase_breakdown``) and the
        worker loop's (``obs.LOOP_PHASES``)."""
        return dict(self._prof.totals) if self._prof is not None \
            else None

    @property
    def tick_wall_ms_total(self) -> float:
        """Cumulative measured tick wall (ms; 0 with the profiler
        off). By the residual construction the ``obs.TICK_PHASES``
        entries of ``tick_phase_totals`` sum to it, up to per-tick
        clamping."""
        return self._prof.wall_total_ms if self._prof is not None \
            else 0.0

    def tick_profile_summary(self) -> Optional[Dict[str, Any]]:
        """Tick count, tick wall, in-tick and loop phase totals and the
        thread's wall so far (None with the profiler off): what
        ``/debugz`` shows and a ``/profilez`` window subtracts."""
        return self._prof.summary() if self._prof is not None else None

    def _phase(self, phase: str):
        """``with self._phase("stage"): ...`` — one bracket of the tick
        profiler, the only form there is: a ``_PhaseSpan``, or the
        shared no-op with the profiler off (one ``None`` check)."""
        prof = self._prof
        return _NO_PHASE if prof is None else prof.span(phase)

    def loop_phase(self, phase: str):
        """``with engine.loop_phase("emit"): ...`` — the same bracket
        under its public name: how the thread that drives ``step()``
        reports what it does BETWEEN steps (one of ``obs.LOOP_PHASES``),
        so that every moment of that thread has a name."""
        return self._phase(phase)

    def tick_profile_doc(self) -> Optional[Dict[str, Any]]:
        """The ``tickphase/1`` ring document (None, profiler off)."""
        if self._prof is None:
            return None
        return self._prof.to_doc(self._obs_labels["engine"])

    def dump_tick_profile(self, path: str) -> Optional[str]:
        """Atomic JSON dump of the tick-phase ring (the artifact
        ``obs_report phase_decompose`` / ``trace_export`` ingest; the
        gateway writes one per replica on drain and on a ``/profilez``
        capture). No-op with the profiler off."""
        doc = self.tick_profile_doc()
        if doc is None:
            return None
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path

    def _flush_tick_profile(self) -> Optional[str]:
        """reset()/drain-time flush into the configured run dir."""
        d = obs.run_dir()
        if d is None or self._prof is None:
            return None
        try:
            return self.dump_tick_profile(os.path.join(
                d, f"tickphase_{self._obs_labels['engine']}.json"))
        except Exception:
            return None

    def _tick_phase_fields(self) -> Optional[Dict[str, float]]:
        """Phase split attached to tick trace events (the most recent
        COMPLETED tick's — ring drains commit one dispatch behind)."""
        return self._prof.last_phases() if self._prof is not None \
            else None

    @property
    def stats(self) -> Dict[str, int]:
        """Scheduler-counter snapshot (pre-migration dict shape; the
        values now come from the observability registry), plus
        ``decode_ticks``, the count per-tick figures divide by. With
        the tick profiler on, also the thread's CPU under each phase so
        far, whole microseconds, as ``phase_cpu_us.<phase>`` (the wall
        is ``tick_phase_totals``): a snapshot of the counters then
        holds both sides of the thread's time."""
        out = {k: int(c.value) for k, c in self._counters.items()}
        out["decode_ticks"] = self.decode_ticks
        if self._prof is not None:
            for p, ms in self._prof.cpu_totals.items():
                out["phase_cpu_us." + p] = int(ms * 1e3)
        return out

    def _count(self, key: str, n: int = 1):
        self._counters[key].inc(n)

    def _count_chunk_rule(self):
        """``_CHUNK_RULE_COUNTERS`` of one prompt call's dispatch."""
        if self._n_state:
            self._count("chunk_rule_layer_calls", self._n_state)
            self._count("chunk_rule_kernel_calls", self._n_chunk_kernel)

    def _count_chunk_experts(self, positions: int):
        """``_CHUNK_EXPERTS_COUNTERS`` of one prompt call's dispatch, a
        call of ``positions`` tokens."""
        if not self._expert_stacks:
            return
        if positions not in self._n_experts_grouped:
            self._n_experts_grouped[positions] = sum(
                chunk_experts_route(jax.ShapeDtypeStruct(
                    (positions, w.shape[1]), w.dtype), w) == "grouped"
                for w in self._expert_stacks)
        self._count("chunk_experts_layer_calls", len(self._expert_stacks))
        self._count("chunk_experts_grouped_calls",
                    self._n_experts_grouped[positions])

    def chunk_attn_routes(self) -> list:
        """The path each cache layer's continuation chunk takes
        (``chunk_attn_route`` asked as the traced program asks it: the
        chunk's own q, the layer's query heads over its kv heads, and
        the layer's K pool); None for a layer that is not walked: state,
        or a latent row, which is expanded."""
        cfg = self.model.config     # (a stub's may name no heads)
        routes = []
        for layer, pool in zip(self._layout, self.pools):
            if len(layer.rows) != 2:
                routes.append(None)
                continue
            heads, width = layer.rows[0]
            h = layer.heads or getattr(cfg, "num_attention_heads", heads)
            q = jax.ShapeDtypeStruct((1, self.chunk, h, width),
                                     pool[0].dtype)
            routes.append(chunk_attn_route(q, pool[0], heads))
        return routes

    def _count_chunk_attention(self, start: int, cached: int):
        """``_CHUNK_ATTN_COUNTERS`` of one ``_chunk_jit.alone`` call of
        a chunk from position ``start`` whose row holds ``cached``
        tokens with the chunk's own in."""
        if self._chunk_attn_routes is None:
            self._chunk_attn_routes = self.chunk_attn_routes()
        scored = live = calls = kernel = 0
        for layer, route in zip(self._layout, self._chunk_attn_routes):
            if route is None:
                continue    # state, or a latent row (expanded, not walked)
            M = self.M if layer.window is None \
                else self._ring_blocks(layer.window)
            s, n = chunk_attention_positions(
                cached, M, self.B, layer.window is not None,
                tiles=(start, self.chunk) if route == "kernel" else None,
                window=layer.window)
            scored, live = scored + s, live + n
            calls, kernel = calls + 1, kernel + (route == "kernel")
        self._count("chunk_attn_positions_scored", scored)
        self._count("chunk_attn_positions_live", live)
        self._count("chunk_attn_layer_calls", calls)
        self._count("chunk_attn_kernel_calls", kernel)

    # ------------------------------------------------------------ jitted
    def _paged_caches(self, call, pools, tables, lens, slots=None,
                      live=None, fresh=None):
        """Each cache layer's view of ``pools`` in a call of kind
        ``call`` (``PagedKV.call``: each program says which it builds),
        for the rows of ``tables`` [rows, M], which are the engine's
        slots in order unless ``slots`` [rows] names them. A
        band-keeping layer's table is not the allocator's: it is the
        ring of pages its slot owns by position, block 0 of its pool
        being the garbage block. A state layer's view is a
        ``SlotState``: ``live`` (a decode tick's rows that advance) and
        ``fresh`` (a prompt call's rows that start from zero) are read
        by it alone."""
        out = []
        for layer, p in zip(self._layout, pools):
            if isinstance(layer, StateLayer):
                out.append(SlotState(tuple(p), slots, lens, live, fresh,
                                     call))
                continue
            tbl = tables
            if layer.window is not None:
                Mw = self._ring_blocks(layer.window)
                rows = jnp.arange(tables.shape[0]) if slots is None \
                    else slots
                tbl = 1 + rows[:, None] * Mw + jnp.arange(Mw)[None, :]
            out.append(PagedKV(p[0], p[1] if len(p) > 1 else None, tbl,
                               lens, layer.rows[0][0],
                               layer.window is not None, call))
        return out

    def _decode_step(self, params, pools, tables, lens, last_tokens,
                     keys, temps, tks, tps, seen, reps, active):
        from .sampling import repetition_penalty_rows, sample_token_rows
        caches = self._paged_caches("decode", pools, tables, lens,
                                    live=active)
        logits, new_caches = self.fn(params, last_tokens[:, None],
                                     kv_caches=caches,
                                     positions=lens[:, None])
        row = repetition_penalty_rows(logits[:, -1].astype(jnp.float32),
                                      seen, reps)
        nxt, lps, new_keys = sample_token_rows(row, keys, temps, tks, tps)
        # active-guarded scatter: inactive rows (idle OR mid-chunk-
        # prefill) sample garbage that must not pollute their masks —
        # the seen analogue of the authoritative req.key protection
        seen = seen.at[jnp.arange(self.R), nxt].max(active)
        return (nxt, lps, new_keys, seen,
                self._pools_out(new_caches, pools))

    def _decode_step_greedy(self, params, pools, tables, lens,
                            last_tokens, seen, reps, active):
        """Argmax-only tick for the common all-greedy batch: skips the
        sort/softmax/categorical machinery (and the key splits) that
        sample_token_rows pays on the hottest serving path. greedy +
        repetition_penalty is still deterministic, so the penalty rides
        here too (a no-op where() for all-1.0 rows — bit-exact)."""
        from .sampling import repetition_penalty_rows
        caches = self._paged_caches("decode", pools, tables, lens,
                                    live=active)
        logits, new_caches = self.fn(params, last_tokens[:, None],
                                     kv_caches=caches,
                                     positions=lens[:, None])
        raw = repetition_penalty_rows(logits[:, -1].astype(jnp.float32),
                                      seen, reps)
        nxt = jnp.argmax(raw, axis=-1).astype(jnp.int32)
        lps = jnp.take_along_axis(jax.nn.log_softmax(raw, axis=-1),
                                  nxt[:, None], axis=-1)[:, 0]
        seen = seen.at[jnp.arange(self.R), nxt].max(active)
        return nxt, lps, seen, self._pools_out(new_caches, pools)

    def _pools_out(self, new_caches, pools, events=None, moved=False):
        """The pools a program hands back: each cache layer's arrays
        and, behind them in an engine over state layers, the two
        counters its prompt calls add up (``_STATE_COUNTERS``): those of
        ``pools`` plus ``events`` (None: as they were), or zeros once a
        tick has ``moved`` them to the token ring."""
        out = [c.pool for c in new_caches]
        if self._n_state:
            ev = pools[-1][0]
            out.append((jnp.zeros_like(ev) if moved
                        else ev if events is None else ev + events,))
        return out

    def _state_events(self, fresh, real=True):
        """A prompt call's (``state_resets``, ``state_carries``): of its
        ``real`` rows (all of them unless given), those that the
        ``fresh`` handed to the state layers starts from zero, and the
        others. None for an engine without state layers (its ``fresh``
        is None)."""
        if fresh is None:
            return None
        return jnp.stack([jnp.sum(real & fresh),
                          jnp.sum(real & ~fresh)]).astype(jnp.int32)

    # ------------------------------------------- fused device-resident tick
    def _tick_counts(self, st):
        """The model's own collector (``count_tick``) of the counters it
        names (``tick_counters``), over the forward traced inside the
        ``with``; ``.total`` is None for a model that names none, and
        the program is then what it was."""
        if not self._model_counter_names:
            return contextlib.nullcontext(_NO_COUNTS)
        return self.model.count_tick(st["active"])

    def _band_counts(self, st):
        """``_BAND_COUNTERS`` of this tick (None for an engine without
        band-keeping layers): a live row's query at position ``lens``
        sees ``lens + 1`` tokens in a whole-context layer and at most
        the window of them in a band-keeping one."""
        if not self._windows:
            return None
        seen = st["lens"] + 1
        pages = (seen + self.B - 1) // self.B
        zero = jnp.zeros_like(seen)
        wb, fb, wt, ft, rel = zero, zero, zero, zero, zero
        for layer in self._layout:
            w = layer.window
            if w is None:
                fb, ft = fb + pages, ft + seen
                continue
            behind = jnp.maximum(seen - w, 0) // self.B
            wb, wt = wb + pages - behind, wt + jnp.minimum(seen, w)
            rel = rel + jnp.maximum(seen + 1 - w, 0) // self.B - behind
        live = st["active"].astype(seen.dtype)
        return jnp.stack([jnp.sum(c * live) for c in (wb, fb, wt, ft, rel)])

    def _state_counts(self, st, events):
        """``_STATE_COUNTERS`` of this tick (None for an engine without
        state layers): every state layer ran once, by the route its
        state's geometry takes as this program is traced, and updated
        the live rows; ``events`` is what the prompt calls since the
        last tick added up."""
        if not self._n_state:
            return None
        n = jnp.int32(self._n_state)
        kernel = sum(
            state_step_route(jax.ShapeDtypeStruct(
                (self.R,) + tuple(l.arrays[0][0]), l.arrays[0][1]))
            == "kernel"
            for l in self._layout if isinstance(l, StateLayer))
        return jnp.concatenate([
            jnp.stack([n, jnp.int32(kernel),
                       n * jnp.sum(st["active"].astype(jnp.int32))]),
            events])

    def _ring_counts(self, ring, counts, st, events=None):
        """The tick's counters (the model's, then the engine's own of a
        band-keeping cache and of state layers) added into the ring's
        spare row, which the drain reads with the tokens: no array of
        their own."""
        parts = [c.astype(ring.dtype)
                 for c in (counts.total, self._band_counts(st),
                           self._state_counts(st, events))
                 if c is not None]
        if not parts:
            return ring
        total = jnp.concatenate(parts)
        return ring.at[self.R, :total.shape[0]].add(total)

    def _fused_epilogue(self, st, new_caches, seen, nxt, lps, new_keys,
                        counts, pools):
        """Device-side tick bookkeeping: advance active rows' lengths /
        last tokens / budgets, fold the emitted token into the seen
        mask, and derive the done flag (eos hit or budget exhausted —
        the same predicate the host evaluates after appending). The
        active mask deactivates done rows so an unserviced row can never
        advance twice; stop-sequence matching stays host-side and is
        reconciled at the finish transition."""
        act = st["active"]
        acti = act.astype(jnp.int32)
        seen = seen.at[jnp.arange(self.R), nxt].max(act)
        rem = st["rem"] - acti
        done = act & (((st["eos"] >= 0) & (nxt == st["eos"]))
                      | (rem <= 0))
        new_st = dict(st)
        new_st.update(lens=st["lens"] + acti,
                      last=jnp.where(act, nxt, st["last"]),
                      keys=new_keys, rem=rem, active=act & ~done)
        # async token ring (ISSUE 11): append this tick's committed
        # token into each active row's ring slot (write cursor mod
        # ring length); inactive rows keep their current entry
        r = jnp.arange(self.R)
        idx = st["wcur"] % st["ring"].shape[1]
        new_st.update(
            ring=self._ring_counts(st["ring"].at[r, idx].set(
                jnp.where(act, nxt, st["ring"][r, idx])), counts, st,
                pools[-1][0] if self._n_state else None),
            rlps=st["rlps"].at[r, idx].set(
                jnp.where(act, lps, st["rlps"][r, idx])),
            wcur=st["wcur"] + acti)
        return (nxt, lps, done, seen,
                self._pools_out(new_caches, pools, moved=True), new_st)

    def _fused_tick(self, params, pools, seen, st):
        """ONE compiled program for a mixed greedy/sampled tick:
        attention (ragged paged kernel when gated) → repetition penalty
        → per-row sampling → done flags + device-state advance. Key
        splits follow `_decode_step` exactly (all rows split), so
        sampled streams are bit-identical to the host-tick path. The
        fused patch stage (ISSUE 19) applies any staged transition
        descriptors first — same program, zero extra dispatches."""
        from .sampling import repetition_penalty_rows, sample_token_rows
        with jax.named_scope("patch"):
            st = self._apply_patch_queue(st)
        caches = self._paged_caches("decode", pools, st["tables"],
                                    st["lens"], live=st["active"])
        with self._tick_counts(st) as counts:
            logits, new_caches = self.fn(params, st["last"][:, None],
                                         kv_caches=caches,
                                         positions=st["lens"][:, None])
        with jax.named_scope("penalty"):    # the last position's slice too
            last = logits[:, -1].astype(jnp.float32)
        raw = repetition_penalty_rows(last, seen, st["reps"])
        nxt, lps, new_keys = sample_token_rows(raw, st["keys"],
                                               st["temps"], st["tks"],
                                               st["tps"])
        with jax.named_scope("epilogue"):
            return self._fused_epilogue(st, new_caches, seen, nxt, lps,
                                        new_keys, counts, pools)

    def _fused_tick_greedy(self, params, pools, seen, st):
        """Argmax-only fused tick (same specialization contract as
        `_decode_step_greedy`: chosen when every ACTIVE row is greedy;
        keys pass through untouched, exactly like the host path's
        no-split greedy executable). Opens with the same fused patch
        stage as `_fused_tick`."""
        from .sampling import repetition_penalty_rows
        with jax.named_scope("patch"):
            st = self._apply_patch_queue(st)
        caches = self._paged_caches("decode", pools, st["tables"],
                                    st["lens"], live=st["active"])
        with self._tick_counts(st) as counts:
            logits, new_caches = self.fn(params, st["last"][:, None],
                                         kv_caches=caches,
                                         positions=st["lens"][:, None])
        with jax.named_scope("penalty"):    # the last position's slice too
            last = logits[:, -1].astype(jnp.float32)
        raw = repetition_penalty_rows(last, seen, st["reps"])
        with jax.named_scope("sample"):
            nxt = jnp.argmax(raw, axis=-1).astype(jnp.int32)
            lps = jnp.take_along_axis(jax.nn.log_softmax(raw, axis=-1),
                                      nxt[:, None], axis=-1)[:, 0]
        with jax.named_scope("epilogue"):
            return self._fused_epilogue(st, new_caches, seen, nxt, lps,
                                        st["keys"], counts, pools)

    def _fused_tick_spec(self, params, pools, seen, st, *, greedy: bool):
        """ONE compiled program for a speculative multi-token tick
        (ISSUE 7, rejection-sampled verify ISSUE 11): per-row
        prompt-lookup drafts -> one k+1-position verify forward through
        the multi-query paged attention -> a sequential in-program
        accept scan over the window -> commit of the per-row accepted
        length (seq lens, committed-stream buffer, budgets, done flags,
        adaptive-k EMA, token ring all advance on device).

        Per-row fallback, not per-batch: a row drafts 0..k tokens
        (``kprop``) depending on its write headroom (allocated blocks,
        read off the table — unallocated entries are the garbage block
        id 0), its remaining budget, and its accept EMA; kprop=0 rows
        ARE the plain 1-token tick inside the same program, so mixed
        spec/non-spec batches stay one dispatch.

        The accept scan walks the k+1 window positions sequentially
        (T is small and each step is O(R*V) elementwise work):

        - position j's logits get the repetition penalty over ``seen``
          AS OF position j — the window's own earlier commits included
          — so penalized rows compose exactly (bitwise vs their
          spec-off sequential ticks when greedy);
        - greedy rows accept draft_j iff it equals the penalized
          argmax (the ISSUE-7 longest-prefix rule, bitwise-pinned);
        - sampled rows run the Leviathan residual rule
          (``sampling.residual_resample_rows``): accept draft_j with
          probability p_j(draft_j) under the row's filtered
          distribution, else emit a residual resample — every
          position's marginal equals the plain tick's, so per-request
          DISTRIBUTIONS are preserved (not bitwise streams: the PRNG
          consumption pattern differs from 1-token ticks by design).
          Mixed ticks split every row's key once (the same per-tick
          carry rate as `_fused_tick`) and fold the tick subkey per
          position;
        - a row stays alive past j only if it accepted a real draft
          there; the first rejection's emitted token IS the
          correction (or the bonus at position k after a full
          accept); eos and budget truncate inside the scan.

        Rejected drafts' K/V and buffer writes sit beyond the
        committed cursor and are overwritten before they become
        readable (the batch path's rewind-free trick)."""
        from .prompt_lookup import mask_drafts, propose_ngram_rows
        from .sampling import (fold_in_rows, repetition_penalty_rows,
                               residual_resample_rows, split_key_rows)
        st = self._apply_patch_queue(st)   # fused patch stage (ISSUE 19)
        k = self._spec_k
        T = k + 1
        lens, active, temps = st["lens"], st["active"], st["temps"]
        rem, tables = st["rem"], st["tables"]
        C = lens + 1                  # committed tokens (active rows)
        # per-row draft cap: adaptive want ∧ write headroom ∧ budget
        alloc = jnp.sum(tables > 0, axis=1).astype(jnp.int32)
        capw = alloc * self.B - lens          # writable slots from lens
        probe = (st["tickc"] % _SPEC_PROBE_EVERY) == 0
        want = jnp.where(st["ema"] >= _SPEC_EMA_FLOOR, k,
                         jnp.where(probe, 1, 0))
        kprop = jnp.where(
            active,
            jnp.clip(jnp.minimum(jnp.minimum(want, capw - 1), rem - 1),
                     0, k), 0)
        drafts = propose_ngram_rows(st["toks"], C, k, self._spec_ngram,
                                    fill=-1)
        drafts = mask_drafts(drafts, kprop)   # -1 never matches/commits
        ids = jnp.concatenate([st["last"][:, None],
                               jnp.maximum(drafts, 0)], axis=1)
        positions = lens[:, None] + jnp.arange(T)[None, :]
        caches = self._paged_caches("decode", pools, tables, lens)
        with self._tick_counts(st) as counts:
            logits, new_caches = self.fn(params, ids, kv_caches=caches,
                                         positions=positions)
        logits = logits.astype(jnp.float32)
        if greedy:
            new_keys = subs = st["keys"]
        else:
            new_keys, subs = split_key_rows(st["keys"])
        r_idx = jnp.arange(self.R)
        # draft column j for traced j (the scan's bonus position k
        # reads the appended -1 column: no draft, plain emit)
        drafts_ext = jnp.concatenate(
            [drafts, jnp.full((self.R, 1), -1, drafts.dtype)], axis=1)

        def pos_step(carry, j):
            seen_c, alive, nem, macc, eos_hit = carry
            raw_j = repetition_penalty_rows(logits[:, j], seen_c,
                                            st["reps"])
            d_j = drafts_ext[:, j]
            if greedy:
                tok = jnp.argmax(raw_j, axis=-1).astype(jnp.int32)
                acc = (d_j >= 0) & (tok == d_j)
                lp = jnp.take_along_axis(
                    jax.nn.log_softmax(raw_j, axis=-1),
                    tok[:, None], axis=-1)[:, 0]
            else:
                tok, acc, lp = residual_resample_rows(
                    raw_j, d_j, fold_in_rows(subs, j), temps,
                    st["tks"], st["tps"])
            emit = alive
            seen_c = seen_c.at[r_idx, tok].max(emit)
            nem = nem + emit.astype(jnp.int32)
            macc = macc + (emit & acc).astype(jnp.int32)
            is_eos = (st["eos"] >= 0) & (tok == st["eos"])
            eos_hit = eos_hit | (emit & is_eos)
            alive = emit & acc & ~is_eos & (nem < rem)
            return (seen_c, alive, nem, macc, eos_hit), (tok, lp)

        carry0 = (seen, active, jnp.zeros((self.R,), jnp.int32),
                  jnp.zeros((self.R,), jnp.int32),
                  jnp.zeros((self.R,), bool))
        (seen, _, nem, m, eos_hit), (Yt, LPt) = jax.lax.scan(
            pos_step, carry0, jnp.arange(T))
        G = jnp.swapaxes(Yt, 0, 1)                            # [R, T]
        LP = jnp.swapaxes(LPt, 0, 1)
        n_eff = jnp.where(active, nem, 0)
        done = active & (eos_hit | (rem - n_eff <= 0))
        # commit: committed-stream buffer takes all T candidates —
        # positions past n_eff sit beyond the committed cursor, are
        # never matched, and are overwritten next tick
        toks = st["toks"].at[r_idx[:, None],
                             C[:, None] + jnp.arange(T)[None, :]].set(G)
        last = jnp.where(
            active,
            jnp.take_along_axis(
                G, jnp.maximum(n_eff - 1, 0)[:, None], axis=1)[:, 0],
            st["last"])
        ema = jnp.where(
            kprop > 0,
            (1.0 - _SPEC_EMA_ALPHA) * st["ema"] + _SPEC_EMA_ALPHA
            * (m.astype(jnp.float32)
               / jnp.maximum(kprop.astype(jnp.float32), 1.0)),
            st["ema"])
        new_st = dict(st)
        new_st.update(lens=lens + n_eff, last=last, keys=new_keys,
                      rem=rem - n_eff, active=active & ~done,
                      toks=toks, ema=ema,
                      tickc=st["tickc"] + active.astype(jnp.int32))
        # ring append of the emitted window (ISSUE 11): entries
        # wcur..wcur+n_eff-1 mod ring_len; non-emitted positions
        # keep the current ring contents. T <= ring_len/2, so the
        # window's indices never collide within a row.
        Lr = st["ring"].shape[1]
        idx = (st["wcur"][:, None] + jnp.arange(T)[None, :]) % Lr
        emit_win = jnp.arange(T)[None, :] < n_eff[:, None]
        new_st.update(
            ring=self._ring_counts(
                st["ring"].at[r_idx[:, None], idx].set(
                    jnp.where(emit_win, G,
                              st["ring"][r_idx[:, None], idx])),
                counts, st),
            rlps=st["rlps"].at[r_idx[:, None], idx].set(
                jnp.where(emit_win, LP, st["rlps"][r_idx[:, None], idx])),
            wcur=st["wcur"] + n_eff,
            kprop_last=kprop, macc_last=m)
        return (G, LP, n_eff, kprop, m, done, seen,
                [c.pool for c in new_caches], new_st)

    # ------------------------------- staged slot transitions (ISSUE 14, 19)
    def _mark_dirty(self, slot_id: int, table_only: bool = False):
        """A slot transition touched ``slot_id``'s mirrors: queue its
        descriptor for the next flush (several transitions of one slot
        coalesce into its final state; ``table_only`` is block growth,
        which any other transition of the slot subsumes). Before there
        is a device state to patch, the rebuild that makes it reads the
        mirrors whole."""
        if self._dev is None or self._dev_dirty:
            self._dev_dirty = True
        else:
            self._delta_rows[slot_id] = not table_only \
                or self._delta_rows.get(slot_id, False)

    @staticmethod
    def _slot_row_fields(s):
        """The (last, eos, rem, active) scalars ONE slot contributes
        to the device tick state — shared by the full rebuild (which
        stacks R of them) and the slot's descriptor (which carries
        exactly one), like ``token_buffer_row``/``seed_key_row``, so
        the two uploads cannot drift apart."""
        eos = -1
        rem = last = act = 0
        if s is not None:
            if s.eos is not None:
                eos = s.eos
            rem = max(s.max_new - len(s.tokens), 0)
            if s.tokens and s.prefill_pos >= len(s.prompt):
                act = 1
                last = s.tokens[-1]
        return last, eos, rem, act

    def _pack_descriptor(self, i: int,
                         table_only: bool = False) -> np.ndarray:
        """Pack slot ``i``'s CURRENT host-mirror state into one int32
        descriptor vector (floats and the uint32 PRNG key ride as raw
        bits). Field values follow ``_refresh_dev``'s per-row rules
        exactly (``_slot_row_fields`` is the shared rule), so a
        patched row is byte-for-byte what a full rebuild would have
        uploaded for it. The PRNG key is flagged
        authoritative only for rows the HOST re-keyed (fresh admits,
        chunk-final): for every other row the device key stream —
        possibly advanced by sampled ticks since the last rebuild —
        must survive the patch untouched. ``table_only`` (a decoding
        row whose table grew) carries the table row and the flag that
        makes the program leave everything else alone: with a tick
        undrained, ``seq_lens`` and ``tokens[-1]`` are one behind the
        device's ``lens`` and ``last``."""
        s = self.slots[i]
        d = np.zeros((self._desc_len,), np.int32)
        d[0] = i
        d[15:15 + self.M] = self.block_tables[i]
        if table_only:
            d[6] = _DESC_TABLE_ONLY
            return d
        d[1] = self.seq_lens[i]
        d[2], d[3], d[4], d[5] = self._slot_row_fields(s)
        d[6] = _DESC_KEY_OVERRIDE if i in self._key_overrides else 0
        d[7] = np.float32(self.temps[i]).view(np.int32)
        d[8] = self.top_ks[i]
        d[9] = np.float32(self.top_ps[i]).view(np.int32)
        d[10] = np.float32(self.reps[i]).view(np.int32)
        d[11:13] = self.keys[i].view(np.int32)
        if self._spec_k:
            from .prompt_lookup import token_buffer_row
            d[13] = np.float32(s.spec_ema if s is not None
                               else 1.0).view(np.int32)
            # d[14] (spec tick counter) stays 0: a patched row's probe
            # cadence restarts, exactly what a rebuild did for it
            d[15 + self.M:] = token_buffer_row(
                s.prompt + s.tokens if s is not None else (),
                self._desc_len - 15 - self.M)
        return d

    def _apply_patch_queue(self, st):
        """The patch stage (ISSUE 19): ONE masked batched scatter
        applying every staged descriptor in ``st["pq"]`` (valid rows:
        index < ``st["pqn"]``) to the device tick state, traced at the
        TOP of every fused tick program — the queue drains in the same
        dispatch that computes the tick, so a transition wave of any
        size costs zero extra dispatches. Ring arrays and write cursors
        are deliberately untouched — the cursors are monotone and the
        host's drained cursor already equals the row's device cursor
        whenever a transition patches it (every deactivation passes
        through a drain first), so a readmitted slot simply continues
        the ring where the previous tenant stopped.
        Invalid queue entries are routed to the out-of-bounds row index
        R and dropped (``mode="drop"``): a zero-count queue makes every
        scatter a bitwise no-op, which is what lets the stage ride
        steady ticks for free; a ``_DESC_TABLE_ONLY`` entry is valid for
        the table scatter and dropped by every other. Descriptor rows
        are unique (host coalescing keys the pending set by slot), so
        scatter order never matters. ``pqn`` resets to 0 in-program;
        the staged ``pq`` array itself is replaced host-side at the
        next flush."""
        from .sampling import override_key_rows
        pq, pqn = st["pq"], st["pqn"]
        M = self.M
        valid = jnp.arange(pq.shape[0]) < pqn
        whole = valid & ((pq[:, 6] & _DESC_TABLE_ONLY) == 0)
        rows = jnp.where(whole, pq[:, 0], self.R)

        def f32(x):
            return jax.lax.bitcast_convert_type(x, jnp.float32)

        def scat(arr, vals, at=rows):
            return arr.at[at].set(vals, mode="drop")

        new = dict(st)
        new["tables"] = scat(st["tables"], pq[:, 15:15 + M],
                             jnp.where(valid, pq[:, 0], self.R))
        new["lens"] = scat(st["lens"], pq[:, 1])
        new["last"] = scat(st["last"], pq[:, 2])
        new["eos"] = scat(st["eos"], pq[:, 3])
        new["rem"] = scat(st["rem"], pq[:, 4])
        new["active"] = scat(st["active"], pq[:, 5] != 0)
        new["temps"] = scat(st["temps"], f32(pq[:, 7]))
        new["tks"] = scat(st["tks"], pq[:, 8])
        new["tps"] = scat(st["tps"], f32(pq[:, 9]))
        new["reps"] = scat(st["reps"], f32(pq[:, 10]))
        keys = jax.lax.bitcast_convert_type(pq[:, 11:13], jnp.uint32)
        new["keys"] = override_key_rows(
            st["keys"], pq[:, 0], keys,
            whole & ((pq[:, 6] & _DESC_KEY_OVERRIDE) != 0))
        if "toks" in st:
            new["toks"] = scat(st["toks"], pq[:, 15 + M:])
            new["ema"] = scat(st["ema"], f32(pq[:, 13]))
            new["tickc"] = scat(st["tickc"], pq[:, 14])
        new["pqn"] = jnp.zeros_like(pqn)
        return new

    def _flush_patches(self):
        """Hand every pending transition to the device, immediately
        before a dispatch: the coalesced descriptors are STAGED into
        the device-resident patch queue with one plain H2D upload — no
        dispatch — and the imminent tick program's
        ``_apply_patch_queue`` stage applies them all in its batched
        scatter. A whole descriptor is packed from mirrors the step's
        drain made current; with a tick undrained (a run-ahead step)
        only table-only ones are pending.

        The caller contract that makes staging safe: `_sync_dev` is
        only ever invoked by `_decode_fused` immediately before its
        dispatch, so a staged queue is always consumed by the very next
        program and key overrides can be discarded at staging time."""
        if not self._pending and \
                int(self._drained.max(initial=0)) > _RING_CURSOR_LIMIT:
            # int32 ring-cursor headroom guard: the device write
            # cursors grow until a rebuild zeroes them, which needs
            # the ring drained (a run-ahead step leaves it to the next
            # transition that is not growth). Counted, so a
            # long-lived replica's lone rebuild reads as cursor
            # hygiene, not a bug.
            self.ring_cursor_rollovers += 1
            self._count("ring_cursor_rollovers")
            self._refresh_dev()
            return
        rows = sorted(self._delta_rows)
        # the queue has a row a slot and descriptors coalesce per slot
        assert len(rows) <= self.R, rows
        pq = np.zeros((self.R, self._desc_len), np.int32)
        for j, i in enumerate(rows):
            whole = self._delta_rows[i]
            pq[j] = self._pack_descriptor(i, table_only=not whole)
            if whole:
                self._key_overrides.discard(i)
        with self._phase("h2d"):
            self._dev["pq"] = self._put(pq)
            self._dev["pqn"] = self._put(np.int32(len(rows)))
        nbytes = pq.nbytes + 4
        self.h2d_uploads += 1
        self.h2d_upload_bytes += nbytes
        self.patches_fused += len(rows)
        self._count("patches_fused", len(rows))
        self._count("h2d_upload_bytes", nbytes)
        self._h_bytes.observe(nbytes)
        self._delta_rows.clear()

    def _sync_dev(self):
        """Bring the device tick state up to date before a dispatch:
        full rebuild when forced (first dispatch, ``hard_reset``), else
        stage the pending descriptors."""
        if self._dev is None or self._dev_dirty:
            self._refresh_dev()
        elif self._delta_rows:
            self._flush_patches()

    def _sync_keys_from_dev(self):
        """Fold the device PRNG keys back into the host mirror. Rows the
        host re-keyed since the last upload (`_key_overrides`: fresh
        admissions, chunk-final authoritative keys) keep their host
        value — the device copy is stale for them until the next
        refresh uploads it."""
        if self._dev is None or not self._dev_keys_dirty:
            return
        dk = np.asarray(self._dev["keys"])
        for r in range(self.R):
            if r not in self._key_overrides:
                self.keys[r] = dk[r]
        self._dev_keys_dirty = False

    def _refresh_dev(self):
        """FULL rebuild of the device-resident tick state from the host
        mirrors: the first dispatch, the one after ``hard_reset`` and
        the ring-cursor headroom guard. Every other transition rides
        the staged queue (``_flush_patches``)."""
        self._sync_keys_from_dev()
        self._key_overrides.clear()
        eos = np.full((self.R,), -1, np.int32)
        rem = np.zeros((self.R,), np.int32)
        last = np.zeros((self.R,), np.int32)
        act = np.zeros((self.R,), bool)
        for i, s in enumerate(self.slots):
            last[i], eos[i], rem[i], a = self._slot_row_fields(s)
            act[i] = bool(a)
        self.h2d_uploads += 1
        self.full_rebuilds += 1
        self._count("full_rebuilds")
        nbytes = (self.block_tables.nbytes + self.seq_lens.nbytes
                  + last.nbytes + self.keys.nbytes + self.temps.nbytes
                  + self.top_ks.nbytes + self.top_ps.nbytes
                  + self.reps.nbytes + eos.nbytes + rem.nbytes
                  + act.nbytes)
        with self._phase("h2d"):
            self._dev = dict(
                tables=self._put(self.block_tables),
                lens=self._put(self.seq_lens),
                last=self._put(last),
                keys=self._put(self.keys),
                temps=self._put(self.temps),
                tks=self._put(self.top_ks),
                tps=self._put(self.top_ps),
                reps=self._put(self.reps),
                eos=self._put(eos),
                rem=self._put(rem),
                active=self._put(act),
            )
            if self._spec_k:
                # committed-stream buffer the n-gram proposer matches over
                # (prompt + emitted tokens per slot; the +k+1 tail slack
                # absorbs the tick's unconditional candidate writes), plus
                # the per-request accept EMA and the probe tick counter
                from .prompt_lookup import token_buffer_row
                Lbuf = self.M * self.B + self._spec_k + 1
                tk = np.zeros((self.R, Lbuf), np.int32)
                ema = np.ones((self.R,), np.float32)
                for i, s in enumerate(self.slots):
                    if s is None:
                        continue
                    tk[i] = token_buffer_row(s.prompt + s.tokens, Lbuf)
                    ema[i] = s.spec_ema
                nbytes += tk.nbytes + ema.nbytes
                self._dev.update(toks=self._put(tk), ema=self._put(ema),
                                 tickc=self._zeros((self.R,), jnp.int32))
            # async token ring (ISSUE 11): rebuilt empty on every
            # refresh — a refresh only ever runs with the ring fully
            # drained (every transition drains first), so resetting
            # the write cursors cannot lose entries
            # (a model with tick counters gets one spare row: theirs)
            spare = 1 if self._tick_counter_names else 0
            self._tick_counts_seen[:] = 0
            self._dev.update(
                ring=self._zeros((self.R + spare, self._ring_len),
                                 jnp.int32),
                rlps=self._zeros((self.R, self._ring_len), jnp.float32),
                wcur=self._zeros((self.R,), jnp.int32))
            if self._spec_k:
                # per-dispatch proposer stats ride the state so the
                # drain can count spec_proposed/accepted without a
                # second readback
                self._dev.update(
                    kprop_last=self._zeros((self.R,), jnp.int32),
                    macc_last=self._zeros((self.R,), jnp.int32))
            self._drained[:] = 0
            # empty staged-patch queue: a rebuild by definition leaves
            # nothing pending (bytes not counted — zeros carry no
            # host-side payload)
            self._dev.update(
                pq=self._zeros((self.R, self._desc_len), jnp.int32),
                pqn=self._zeros((), jnp.int32))
        self.h2d_upload_bytes += nbytes
        self._count("h2d_upload_bytes", nbytes)
        self._h_bytes.observe(nbytes)
        self._delta_rows.clear()
        self._dev_dirty = False

    def _prefill(self, params, pools, table_row, ids, length, key,
                 temp, tk, tp, rep, slot, *, bucket: int):
        from .sampling import repetition_penalty_rows, sample_token_rows
        tables = jnp.broadcast_to(table_row[None], (1, self.M))
        lens = jnp.asarray([length], jnp.int32)
        # a whole prompt: state layers start from zero
        fresh = jnp.ones((1,), bool) if self._n_state else None
        caches = self._paged_caches("prompt", pools, tables, lens,
                                    jnp.asarray(slot, jnp.int32)[None],
                                    fresh=fresh)
        positions = jnp.arange(bucket)[None, :]
        logits, new_caches = self.fn(params, ids, kv_caches=caches,
                                     positions=positions)
        # seen mask seeded from the live prompt region (pads excluded)
        seen_row = jnp.zeros((logits.shape[-1],), bool) \
            .at[ids[0]].max(jnp.arange(bucket) < length)
        row = repetition_penalty_rows(
            logits[0, length - 1][None].astype(jnp.float32),
            seen_row[None], rep[None])
        nxt, lps, new_key = sample_token_rows(row, key[None],
                                              temp[None], tk[None],
                                              tp[None])
        seen_row = seen_row.at[nxt[0]].set(True)
        return (nxt[0], lps[0], new_key[0], seen_row,
                self._pools_out(new_caches, pools,
                                self._state_events(fresh)))

    def _chunk_prefill(self, params, pools, table_row, ids, start,
                       total_len, key, temp, tk, tp, rep, seen_row,
                       slot=np.int32(0), *, bucket: int):
        """One prompt chunk at global positions [start, start+bucket):
        writes its K/V (live = positions < total_len) and attends to the
        already-cached chunks. The chosen-token sample at the last live
        position is returned EVERY chunk (one executable); the host only
        keeps it — and the advanced key — for the final chunk, so a
        request still consumes exactly one split per emitted token. The
        seen mask accumulates each chunk's live ids (prefix-cache-skipped
        chunks were seeded at admission). ``slot`` is read by a
        band-keeping layer alone: its ring is its slot's."""
        from .sampling import repetition_penalty_rows, sample_token_rows
        tables = jnp.broadcast_to(table_row[None], (1, self.M))
        lens = jnp.asarray([total_len], jnp.int32)
        # state layers: from zero at position 0, else from what the
        # chunk before this one left in the slot
        fresh = (jnp.asarray(start) == 0)[None] if self._n_state else None
        caches = self._paged_caches("chunk", pools, tables, lens,
                                    jnp.asarray(slot, jnp.int32)[None],
                                    fresh=fresh)
        positions = start + jnp.arange(bucket)[None, :]
        logits, new_caches = self.fn(params, ids, kv_caches=caches,
                                     positions=positions)
        with jax.named_scope("penalty"):
            seen_row = seen_row.at[ids[0]].max(
                jnp.arange(bucket) < total_len - start)
            row = logits[0, total_len - start - 1][None]
        row = repetition_penalty_rows(row.astype(jnp.float32),
                                      seen_row[None], rep[None])
        nxt, lps, new_key = sample_token_rows(row, key[None],
                                              temp[None], tk[None],
                                              tp[None])
        with jax.named_scope("epilogue"):
            seen_out = seen_row.at[nxt[0]].set(True)
        return (nxt[0], lps[0], new_key[0], seen_row, seen_out,
                self._pools_out(new_caches, pools,
                                self._state_events(fresh)))

    def _chunk_prefill_packed(self, params, pools, seen, call):
        """One call of up to ``_pack_segments`` prompts side by side,
        each from its position 0 with nothing cached behind it (the
        first ``chunk`` tokens of a longer one). ONE executable whatever
        the number of segments: ``call`` is the int32 vector
        ``_pack_call`` lays out, dead segments have length 0 and slot
        ``R``. Every token's K/V goes to its own prompt's blocks, the
        attention runs over the call's own rows under the segment-causal
        mask (no page gathered), and each segment's row of ``seen`` is
        built here from its own ids and written back by slot. The row
        of every segment's last live position is penalised and sampled
        as ``_chunk_prefill`` does it; only a segment that samples pays
        the filter's sort (``sample_token_segments``). Returns (int32
        [S, 4]: token, logprob bits, the advanced key; ``seen``; the
        pools): one array for the host to read."""
        from .sampling import repetition_penalty_rows, sample_token_segments
        C, S, M = self.chunk, self._pack_segments, self.M
        with jax.named_scope("patch"):      # the upload taken apart
            ids, seg, pos = call[:3 * C].reshape(3, C)
            sg = call[3 * C:].reshape(S, M + _SEG_WORDS)
            tables = sg[:, :M]
            lens, slots, last, final, tks = (sg[:, M + w] for w in range(5))
            temps, tps, reps = jax.lax.bitcast_convert_type(
                sg[:, M + 5:M + 8], jnp.float32).T
            keys = jax.lax.bitcast_convert_type(sg[:, M + 8:], jnp.uint32)
        # every segment from position 0: state layers start from zero
        fresh = jnp.ones((S,), bool) if self._n_state else None
        caches = self._paged_caches("packed", pools, tables, lens, slots,
                                    fresh=fresh)
        logits, new_caches = self.fn(params, ids[None], kv_caches=caches,
                                     positions=pos[None],
                                     segment_ids=seg[None])
        with jax.named_scope("penalty"):
            seen_rows = jnp.zeros((S, seen.shape[1]), bool) \
                .at[seg, ids].max(pos < lens[seg])
            rows = logits[0, last].astype(jnp.float32)
        rows = repetition_penalty_rows(rows, seen_rows, reps)
        nxt, lps, new_keys = sample_token_segments(rows, keys, temps, tks,
                                                   tps, lens > 0)
        with jax.named_scope("epilogue"):
            # a prompt's last chunk commits its sample to the mask; a
            # longer prompt's first chunk keeps the ids alone
            seen_rows = seen_rows.at[jnp.arange(S), nxt].max(final > 0)
            seen = seen.at[slots].set(seen_rows, mode="drop")
            out = jnp.concatenate(
                [nxt[:, None], jax.lax.bitcast_convert_type(lps, jnp.int32)
                 [:, None], jax.lax.bitcast_convert_type(new_keys,
                                                         jnp.int32)], axis=1)
        return out, seen, self._pools_out(
            new_caches, pools, self._state_events(fresh, lens > 0))

    # ------------------------------------------------------------- host
    @_on_device
    def submit(self, request_id, input_ids, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 1.0, seed: Optional[int] = None,
               stop_sequences=None, repetition_penalty: float = 1.0,
               timeout_s: Optional[float] = None,
               resume_tokens=None, resume_lps=None):
        """temperature <= 0 keeps the bit-exact greedy path; a sampled
        request gets its own PRNG stream seeded by ``seed`` (default: a
        per-engine submission counter), so outputs are reproducible per
        request regardless of what else shares the batch.

        ``stop_sequences``: token-id sequences that end the request the
        moment the GENERATED stream ends with one; the matched sequence
        is trimmed from the returned tokens (vLLM's stop semantics).
        Matching is host-side bookkeeping — the jitted step is
        untouched.

        Admission is bounded: with ``max_queue`` set, a submit past
        capacity raises BackpressureError instead of growing the
        backlog. ``timeout_s`` (default: the engine's
        ``default_timeout_s``) caps the request's wall-clock lifetime;
        an expired request is aborted at the next tick and recorded in
        ``self.cancelled`` with reason "timeout".

        ``resume_tokens`` (ISSUE 12, in-flight failover): tokens this
        request ALREADY emitted on another engine before its replica
        died, which must form the TAIL of ``input_ids`` — the same
        fold-into-the-prompt transform ``_preempt_youngest`` applies,
        so the re-prefill rebuilds identical K/V and a greedy stream
        continues bitwise exactly where the dead replica stopped
        (``results`` returns resume_tokens + the continuation; stop
        sequences spanning the boundary still match/trim).
        ``resume_lps`` carries their logprobs. ``max_new_tokens``
        counts only the tokens still to emit."""
        if self.max_queue is not None:
            # reap already-dead queued requests first: capacity held by
            # expired work must not reject a live submit
            self._expire()
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self._count("rejected")
            obs.record_event("serve_reject",
                             engine=self._obs_labels["engine"],
                             request_id=request_id,
                             queued=len(self.queue))
            raise BackpressureError(
                f"engine admission queue at capacity ({self.max_queue} "
                f"queued); shed load or retry with backoff")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        stop = tuple(tuple(int(t) for t in s)
                     for s in (stop_sequences or ()))
        if any(len(s) == 0 for s in stop):
            raise ValueError("empty stop sequence")
        if repetition_penalty <= 0:
            raise ValueError("repetition_penalty must be > 0")
        ids = list(np.asarray(input_ids).reshape(-1))
        total = len(ids) + max_new_tokens
        if total > self.M * self.B:
            raise ValueError(f"request needs {total} tokens > "
                             f"max_blocks_per_seq*block_size "
                             f"{self.M * self.B}")
        if self._blocks_needed(total) > self.P - 1:
            raise ValueError("request alone exceeds the block pool")
        self._submit_counter += 1
        if seed is None:
            # monotone per-engine counter: never resets (results may be
            # cleared by serve_stream between calls), so repeated
            # unseeded sampled requests get distinct streams
            seed = self._submit_counter
        from .sampling import seed_key_row
        key = seed_key_row(seed)
        timeout_s = timeout_s if timeout_s is not None \
            else self.default_timeout_s
        deadline = (time.monotonic() + timeout_s) \
            if timeout_s is not None else None
        resume = [int(t) for t in (resume_tokens or ())]
        if resume and ids[-len(resume):] != resume:
            raise ValueError(
                "resume_tokens must be the tail of input_ids (the "
                "preemption fold: prompt' = prompt + emitted)")
        rlps = [float(v) for v in (resume_lps or ())]
        if resume and len(rlps) != len(resume):
            rlps = [float("nan")] * len(resume)
        self.queue.append(_Request(request_id, ids, max_new_tokens,
                                   eos_token_id, float(temperature),
                                   int(top_k), float(top_p), key,
                                   prefix=resume, prefix_lps=rlps,
                                   stop=stop,
                                   rep=float(repetition_penalty),
                                   deadline=deadline))
        if self.trace_sink is not None:
            self.trace_sink(request_id, "engine_queue",
                            queued=len(self.queue))
        if self._fused and self.chunk is not None:
            # ROADMAP 4(b), first rung: a warm replica admits eagerly
            # at submit time. Chunked admission is dispatch-free — it
            # claims a slot, allocates blocks and marks the row dirty;
            # the descriptor then rides the staged patch queue into the
            # next tick's program, so admission costs the replica zero
            # extra dispatches (the tick it would have run anyway).
            # Non-chunked admission runs a prefill dispatch inline and
            # stays in the tick loop's _admit.
            while self._try_admit():
                pass

    def _blocks_needed(self, n_tokens: int) -> int:
        return (n_tokens + self.B - 1) // self.B

    # -------------------------------------------------- prefix caching
    def _alloc_block(self) -> Optional[int]:
        """A fresh block: the free list first, then evict the
        least-recently-parked cached-free block (its registrations die
        with it)."""
        if self.free_blocks:
            b = self.free_blocks.pop()
        elif self.cached_free:
            b = next(iter(self.cached_free))
            # spill-before-evict (ISSUE 17): the dying spans' KV goes
            # D2H into the arena first, so the digests stay restorable
            self._spill_evicted(b)
            self._evict_registered(b)
            # the cascade moves co-members — possibly b itself — to the
            # free list as their registrations die; track b either way
            if b in self.cached_free:
                del self.cached_free[b]
            else:
                self.free_blocks.remove(b)
        else:
            return None
        self.block_refs[b] = 1
        return b

    def _unhook(self, key, entry):
        """Remove one (key -> entry) registration; member blocks that
        lose their last registration while parked in cached_free fall
        through to the plain free list."""
        for ob in entry:
            keys = self._prefix_rev.get(ob)
            if keys is not None:
                keys.discard(key)
                if not keys:
                    del self._prefix_rev[ob]
                    if ob in self.cached_free:
                        del self.cached_free[ob]
                        self.free_blocks.append(ob)

    def _evict_registered(self, b: int):
        """Drop every prefix entry that contains block ``b``."""
        for key in list(self._prefix_rev.get(b, ())):
            entry = self.prefix_cache.pop(key, None)
            if entry is not None:
                self._unhook(key, entry)
                self.prefix_generation += 1
        self._prefix_rev.pop(b, None)

    def _release_block(self, b: int):
        rc = self.block_refs.get(b, 1) - 1
        if rc > 0:
            self.block_refs[b] = rc
            return
        self.block_refs.pop(b, None)
        if b in self._prefix_rev:        # registered: park for reuse
            self.cached_free[b] = None
        else:
            self.free_blocks.append(b)

    # ------------------------------------------------ host-RAM spill tier
    def attach_spill(self, arena):
        """Attach (or detach with None) a
        :class:`~..serving.kvspill.KVSpillArena`. Called by the owner of
        the arena — the gateway worker — at engine construction AND
        after every supervisor rebuild, which is the whole point: the
        arena's spans outlive this engine. Refused for a model with
        band-keeping layers, as prefix adoption is: a span's pages there
        were released as its prompt advanced."""
        if arena is not None and self._windows:
            raise ValueError(
                "attach_spill: this model has layers that keep only "
                "their window's band of a sequence; a span's blocks "
                "there are gone before it could be spilled")
        if arena is not None and self._n_state:
            raise ValueError(
                "attach_spill: this model has layers that keep recurrent "
                "state by slot; a span's blocks hold none of it, so a "
                "restored span could not be decoded from")
        self._spill = arena

    def _spill_geometry(self) -> tuple:
        """The layout tuple a spilled payload is only valid under. Any
        skew (different model depth/heads/dims, block size, dtype, or
        chunk grid) makes the bytes meaningless — the arena refuses the
        restore and the request re-prefills."""
        if self._n_state:
            raise ValueError(
                "block export / import: this model has layers that keep "
                "recurrent state by slot, which no block holds; a span "
                "of its blocks is not a prefix's cache (shipping state "
                "is not built)")
        kvh, d = self._cache_rows()[0]
        return (len(self.pools), int(self.B), int(kvh), int(d),
                str(self.pools[0][0].dtype), self.chunk)

    def _spill_fetch(self, entry) -> bytes:
        """D2H gather of a span's KV: every layer's pool rows for
        ``entry``'s blocks, packed as one ``(A*L, n, B, kvh*d)`` buffer
        (layer-major; A arrays a layer: K before V, or the one latent
        array) — the byte layout ``_arena_restore`` reverses. In host
        order these are the bytes of ``(A*L, n, B, kvh, d)``: a record
        banked from a pool that kept its heads apart restores here."""
        idx = np.asarray(entry, np.int32)
        stacked = jnp.stack([p[idx] for pair in self.pools
                             for p in pair])
        return np.asarray(jax.device_get(stacked)).tobytes()

    def _spill_evicted(self, b: int):
        """Bank every registered span that dies with block ``b`` before
        ``_evict_registered`` drops it. Failures are the arena's
        problem (counted drops) — eviction proceeds regardless."""
        if self._spill is None:
            return
        spans = [(key, entry) for key in self._prefix_rev.get(b, ())
                 for entry in (self.prefix_cache.get(key),)
                 if entry is not None]
        if not spans:
            return
        # live sub-spans of a dying span ride along: their KV is a
        # block-prefix of the dying payload, so the arena indexes them
        # as aliases with NO extra D2H — this is what keeps a HOT
        # shared prefix restorable after a crash, even though only its
        # cold long descendants ever face eviction themselves
        dying_keys = {k for k, _ in spans}
        dying_entries = [e for _, e in spans]
        for key, entry in list(self.prefix_cache.items()):
            if key in dying_keys:
                continue
            if any(len(e) > len(entry) and e[:len(entry)] == entry
                   for e in dying_entries):
                spans.append((key, tuple(entry)))
        n = self._spill.spill(spans, self._spill_fetch,
                              self._spill_geometry(),
                              self.prefix_generation)
        self._count("spill_spans", n)

    @_on_device
    def spill_parked(self) -> int:
        """Bank EVERY live prefix-cache span into the arena (gateway
        drain / SIGTERM: the device pool is about to die, the arena is
        what survives). Returns payload records stored."""
        if self._spill is None or not self.prefix_cache:
            return 0
        spans = list(self.prefix_cache.items())
        n = self._spill.spill(spans, self._spill_fetch,
                              self._spill_geometry(),
                              self.prefix_generation)
        self._count("spill_spans", n)
        return n

    @_on_device
    def spill_live(self) -> int:
        """Bank every ACTIVE slot's computed KV span into the arena
        (drain migration / crash salvage, ISSUE 18). For each live
        request the exportable span is the chunk-grid prefix of
        ``prompt + generated`` whose KV the device has actually
        written (``seq_lens`` is host-authoritative) — exactly what a
        survivor restores through ``_arena_restore`` instead of
        re-prefilling prompt+committed. Whole sub-span chains go in
        one call so shorter digests alias the one D2H payload.
        Returns payload records stored; any per-slot failure skips
        that slot (its stream just re-prefills)."""
        if self._spill is None or not self.prefix_caching:
            return 0
        spans = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            try:
                ids = list(req.prompt) + [int(t) for t in req.tokens]
                n_kv = min(int(self.seq_lens[i]), len(ids))
                n_full = (n_kv // self.chunk) * self.chunk
                if n_full <= 0:
                    continue
                blocks = tuple(int(b)
                               for b in req.blocks[:n_full // self.B])
                if len(blocks) * self.B < n_full:
                    continue
                for k, dkey in enumerate(
                        self._chunk_digests(ids, n_full)):
                    nb = (k + 1) * self.chunk // self.B
                    spans.append((dkey, blocks[:nb]))
            except Exception:
                continue
        if not spans:
            return 0
        n = self._spill.spill(spans, self._spill_fetch,
                              self._spill_geometry(),
                              self.prefix_generation)
        self._count("spill_spans", n)
        return n

    def _spill_upload(self, pools, idx, data):
        """spill_reupload_program: scatter a restored span's packed KV
        ``(A*L, npad, B, kvh*d)`` into block rows ``idx`` of every
        layer's pools. Pad rows target the garbage block 0."""
        A = len(pools[0])
        return [tuple(p.at[idx].set(data[A * l + a])
                      for a, p in enumerate(layer))
                for l, layer in enumerate(pools)]

    def _arena_restore(self, ids: List[int]):
        """Admission-side arena probe: if the arena holds a strictly
        longer span of ``ids`` than the device cache does, re-upload it
        into fresh blocks and register it — the normal
        ``_prefix_lookup`` adoption path then hits it like any warm
        span (``prefix_hit_tokens`` counts it; the skipped prefill is
        the win). Every failure mode — checksum, truncation, geometry
        skew, no block headroom — is counted and falls through to
        plain re-prefill."""
        if self._spill is None or not self.prefix_caching:
            return
        chain = self._chunk_digests(ids, len(ids) - 1)
        if not chain:
            return
        live = 0
        for i, d in enumerate(chain):
            if d in self.prefix_cache:
                live = i + 1
        for i in range(len(chain) - 1, live - 1, -1):
            if self._spill.probe(chain[i]) is None:
                continue
            if self._restore_span(chain, i):
                return
            # failed take evicted that record; shorter spans may live
            # in OTHER records — keep probing down the chain

    def _restore_span(self, chain: List[bytes], i: int) -> bool:
        C = self.chunk
        n_blocks = (i + 1) * C // self.B
        if len(self.free_blocks) + len(self.cached_free) < n_blocks:
            self._count("spill_restore_failures")
            return False
        got = self._spill.take(chain[i], self._spill_geometry())
        if got is None:
            self._count("spill_restore_failures")
            return False
        payload, rec_tokens = got
        kp = self.pools[0][0]
        _, B, row = kp.shape
        L = len(self.pools) * len(self.pools[0])     # arrays in all
        rec_blocks = rec_tokens // B
        expect = L * rec_blocks * B * row * kp.dtype.itemsize
        if len(payload) != expect or rec_blocks < n_blocks:
            self._count("spill_restore_failures")  # tokens/geometry skew
            return False
        data = np.frombuffer(payload, dtype=kp.dtype).reshape(
            L, rec_blocks, B, row)[:, :n_blocks]
        blocks: List[int] = []
        for _ in range(n_blocks):
            b = self._alloc_block()      # may cascade-spill more spans
            if b is None:
                for ob in blocks:
                    self._release_block(ob)
                self._count("spill_restore_failures")
                return False
            blocks.append(b)
        npad = 1
        while npad < n_blocks:
            npad *= 2
        idx = np.zeros((npad,), np.int32)          # pad -> garbage block
        idx[:n_blocks] = blocks
        padded = np.zeros((L, npad, B, row), kp.dtype)
        padded[:, :n_blocks] = data
        self.dispatch_count += 1
        self._count("dispatches")
        self.h2d_uploads += 1
        self.h2d_upload_bytes += padded.nbytes
        self._count("h2d_upload_bytes", padded.nbytes)
        self._h_bytes.observe(padded.nbytes)
        self.pools = self._spill_upload_jit(self.pools,
                                            self._put(idx),
                                            self._put(padded))
        # register every sub-span over the restored blocks (mirror of
        # _register_prefix), then park them: the caller's normal
        # _prefix_lookup adoption does the rest
        for j in range(i + 1):
            key = chain[j]
            entry = tuple(blocks[:(j + 1) * C // self.B])
            old = self.prefix_cache.get(key)
            if old == entry:
                continue
            if old is not None:
                self._unhook(key, old)
            self.prefix_cache[key] = entry
            self.prefix_generation += 1
            for b in entry:
                self._prefix_rev.setdefault(b, set()).add(key)
        for b in blocks:
            self._release_block(b)       # registered: parks in cached_free
        tokens = (i + 1) * C
        self._count("spill_restores")
        self._count("spill_restored_tokens", tokens)
        obs.record_event("kv_spill_restore",
                         engine=self._obs_labels["engine"],
                         tokens=tokens, blocks=n_blocks)
        return True

    def _chunk_digests(self, ids: List[int], max_tokens: int):
        """SHA-256 chain digest per chunk-grid prefix span (digest_k =
        H(digest_{k-1} || chunk_k tokens)) for every k*C <= max_tokens.
        O(n) total — keys are 32 bytes regardless of prefix length, and
        a digest is computable from tokens alone, so a lookup can still
        hit a LONG span whose shorter sub-spans were evicted."""
        import hashlib
        C = self.chunk
        digests = []
        d = b""
        k = 1
        while k * C <= max_tokens:
            h = hashlib.sha256(d)
            h.update(np.asarray(ids[(k - 1) * C:k * C],
                                np.int64).tobytes())
            d = h.digest()
            digests.append(d)
            k += 1
        return digests

    def prefix_digests(self, input_ids,
                       max_tokens: Optional[int] = None) -> List[str]:
        """Public prompt-digest helper (ISSUE 9 satellite): the hex
        SHA-256 chain digests of EVERY chunk-grid prefix span of
        ``input_ids`` (shortest first) — each byte-for-byte a key
        ``prefix_cache`` files that span under, so a multi-replica
        router can probe "who holds this warm" against the exact keys
        the blocks are registered by (router-key == cache-key, pinned
        by test). The whole chain matters: a request whose unique tail
        crosses a chunk boundary shares only its SHORTER spans with
        its siblings, and affinity that probed just the longest digest
        would silently miss the warm replica. ``max_tokens`` overrides
        the default span cap of ``len(ids) - 1`` (the same cap
        ``_prefix_lookup`` uses: at least one live token must remain
        to prefill). Empty when no grid-aligned span exists.
        Deterministic across engines with the same
        ``chunk_prefill_tokens``, which is what makes it a routing
        key."""
        if self.chunk is None:
            raise ValueError(
                "prefix_digest requires chunk_prefill_tokens: digests "
                "are keyed to the chunk grid the prefix cache reuses "
                "on")
        ids = [int(t) for t in np.asarray(input_ids).reshape(-1)]
        cap = len(ids) - 1 if max_tokens is None \
            else min(int(max_tokens), len(ids))
        return [d.hex() for d in self._chunk_digests(ids, cap)]

    def prefix_digest(self, input_ids,
                      max_tokens: Optional[int] = None) -> str:
        """The LONGEST span's digest (see ``prefix_digests``);
        ``""`` when no grid-aligned span exists (short prompt)."""
        digests = self.prefix_digests(input_ids, max_tokens)
        return digests[-1] if digests else ""

    def has_prefix(self, digest: str) -> bool:
        """True when ``digest`` (hex, as returned by
        ``prefix_digest``) currently has live blocks in the prefix
        cache — the router's "is this replica warm" probe. An attached
        spill arena extends the warm tier: a span restorable from host
        RAM costs one H2D scatter, not a re-prefill, so a rebuilt
        replica advertises (and receives) shared-prefix traffic the
        moment it re-attaches — that routing is what actually pulls
        the restore through ``_arena_restore`` at admission."""
        if not self.prefix_caching or not digest:
            return False
        try:
            raw = bytes.fromhex(digest)
        except ValueError:
            return False
        if raw in self.prefix_cache:
            return True
        return (self._spill is not None
                and self._spill.probe(raw) is not None)

    def _prefix_lookup(self, ids: List[int]):
        """Longest chunk-grid prefix of ``ids`` with a live cache entry,
        capped so at least one live token remains to prefill (the chunk
        that samples the first generated token). Returns
        (cached_tokens, adopted_block_ids) WITHOUT mutating state."""
        if not self.prefix_caching:
            return 0, ()
        C = self.chunk
        cached, best = 0, ()
        for i, d in enumerate(self._chunk_digests(ids, len(ids) - 1)):
            entry = self.prefix_cache.get(d)
            if entry is not None:  # keep scanning: a longer span may
                cached = (i + 1) * C   # survive its evicted sub-spans
                best = entry
        return cached, best

    def _register_prefix(self, req: "_Request"):
        """Called when a prompt is fully cached: publish every
        chunk-grid-aligned prefix span -> its physical blocks."""
        if not self.prefix_caching:
            return
        C, ids = self.chunk, req.prompt
        for i, key in enumerate(self._chunk_digests(ids, len(ids))):
            entry = tuple(req.blocks[:(i + 1) * C // self.B])
            old = self.prefix_cache.get(key)
            if old == entry:
                continue
            if old is not None:  # last-writer-wins
                self._unhook(key, old)
            self.prefix_cache[key] = entry
            self.prefix_generation += 1
            for b in entry:
                self._prefix_rev.setdefault(b, set()).add(key)

    def _try_admit(self) -> bool:
        """Prefill ONE queued request into a free slot if blocks allow."""
        if not self.queue:
            return False
        req = self.queue[0]
        try:
            slot_id = self.slots.index(None)
        except ValueError:
            return False
        ids = req.prompt
        if self._spill is not None:
            # warm-miss probe of the host spill tier: a restored span
            # registers itself and the normal lookup below adopts it
            self._arena_restore(ids)
        cached, adopted = self._prefix_lookup(ids)
        need = self._blocks_needed(len(ids) + 1)
        fresh = need - len(adopted)
        evictable = sum(1 for b in self.cached_free if b not in adopted)
        if len(self.free_blocks) + evictable < fresh:
            return False
        self.queue.pop(0)
        self._admit_counter += 1
        req.admit_seq = self._admit_counter
        req.blocks = []
        for b in adopted:            # shared prefix blocks: bump owners
            self.cached_free.pop(b, None)
            self.block_refs[b] = self.block_refs.get(b, 0) + 1
            req.blocks.append(b)
        for _ in range(fresh):
            req.blocks.append(self._alloc_block())
        if cached:
            self._count("prefix_hit_tokens", cached)
            self._count("prefix_adopted_blocks", len(adopted))
        self._h_wait.observe((time.monotonic() - req.t_submit) * 1e3)
        obs.record_event("serve_admit",
                         engine=self._obs_labels["engine"],
                         request_id=req.request_id, slot=slot_id)
        if self.trace_sink is not None:
            self.trace_sink(req.request_id, "slot_take", slot=slot_id,
                            prefix_hit_tokens=cached, blocks=need)
        self.slots[slot_id] = req
        row = np.zeros((self.M,), np.int32)
        row[:need] = req.blocks
        self.block_tables[slot_id] = row
        self.temps[slot_id] = req.temperature
        self.top_ks[slot_id] = req.top_k
        self.top_ps[slot_id] = req.top_p
        self.reps[slot_id] = req.rep
        self.keys[slot_id] = req.key
        self._key_overrides.add(slot_id)
        self._mark_dirty(slot_id)

        if self.chunk is not None:
            # chunked mode: admission only claims the slot + blocks; the
            # prompt enters the cache chunk-by-chunk on later ticks,
            # starting AFTER any shared-prefix tokens already in the pool
            req.prefill_pos = cached
            self.seq_lens[slot_id] = cached
            # seed the seen mask with prefix-cache-skipped tokens (their
            # chunks never run); later chunks scatter their own ids. A
            # prompt that starts at 0 gets its whole row from the packed
            # call that serves it
            if cached:
                seen0 = self._zeros((self.seen.shape[1],), bool) \
                    .at[np.asarray(ids[:cached])].set(True)
                self.seen = self.seen.at[slot_id].set(seen0)
            return True

        bucket = next((b for b in self.prefill_buckets if b >= len(ids)),
                      None)
        if bucket is None:
            bucket = self.prefill_buckets[-1]
            while bucket < len(ids):
                bucket *= 2
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(ids)] = ids
        self.dispatch_count += 1
        self._count("dispatches")
        nxt, lp, new_key, seen_row, self.pools = self._prefill_jit(
            self.params, self.pools, self._put(row),
            self._put(padded), np.int32(len(ids)),
            self._put(req.key), np.float32(req.temperature),
            np.int32(req.top_k), np.float32(req.top_p),
            np.float32(req.rep), np.int32(slot_id), bucket=bucket)
        self.seen = self.seen.at[slot_id].set(seen_row)
        self._count("prefills")
        self._count_chunk_rule()
        self._count_chunk_experts(bucket)
        first = int(nxt)
        self.keys[slot_id] = np.asarray(new_key)
        self._key_overrides.add(slot_id)
        req.key = self.keys[slot_id].copy()
        req.tokens.append(first)
        req.lps.append(float(lp))
        req.prefill_pos = len(ids)
        self.seq_lens[slot_id] = len(ids)
        if self.trace_sink is not None:
            self.trace_sink(req.request_id, "prefill_done",
                            tokens=len(ids), bucket=bucket)
        # stop check FIRST: a stop completing on the final budgeted (or
        # eos) token must still be trimmed
        if self._stop_hit(req) or req.max_new <= 1 \
                or (req.eos is not None and first == req.eos):
            self._finish(slot_id)
        return True

    def _pack_calls(self, slot_ids: List[int]) -> List[List[int]]:
        """The packed prefill calls of one step: the slots whose prompt
        starts at position 0, in admission order, placed FIRST-FIT into
        open calls of at most ``chunk`` positions and ``_pack_segments``
        segments; a call is dispatched in the order of its first member.
        A slot's live length is ``min(chunk, len(prompt))``, which an
        empty call always holds, so every one of them is served this
        step."""
        calls: List[List[int]] = []
        room: List[int] = []
        for i in sorted(slot_ids, key=lambda i: self.slots[i].admit_seq):
            live = min(self.chunk, len(self.slots[i].prompt))
            at = next((c for c, left in enumerate(room) if left >= live
                       and len(calls[c]) < self._pack_segments), None)
            if at is None:
                calls.append([])
                room.append(self.chunk)
                at = len(calls) - 1
            calls[at].append(i)
            room[at] -= live
        return calls

    def _pack_call(self, slot_ids: List[int]):
        """The one upload of a packed call (``_chunk_prefill_packed``
        reads it back apart): int32 [3 * chunk] token ids, segment index
        and position, then a row a segment of its block-table row and
        ``_SEG_WORDS`` words. The pads ride behind the last segment,
        past its length; a dead segment has length 0 and slot ``R``.
        Returns (the vector, each segment's live length)."""
        C, S, M = self.chunk, self._pack_segments, self.M
        tok = np.zeros((3, C), np.int32)
        sg = np.zeros((S, M + _SEG_WORDS), np.int32)
        sg[:, M + 1] = self.R
        floats = sg[:, M + 5:M + 8].view(np.float32)
        lives, at = [], 0
        for j, i in enumerate(slot_ids):
            req = self.slots[i]
            live = min(C, len(req.prompt))
            tok[0, at:at + live] = req.prompt[:live]
            tok[1, at:] = j
            tok[2, at:] = np.arange(C - at)
            at += live
            sg[j, :M] = self.block_tables[i]
            sg[j, M:M + 5] = (live, i, at - 1, live == len(req.prompt),
                              req.top_k)
            floats[j] = (req.temperature, req.top_p, req.rep)
            sg[j, M + 8:] = req.key.view(np.int32)
            lives.append(live)
        return np.concatenate([tok.ravel(), sg.ravel()]), lives

    def _advance_packed(self, slot_ids: List[int]):
        """Run ONE packed call: the first chunk of every slot in
        ``slot_ids`` (``_pack_calls``), one upload, one program, one
        readback; then each segment's bookkeeping (``_chunk_served``)."""
        t_chunk = time.perf_counter()
        with self._phase("chunk") as br:
            call, lives = self._pack_call(slot_ids)
            for i in slot_ids:
                self._mark_dirty(i)     # lens/activation change this tick
            self.dispatch_count += 1
            self._count("dispatches")
            with self._phase("h2d") as up:
                call = self._put(call)
                up.switch("dispatch")
                out, self.seen, self.pools = self._chunk_jit.packed(
                    self.params, self.pools, self.seen, call)
            self._count("prefill_chunks")
            self._count("prefill_segments", len(slot_ids))
            self._count_chunk_rule()
            self._count_chunk_experts(self.chunk)
            # the segments whose prompt ends in this call: their first
            # token comes back with it
            done = [live == len(self.slots[i].prompt)
                    for i, live in zip(slot_ids, lives)]
            if any(done):
                if br.on:
                    # the read below waits for the call's program
                    with self._phase("device"):
                        try:
                            jax.block_until_ready(out)
                        except Exception:
                            pass
                out = np.asarray(out)
            for j, (i, live) in enumerate(zip(slot_ids, lives)):
                first = (int(out[j, 0]),
                         float(out[j, 1:2].view(np.float32)[0]),
                         out[j, 2:].view(np.uint32)) if done[j] else None
                self._chunk_served(i, live, first)
        self._h_chunk.observe((time.perf_counter() - t_chunk) * 1e3)

    def _advance_chunk(self, slot_id: int):
        """Run ONE chunk of a slot's prompt that has cached context
        behind it (earlier chunks, an adopted prefix), alone; on the
        final chunk the first generated token materializes and the slot
        joins decode."""
        t_chunk = time.perf_counter()
        # everything here that is not an upload, the program's call or
        # the wait for its result is the chunk's own host work
        with self._phase("chunk") as br:
            req = self.slots[slot_id]
            ids = req.prompt
            start = req.prefill_pos
            live = min(self.chunk, len(ids) - start)
            last = start + live >= len(ids)
            padded = np.zeros((1, self.chunk), np.int32)
            padded[0, :live] = ids[start:start + live]
            row = self.block_tables[slot_id]
            self._mark_dirty(slot_id)    # lens/activation change this tick
            self.dispatch_count += 1
            self._count("dispatches")
            with self._phase("h2d") as call:
                row, padded, key = (self._put(row), self._put(padded),
                                    self._put(req.key))
                call.switch("dispatch")
                (nxt, lp, new_key, seen_mid, seen_fin,
                 self.pools) = self._chunk_jit.alone(
                    self.params, self.pools, row, padded, np.int32(start),
                    np.int32(start + live), key,
                    np.float32(req.temperature), np.int32(req.top_k),
                    np.float32(req.top_p), np.float32(req.rep),
                    self.seen[slot_id], np.int32(slot_id),
                    bucket=self.chunk)
            self._count("prefill_chunks")
            self._count("prefill_segments")
            self._count_chunk_rule()
            self._count_chunk_experts(self.chunk)
            self._count_chunk_attention(start, start + live)
            # mid chunks keep the ids-only mask; the final chunk's
            # committed sample rides in seen_fin (mirrors the PRNG-key
            # protocol)
            self.seen = self.seen.at[slot_id].set(seen_fin if last
                                                  else seen_mid)
            first = None
            if last:
                if br.on:
                    # the reads below wait for the chunk's program
                    with self._phase("device"):
                        try:
                            jax.block_until_ready((new_key, nxt, lp))
                        except Exception:
                            pass
                first = (int(nxt), float(lp), np.array(new_key))
            self._chunk_served(slot_id, live, first)
        self._h_chunk.observe((time.perf_counter() - t_chunk) * 1e3)

    def _chunk_served(self, slot_id: int, live: int, first=None):
        """What the host does for ONE slot after the call that served
        ``live`` tokens of its prompt, packed or alone. ``first`` (the
        prompt's last chunk): the first generated token, its logprob
        and the advanced key, read back from the call."""
        req = self.slots[slot_id]
        if self.trace_sink is not None:
            self.trace_sink(req.request_id, "prefill_chunk",
                            start=req.prefill_pos, tokens=live)
        if self._windows:       # pages the prompt's advance left behind
            self._count("kv_window_blocks_released",
                        self._band_behind(req.prefill_pos + live)
                        - self._band_behind(req.prefill_pos))
        req.prefill_pos += live
        self.seq_lens[slot_id] = req.prefill_pos
        if first is None:
            return
        token, lp, key = first
        self._count("prefills")
        self._register_prefix(req)
        self.keys[slot_id] = key
        self._key_overrides.add(slot_id)
        req.key = self.keys[slot_id].copy()
        req.tokens.append(token)
        req.lps.append(lp)
        if self.trace_sink is not None:
            self.trace_sink(req.request_id, "prefill_done",
                            tokens=len(req.prompt))
        if self._stop_hit(req) or req.max_new <= 1 \
                or (req.eos is not None and token == req.eos):
            self._finish(slot_id)

    def _grow_blocks(self, slot_id: int, need: int,
                     reserve: int = 0) -> bool:
        """Grow a slot's table to ``need`` blocks from the allocator
        (one shared implementation for decode growth and spec
        headroom). ``reserve`` refuses to dip the allocatable pool
        (free + parked) at or below that count — speculative callers
        use it so their grabs can never starve `_ensure_block`.
        Returns False when the pool cannot serve."""
        slot = self.slots[slot_id]
        while len(slot.blocks) < need:
            if reserve and len(self.free_blocks) + \
                    len(self.cached_free) <= reserve:
                return False
            b = self._alloc_block()
            if b is None:
                return False
            slot.blocks.append(b)
            self.block_tables[slot_id, len(slot.blocks) - 1] = b
            self._mark_dirty(slot_id, table_only=True)
        return True

    def _ensure_block(self, slot_id: int, ahead: int = 0) -> bool:
        """The next decode writes at seq_lens[slot_id], ``ahead`` past
        it when that many ticks of the row are undrained (the mirror
        lags the device's ``lens`` by one each); allocate the covering
        block if the row hasn't got it yet."""
        need = self._blocks_needed(int(self.seq_lens[slot_id]) + ahead + 1)
        return self._grow_blocks(slot_id, need)

    @staticmethod
    def _stop_hit(req) -> bool:
        """True when the generated stream ends with one of the request's
        stop sequences; records the matched length for trimming. Only
        the last max-stop-length tokens are materialized (O(1) per tick,
        not a prefix+tokens copy)."""
        if not req.stop:
            return False
        need = max(len(s) for s in req.stop)
        tail = req.tokens[-need:]
        if len(tail) < need and req.prefix:  # stop spans a preemption
            take = need - len(tail)
            tail = req.prefix[-take:] + tail
        for s in req.stop:
            if len(tail) >= len(s) and tuple(tail[-len(s):]) == s:
                req.trim = len(s)
                return True
        return False

    def _finish(self, slot_id: int):
        slot = self.slots[slot_id]
        toks = slot.prefix + slot.tokens
        lps = slot.prefix_lps + slot.lps
        if slot.trim:               # cut the matched stop sequence
            toks = toks[:-slot.trim]
            lps = lps[:-slot.trim]
        self.results[slot.request_id] = toks
        self.logprobs[slot.request_id] = lps
        if self.trace_sink is not None:
            self.trace_sink(slot.request_id, "engine_finish",
                            tokens=len(toks))
        self._release(slot_id)

    def _release(self, slot_id: int):
        for b in self.slots[slot_id].blocks:
            self._release_block(b)
        if self._windows:       # what was still inside the bands
            self._count("kv_window_blocks_released",
                        self._band_live(int(self.seq_lens[slot_id])))
        self.block_tables[slot_id] = 0
        self.seq_lens[slot_id] = 0
        self.temps[slot_id] = 0.0
        self.top_ks[slot_id] = 0
        self.top_ps[slot_id] = 1.0
        self.reps[slot_id] = 1.0
        self.seen = self.seen.at[slot_id].set(False)
        self.slots[slot_id] = None
        self._key_overrides.discard(slot_id)
        self._mark_dirty(slot_id)

    def _preempt_youngest(self, exclude: int) -> bool:
        """Memory pressure: requeue the most recently admitted OTHER
        request (vLLM's recompute-mode preemption — its emitted tokens
        fold into the prompt, so the re-prefill rebuilds the same KV
        deterministically and the output stays exact; the carried PRNG
        key means a SAMPLED victim also resumes its stream exactly —
        every emitted token consumed one split, prefill or decode)."""
        cands = [i for i, s in enumerate(self.slots)
                 if s is not None and i != exclude]
        if not cands:
            return False
        victim = max(cands, key=lambda i: self.slots[i].admit_seq)
        s = self.slots[victim]
        # s.key is the authoritative stream state: synced from the jit
        # after every decode tick / final chunk, and NOT perturbed by the
        # all-rows key split that garbage-advances self.keys for rows
        # still mid-chunk-prefill
        if self._fused and s.tokens and victim not in self._key_overrides:
            # fused mode never syncs s.key per tick; for a DECODE-active
            # victim the truth is the device key stream (or the mirror
            # refreshed from it). Mid-prefill victims (no tokens) keep
            # their untouched authoritative s.key exactly as before.
            if self._dev is not None and self._dev_keys_dirty:
                s.key = np.asarray(self._dev["keys"])[victim].copy()
            else:
                s.key = self.keys[victim].copy()
        requeued = _Request(s.request_id, s.prompt + s.tokens,
                            s.max_new - len(s.tokens), s.eos,
                            s.temperature, s.top_k, s.top_p,
                            s.key.copy(),
                            prefix=s.prefix + s.tokens,
                            prefix_lps=s.prefix_lps + s.lps,
                            stop=s.stop, rep=s.rep, deadline=s.deadline)
        requeued.spec_ema = s.spec_ema   # adaptive k survives preemption
        self.queue.insert(0, requeued)
        self._release(victim)
        self._count("preemptions")
        if self.trace_sink is not None:
            self.trace_sink(s.request_id, "preempt",
                            emitted=len(s.tokens))
        obs.record_event("serve_preempt",
                         engine=self._obs_labels["engine"],
                         request_id=s.request_id,
                         emitted=len(s.tokens))
        return True

    # -------------------------------------------------- overload control
    def _abort(self, req: "_Request", reason: str,
               slot_id: Optional[int] = None):
        self.cancelled[req.request_id] = reason
        self._count("timeouts" if reason == "timeout"
                    else "cancellations")
        if self.trace_sink is not None:
            self.trace_sink(req.request_id, "engine_abort",
                            reason=reason, in_slot=slot_id is not None)
        if slot_id is not None:
            self._release(slot_id)

    def _expire(self):
        """Abort queued and running requests whose deadline passed (the
        per-request timeout contract: checked once per scheduler tick —
        a jitted call is never interrupted mid-flight). A running
        expiry drains first (never abort against a stale mirror / an
        in-flight dispatch), its own row only: a queue-capacity reap on
        the submit path forces no global drain."""
        now = time.monotonic()
        for req in [r for r in self.queue
                    if r.deadline is not None and now > r.deadline]:
            self.queue.remove(req)
            self._abort(req, "timeout")
        for i in range(self.R):
            s = self.slots[i]
            if s is not None and s.deadline is not None \
                    and now > s.deadline:
                self._drain_row(i)
                s = self.slots[i]   # the drain may have finished it
                if s is not None and s.deadline is not None \
                        and now > s.deadline:
                    self._abort(s, "timeout", slot_id=i)

    @_on_device
    def cancel(self, request_id) -> bool:
        """Abort a queued or running request (client disconnect). Its
        blocks/slot free immediately; no result is recorded. Returns
        False if the request is unknown or already finished.

        A RUNNING cancel racing an in-flight dispatch drains that
        slot's undrained ring entries first, so the release below
        cannot orphan ring tokens or free blocks the in-flight program
        still writes — scoped to the cancelled row (ISSUE 14: the
        siblings' pending tokens stay pending)."""
        for req in self.queue:
            if req.request_id == request_id:
                self.queue.remove(req)
                self._abort(req, "cancelled")
                return True
        for i in range(self.R):
            s = self.slots[i]
            if s is not None and s.request_id == request_id:
                self._drain_row(i)
                s = self.slots[i]
                if s is None or s.request_id != request_id:
                    return False   # finished in the drained entries
                self._abort(s, "cancelled", slot_id=i)
                return True
        return False

    def health(self) -> Dict[str, Any]:
        """Stats snapshot for load balancers / probes: scheduler
        counters plus live occupancy (slots, blocks, queue depth)."""
        snap = dict(self.stats)
        prop = snap.get("spec_proposed", 0)
        snap["spec_accept_rate"] = round(
            snap.get("spec_accepted", 0) / prop, 4) if prop else 0.0
        # the one-dispatch-per-tick claim (ISSUE 19), observable
        # fleet-wide: a steady fused replica reads ~1.0 plus the
        # amortized prefill share
        ticks = snap.get("decode_steps", 0)
        snap["dispatches_per_tick"] = round(
            snap.get("dispatches", 0) / ticks, 4) if ticks else 0.0
        # decode dispatches not yet drained: 1 between the steps of a
        # decoding engine, 0 when it idles
        snap["outstanding_dispatches"] = len(self._pending)
        dev = self.device or jax.devices()[0]
        snap.update(
            device={"platform": dev.platform, "kind": dev.device_kind},
            queued=len(self.queue),
            queue_capacity=self.max_queue,
            active_slots=sum(s is not None for s in self.slots),
            max_slots=self.R,
            free_blocks=len(self.free_blocks),
            cached_free_blocks=len(self.cached_free),
            total_blocks=self.P - 1,
            # pages inside the bands of the band-keeping layers (0
            # without such layers), off the host's mirrors
            window_blocks_live=sum(
                self._band_live(int(n))
                for n, s in zip(self.seq_lens, self.slots)
                if s is not None) if self._windows else 0,
            spill_attached=self._spill is not None,
            results_pending=len(self.results),
            aborted=len(self.cancelled))
        return snap

    def debug_snapshot(self, max_digests: int = 32) -> Dict[str, Any]:
        """Live engine introspection for the gateway's ``/debugz``
        (ISSUE 10): the slot map, block-pool occupancy (``live`` =
        blocks owned by running requests; ``fragmentation_frac`` = the
        share of the pool parked in prefix-cache entries — reusable
        only via eviction, the paged analogue of fragmentation), the
        prefix-cache digests the router probes against, and the queued
        request ids. Read cross-thread without stopping the tick
        thread: every field is O(1)/O(R) host bookkeeping and a
        slightly torn snapshot only costs debug fidelity, never
        correctness."""
        now = time.monotonic()
        slots: List[Optional[Dict[str, Any]]] = []
        for i, s in enumerate(list(self.slots)):
            if s is None:
                slots.append(None)
                continue
            slots.append({
                "request_id": str(s.request_id),
                "seq_len": int(self.seq_lens[i]),
                "prompt_tokens": len(s.prompt),
                "prefill_pos": s.prefill_pos,
                "emitted": len(s.prefix) + len(s.tokens),
                "remaining_budget": max(s.max_new - len(s.tokens), 0),
                "blocks": len(s.blocks),
                "spec_ema": round(float(s.spec_ema), 4),
                "deadline_in_s": round(s.deadline - now, 3)
                if s.deadline is not None else None,
            })
        total = self.P - 1               # block 0 is the garbage block
        free = len(self.free_blocks)
        parked = len(self.cached_free)
        live = max(total - free - parked, 0)
        try:
            digests = [k.hex() for k in
                       list(self.prefix_cache)[:max_digests]]
            n_entries = len(self.prefix_cache)
        except RuntimeError:             # resized mid-iteration: retry-free
            digests, n_entries = [], -1
        try:
            # same cross-thread torn-read contract as the digests: the
            # tick thread mutates _delta_rows; a mid-iteration resize
            # costs this field, never the whole snapshot
            pending = sorted(self._delta_rows)
        except RuntimeError:
            pending = []
        return {
            "slots": slots,
            "block_pool": {
                "total": total, "free": free, "cached_free": parked,
                "live": live,
                "occupancy_frac": round(live / max(total, 1), 4),
                "free_frac": round((free + parked) / max(total, 1), 4),
                "fragmentation_frac": round(parked / max(total, 1), 4),
            },
            "prefix_cache": {"entries": n_entries, "digests": digests,
                             "generation": self.prefix_generation},
            "spill": {
                "attached": self._spill is not None,
                "restores": int(
                    self._counters["spill_restores"].value),
                "restored_tokens": int(
                    self._counters["spill_restored_tokens"].value),
                "restore_failures": int(
                    self._counters["spill_restore_failures"].value),
                "spilled_spans": int(
                    self._counters["spill_spans"].value),
            },
            "queued": [str(r.request_id)
                       for r in list(self.queue)[:max_digests]],
            "spec": {"enabled": bool(self._spec_k), "k": self._spec_k,
                     "ngram": self._spec_ngram if self._spec_k else 0},
            "ring": {"ring_len": self._ring_len,
                     "outstanding": len(self._pending),
                     "drains": self.ring_drains,
                     "blocking_drains": self.ring_blocking_drains,
                     "scoped_drains": self.ring_scoped_drains,
                     "d2h_syncs": self.d2h_syncs},
            # slot-transition cost accounting (ISSUE 14): how churn is
            # being paid for — descriptors staged vs full-state
            # rebuilds, and the H2D bytes either way
            "transitions": {
                "full_rebuilds": self.full_rebuilds,
                "patches_fused": self.patches_fused,
                "ring_cursor_rollovers": self.ring_cursor_rollovers,
                "pending_patch_rows": pending,
                "h2d_uploads": self.h2d_uploads,
                "h2d_upload_bytes": self.h2d_upload_bytes,
                "dispatches": self.dispatch_count,
                "dispatches_per_tick": round(
                    self.dispatch_count
                    / max(int(self._counters["decode_steps"].value), 1),
                    4),
            },
            # tick-phase profiler (ISSUE 20): where the last tick's
            # wall time went + lifetime totals, when tick_profile is on
            "tick_profile": dict(
                self._prof.summary(), enabled=True,
                last_tick=self._prof.last_phases(),
            ) if self._prof is not None else {"enabled": False},
        }

    # ------------------------------------------------- fleet fault tolerance
    def export_resumable(self) -> Dict[Any, Dict[str, Any]]:
        """Resume descriptors for every queued or running request, read
        from HOST mirrors only (ISSUE 12: the failover path calls this
        on a crashed or hung engine — no device access, no jitted
        calls, so it works whatever state the accelerator is in).

        The host mirrors advance only when tokens are DRAINED
        (``_commit_row_drain``), so an in-flight ring/fused dispatch's
        uncommitted tokens are invisible here and simply die with the
        replica — exactly the tokens no client ever saw. Each
        descriptor is the ``_preempt_youngest`` transform, ready for
        ``submit(prompt, max_new_tokens=remaining,
        resume_tokens=committed, ...)`` on a SURVIVING engine: a greedy
        resume is bitwise the uninterrupted stream; a sampled resume
        needs a re-derived key (the caller's job) and is
        distribution-preserving, not bitwise."""
        out: Dict[Any, Dict[str, Any]] = {}

        def _desc(s: "_Request") -> Dict[str, Any]:
            # one consistent snapshot of the (tokens, lps) pair: a
            # SLOW-but-alive tick can still be appending (tokens
            # first, then lps — see _commit_row_drain), so read lps
            # first and truncate both to the paired length; every derived
            # field below uses the SAME n, keeping committed a strict
            # tail of prompt and remaining consistent with it
            lps = list(s.lps)
            toks = list(s.tokens)[:len(lps)]
            n = len(toks)
            return {
                "prompt": list(s.prompt) + toks,
                "committed": list(s.prefix) + toks,
                "committed_lps": list(s.prefix_lps) + lps[:n],
                "remaining": max(s.max_new - n, 0),
                "eos": s.eos,
                "temperature": s.temperature,
                "top_k": s.top_k,
                "top_p": s.top_p,
                "stop": [list(x) for x in s.stop],
                "rep": s.rep,
                "deadline": s.deadline,
            }

        for s in list(self.queue):
            out[s.request_id] = _desc(s)
        for s in list(self.slots):
            if s is not None:
                out[s.request_id] = _desc(s)
        return out

    @_on_device
    def hard_reset(self):
        """Forcibly return the engine to its empty post-``__init__``
        state WITHOUT touching whatever the device is doing (ISSUE 12:
        the supervisor's rebuild-in-place path after a tick-thread
        crash or an abandoned hung dispatch). Every queued/running
        request is dropped on the floor — the caller already failed
        them over — and the KV pools and ``seen`` masks are rebuilt as
        FRESH arrays: the old ones may have been donated into (or
        still be owned by) a dead or in-flight program, so they are
        never reused. Compiled executables survive (the jit caches key
        on shapes, which don't change), so a restart costs one
        allocation, not a recompile. Counters are monotonic and keep
        counting across the reset."""
        self.pools, self.seen = self._fresh_device_arrays()
        self.free_blocks = list(range(1, self.P))
        self.block_tables = np.zeros((self.R, self.M), np.int32)
        self.seq_lens = np.zeros((self.R,), np.int32)
        self.temps = np.zeros((self.R,), np.float32)
        self.top_ks = np.zeros((self.R,), np.int32)
        self.top_ps = np.ones((self.R,), np.float32)
        self.reps = np.ones((self.R,), np.float32)
        self.keys = np.zeros((self.R, 2), np.uint32)
        self.slots = [None] * self.R
        self.queue = []
        self.results = {}
        self.logprobs = {}
        self.cancelled = {}
        if self.prefix_cache:
            # the cache set changed (to empty): gossip must notice
            self.prefix_generation += 1
        self.prefix_cache = {}
        self._prefix_rev = {}
        self.block_refs = {}
        self.cached_free = {}
        self._key_overrides = set()
        self._dev = None
        self._dev_dirty = True
        self._dev_keys_dirty = False
        self._delta_rows = {}
        self._pending.clear()
        self._drained[:] = 0
        obs.record_event("paged_hard_reset",
                         engine=self._obs_labels["engine"])

    @_on_device
    def close(self, drain: bool = True):
        """``drain=True`` (default) runs the engine until every queued
        and in-flight request completes (graceful shutdown);
        ``drain=False`` aborts everything still pending (emergency
        stop), recording each as "cancelled"."""
        if drain:
            self.run()
            return
        self._drain_pending()
        for req in list(self.queue):
            self.queue.remove(req)
            self._abort(req, "cancelled")
        for i in range(self.R):
            if self.slots[i] is not None:
                self._abort(self.slots[i], "cancelled", slot_id=i)

    @_on_device
    def step(self):
        """One scheduler tick. With something to decide: drain every
        outstanding dispatch's ring slice (its tokens land here, one
        step behind the device), expire overdue requests, admit EVERY
        queued request that fits (slots + blocks), advance one prefill
        chunk per prefilling slot, then one decode for all
        prefill-complete slots (dispatched WITHOUT a readback). With
        nothing to decide (``_may_run_ahead``: a full house of decoding
        rows) the same two halves in the other order: the next decode
        is dispatched first, behind the tick still running, and that
        tick is drained under it.

        With ``tick_profile`` on, the whole tick runs inside one
        profiler window: every bracketed phase of ``obs.TICK_PHASES``
        plus the host residual land in the per-tick ring and the phase
        histograms."""
        prof = self._prof
        if prof is not None:
            prof.begin()
            d0, u0 = self.dispatch_count, self.h2d_uploads
            b0, p0 = self.h2d_upload_bytes, self.patches_fused
        # ONE call site, profiler on or off: the programs traced below
        # carry this stack in their metadata, which is part of their
        # compile-cache key (utils/compile_cache.py)
        try:
            return self._step_inner()
        finally:
            if prof is not None:
                prof.end(
                    dispatches=self.dispatch_count - d0,
                    uploads=self.h2d_uploads - u0,
                    nbytes=self.h2d_upload_bytes - b0,
                    patches=self.patches_fused - p0,
                    active=sum(1 for s in self.slots if s is not None))

    def _step_inner(self):
        with self._phase("stage"):
            ahead = self._may_run_ahead()
        if ahead:
            # tick N+1 queues behind tick N on the device; the wait for
            # N, its drain and commit, and the caller's emit and next
            # round of scheduling all run under N+1
            self.decode_ticks += 1
            self._decode_fused(list(range(self.R)))
            self._drain_oldest()
            return True
        self._drain_pending()
        with self._phase("expire") as br:
            self._expire()
            br.switch("admit")
            while self._try_admit():
                pass
        if self.chunk is not None:
            # one chunk a prefilling slot and step: the prompts that
            # start at position 0 share calls (_pack_calls), a chunk
            # with cached context behind it runs alone
            todo = [i for i, s in enumerate(self.slots)
                    if s is not None and s.prefill_pos < len(s.prompt)]
            fresh = [i for i in todo if self.slots[i].prefill_pos == 0]
            rest = [i for i in todo if self.slots[i].prefill_pos > 0]
            for call in self._pack_calls(fresh):
                self._advance_packed(call)
            for i in rest:
                self._advance_chunk(i)
        with self._phase("stage"):
            for i in range(self.R):
                if self.slots[i] is None or \
                        self.slots[i].prefill_pos < len(self.slots[i].prompt):
                    continue
                while not self._ensure_block(i):
                    if not self._preempt_youngest(exclude=i):
                        raise RuntimeError(
                            "paged KV pool cannot hold even one request; "
                            "raise num_blocks")
            active = [i for i, s in enumerate(self.slots)
                      if s is not None and s.tokens]
            if active and self._spec_k:
                self._spec_headroom(active)
        if not active:
            return
        self.decode_ticks += 1
        if not self._fused:
            return self._decode_host(active)
        return self._decode_fused(active)

    def _may_run_ahead(self) -> bool:
        """True when this step has nothing to decide before the next
        decode tick, read off the slots: ONE dispatch is outstanding
        and it served every row; every slot holds a decoding request
        (no free slot, none mid-prefill: an arrival could not be
        admitted before a row finishes anyway) that the outstanding
        tick leaves budget and whose deadline has not passed; no
        transition is staged; the pool serves the blocks the next tick
        writes (``_ensure_block`` one position past the undrained
        tick's, the only transition such a step makes). Anything else
        (a finished, cancelled or expired row, an admission, a chunk,
        pool pressure and its preemption, a rebuild, a speculative
        engine, whose acceptance mirror is read at the drain) takes the
        drain-first order, so a row that finishes collapses the
        pipeline for the steps that refill its slot and it fills again
        by itself. An eos or a stop sequence is not foreseen: the next
        tick then runs with that row already finished on the device
        (it advances nothing) or still active (its token and K/V write
        die with the release, the over-commit contract)."""
        if len(self._pending) != 1 or self._spec_k or self._dev_dirty \
                or any(self._delta_rows.values()) \
                or len(self._pending[0]["rows"]) != self.R:
            return False
        now = time.monotonic()
        for s in self.slots:
            if s is None or not s.tokens \
                    or s.prefill_pos < len(s.prompt) \
                    or s.max_new - len(s.tokens) < 2 \
                    or (s.deadline is not None and now > s.deadline):
                return False
        if any(r.deadline is not None and now > r.deadline
               for r in self.queue):
            return False
        # a block a row got before the pool ran dry is the block its
        # next drain-first tick asks for: falling back wastes nothing
        return all(self._ensure_block(i, ahead=1) for i in range(self.R))

    def _drain_pending(self):
        """Consume EVERY outstanding dispatch, oldest first: the top of
        a drain-first step() and every out-of-band path that touches
        all slots (``close``), so slot transitions never run against a
        stale mirror. No-op when nothing is outstanding."""
        while self._pending:
            self._drain_oldest()

    def _drain_oldest(self):
        """Consume the oldest outstanding dispatch: fetch the ring
        entries it committed since the last drain and run the host
        bookkeeping the reference path does inline — token/logprob
        appends, stop matching (a stop completing from a DRAINED token
        finishes the request; tokens the device committed past it die
        with the slot release), device finish flags, spec counters/EMA
        mirrors, trace events.

        The D2H here is the double-buffered read: in a drain-first step
        the dispatch was issued one host iteration ago and the wait is
        what is left of its program; in a run-ahead step the next
        program is already queued behind it, so the wait (phase
        ``device``) and everything the host does until the step after
        next run under that program — instrumented via
        ``ring_blocking_drains`` (drains whose arrays were not yet
        ready) against ``ring_drains`` (all of them)."""
        p = self._pending.popleft()
        arrs = p["arrs"]
        spec = self._spec_k > 0
        self.ring_drains += 1
        if not all(a.is_ready() for a in arrs):
            self.ring_blocking_drains += 1
            self.d2h_syncs += 1
        with self._phase("device") as br:
            if br.on:
                # device-wait vs D2H split (ISSUE 20): block-until-ready
                # is the program-bound wait; the device_get after it is
                # pure drain. Semantically free — device_get blocks on
                # readiness anyway — so profile-on streams stay bitwise
                # identical.
                try:
                    jax.block_until_ready(arrs)
                except Exception:
                    pass
                br.switch("drain")
            t0 = time.perf_counter()
            vals = jax.device_get(arrs)
            # the decode-step histogram's window is the drain wait — the
            # only host-visible program-bound time left on the path
            self._h_decode.observe((time.perf_counter() - t0) * 1e3)
            br.switch("commit")
            ring, rlps, wcur, act_now = vals[:4]
            if self._tick_counter_names:
                # cumulative on the device, in int32: count what was
                # added since the last drain, whatever has wrapped
                now = ring[self.R, :len(self._tick_counter_names)] \
                    .astype(np.int64)
                for name, d in zip(self._tick_counter_names,
                                   (now - self._tick_counts_seen)
                                   % (1 << 32)):
                    self._count(name, int(d))
                self._tick_counts_seen = now
            kprop = macc = None
            if spec:
                kprop, macc = vals[4], vals[5]
                rows = list(p["rows"])
                prop = int(kprop[rows].sum())
                if prop:
                    self._count("spec_proposed", prop)
                    acc = int(macc[rows].sum())
                    if acc:
                        self._count("spec_accepted", acc)
            lag = self.dispatch_count - p["seq"] + 1   # dispatches until drain
            for i, req in p["rows"].items():
                self._commit_row_drain(
                    i, req, ring[i], rlps[i], wcur[i], act_now[i],
                    int(kprop[i]) if spec else 0,
                    int(macc[i]) if spec else 0, lag)

    def _commit_row_drain(self, i, req, ring_i, rlps_i, wc, act_i,
                          kp, ma, lag) -> bool:
        """Per-row host bookkeeping shared by the global drain's loop
        and the scoped drain (ISSUE 14) — one implementation so the
        two paths cannot drift: advance the drained cursor, mirror the
        device spec EMA, append the row's new ring entries — stop check
        FIRST, so a stop completing on the final budgeted (or eos)
        token still records its trim length; tokens the device
        committed past a stop die with the slot release (the
        spec/run-ahead over-commit contract) — emit the trace tick
        event, then finish on a host stop or the device finish flag
        (the tick -> engine_finish event order the reqtrace pins rely
        on). ``req`` is the request the dispatch served in this row;
        ``ring_i``/``rlps_i`` are the row's ring slices; ``kp``/``ma``
        its spec counters (0 when spec is off). Returns False for rows
        released since dispatch, out of band or by the drain of an
        older dispatch (cursor still advanced: what the device
        committed for the row after its request ended is never read,
        and the slot's next tenant starts at the device's cursor)."""
        slot = self.slots[i]
        base = int(self._drained[i])
        n_new = int(wc) - base
        self._drained[i] = int(wc)
        if slot is not req:     # released since dispatch
            return False
        if self._spec_k:
            self._h_tpf.observe(n_new)
            if kp:
                # host mirror of the device EMA (same update; the
                # authority switch happens at the next refresh)
                slot.spec_ema = ((1.0 - _SPEC_EMA_ALPHA) * slot.spec_ema
                                 + _SPEC_EMA_ALPHA
                                 * (float(ma) / float(kp)))
        appended = 0
        stopped = False
        for j in range(base, base + n_new):
            self._count("active_slot_steps")
            self.seq_lens[i] += 1   # device advanced its copy too
            slot.tokens.append(int(ring_i[j % self._ring_len]))
            slot.lps.append(float(rlps_i[j % self._ring_len]))
            appended += 1
            if self._stop_hit(slot):
                stopped = True
                break
        if self.trace_sink is not None:
            ev = dict(n=appended, ring_lag=lag)
            if self._spec_k:
                ev.update(proposed=int(kp), accepted=int(ma))
            # ring drains commit one dispatch behind — this is the LAST
            # COMPLETED tick's split, the one whose tokens are being
            # committed here
            ph = self._tick_phase_fields()
            if ph is not None:
                ev["phase"] = ph
            self.trace_sink(slot.request_id, "tick", **ev)
        if stopped or not bool(act_i):
            # host stop, or the device finish flag (eos/budget)
            self._finish(i)
        return True

    def _drain_row(self, i: int):
        """SCOPED ring drain (ISSUE 14): consume ONLY slot ``i``'s
        pending entries, from every outstanding dispatch that served
        it, oldest first. An out-of-band transition (cancel, deadline
        expiry) synchronizes with the in-flight programs through this
        row's output slices alone — the ``device_get`` still waits for
        the whole program, so releasing the row's blocks afterwards
        can never race an in-flight write — while the SIBLING rows'
        entries stay pending for the next ``step()``'s normal drain:
        their mirrors are untouched, their tokens survive. No-op when
        nothing is outstanding or the row was in no dispatch."""
        for p in [p for p in self._pending if i in p["rows"]]:
            self._drain_row_of(p, i)

    def _drain_row_of(self, p, i: int):
        base_arrs = p["arrs"]
        spec = self._spec_k > 0
        # a scoped drain IS a ring drain: counting it in both keeps
        # the blocking/all ratio a profiler reads <= 1
        self.ring_drains += 1
        self.ring_scoped_drains += 1
        # probe the DISPATCH OUTPUTS, not the row slices built below —
        # the slices are freshly enqueued computations whose is_ready()
        # would read False even when the in-flight program finished
        # long ago, inflating the blocking-drain counters a profiler
        # reads as "host falling behind"
        if not all(a.is_ready() for a in base_arrs):
            self.ring_blocking_drains += 1
            self.d2h_syncs += 1
        # same device/drain/commit bracketing as the global drain;
        # outside an open tick (cancel/expiry between steps) the
        # windows feed totals + histograms only
        with self._phase("device") as br:
            if br.on:
                try:
                    jax.block_until_ready(base_arrs)
                except Exception:
                    pass
                br.switch("drain")
            t0 = time.perf_counter()
            vals = jax.device_get([a[i] for a in base_arrs])
            # same histogram window as the global drain
            self._h_decode.observe((time.perf_counter() - t0) * 1e3)
            br.switch("commit")
            ring_i, rlps_i, wc, act_i = vals[:4]
            req = p["rows"].pop(i)
            if not p["rows"]:
                self._pending.remove(p)
            kp = ma = 0
            if spec:
                kp, ma = int(vals[4]), int(vals[5])
            if self._commit_row_drain(
                    i, req, ring_i, rlps_i, wc, act_i, kp, ma,
                    self.dispatch_count - p["seq"] + 1) and kp:
                self._count("spec_proposed", kp)
                if ma:
                    self._count("spec_accepted", ma)

    def _up(self, x):
        """Host-mirror upload on the per-tick host path (counted so the
        fused path's zero-upload steady state is testable; bytes too —
        the ISSUE 14 cost accounting covers every upload flavor)."""
        self.h2d_uploads += 1
        self.h2d_upload_bytes += x.nbytes
        self._count("h2d_upload_bytes", x.nbytes)
        self._h_bytes.observe(x.nbytes)
        with self._phase("h2d"):
            return self._put(x)

    def _decode_host(self, active):
        """The REFERENCE tick (``fused_tick=False``), not a served path:
        re-uploads every mirror, reads (next_token, logprob) back in
        the tick and runs all stop/eos/budget bookkeeping in Python.
        It is the one bit-exactness reference: every stream of the
        fused tick is compared with this one's."""
        t_decode = time.perf_counter()
        last = np.zeros((self.R,), np.int32)
        for i in active:
            last[i] = self.slots[i].tokens[-1]
        act_mask = np.zeros((self.R,), bool)
        act_mask[active] = True
        self.dispatch_count += 1
        self._count("dispatches")
        self.d2h_syncs += 1
        # the jit-call expression below interleaves _up uploads with
        # the dispatch: their h2d brackets nest in this one and take
        # their time out of it
        with self._phase("dispatch") as br:
            if np.all(self.temps[active] <= 0.0):
                # all-greedy tick: the argmax-only executable
                nxt, lps, self.seen, self.pools = self._decode_greedy_jit(
                    self.params, self.pools, self._up(self.block_tables),
                    self._up(self.seq_lens), self._up(last),
                    self.seen, self._up(self.reps), self._up(act_mask))
            else:
                nxt, lps, new_keys, self.seen, self.pools = self._decode_jit(
                    self.params, self.pools, self._up(self.block_tables),
                    self._up(self.seq_lens), self._up(last),
                    self._up(self.keys), self._up(self.temps),
                    self._up(self.top_ks), self._up(self.top_ps),
                    self.seen, self._up(self.reps), self._up(act_mask))
                self.keys = np.array(new_keys)  # copy: jax views read-only
            if br.on:
                br.switch("device")
                try:
                    jax.block_until_ready((nxt, lps))
                except Exception:
                    pass
                br.switch("drain")
            nxt = np.asarray(nxt)
            lps = np.asarray(lps)
            br.switch("commit")
            # the np.asarray above synced the device, so this is the REAL
            # per-tick latency (dispatch + compute), not just dispatch
            self._h_decode.observe((time.perf_counter() - t_decode) * 1e3)
            self._count("decode_steps")
            self._count("slot_steps", self.R)
            self._count("active_slot_steps", len(active))
            sink = self.trace_sink
            for i in active:
                slot = self.slots[i]
                self.seq_lens[i] += 1   # the decode wrote last token's K/V
                tok = int(nxt[i])
                slot.tokens.append(tok)
                slot.lps.append(float(lps[i]))
                slot.key = self.keys[i].copy()
                if sink is not None:
                    ev = dict(n=1)
                    ph = self._tick_phase_fields()
                    if ph is not None:
                        ev["phase"] = ph
                    sink(slot.request_id, "tick", **ev)
                done = self._stop_hit(slot) or \
                    len(slot.tokens) >= slot.max_new or \
                    (slot.eos is not None and tok == slot.eos)
                if done:
                    # the final token's K/V was never written - fine, it is
                    # never attended to
                    self._finish(i)
        return True

    def _decode_fused(self, active):
        """The served tick's host half: ONE compiled dispatch advancing
        every active slot (staged transitions → attention → penalty →
        sampling → done flags → ring append, all device-state mutations
        inside the program) and NO readback: the committed tokens land
        in the device ring and a later drain consumes them (the next
        ``step()``'s, or this one's own second half when it runs
        ahead). Host bookkeeping (appends, stops inside a speculative
        window, finishes, spec counters and the EMA mirror, traces)
        happens there, one step behind the device. The program is the
        speculative tick under ``spec_tokens``, else the plain tick;
        its token outputs are not fetched."""
        with self._phase("stage") as br:
            self._sync_dev()
            self.dispatch_count += 1
            self._count("dispatches")
            greedy = np.all(self.temps[active] <= 0.0)
            if self._spec_k:
                fn = self._tick_spec_greedy_jit if greedy \
                    else self._tick_spec_jit
            else:
                fn = self._tick_greedy_jit if greedy else self._tick_jit
            # dispatch = the program CALL (enqueue; asynchronous) —
            # compute lands in the drain boundary's device wait
            br.switch("dispatch")
            *_, self.seen, self.pools, st = fn(
                self.params, self.pools, self.seen, self._dev)
        self._dev = st
        if not greedy:
            self._dev_keys_dirty = True
        if self._pending:
            self._count("runahead_ticks")
        arrs = [st["ring"], st["rlps"], st["wcur"], st["active"]]
        if self._spec_k:
            arrs += [st["kprop_last"], st["macc_last"]]
        self._pending.append(dict(
            rows={i: self.slots[i] for i in active},
            seq=self.dispatch_count, arrs=arrs))
        self._count("decode_steps")
        self._count("slot_steps", self.R)
        return True

    def _spec_headroom(self, active):
        """Best-effort block preallocation so spec-eligible rows — ALL
        active rows since the rejection-sampled verify (ISSUE 11);
        sampled and penalized rows draft too — can write k+1 tokens
        this tick. Never preempts and keeps a one-block-per-active-row
        reserve; a row that cannot get headroom simply drafts less (or
        nothing): the device caps its kprop by the write capacity read
        off the block table, which IS the clean per-row 1-token
        fallback. Collapsed-EMA rows only reserve probe headroom (one
        draft) instead of k."""
        for i in active:
            s = self.slots[i]
            if s.max_new - len(s.tokens) < 2:
                continue
            k_want = self._spec_k if s.spec_ema >= _SPEC_EMA_FLOOR else 1
            # a table holds at most M blocks: near the capacity edge the
            # device write-capacity clamp shrinks kprop instead
            need = min(
                self._blocks_needed(int(self.seq_lens[i]) + k_want + 1),
                self.M)
            if not self._grow_blocks(i, need, reserve=len(active)):
                return

    def run(self) -> Dict[Any, List[int]]:
        """Drive until queue and slots drain; returns request_id ->
        generated token list (prompt excluded)."""
        while self.queue or any(s is not None for s in self.slots):
            self.step()
        return dict(self.results)

    def stream(self):
        """Generator over (request_id, token) pairs in emission order:
        each tick's newly generated tokens are yielded as they land
        (token-streaming serving APIs). Requests with stop_sequences
        hold back the last max-stop-length tokens until they finish, so
        the consumer sees EXACTLY the tokens that end up in ``results``
        (a yielded token is never retracted by the stop trim). Drives
        the engine to drain; submits made during iteration join the
        stream."""
        emitted: Dict[Any, int] = {}
        # results from BEFORE this call (engines are reused across
        # serve_stream calls) must not replay into this stream
        flushed = set(self.results)
        while self.queue or any(s is not None for s in self.slots):
            self.step()
            for s in self.slots:
                if s is None:
                    continue
                rid = s.request_id
                hold = max((len(x) for x in s.stop), default=0)
                n_pre = len(s.prefix)
                start = emitted.get(rid, 0)
                # yield only the [start, upto) window — no prefix+tokens
                # concatenation per tick (cf. _stop_hit's O(1) note)
                upto = max(n_pre + len(s.tokens) - hold, start)
                for i in range(start, upto):
                    yield (rid, s.prefix[i] if i < n_pre
                           else s.tokens[i - n_pre])
                emitted[rid] = upto
            if len(self.results) > len(flushed):
                # something finished this tick: flush the rest of its
                # (stop-trimmed) final tokens. flushed only ever grows
                # with results, so the length compare is exact and the
                # set difference runs only on finishing ticks.
                for rid in set(self.results) - flushed:
                    for t in self.results[rid][emitted.pop(rid, 0):]:
                        yield (rid, t)
                    flushed.add(rid)
