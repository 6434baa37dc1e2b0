"""Profiling (reference: paddle.profiler.Profiler — scheduler, timer_only
mode, chrome-trace export).

TPU-native: wraps `jax.profiler` (perfetto/xplane traces viewable in
tensorboard or perfetto.dev) and adds the numbers people actually watch in
training loops: step time, tokens/sec, and MFU against the chip's peak."""
from __future__ import annotations

import contextlib
import sys
import time
from dataclasses import dataclass, field
from typing import Optional

import jax

# Peak dense bf16 FLOP/s of ONE chip, keyed by jax's ``device_kind``.
# The repository's only peak table (bench.py reads it too). Source:
# Google Cloud TPU documentation, the per-generation "System
# architecture" pages (v4: 275 TFLOP/s; v5e: 197; v5p: 459; v6e
# "Trillium": 918). A device that is not listed has no peak here and
# therefore no MFU — never a default.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e, as jax reports it
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e
}


def device_peak_flops(device=None) -> Optional[float]:
    """Published bf16 peak of ``device`` (default: the first device),
    None when its ``device_kind`` is not in ``PEAK_BF16_FLOPS``."""
    device = device or jax.devices()[0]
    return PEAK_BF16_FLOPS.get(device.device_kind)


def local_peak_flops() -> Optional[float]:
    """Aggregate peak of every local chip. The trainer's token counts
    span the whole per-process batch (all local mesh devices), so MFU
    must divide by the matching aggregate peak — a single chip's peak
    would overstate it by the local device count. None when any local
    device has no published peak (the CPU)."""
    peaks = [device_peak_flops(d) for d in jax.local_devices()]
    return None if None in peaks else sum(peaks)


# jax.profiler supports ONE live trace per process; the owner lets
# stop() know whether this instance actually holds it
_trace_owner: Optional["Profiler"] = None


class Profiler:
    """paddle.profiler.Profiler-shaped facade over jax.profiler.

    A trace is taken WITHOUT jax's Python tracer unless
    ``python_tracer=True``: the tracer hooks every Python call of every
    thread, which triples a serving tick for the length of the capture
    and fills the host lines with its own events (PERF.md section 6, PR
    35). The program's own spans (``TraceAnnotation``: ``tick/<phase>``,
    ``loop/write``, ``annotate``) and the device's ops are in the trace
    either way; the tracer is for someone hunting a Python function."""

    def __init__(self, logdir: str = "runs/profile", timer_only: bool = False,
                 python_tracer: bool = False):
        self.logdir = logdir
        self.timer_only = timer_only
        self.python_tracer = bool(python_tracer)
        self._active = False

    def start(self):
        """Idempotent: a second ``start()`` on a live profiler — or a
        ``start()`` while ANOTHER profiler's trace is still open — warns
        and returns instead of surfacing jax.profiler's raw "trace
        already started" error mid-run."""
        global _trace_owner
        if self._active:
            print("[profiler] start() called on an already-active "
                  "profiler; ignoring", file=sys.stderr, flush=True)
            return
        if not self.timer_only:
            if _trace_owner is not None:
                print(f"[profiler] a trace is already running "
                      f"(logdir={_trace_owner.logdir}); start() falls "
                      f"back to timer-only for this profiler",
                      file=sys.stderr, flush=True)
            else:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = int(self.python_tracer)
                jax.profiler.start_trace(self.logdir,
                                         profiler_options=opts)
                _trace_owner = self
        self._active = True

    def stop(self):
        global _trace_owner
        if self._active and _trace_owner is self:
            try:
                jax.profiler.stop_trace()
            finally:
                # release the latch even when stop_trace() raises: the
                # jax trace is in an unknown state either way, but a
                # held latch would wedge every future profiler in this
                # process into timer-only fallback
                _trace_owner = None
        self._active = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


@contextlib.contextmanager
def annotate(name: str):
    """Trace annotation visible in the profile (reference:
    paddle.profiler.RecordEvent)."""
    with jax.profiler.TraceAnnotation(name):
        yield


@dataclass
class StepTimer:
    """Running step-time / throughput / MFU meter."""
    flops_per_token: float = 0.0
    peak_flops: Optional[float] = field(default_factory=local_peak_flops)
    _t0: Optional[float] = None
    steps: int = 0
    total_s: float = 0.0
    total_tokens: int = 0

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self, tokens: int = 0, steps: int = 1):
        """Close a timing window covering ``steps`` training steps (the
        trainer logs once per ``logging_steps`` window, so per-step
        averages need the real step count, not the window count)."""
        if self._t0 is None:
            raise RuntimeError(
                "StepTimer.stop() called with no open window; call "
                "start() first")
        dt = time.perf_counter() - self._t0
        self._t0 = None          # window closed; a second stop() raises
        self.steps += steps
        self.total_s += dt
        self.total_tokens += tokens
        return dt

    @property
    def avg_step_s(self) -> float:
        return self.total_s / max(self.steps, 1)

    @property
    def tokens_per_sec(self) -> float:
        return self.total_tokens / max(self.total_s, 1e-9)

    @property
    def mfu(self) -> Optional[float]:
        """None on a device with no published peak."""
        if self.peak_flops is None:
            return None
        return self.flops_per_token * self.tokens_per_sec / self.peak_flops


def llama_flops_per_token(n_params: int, num_layers: int, seq_len: int,
                          hidden: int) -> float:
    """6N matmul + causal-attention term (fwd+bwd)."""
    return 6.0 * n_params + 6.0 * num_layers * seq_len * hidden
