"""Async device-prefetch pipeline + persistent compile cache (ISSUE 4).

Unit layer: the `DevicePrefetcher` contract — overlap actually happens,
the buffer stays bounded, teardown is clean on early break, errors
propagate, and `state_dict()` reports the CONSUMER position even while
the producer runs ahead (the invariant preemption-exact resume rides
on). Trainer layer: tokens/sec + MFU in the logs, bit-identical loss
trajectory with prefetch on vs off, save/eval wall time excluded from
throughput windows, the single-host-sync eval loop, and the seeded
`prefetch_stall` fault degrading to synchronous feeding instead of
deadlocking. Cache layer: `compile_cache.enable()` un-latches jax's
once-only cache initialization, a cold `Trainer.train` populates the
directory, and a second trainer's startup HITS it (event-counted, not
wall-clocked). Everything stays seconds-fast: tier-1 is ~835s of 870s.
"""
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.io import DataLoader, DevicePrefetcher, RandomSampler
from paddle_tpu.io.device_prefetch import default_device_put
from paddle_tpu.utils import compile_cache, faults

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _host(b):
    """Identity placement: unit tests exercise threading, not devices."""
    return b


class _CountingSource:
    """Iterable that records how many items were drawn and when."""

    def __init__(self, n=100, delay_s=0.0, fail_at=None):
        self.n = n
        self.delay_s = delay_s
        self.fail_at = fail_at
        self.drawn = 0

    def __iter__(self):
        for i in range(self.n):
            if self.delay_s:
                time.sleep(self.delay_s)
            if self.fail_at is not None and i == self.fail_at:
                raise RuntimeError(f"source failed at item {i}")
            self.drawn += 1
            yield i

    def __len__(self):
        return self.n


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name == "device-prefetch" and t.is_alive()]


# ================================================================= unit
class TestDevicePrefetcher:
    def test_yields_everything_in_order_with_prep(self):
        pf = DevicePrefetcher(_CountingSource(12), prep=lambda x: x * 10,
                              depth=3, place=_host)
        assert len(pf) == 12
        assert list(pf) == [i * 10 for i in range(12)]
        pf.close()

    def test_overlap_host_feed_with_consumer_work(self):
        """ACCEPTANCE (unit): with a slow host feed AND consumer-side
        work, the prefetched wall clock approaches max(feed, work), not
        feed + work. Generous margins: sync costs n*(a+b)=0.72s, the
        overlapped run should land near 0.39s; we only require < 75%."""
        n, feed_s, work_s = 12, 0.03, 0.03

        t0 = time.perf_counter()
        for _ in _CountingSource(n, delay_s=feed_s):
            time.sleep(work_s)
        sync_wall = time.perf_counter() - t0

        pf = DevicePrefetcher(_CountingSource(n, delay_s=feed_s), depth=2,
                              place=_host)
        t0 = time.perf_counter()
        got = 0
        for _ in pf:
            time.sleep(work_s)
            got += 1
        pf_wall = time.perf_counter() - t0
        pf.close()
        assert got == n
        assert pf_wall < 0.75 * sync_wall, (pf_wall, sync_wall)

    def test_buffer_stays_bounded(self):
        """A stalled consumer must not let the producer drain the whole
        source into memory: at most depth (queued) + 1 (in flight) + the
        consumed item may be drawn."""
        src = _CountingSource(100)
        pf = DevicePrefetcher(src, depth=2, place=_host)
        it = iter(pf)
        next(it)
        time.sleep(0.3)           # producer runs ahead only to the bound
        assert src.drawn <= 1 + 2 + 1
        pf.close()

    def test_early_break_tears_down_producer(self):
        src = _CountingSource(1000, delay_s=0.001)
        pf = DevicePrefetcher(src, depth=2, place=_host)
        for i, _ in enumerate(pf):
            if i == 1:
                break
        pf.close()
        assert not _prefetch_threads()
        assert src.drawn < 1000   # and it never drained the source
        pf.close()                # idempotent

    def test_reiter_starts_fresh_epoch_and_replaces_thread(self):
        pf = DevicePrefetcher(_CountingSource(6), depth=2, place=_host)
        assert list(pf) == list(range(6))
        assert list(pf) == list(range(6))     # second epoch, same feed
        pf.close()
        assert not _prefetch_threads()

    def test_producer_error_propagates_to_consumer(self):
        pf = DevicePrefetcher(_CountingSource(10, fail_at=3), depth=2,
                              place=_host)
        it = iter(pf)
        got = [next(it), next(it), next(it)]
        with pytest.raises(RuntimeError, match="failed at item 3"):
            next(it)
        assert got == [0, 1, 2]
        assert not _prefetch_threads()

    def test_state_dict_is_consumer_position_not_producer(self):
        """THE preemption invariant: while the producer runs ahead by
        the buffer depth, state_dict() must report the last-YIELDED
        batch's position — a checkpoint taken mid-prefetch then resumed
        must train exactly the un-yielded remainder (nothing skipped,
        nothing double-trained)."""
        data = list(np.arange(48, dtype=np.int64))
        mk = lambda: DataLoader(data, batch_size=4,
                                sampler=RandomSampler(data, generator=11))

        # reference: consumer position after 4 batches, synchronously
        sync = mk()
        sit = iter(sync)
        consumed = [np.asarray(next(sit)).copy() for _ in range(4)]
        want_state = sync.state_dict()
        want_rest = [np.asarray(b).copy() for b in sit]

        pf = DevicePrefetcher(mk(), depth=3, place=_host)
        it = iter(pf)
        got = [np.asarray(next(it)).copy() for _ in range(4)]
        time.sleep(0.2)                      # let the producer run ahead
        assert pf.state_dict() == want_state
        pf.close()                           # "preemption": buffered lost
        assert pf.state_dict() == want_state  # position survives close

        resumed = mk()
        resumed.load_state_dict(pf.state_dict())
        rest = [np.asarray(b) for b in resumed]
        for a, b in zip(got, consumed):
            np.testing.assert_array_equal(a, b)
        assert len(rest) == len(want_rest)
        for a, b in zip(rest, want_rest):
            np.testing.assert_array_equal(a, b)

    def test_stall_fault_degrades_to_synchronous_feed(self, monkeypatch):
        """Seeded `prefetch_stall` wedges the producer every cycle; the
        consumer must degrade to feeding itself through the fetch lock —
        every batch delivered exactly once, no deadlock."""
        assert "prefetch_stall" in faults.SITES
        monkeypatch.setenv(faults.PREFETCH_STALL_ENV_VAR, "0.5")
        pf = DevicePrefetcher(_CountingSource(6), depth=2, place=_host,
                              stall_timeout_s=0.05)
        with faults.scoped("prefetch_stall"):
            got = list(pf)
        assert got == list(range(6))          # exactly once, in order
        assert pf.sync_fallbacks >= 1
        pf.close()

    def test_transient_stall_recovery_does_not_deadlock(self, monkeypatch):
        """One-shot stall (`prefetch_stall@1`): the consumer latches into
        degraded mode, then the producer RECOVERS, refills the bounded
        queue, and blocks in its put while holding the fetch lock. The
        latched consumer must drain the queue without the lock (and
        un-latch), not spin on a lock the wedged producer can never
        release — regression for the post-recovery deadlock."""
        monkeypatch.setenv(faults.PREFETCH_STALL_ENV_VAR, "0.35")
        src = _CountingSource(10)
        pf = DevicePrefetcher(src, depth=1, place=_host,
                              stall_timeout_s=0.05)
        got = []

        def consume():
            with faults.scoped("prefetch_stall@1"):
                for b in pf:                   # slow consumer: the
                    got.append(b)              # recovered producer gets
                    time.sleep(0.06)           # ahead and fills the queue

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        t.join(timeout=20)
        assert not t.is_alive(), (
            f"prefetch consumer deadlocked after transient stall "
            f"({len(got)}/10 batches delivered)")
        assert got == list(range(10))          # exactly once, in order
        assert src.drawn == 10
        assert pf.sync_fallbacks >= 1          # the stall did latch
        pf.close()

    def test_default_device_put_modes(self):
        """No mesh + several virtual devices -> host pass-through (jit
        places); a live mesh -> committed, fully-replicated placement."""
        from paddle_tpu.distributed import env
        x = np.ones((4, 2), dtype=np.float32)
        assert len(jax.local_devices()) > 1    # conftest forces 8
        assert default_device_put(x) is x
        mesh = env.init_parallel_env({"dp": 2}, devices=jax.devices()[:2])
        try:
            placed = default_device_put({"input_ids": x})
            arr = placed["input_ids"]
            assert arr.sharding.is_fully_replicated
            assert set(arr.sharding.device_set) == set(mesh.devices.flat)
        finally:
            env.clear_mesh()


# ======================================================== trainer layer
def _tiny_trainer(out_dir, *, batches=None, max_steps=6, **kw):
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    from paddle_tpu.trainer import Trainer, TrainingArguments
    pt.seed(0)
    if batches is None:
        rng = np.random.RandomState(3)
        batches = [jnp.asarray(rng.randint(0, 256, (4, 16)))
                   for _ in range(8)]
    args = TrainingArguments(output_dir=str(out_dir), max_steps=max_steps,
                             logging_steps=2, seed=42,
                             resume_from_checkpoint=False, **kw)
    return Trainer(LlamaForCausalLM(llama_tiny()),
                   pt.optimizer.AdamW(learning_rate=1e-3), args,
                   train_dataloader=batches)


class TestTrainerIntegration:
    def test_logs_carry_tokens_per_sec_and_mfu(self, tmp_path,
                                               monkeypatch):
        """ACCEPTANCE: the bench-visible numbers get a first-class
        in-loop source. MFU needs the device's published peak, so this
        run gives the CPU one; without it a run logs no "mfu" at all
        (next test)."""
        from paddle_tpu.utils import profiler
        monkeypatch.setitem(profiler.PEAK_BF16_FLOPS,
                            jax.devices()[0].device_kind, 1e12)
        tr = _tiny_trainer(tmp_path, max_steps=4)
        tr.train()
        hist = tr.logger.history
        assert {"loss", "steps_per_sec", "tokens_per_sec", "mfu"} <= set(hist)
        assert all(v > 0 for _, v in hist["tokens_per_sec"])
        assert all(v >= 0 for _, v in hist["mfu"])
        # the MFU source: flops/token derived from the model config once
        assert tr.step_timer.flops_per_token > 0
        assert tr.step_timer.total_tokens == 4 * 4 * 16  # steps*b*s

    def test_loss_trajectory_bit_identical_prefetch_on_off(self, tmp_path):
        """ACCEPTANCE: the async feed changes WHEN batches reach the
        device, never WHAT the step computes — the loss trajectory is
        bit-identical with prefetch on vs off."""
        off = _tiny_trainer(tmp_path / "off", prefetch_depth=0)
        off.train()
        on = _tiny_trainer(tmp_path / "on", prefetch_depth=3)
        on.train()
        h_off = [(s, v) for s, v in off.logger.history["loss"]]
        h_on = [(s, v) for s, v in on.logger.history["loss"]]
        assert h_off == h_on                  # exact float equality
        # the CPU has no published peak: no MFU, never one against an
        # assumed peak
        assert on.step_timer.peak_flops is None
        assert on.step_timer.mfu is None
        assert "mfu" not in on.logger.history

    def test_save_wall_time_excluded_from_throughput(self, tmp_path,
                                                     monkeypatch):
        """ISSUE 4 satellite: a slow save must pollute neither the next
        steps_per_sec window nor the StepTimer totals."""
        sleep_s = 0.4
        # aot_warmup keeps the jit compile out of the first window, so
        # EVERY window is a pure step window the assertion can bound
        tr = _tiny_trainer(tmp_path, max_steps=6, save_steps=2,
                           aot_warmup=True)
        monkeypatch.setattr(tr, "save_checkpoint",
                            lambda *a, **k: time.sleep(sleep_s))
        tr.train()
        rates = [v for _, v in tr.logger.history["steps_per_sec"]]
        # windows 2 and 3 each follow a 0.4s save — leaked save wall
        # time would cap them at 2/0.4 = 5 steps/s, real CPU step
        # windows run far faster
        assert len(rates) == 3
        assert min(rates) > 2 / sleep_s * 2, rates
        # and the timer that feeds tokens_per_sec/mfu excluded all 3
        # sleeps (1.2s) from its totals
        assert tr.step_timer.total_s < sleep_s, tr.step_timer.total_s

    def test_steps_per_sec_consistent_when_save_splits_log_window(
            self, tmp_path, monkeypatch):
        """A save landing MID logging-window (save_steps=3 with
        logging_steps=2) resets the wall-clock window, so the step-4 log
        spans ONE step; a numerator of args.logging_steps would report
        ~2x the real rate. Invariant: within any one log record,
        tokens_per_sec / steps_per_sec ≈ tokens-per-step (64), since
        both meters span the same window."""
        tr = _tiny_trainer(tmp_path, max_steps=6, save_steps=3,
                           aot_warmup=True)
        monkeypatch.setattr(tr, "save_checkpoint", lambda *a, **k: None)
        tr.train()
        sps = dict(tr.logger.history["steps_per_sec"])
        tps = dict(tr.logger.history["tokens_per_sec"])
        for step in (2, 4, 6):
            ratio = tps[step] / sps[step]
            assert 64 * 0.7 < ratio < 64 * 1.4, (step, ratio)

    def test_eval_syncs_host_once_not_per_batch(self, tmp_path,
                                                monkeypatch):
        """ISSUE 4 satellite: evaluate() collects DEVICE scalars and
        blocks once at the end — one device_get carrying jax arrays,
        not a float() per batch."""
        rng = np.random.RandomState(3)
        evals = [jnp.asarray(rng.randint(0, 256, (4, 16)))
                 for _ in range(5)]
        tr = _tiny_trainer(tmp_path, max_steps=2)
        tr.eval_dataloader = evals
        tr.train()
        captured = []
        orig = jax.device_get

        def spy(x):
            captured.append(x)
            return orig(x)

        monkeypatch.setattr(jax, "device_get", spy)
        mean = tr.evaluate()
        assert len(captured) == 1             # ONE host sync
        assert len(captured[0]) == len(evals)
        assert all(isinstance(l, jax.Array) for l in captured[0])
        np.testing.assert_allclose(
            mean, float(np.mean(orig(captured[0]))), rtol=1e-6)
        assert tr.logger.history["eval_loss"][-1][1] == mean

    def test_trainer_degrades_on_prefetch_stall(self, tmp_path,
                                                monkeypatch):
        """ISSUE 4 satellite (trainer level): a wedged prefetch thread
        degrades the loop to synchronous feeding — training completes,
        no deadlock."""
        monkeypatch.setenv(faults.PREFETCH_STALL_ENV_VAR, "0.7")
        tr = _tiny_trainer(tmp_path, max_steps=4,
                           prefetch_stall_timeout_s=0.05)
        with faults.scoped("prefetch_stall"):
            tr.train()
        assert tr.global_step == 4
        assert tr._data_feed.sync_fallbacks >= 1
        assert np.isfinite(tr.logger.history["loss"][-1][1])


# ========================================================= compile cache
@pytest.fixture
def _isolated_cache(tmp_path, monkeypatch):
    """Redirect the persistent cache for one test through the standard
    variable, then restore (and re-latch) the suite-wide cache
    conftest.py installed."""
    prev_dir = os.environ[compile_cache.ENV_VAR]
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    cache = str(tmp_path / "xla_cache")
    monkeypatch.setenv(compile_cache.ENV_VAR, cache)
    yield cache
    monkeypatch.setenv(compile_cache.ENV_VAR, prev_dir)
    compile_cache.enable(min_compile_time_s=prev_min)


class TestCompileCache:
    def test_enable_unlatches_jax_once_only_cache_init(self, _isolated_cache):
        """Regression for the latch bug: jax initializes its cache
        object at most once, on the FIRST compile — enable() after that
        compile must still take effect (reset + re-init), because
        Trainer.train always runs after model init has compiled ops."""
        jax.jit(lambda x: x * 2 + 1)(jnp.ones((8, 8))).block_until_ready()
        assert compile_cache.enable(min_compile_time_s=0.0) \
            == _isolated_cache
        assert compile_cache.active_dir() == _isolated_cache

        @jax.jit
        def f(x):
            for _ in range(4):
                x = jnp.tanh(x) @ x
            return x

        f(jnp.ones((16, 16))).block_until_ready()
        assert len(compile_cache.entries(_isolated_cache)) > 0

    def test_second_trainer_startup_hits_cache(self, tmp_path, monkeypatch,
                                               _isolated_cache):
        """ACCEPTANCE: a cold Trainer.train populates the cache dir; a
        second trainer's startup restores the step executable from it —
        asserted via population (no new entries) plus jax's own
        cache-hit events, not wall time."""
        from jax._src import monitoring as _mon
        compile_cache.enable(min_compile_time_s=0.0)
        cold = _tiny_trainer(tmp_path / "cold", max_steps=2)
        cold.train()
        populated = set(compile_cache.entries(_isolated_cache))
        assert populated                      # cold startup wrote programs

        hits = []
        saved = list(_mon.get_event_listeners())
        _mon.register_event_listener(
            lambda name, **kw: hits.append(name)
            if name == "/jax/compilation_cache/cache_hits" else None)
        try:
            warm = _tiny_trainer(tmp_path / "warm", max_steps=2)
            warm.train()
        finally:
            _mon._event_listeners[:] = saved
        assert set(compile_cache.entries(_isolated_cache)) == populated
        assert hits                           # executables restored, not rebuilt

    @pytest.mark.parametrize("env,added", [(None, 2), ("0", 1)])
    def test_a_scope_only_edit_is_its_own_entry(self, monkeypatch,
                                                _isolated_cache, env, added):
        """enable() puts op metadata into the cache key, so a program
        whose named scopes alone changed is compiled again and a trace
        read by scope never shows the old names; the operator's
        variable (the suite sets it to 0) keeps jax's default, under
        which both spellings are one entry."""
        flag = "jax_compilation_cache_include_metadata_in_key"
        prev = getattr(jax.config, flag)
        suite = os.environ[compile_cache.META_ENV_VAR]     # conftest's
        if env is None:
            monkeypatch.delenv(compile_cache.META_ENV_VAR, raising=False)
        else:
            monkeypatch.setenv(compile_cache.META_ENV_VAR, env)

        def scoped(name):
            def f(x):
                with jax.named_scope(name):
                    return jnp.tanh(x) @ x
            return jax.jit(f)

        x = jnp.ones((16, 16))
        try:
            compile_cache.enable(min_compile_time_s=0.0)
            assert getattr(jax.config, flag) is (env is None)
            before = len(compile_cache.entries(_isolated_cache))
            for name in ("old", "new"):
                scoped(name)(x).block_until_ready()
            n = len(compile_cache.entries(_isolated_cache)) - before
        finally:
            # before the fixture's own enable() runs at teardown
            monkeypatch.setenv(compile_cache.META_ENV_VAR, suite)
            jax.config.update(flag, prev)
        assert n == added

    def test_entries_and_child_env(self, monkeypatch, tmp_path):
        """(The resolver's own order — the standard variable over every
        argument, else the fixed in-checkout path — is pinned in
        tests/test_chip_smoke.py.)"""
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        env = compile_cache.child_env("/a/b", base={"PATH": "/bin"})
        assert env[compile_cache.ENV_VAR] == "/a/b"
        assert env["PATH"] == "/bin"
        assert compile_cache.ENV_VAR not in compile_cache.child_env(
            base={"PATH": "/bin"})
        # entries() hides -atime bookkeeping files
        d = tmp_path / "c"
        d.mkdir()
        (d / "prog-1-cache").write_bytes(b"x")
        (d / "prog-1-atime").write_bytes(b"")
        assert compile_cache.entries(str(d)) == ["prog-1-cache"]

    def test_supervise_propagates_cache_dir_to_children(self, tmp_path,
                                                        monkeypatch):
        """elastic.supervise hands $JAX_COMPILATION_CACHE_DIR to every
        (re)launch, so a preempted-and-relaunched worker resolves the
        same cache without trainer-side plumbing (jax-free child:
        tier-1 budget). The supervisor's own variable would win over
        the argument, so it is cleared here."""
        from paddle_tpu.distributed.elastic import supervise
        monkeypatch.delenv(compile_cache.ENV_VAR)
        out = tmp_path / "seen"
        child = (f"import os; open({str(out)!r}, 'w').write("
                 f"os.environ.get('{compile_cache.ENV_VAR}', 'MISSING'))")
        rc = supervise([sys.executable, "-c", child], max_restarts=0,
                       backoff_s=0.01, compile_cache_dir=str(tmp_path / "cc"))
        assert rc == 0
        assert out.read_text() == str(tmp_path / "cc")
