"""Trainer (reference: PaddleNLP paddlenlp/trainer/trainer.py — the
train loop with gradient accumulation, hybrid-parallel awareness, AMP,
checkpointing/auto-resume, callbacks, and eval).

TPU-native: ONE jitted train step (loss -> grads -> clip -> optimizer)
with donated (params, opt_state) so the update is in-place in HBM.
Gradient accumulation folds into the same program via `lax.scan` over the
microbatch dim — not N python-side steps. Hybrid parallelism is ambient:
if a mesh is installed, params are sharded by their partition metadata
(fleet.distributed_model) and the step compiles to SPMD; the loop itself
is identical single-chip vs pod. Aux wiring: JSONL metrics (C21), NaN
watchdog (C20), orbax auto-resume (C14)."""
from __future__ import annotations

import functools
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .models.llama import causal_lm_loss
from .nn.layer import Layer
from .optimizer.optimizers import Optimizer
from .utils import compile_cache, faults
from .utils import observability as obs
from .utils.logging import LogWriter
from .utils.profiler import StepTimer, llama_flops_per_token
from .utils.shutdown import PREEMPTED_RC, GracefulShutdown
from .utils.watchdog import DivergenceError, StepWatchdog


@dataclass
class TrainingArguments:
    """Reference: paddlenlp.trainer.TrainingArguments (subset that matters)."""
    output_dir: str = "output"
    max_steps: int = 1000
    gradient_accumulation_steps: int = 1
    logging_steps: int = 10
    save_steps: int = 0              # 0 = no periodic ckpt
    eval_steps: int = 0
    resume_from_checkpoint: bool = True
    max_grad_norm: float = 1.0
    seed: int = 42
    nan_patience: int = 3
    donate_state: bool = True
    # elastic training (reference: paddle.distributed.elastic): a step
    # that exceeds hang_timeout_s triggers best-effort checkpoint +
    # process exit with hang_exit_code; a supervisor
    # (distributed.elastic.supervise) relaunches and auto-resume picks
    # up from the latest complete checkpoint.
    hang_timeout_s: Optional[float] = None
    hang_exit_code: int = 17
    # on resume, fast-forward the data stream past the batches the
    # checkpointed steps already consumed (reference: PaddleNLP Trainer's
    # skip_first_batches / consumed_samples accounting) so the loss
    # trajectory continues instead of re-seeing epoch-start data
    skip_data_on_resume: bool = True
    # interleaved pipeline: virtual chunks per pp device (Megatron-style
    # virtual_pp_degree); >1 shrinks the pipeline bubble that many times
    virtual_pp_degree: int = 1
    # divergence recovery (chaos hardening): on DivergenceError reload
    # the latest complete checkpoint and continue — the data iterator is
    # NOT rewound, so the poisoned window (batches between checkpoint
    # and divergence) is skipped rather than replayed. After this many
    # rollbacks in one train() call the error propagates (a persistent
    # NaN is a bug or a bad lr, not a transient).
    max_divergence_rollbacks: int = 2
    # preemption safety: install a SIGTERM/SIGINT GracefulShutdown
    # handler for the duration of train(); the loop polls it at step
    # boundaries and, when tripped (scheduler preemption notice, ^C, or
    # the seeded `preempt` fault site), checkpoints the exact current
    # step, drains the async writer, and exits preempt_exit_code — which
    # distributed.elastic.supervise restarts for free (a preemption is
    # not a failure and never consumes a max_restarts attempt).
    graceful_shutdown: bool = True
    preempt_exit_code: int = PREEMPTED_RC
    # async input pipeline (perf): wrap the dataloader in a
    # DevicePrefetcher so batch prep + the H2D copy of step N+1 overlap
    # step N's compute instead of serializing with it. 0 disables
    # (synchronous feeding, the pre-ISSUE-4 behavior). Checkpoint meta
    # always records the CONSUMER position, so preemption/resume is
    # bit-identical with or without prefetch.
    prefetch_depth: int = 2
    # a prefetch producer that delivers nothing for this long (wedged
    # host pipeline, the seeded `prefetch_stall` fault) degrades the
    # loop to synchronous feeding instead of deadlocking it
    prefetch_stall_timeout_s: float = 5.0
    # compile the train step ahead-of-time on the first batch (before
    # step 0 "runs"), so compile time never counts against the first
    # checkpoint/logging interval
    aot_warmup: bool = False
    # per-token model FLOPs for the in-loop MFU log; 0 derives it from
    # the model config (llama-family) on the first batch
    flops_per_token: float = 0.0


class TrainerCallback:
    def on_step_end(self, step: int, logs: Dict[str, float]):  # noqa: D401
        pass

    def on_save(self, step: int):
        pass

    def on_train_end(self, step: int):
        pass


class Trainer:
    def __init__(self, model: Layer, optimizer: Optimizer,
                 args: Optional[TrainingArguments] = None,
                 loss_fn: Optional[Callable] = None,
                 train_dataloader: Optional[Iterable] = None,
                 eval_dataloader: Optional[Iterable] = None,
                 callbacks: Optional[List[TrainerCallback]] = None,
                 scaler=None, logits_loss: Optional[Callable] = None):
        self.model = model
        self.optimizer = optimizer
        self.args = args or TrainingArguments()
        # loss_fn(pure_fn, params, batch) -> scalar; default: causal LM on
        # a batch of token ids (the flagship recipe). logits_loss(logits,
        # labels) -> scalar swaps just the loss head while keeping the
        # token-ids recipe — unlike loss_fn it also works under pipeline
        # parallelism, where the loss must live at the LAST stage and a
        # whole-model loss_fn cannot be decomposed.
        if loss_fn is not None and logits_loss is not None:
            raise ValueError("pass loss_fn OR logits_loss, not both")
        self._default_loss = loss_fn is None
        self._logits_loss = logits_loss
        if loss_fn is not None:
            self.loss_fn = loss_fn
        elif logits_loss is not None:
            self.loss_fn = (
                lambda fn, p, batch: logits_loss(fn(p, batch), batch))
        else:
            self.loss_fn = (
                lambda fn, p, batch: causal_lm_loss(fn(p, batch), batch))
        self.train_dataloader = train_dataloader
        self.eval_dataloader = eval_dataloader
        self.callbacks = callbacks or []
        self.logger = LogWriter(os.path.join(self.args.output_dir, "runs"))
        self.watchdog = StepWatchdog(
            nan_patience=self.args.nan_patience,
            hang_timeout_s=self.args.hang_timeout_s,
            on_hang=self._on_hang if self.args.hang_timeout_s else None)
        # plain dict, NOT the OrderedDict functional() hands back: the
        # jitted step returns plain-dict params, and dict/OrderedDict are
        # DIFFERENT pytree node types — an OrderedDict here means step 2
        # silently retraces+recompiles the whole step (and permanently
        # invalidates the AOT-warmed executable)
        pure_fn, params = model.functional()
        self._pure_fn, self._params = pure_fn, dict(params)
        # PEFT/LoRA: parameters whose ParamMeta says trainable=False are
        # frozen — grads are taken only w.r.t. the trainable subset and
        # the optimizer holds state only for it (frozen weights never get
        # Adam moments). Empty tuple = everything trains (the usual case).
        meta = model.param_meta()
        self._trainable_keys = tuple(
            k for k in self._params if meta[k].trainable)
        self._has_frozen = len(self._trainable_keys) < len(self._params)
        self._opt_state = None
        self._step_fn = None
        self._eval_fn = None
        # fp16 loss scaling (amp.GradScaler); scaler state lives INSIDE the
        # jitted step — inf steps skip the update branchlessly (C6).
        self.scaler = scaler if (scaler is not None and scaler.is_enable()) \
            else None
        self._scaler_state = (self.scaler.init_state() if self.scaler
                              else None)
        self.global_step = 0
        self._rollbacks = 0
        self._in_recovery = False
        self._shutdown: Optional[GracefulShutdown] = None
        self._sampler_restored = False
        # live feed for the current/most-recent train(): the raw
        # dataloader, or the DevicePrefetcher wrapping it — checkpoint
        # meta must read sampler state from HERE (consumer position),
        # never from a loader the prefetcher has run ahead on
        self._data_feed = None
        self.step_timer: Optional[StepTimer] = None
        self._aot_done = False
        self._derived_flops: Optional[float] = None

    # ------------------------------------------------------------ jit step
    def _pp_degree(self) -> int:
        from .distributed import env
        return env.get_mesh().shape.get("pp", 1) if env.has_mesh() else 1

    def _build_step(self):
        fn, opt, args = self._pure_fn, self.optimizer, self.args
        scaler = self.scaler
        accum = args.gradient_accumulation_steps

        pp = self._pp_degree()
        if pp > 1 and hasattr(self.model, "pipeline_functional"):
            # 1F1B pipeline path: the schedule computes loss AND grads in
            # one manual-SPMD program (microbatches = grad-accum steps).
            if self._has_frozen:
                raise ValueError(
                    "frozen parameters (PEFT/LoRA) are not supported on "
                    "the pipeline-parallel path: the 1F1B schedule "
                    "differentiates the full stage stack; run LoRA under "
                    "tp/fsdp/dp instead")
            if scaler is not None:
                raise ValueError("fp16 GradScaler is not supported with "
                                 "pipeline parallelism (use bf16)")
            if not self._default_loss:
                raise ValueError(
                    "a whole-model loss_fn cannot be decomposed onto "
                    "pipeline stages; pass logits_loss=(logits, labels) -> "
                    "scalar instead — it runs at the last stage")
            vag = self.model.pipeline_functional(
                pp, logits_loss=self._logits_loss,
                vpp=args.virtual_pp_degree)

            def pp_step(params, state, sstate, stepno, batch):
                if not hasattr(batch, "ndim"):
                    raise TypeError(
                        "pipeline path expects a token-id array batch "
                        f"[n_micro, b, s] or [b, s], got {type(batch)}")
                if batch.ndim == 2:  # [b, s] -> single microbatch
                    batch = batch[None]
                loss, grads = vag(params, batch)
                params, state = opt.apply(params, grads, state, stepno)
                return params, state, sstate, loss

            donate = (0, 1) if args.donate_state else ()
            return jax.jit(pp_step, donate_argnums=donate)

        # One unified step: differentiate w.r.t. the TRAINABLE subset only
        # (PEFT/LoRA freezes the rest; the all-trainable case is simply
        # frozen = {}). Frozen params ride along as (donated) jit inputs,
        # not constants, and the optimizer sees only the trainable subset.
        tkeys = frozenset(self._trainable_keys)

        def loss_of(p, batch, stepno, mbidx):
            # route next_key() through a per-step traced key so dropout
            # masks change every step (a bare next_key() during tracing
            # would bake ONE host key in as a constant); fold the
            # microbatch index in too so grad-accum microbatches don't
            # share one dropout mask
            from .utils.rng import key_context
            key = jax.random.fold_in(jax.random.PRNGKey(args.seed), stepno)
            key = jax.random.fold_in(key, mbidx)
            with key_context(key):
                return self.loss_fn(fn, p, batch)

        def scaled_loss(p, mb, sstate, stepno, mbidx):
            loss = loss_of(p, mb, stepno, mbidx)
            scaled = scaler.scale(loss, sstate) if scaler else loss
            return scaled, loss

        def step(params, state, sstate, stepno, batch):
            frozen = {k: v for k, v in params.items() if k not in tkeys}
            tp = {k: v for k, v in params.items() if k in tkeys}
            vg = jax.value_and_grad(
                lambda t, b, ss, mi: scaled_loss({**frozen, **t}, b, ss,
                                                 stepno, mi),
                has_aux=True)
            if accum == 1:
                (_, loss), grads = vg(tp, batch, sstate, jnp.int32(0))
            else:
                # batch leading dim = accum: scan microbatches, mean grads
                # (dropout masks vary per step via stepno AND per
                # microbatch via the scanned index)
                def micro(carry, xs):
                    mi, mb = xs
                    gsum, lsum = carry
                    (_, l), g = vg(tp, mb, sstate, mi)
                    return (jax.tree.map(jnp.add, gsum, g), lsum + l), None
                zeros = jax.tree.map(jnp.zeros_like, tp)
                (gsum, lsum), _ = jax.lax.scan(
                    micro, (zeros, 0.0), (jnp.arange(accum), batch))
                grads = jax.tree.map(lambda g: g / accum, gsum)
                loss = lsum / accum
            if scaler is None:
                new_tp, state = opt.apply(tp, grads, state, stepno)
            else:
                # fp16: unscale, branchlessly skip the update on inf/nan
                # grads, and advance the dynamic loss scale — in this jit.
                grads, found_inf = scaler.unscale(grads, sstate)
                cand_tp, cand_state = opt.apply(tp, grads, state, stepno)
                new_tp = scaler.select(found_inf, tp, cand_tp)
                state = scaler.select(found_inf, state, cand_state)
                sstate = scaler.update_state(sstate, found_inf)
            params = {**params, **new_tp}
            return params, state, sstate, loss

        donate = (0, 1) if args.donate_state else ()
        return jax.jit(step, donate_argnums=donate)

    # ------------------------------------------------------------- train
    def train(self, max_steps: Optional[int] = None):
        args = self.args
        max_steps = max_steps or args.max_steps
        # persistent compilation cache BEFORE anything traces: a
        # relaunched (e.g. preempted) worker restores the byte-identical
        # step executable from disk instead of recompiling
        # ($JAX_COMPILATION_CACHE_DIR, else the in-checkout default).
        compile_cache.enable()
        # observability artifacts (trace_<attempt>.json,
        # flight_<attempt>.json, metrics.prom) land in the SAME run dir
        # as the JSONL metrics — one dir answers "what happened"
        obs.configure(os.path.join(args.output_dir, "runs"))
        obs.record_event("train_start", step=self.global_step,
                         max_steps=max_steps, run_id=obs.run_id(),
                         attempt=obs.attempt_id())
        if self._opt_state is None:
            self._opt_state = self.optimizer.init(
                {k: self._params[k] for k in self._trainable_keys}
                if self._has_frozen else self._params)
        if args.resume_from_checkpoint and args.save_steps:
            self._try_resume()
        if self._step_fn is None:
            self._step_fn = self._build_step()

        assert self.train_dataloader is not None, "pass train_dataloader"
        # async feed (AFTER _try_resume restored the sampler position):
        # prep + device placement of batch N+1 overlap step N's compute
        feed = self.train_dataloader
        # legacy fallback: no sampler state in the checkpoint (plain
        # iterables, pre-meta checkpoints) — blind O(global_step) replay
        # of the stream. Loaders with state_dict support are restored in
        # O(1) by _try_resume instead.
        legacy_skip = bool(self.global_step and args.skip_data_on_resume
                           and not self._sampler_restored)
        if args.prefetch_depth > 0:
            initial_iter = None
            if legacy_skip:
                # skip on the RAW loader: discarded batches must not pay
                # accum-fold prep + an H2D copy in the producer thread
                initial_iter = self._skip_consumed(
                    iter(self.train_dataloader), self.global_step,
                    source=self.train_dataloader)
            from .io.device_prefetch import DevicePrefetcher
            feed = DevicePrefetcher(
                self.train_dataloader, prep=self._prep_batch,
                depth=args.prefetch_depth,
                stall_timeout_s=args.prefetch_stall_timeout_s,
                initial_iter=initial_iter)
        self._data_feed = feed
        data = iter(feed)
        if legacy_skip and feed is self.train_dataloader:
            data = self._skip_consumed(data, self.global_step)
        self._rollbacks = 0
        if self._shutdown is not None:
            # a latch tripped in a PREVIOUS train() call must not make
            # this one exit before its first step
            self._shutdown.clear()
        if args.graceful_shutdown:
            if self._shutdown is None:
                self._shutdown = GracefulShutdown()
            self._shutdown.install()
        try:
            return self._train_loop(data, max_steps)
        except SystemExit:
            raise      # preempt/hang exits dump their own flight record
        except BaseException as e:
            # crash postmortem: the last ring-buffer window (recent
            # steps, fault fires, rollbacks, ckpt events) hits disk
            # BEFORE the exception unwinds out of the trainer
            obs.record_event("crash", step=self.global_step,
                             error=repr(e))
            obs.dump_flight(f"crash:{type(e).__name__}")
            raise
        finally:
            # the trace + Prometheus snapshot are written on EVERY exit
            # path (normal completion included)
            obs.flush()
            if feed is not self.train_dataloader:
                # tears the producer thread down; the prefetcher retains
                # the consumer position so a post-train save_checkpoint
                # still records truthful sampler state
                feed.close()
            if self._shutdown is not None:
                self._shutdown.uninstall()

    def _train_loop(self, data, max_steps: int):
        args = self.args
        prefetching = self._data_feed is not self.train_dataloader
        # windowed throughput meter: totals accumulate only while the
        # loop is actually stepping — save/eval wall time is stopped out
        # of the window, so tokens_per_sec/mfu measure the step loop,
        # not checkpoint I/O
        timer = self.step_timer = StepTimer(
            flops_per_token=args.flops_per_token)
        # registry handles cached outside the loop: the per-step cost is
        # an inc/observe (one small lock), not a registry lookup
        m_steps = obs.counter("train_steps_total")
        h_step = obs.histogram("train_step_wall_ms")
        win_tokens = 0
        win_steps = 0
        t_last = time.perf_counter()
        timer.start()
        while self.global_step < max_steps:
            t_step = time.perf_counter()
            if faults.inject("preempt", step=self.global_step):
                # chaos: deterministic stand-in for a scheduler
                # preemption notice (SIGTERM) landing between steps
                sd = self._shutdown or GracefulShutdown()
                self._shutdown = sd
                sd.request("injected preempt")
            if self._shutdown is not None and self._shutdown.requested():
                self._preempt_exit()
            if faults.inject("hang", step=self.global_step):
                # chaos: simulated stuck step (preempted chip) — the
                # StepWatchdog hang path must checkpoint and exit
                time.sleep(faults.hang_seconds())
            try:
                batch = next(data)
            except StopIteration:
                data = iter(self._data_feed)
                try:
                    batch = next(data)
                except StopIteration:
                    # a bare StopIteration from the second next() would
                    # leak out of the loop as a silent early return
                    raise ValueError("train_dataloader is empty") from None
            if not prefetching:
                # the prefetcher already prepped + placed in its thread
                batch = self._prep_batch(batch)
            if timer.flops_per_token == 0.0:
                if self._derived_flops is None:
                    self._derived_flops = self._derive_flops_per_token(batch)
                timer.flops_per_token = self._derived_flops
            if args.aot_warmup and not self._aot_done:
                self._aot_warmup(batch)
                # compile happened before "step 0"; don't bill it to the
                # first throughput window
                timer.start()
                t_last = time.perf_counter()
            stepno = self.global_step
            with obs.span("train_step", step=stepno):
                self._params, self._opt_state, self._scaler_state, loss = \
                    self._step_fn(self._params, self._opt_state,
                                  self._scaler_state,
                                  jnp.int32(stepno), batch)
            self.global_step += 1
            # host-side step wall (data wait + dispatch; device compute
            # overlaps asynchronously and is amortized into the window
            # by the logging-step sync) — the per-step series behind
            # obs_report's p50/p99 and the flight record's recent
            # window. step= matches the train_step span's number (the
            # step just executed), so trace and flight cross-reference.
            step_ms = (time.perf_counter() - t_step) * 1e3
            h_step.observe(step_ms)
            m_steps.inc()
            obs.record_event("step_end", step=stepno,
                             ms=round(step_ms, 3))
            win_tokens += self._batch_tokens(batch)
            win_steps += 1
            self.watchdog.beat()
            if faults.inject("step_nan", step=self.global_step):
                # chaos: numeric divergence — NaN the float params (as a
                # real NaN-grad step would) and the reported loss, then
                # let the watchdog + rollback loop recover
                self._params = jax.tree.map(
                    lambda x: x * float("nan")
                    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
                    else x, self._params)
                loss = jnp.float32(float("nan"))
            if self.global_step % args.logging_steps == 0 or \
                    self.global_step == max_steps:
                loss_val = float(loss)   # host sync: closes the window
                try:
                    self.watchdog.check_loss(loss_val, self.global_step)
                except DivergenceError:
                    obs.record_event("divergence", step=self.global_step,
                                     loss=loss_val)
                    if not self._maybe_rollback():
                        raise
                    # rollback time (restore I/O) is not step time
                    t_last = time.perf_counter()
                    timer.start()
                    win_tokens = 0
                    win_steps = 0
                    continue
                now = time.perf_counter()
                dt = timer.stop(win_tokens, win_steps)
                tps = win_tokens / max(dt, 1e-9)
                logs = {"loss": loss_val,
                        # win_steps, not args.logging_steps: a save/eval
                        # (or resume) landing mid-window resets t_last,
                        # so the denominator only spans the steps since —
                        # the numerator must match
                        "steps_per_sec": win_steps / (now - t_last),
                        "tokens_per_sec": tps}
                if timer.peak_flops is not None:
                    # no published peak for this device (CPU): no MFU
                    logs["mfu"] = \
                        timer.flops_per_token * tps / timer.peak_flops
                win_tokens = 0
                win_steps = 0
                t_last = now
                timer.start()
                self.logger.add_scalars(logs, self.global_step)
                # mirror the window metrics into registry gauges and
                # merge the WHOLE registry (serving counters, prefetch
                # gauges, ckpt histograms included) into the same JSONL
                # stream the dashboards already tail
                for k, v in logs.items():
                    obs.gauge(f"train_{k}").set(v)
                try:
                    obs.gauge("train_lr").set(self.optimizer.get_lr())
                except Exception:
                    pass       # exotic schedules: lr gauge is optional
                obs.publish(self.logger, self.global_step)
                for cb in self.callbacks:
                    cb.on_step_end(self.global_step, logs)
            due_save = args.save_steps and \
                self.global_step % args.save_steps == 0
            due_eval = args.eval_steps and self.eval_dataloader is not None \
                and self.global_step % args.eval_steps == 0
            if due_save or due_eval:
                # close the throughput window BEFORE the save/eval (and
                # drain in-flight compute so it isn't silently credited
                # to the excluded span); the timer restart + t_last
                # reset below keep save/eval wall time out of both the
                # StepTimer totals and the next steps_per_sec window.
                # Skipped when the logging branch just closed it —
                # stopping an empty window would pad StepTimer.steps
                # with a zero-length entry and skew avg_step_s.
                if win_steps:
                    jax.block_until_ready(loss)
                    timer.stop(win_tokens, win_steps)
                    win_tokens = 0
                    win_steps = 0
                if due_save:
                    self.save_checkpoint()
                    self.watchdog.beat()  # a long save is not a hung step
                if due_eval:
                    self.evaluate()
                    self.watchdog.beat()  # ditto a long eval
                timer.start()
                t_last = time.perf_counter()
        for cb in self.callbacks:
            cb.on_train_end(self.global_step)
        if getattr(self, "_ckpt", None) is not None:
            # drain the cached manager's async write + manifest so a
            # finished run's last checkpoint is durable
            self._ckpt.wait_until_finished()
        # leave the module tree holding the trained weights
        self.model.bind(self._params)
        return self

    def _skip_consumed(self, data, n: int, source=None):
        """Advance the data iterator past ``n`` already-trained batches,
        re-iterating ``source`` (default: the live feed) at epoch
        boundaries."""
        if source is None:
            source = self._data_feed
        skip = n
        while skip > 0:
            got_any = False
            try:
                next(data)
                got_any = True
                skip -= 1
            except StopIteration:
                data = iter(source)
                try:
                    next(data)
                    skip -= 1
                except StopIteration:
                    if not got_any:
                        raise RuntimeError("train_dataloader is empty; "
                                           "cannot skip consumed batches")
        return data

    def _prep_batch(self, batch):
        accum = self.args.gradient_accumulation_steps
        if accum > 1:
            def fold(x):
                b = x.shape[0]
                assert b % accum == 0, f"batch {b} % accum {accum} != 0"
                return x.reshape((accum, b // accum) + x.shape[1:])
            if hasattr(batch, "shape"):
                batch = fold(batch)
            elif isinstance(batch, dict):  # SFT/DPO dict batches
                batch = {k: fold(v) for k, v in batch.items()}
        return batch

    # ------------------------------------------------------- perf meters
    @staticmethod
    def _token_array(batch):
        """The token-id array of a batch ([b, s] or the accum-folded
        [accum, b, s]): dict batches by ``input_ids``, tuple batches by
        first element. None when the batch carries no shaped array —
        the one unwrap heuristic shared by token counting and FLOPs
        derivation, so the mfu ratio can't silently diverge."""
        x = batch
        if isinstance(x, dict):
            x = x.get("input_ids", next(iter(x.values())))
        elif isinstance(x, (list, tuple)) and x:
            x = x[0]
        return x if getattr(x, "shape", None) else None

    @classmethod
    def _batch_tokens(cls, batch) -> int:
        """Token count of a step's batch for the throughput log."""
        x = cls._token_array(batch)
        return int(np.prod(x.shape)) if x is not None else 0

    def _derive_flops_per_token(self, batch) -> float:
        """Per-token train FLOPs for the MFU log when args.flops_per_token
        is unset: the 6N + attention estimate from the model config
        (llama-family shape); 0.0 when the config doesn't expose the
        needed fields (mfu then logs as 0)."""
        cfg = getattr(self.model, "config", None)
        layers = getattr(cfg, "num_hidden_layers", None)
        hidden = getattr(cfg, "hidden_size", None)
        if not layers or not hidden:
            return 0.0
        x = self._token_array(batch)
        if x is None:
            return 0.0
        seq = int(x.shape[-1])
        n_params = sum(int(np.prod(v.shape)) for v in self._params.values()
                       if hasattr(v, "shape"))
        # honest 6N, matching bench.py's headline formula: the input
        # embedding is a gather, not a matmul, so its params don't
        # belong in 6N (lm_head does — it IS a matmul)
        vocab = getattr(cfg, "vocab_size", None)
        if vocab:
            n_params -= vocab * hidden
        return llama_flops_per_token(n_params, layers, seq, hidden)

    def _aot_warmup(self, batch):
        """Compile the train step ahead of the first dispatch
        (jit(...).lower().compile()), so XLA compile time lands before
        step 0 instead of inside the first checkpoint interval. The
        compiled executable is shape-pinned; if a later batch drifts
        (e.g. a ragged epoch tail) the wrapper falls back to the
        original jit, which recompiles for the new shape as before."""
        self._aot_done = True
        jitted = self._step_fn
        if not hasattr(jitted, "lower"):   # already warmed/wrapped
            return
        t0 = time.perf_counter()
        try:
            compiled = jitted.lower(
                self._params, self._opt_state, self._scaler_state,
                jnp.int32(self.global_step), batch).compile()
        except Exception as e:
            print(f"[trainer] AOT warmup failed ({e}); falling back to "
                  f"on-demand jit", file=sys.stderr, flush=True)
            return
        print(f"[trainer] AOT warmup: train step compiled in "
              f"{time.perf_counter() - t0:.1f}s before step 0",
              file=sys.stderr, flush=True)
        self.watchdog.beat()               # a long compile is not a hang

        def stepper(*a):
            try:
                return compiled(*a)
            except (TypeError, ValueError):
                # shape drift: the AOT executable rejects BEFORE running
                # (donated buffers untouched); jit handles it
                return jitted(*a)

        self._step_fn = stepper

    # ------------------------------------------------------------- eval
    def evaluate(self) -> float:
        assert self.eval_dataloader is not None
        fn = self._pure_fn
        losses = []
        # trace the eval program with the module tree in eval mode so
        # dropout (incl. LoRA adapter dropout) is OFF — training flags are
        # trace-time constants, so flipping them here bakes eval semantics
        # into this executable without touching the jitted train step
        was_training = self.model.training
        self.model.eval()
        try:
            with obs.span("evaluate", step=self.global_step):
                if self._eval_fn is None:  # built once; jit caches/shape
                    self._eval_fn = jax.jit(
                        lambda p, b: self.loss_fn(fn, p, b))
                for batch in self.eval_dataloader:
                    # collect DEVICE scalars: each float() here would
                    # block the host once per batch, serializing dispatch
                    # with compute — one device_get at the end syncs once
                    losses.append(self._eval_fn(self._params, batch))
                losses = jax.device_get(losses) if losses else []
        finally:
            if was_training:
                self.model.train()
        mean = float(np.mean(losses)) if len(losses) else float("nan")
        self.logger.add_scalar("eval_loss", mean, self.global_step)
        obs.record_event("eval", step=self.global_step, loss=mean)
        return mean

    # --------------------------------------------------------- checkpoint
    def _ckpt_dir(self):
        return os.path.join(self.args.output_dir, "checkpoints")

    def _ckpt_manager(self):
        """ONE long-lived DistributedCheckpoint across the run: per-save
        create/close would force every periodic save to drain the async
        write AND hash the integrity manifest synchronously in the train
        loop — the cached manager keeps both in the background."""
        if getattr(self, "_ckpt", None) is None:
            from .checkpoint.distributed_ckpt import DistributedCheckpoint
            self._ckpt = DistributedCheckpoint(self._ckpt_dir())
        return self._ckpt

    def save_checkpoint(self, wait: bool = False):
        ckpt = self._ckpt_manager()
        tree = {"params": dict(self._params), "opt_state": self._opt_state}
        if self._scaler_state is not None:
            tree["scaler"] = self._scaler_state
        if self.args.donate_state and not wait:
            # the async write drains AFTER the next step DONATES these
            # exact buffers — hand orbax its own device-side copy or the
            # checkpoint bytes become whatever the reused buffers hold
            tree = jax.tree.map(
                lambda x: jnp.copy(x) if hasattr(x, "dtype") else x, tree)
        with obs.span("checkpoint_save", step=self.global_step,
                      wait=wait):
            ckpt.save(self.global_step, tree, wait=wait,
                      meta=self._checkpoint_meta())
        for cb in self.callbacks:
            cb.on_save(self.global_step)

    def _dp_degree(self) -> int:
        """Batch-sharding degree of the live mesh (dp and fsdp both
        split the batch; 1 with no mesh installed)."""
        from .distributed import env
        if not env.has_mesh():
            return 1
        shape = env.get_mesh().shape
        return int(shape.get("dp", 1)) * int(shape.get("fsdp", 1))

    def _checkpoint_meta(self) -> Dict[str, Any]:
        """Host-side sidecar for the step: sampler position (O(1)
        resume) + the topology manifest (cross-topology reconcile)."""
        topo: Dict[str, Any] = {
            "device_count": jax.device_count(),
            "dp": self._dp_degree(),
            "accum": self.args.gradient_accumulation_steps,
        }
        mesh_shape = self._live_mesh_shape()
        if mesh_shape is not None:
            topo["mesh"] = mesh_shape
        meta: Dict[str, Any] = {"step": self.global_step,
                                "topology": topo}
        # read sampler state from the live feed: with prefetch active
        # the raw loader has run AHEAD by the buffer depth, and saving
        # its cursor would skip buffered-but-untrained batches on
        # resume; the DevicePrefetcher reports the consumer position
        dl = self._data_feed if self._data_feed is not None \
            else self.train_dataloader
        if dl is not None and hasattr(dl, "state_dict"):
            try:
                sd = dl.state_dict()
                if sd:
                    meta["sampler"] = sd
            except Exception as e:  # sampler state is best-effort
                print(f"[trainer] sampler state_dict failed: {e}",
                      file=sys.stderr, flush=True)
        return meta

    def _preempt_exit(self):
        """Graceful-shutdown path: checkpoint the EXACT current step
        (sampler cursor included), drain the async writer so the save is
        durable, and exit with the preemption code the elastic
        supervisor restarts for free. SystemExit (not os._exit): the
        main thread is healthy here and should unwind cleanly."""
        reason = (self._shutdown.reason if self._shutdown else None) \
            or "requested"
        print(f"[trainer] preemption ({reason}) at global_step="
              f"{self.global_step}: checkpointing and exiting "
              f"rc={self.args.preempt_exit_code}",
              file=sys.stderr, flush=True)
        obs.record_event("preempt_exit", step=self.global_step,
                         reason=reason, rc=self.args.preempt_exit_code)
        try:
            self.save_checkpoint(wait=True)
        except Exception as e:
            # the grace window beats a perfect save: the latest periodic
            # checkpoint stands and the relaunch resumes from it
            print(f"[trainer] checkpoint during preemption failed: {e}; "
                  f"exiting anyway", file=sys.stderr, flush=True)
            obs.record_event("preempt_ckpt_failed", error=repr(e))
        # the flight dump happens AFTER the shutdown checkpoint so the
        # record's tail shows the fault/latch AND the save that answered
        # it — the acceptance shape of a clean preemption postmortem
        obs.dump_flight("preempt")
        raise SystemExit(self.args.preempt_exit_code)

    def _on_hang(self):
        """Monitor-thread path for a hung step (preempted chip, stuck
        host callback): best-effort checkpoint, then hard-exit so the
        elastic supervisor can relaunch. os._exit, not sys.exit — the
        main thread is stuck and would never unwind."""
        import sys
        print(f"[watchdog] step hung > {self.args.hang_timeout_s}s at "
              f"global_step={self.global_step}; checkpointing and exiting "
              f"rc={self.args.hang_exit_code}", file=sys.stderr, flush=True)
        obs.record_event("hang", step=self.global_step,
                         timeout_s=self.args.hang_timeout_s)
        obs.dump_flight("hang")
        if self._in_recovery:
            # wedged INSIDE a divergence rollback: params are NaN — a
            # snapshot now would become the latest checkpoint and poison
            # every future auto-resume. Exit without saving; the last
            # complete checkpoint stands and the supervisor relaunches.
            print("[watchdog] hang during divergence recovery; exiting "
                  "WITHOUT checkpointing (params are diverged)",
                  file=sys.stderr, flush=True)
            os._exit(self.args.hang_exit_code)
        # the save itself can wedge if the device is gone (device->host
        # copies blocking, not raising) — give it a bounded side thread
        # and exit regardless, or the detected hang becomes permanent
        import threading

        def _save():
            try:
                self.save_checkpoint(wait=True)
            except Exception as e:
                print(f"[watchdog] checkpoint during hang failed: {e}",
                      file=sys.stderr, flush=True)

        t = threading.Thread(target=_save, daemon=True)
        t.start()
        t.join(timeout=max(30.0, 2 * self.args.hang_timeout_s))
        if t.is_alive():
            print("[watchdog] checkpoint did not finish in time; exiting "
                  "anyway (latest periodic checkpoint stands)",
                  file=sys.stderr, flush=True)
        os._exit(self.args.hang_exit_code)

    def _maybe_rollback(self) -> bool:
        """Bounded divergence recovery: reload the latest complete (and
        checksum-verified) checkpoint and continue training. The data
        iterator is deliberately NOT rewound — the poisoned window
        (batches consumed between the checkpoint and the divergence) is
        skipped, not replayed into the restored params. Returns False
        (caller re-raises) when rollbacks are exhausted or there is no
        checkpoint to return to."""
        import sys
        if self._rollbacks >= self.args.max_divergence_rollbacks:
            print(f"[trainer] divergence persists after {self._rollbacks} "
                  f"rollback(s); giving up", file=sys.stderr, flush=True)
            return False
        diverged_at = self.global_step
        # a long restore must not trip the hang watchdog: params are NaN
        # right now, and _on_hang would checkpoint them as the new
        # latest (a permanent NaN resume loop). Flag the recovery so the
        # hang path skips its snapshot, and beat around the restore.
        self._in_recovery = True
        self.watchdog.beat()
        try:
            # restore_data=False: the live iterator is deliberately NOT
            # rewound (poisoned-window skip) — restoring the sampler
            # cursor here would replay checkpointed-epoch data at the
            # next epoch wrap
            restored = self._try_resume(restore_data=False)
        finally:
            self._in_recovery = False
            self.watchdog.beat()
        if restored is None:
            print("[trainer] divergence with no complete checkpoint to "
                  "roll back to", file=sys.stderr, flush=True)
            return False
        self._rollbacks += 1
        self.watchdog.reset_nan()
        print(f"[trainer] divergence at step {diverged_at}: rolled back "
              f"to checkpoint step {restored} "
              f"(rollback {self._rollbacks}/"
              f"{self.args.max_divergence_rollbacks}); skipping the "
              f"poisoned data window", file=sys.stderr, flush=True)
        obs.counter("train_rollbacks_total").inc()
        obs.record_event("rollback", diverged_at=diverged_at,
                         restored_step=restored,
                         rollback=self._rollbacks)
        obs.dump_flight("divergence_rollback")
        return True

    def _try_resume(self, restore_data: bool = True) -> Optional[int]:
        """Restore the latest complete checkpoint if one exists; returns
        the restored step (None if there was nothing to restore).
        ``restore_data=False`` (divergence rollback) restores arrays
        only, leaving the live data iterator's position untouched."""
        if not os.path.isdir(self._ckpt_dir()):
            return None
        ckpt = self._ckpt_manager()
        # rollback can race an in-flight async save: make it durable
        # (and its manifest written) before choosing the restore step
        ckpt.wait_until_finished()
        step = ckpt.latest_complete_step()
        if step is not None:
            base = {"params": dict(self._params),
                    "opt_state": self._opt_state}
            # the checkpoint may or may not contain scaler state (run
            # restarted with/without fp16): try the matching tree first,
            # fall back to the other shape rather than aborting resume.
            likes = [base]
            if self._scaler_state is not None:
                likes.insert(0, {**base, "scaler": self._scaler_state})
            else:
                from .amp import GradScaler
                likes.append({**base, "scaler": GradScaler().init_state()})
            restored = None
            first_err = None
            for like in likes:
                try:
                    restored = ckpt.restore(step, like=like)
                    break
                except Exception as e:
                    first_err = first_err or e
            if restored is None:
                # every tree shape failed: report the PRIMARY error (the
                # fallback's mismatch error would mislead diagnosis)
                raise first_err
            # Two placement fixups in one pass:
            # - defensive copy (donate_state): the jitted step DONATES
            #   params/opt state, but orbax-restored arrays can share
            #   internal buffers with the restore machinery — donating
            #   those double-frees and corrupts the heap (observed on
            #   XLA:CPU). A fresh copy owns its buffers.
            # - mesh re-placement (cross-topology resume): orbax commits
            #   restored arrays to the devices of the restore target; if
            #   that target was not laid out on the LIVE mesh (plain
            #   host params as `like`, or a checkpoint from a different
            #   topology), the committed placement conflicts with the
            #   step's mesh sharding constraints — replicate such arrays
            #   onto the current mesh (arrays already spanning the mesh
            #   keep their sharding).
            from .distributed import env as denv
            mesh = denv.get_mesh() if denv.has_mesh() else None
            mesh_devs = set(mesh.devices.flat) if mesh is not None else None

            def _fix(x):
                if not hasattr(x, "dtype"):
                    return x
                sh = getattr(x, "sharding", None)
                if mesh is not None and (
                        sh is None or set(sh.device_set) != mesh_devs):
                    from jax.sharding import NamedSharding, PartitionSpec
                    return jax.device_put(
                        x, NamedSharding(mesh, PartitionSpec()))
                return jnp.copy(x) if self.args.donate_state else x

            restored = jax.tree.map(_fix, restored)
            self._params = restored["params"]
            self._opt_state = restored["opt_state"]
            if self._scaler_state is not None and "scaler" in restored:
                self._scaler_state = restored["scaler"]
            # restore() may have fallen back past a corrupt latest step;
            # global_step must track what was actually loaded
            step = ckpt.last_restored_step
            self.global_step = step
            if restore_data:
                self._restore_meta(ckpt, step)
        return step

    def _restore_meta(self, ckpt, step: int):
        """Apply the step's meta sidecar: O(1) sampler-position restore
        (replacing _skip_consumed's blind replay) and cross-topology
        reconciliation when the checkpoint was written under a different
        mesh."""
        self._sampler_restored = False
        meta = ckpt.load_meta(step)
        if not meta:
            return
        self._reconcile_topology(meta.get("topology"))
        sd = meta.get("sampler")
        dl = self.train_dataloader
        if sd and dl is not None and hasattr(dl, "load_state_dict"):
            try:
                dl.load_state_dict(sd)
                self._sampler_restored = True
            except Exception as e:
                print(f"[trainer] sampler state restore failed ({e}); "
                      f"falling back to data replay",
                      file=sys.stderr, flush=True)

    def _reconcile_topology(self, saved: Optional[Dict[str, Any]]):
        """The job may come back with a different world size (preemptible
        pods): keep the EFFECTIVE global batch constant by recomputing
        gradient accumulation from the saved dp degree, and log the
        change. The per-rank index space re-shards inside
        DistributedBatchSampler.load_state_dict (its consumed counter is
        topology-independent), and orbax re-shards the arrays onto the
        live mesh via the restore target shardings."""
        if not saved:
            return
        cur_dp = self._dp_degree()
        old_dp = int(saved.get("dp", cur_dp) or cur_dp)
        if old_dp == cur_dp:
            return
        old_accum = int(saved.get("accum",
                                  self.args.gradient_accumulation_steps))
        effective = old_dp * old_accum
        new_accum = max(1, effective // cur_dp)
        # the accum factor must divide the loader batch (the jitted step
        # folds the batch into accum microbatches) — clamp down to the
        # nearest divisor rather than crashing the first resumed step
        batch = self._loader_batch_size()
        if batch:
            while batch % new_accum:
                new_accum -= 1
        if new_accum * cur_dp != effective:
            print(f"[trainer] effective global batch not exactly "
                  f"preservable: dp {old_dp}->{cur_dp} with accum "
                  f"{old_accum}, loader batch {batch} "
                  f"(using accum={new_accum})",
                  file=sys.stderr, flush=True)
        print(f"[trainer] topology change on resume: dp {old_dp} -> "
              f"{cur_dp} (mesh {saved.get('mesh')} -> now "
              f"{self._live_mesh_shape()}); gradient accumulation "
              f"{old_accum} -> {new_accum} to preserve the effective "
              f"global batch", file=sys.stderr, flush=True)
        if new_accum != self.args.gradient_accumulation_steps:
            self.args.gradient_accumulation_steps = new_accum
            self._step_fn = None   # rebuilt with the new accum factor

    def _live_mesh_shape(self) -> Optional[Dict[str, int]]:
        from .distributed import env
        if not env.has_mesh():
            return None
        return {a: int(d) for a, d in env.get_mesh().shape.items()}

    def _loader_batch_size(self) -> Optional[int]:
        """The per-step batch the dataloader feeds, when discoverable
        (None for plain iterables)."""
        dl = self.train_dataloader
        bs = getattr(getattr(dl, "batch_sampler", None), "batch_size",
                     None) or getattr(dl, "batch_size", None)
        try:
            return int(bs) if bs else None
        except (TypeError, ValueError):
            return None
