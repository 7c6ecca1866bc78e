"""High-level Trainer with event callbacks, auto-checkpoint and auto-resume.

Reference: ``python/paddle/fluid/trainer.py:169`` (Trainer(train_func,
optimizer_func) driving train_loop with Begin/EndEpochEvent +
Begin/EndStepEvent callbacks), ``trainer.py:100`` (CheckpointConfig),
``trainer.py:594,663,763`` (auto-resume on init, save_checkpoint per
epoch/step interval, trainer metadata), ``trainer.py:324`` (cluster-role
wiring from env vars), ``trainer.py:541`` (ParallelExecutor path).

TPU-native: the "program pair" (startup + main) collapses into
``Model.init`` + a compiled train step; the ParallelExecutor path becomes
:class:`paddle_tpu.parallel.DataParallel` over a mesh; PS-mode transpilation
is replaced by multi-host mesh initialization (see
``paddle_tpu.transpiler.distributed``).
"""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Any, Callable, Iterable, Optional, Sequence, Tuple

import jax
import numpy as np

from paddle_tpu import checkpoint as ckpt_mod
from paddle_tpu import observability as obs
from paddle_tpu import tracing
from paddle_tpu.checkpoint import CheckpointConfig
from paddle_tpu.core import logging as ptlog
from paddle_tpu.core import profiler as prof
from paddle_tpu.core.enforce import EnforceError, enforce
from paddle_tpu.executor import Executor
from paddle_tpu.framework import Model, Variables
from paddle_tpu.observability import mfu as obs_mfu
from paddle_tpu.observability import runlog
from paddle_tpu.optimizer import Optimizer, OptState, StepOutput
from paddle_tpu.resilience import ResilienceConfig, faults
from paddle_tpu.resilience import elastic as elastic_mod
from paddle_tpu.resilience.watchdog import StepWatchdog

__all__ = [
    "Trainer",
    "BeginEpochEvent",
    "EndEpochEvent",
    "BeginStepEvent",
    "EndStepEvent",
    "CheckpointConfig",
    "ResilienceConfig",
]


class BeginEpochEvent:
    def __init__(self, epoch_id: int):
        self.epoch = epoch_id


class EndEpochEvent:
    def __init__(self, epoch_id: int):
        self.epoch = epoch_id


class BeginStepEvent:
    def __init__(self, epoch_id: int, step_id: int):
        self.epoch = epoch_id
        self.step = step_id
        # mirrors reference BeginStepEvent.fetch_metrics (trainer.py:158)
        self.fetch_metrics = True


class EndStepEvent:
    def __init__(self, epoch_id: int, step_id: int, metrics):
        self.epoch = epoch_id
        self.step = step_id
        self.metrics = metrics


class Trainer:
    """Drive training of a built Model with events + checkpointing.

    ``train_func`` builds and returns the model (a :class:`Model` or a plain
    layer-calling function, which is wrapped); its forward must return the
    loss first. ``optimizer_func`` returns an :class:`Optimizer`.

    The Trainer owns ``variables`` and ``opt_state``: each step consumes the
    arrays it is handed (they are donated to the compiled step, which writes
    the new state into their buffers) and the Trainer holds what the step
    returned. An array read from ``trainer.variables`` or
    ``trainer.opt_state`` is therefore valid until the next step; a caller
    who keeps one across a step copies it first:
    ``jax.tree_util.tree_map(jnp.array, trainer.variables)``.
    """

    def __init__(
        self,
        train_func: Callable[[], Any],
        optimizer_func: Callable[[], Optimizer],
        place=None,
        parallel: bool = False,
        checkpoint_config: Optional[CheckpointConfig] = None,
        rng: int | jax.Array | None = 0,
        parallel_kwargs: Optional[dict] = None,
        prefetch: bool = False,
        resilience: Optional[ResilienceConfig] = None,
        observability: Optional["obs.ObservabilityConfig"] = None,
        watch: Optional[Any] = None,
    ):
        from paddle_tpu.framework import build

        # flags-driven (or explicit) telemetry: exporter + runlog, idempotent
        obs.setup(observability)

        model = train_func()
        self.model = model if isinstance(model, Model) else build(model)
        self.optimizer = optimizer_func()
        self.parallel = parallel
        # extra DataParallel options (mesh=..., zero_shard_optimizer=True, ...)
        self.parallel_kwargs = dict(parallel_kwargs or {})
        # async host->device double buffering of reader batches (the
        # reference's double_buffer reader, operators/reader/buffered_reader.cc)
        self.prefetch = prefetch
        self.checkpoint_cfg = checkpoint_config
        self.rng = rng
        self.place = place
        self.exe = Executor(place)
        self.trainer_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        self._dp = None
        self._step_fn = None
        self._donation_noted = False  # gauge trainer.state_donated is set
        self.variables: Optional[Variables] = None
        self.opt_state: Optional[OptState] = None
        self.epoch = 0
        self.global_step = 0
        self._last_saved_step = -1
        # preemption-aware save (SURVEY §5.3): SIGTERM during train() is
        # caught at the next step boundary → checkpoint + clean return.
        # True after train() returned early because of a signal.
        self.preempted = False
        self._preempt_requested = False
        # self-healing policy (default from flags: PADDLE_TPU_CHECK_NAN_INF_POLICY
        # etc.; the flags default is "raise", the pre-resilience behavior)
        self.resilience = resilience if resilience is not None else ResilienceConfig.from_flags()
        self.bad_steps = 0  # non-finite steps whose update was dropped
        self.rollbacks = 0  # checkpoint restores triggered by the nan policy
        self._consec_bad = 0
        self._rollbacks_since_good = 0
        self._watchdog: Optional[StepWatchdog] = None
        # elastic supervisor (ResilienceConfig(elastic=True)): created in
        # _ensure_initialized once the mesh exists
        self._elastic: Optional[elastic_mod.ElasticSupervisor] = None
        # -- telemetry (paddle_tpu.observability / paddle_tpu.tracing) -----
        self.goodput = obs_mfu.GoodputTracker()
        self._ema_eps: Optional[float] = None  # EMA examples/sec
        self._step_flops: Optional[float] = None  # XLA cost-model FLOPs/step
        # temporal skew watch over step durations: a step that blows past
        # this trainer's own recent median gets flagged (per-device spatial
        # attribution needs one timing per device, which a single-host
        # pjit step does not expose — the detector accepts external
        # per-device keys when a multi-host launcher has them)
        self._straggler = tracing.StragglerDetector("trainer.step")
        # watch layer: anomaly detectors / SLOs over this trainer's metric
        # streams (step time, MFU, goodput), attached via config
        # (a paddle_tpu.watch.WatchConfig; None = no watching)
        self._watcher = None
        if watch is not None:
            from paddle_tpu import watch as watch_mod

            self._watcher = watch_mod.build(watch)

    # -- init / resume ------------------------------------------------------
    def _ensure_initialized(self, first_batch: Sequence[Any]):
        if self.variables is not None:
            return
        if self.parallel:
            from paddle_tpu.parallel import DataParallel
            from paddle_tpu.parallel.mesh import default_mesh

            kw = dict(self.parallel_kwargs)
            kw.setdefault("mesh", default_mesh())
            self._dp = DataParallel(self.model, self.optimizer, **kw)
            self.variables, self.opt_state = self._dp.init(self.rng, *first_batch)
        else:
            self.variables = self.model.init(self.rng, *first_batch)
            self.opt_state = self.optimizer.create_state(self.variables.params)

        if self.resilience is not None and getattr(self.resilience, "elastic", False):
            enforce(self.parallel, "elastic training requires parallel=True (a mesh to shrink)")
            enforce(
                self.checkpoint_cfg is not None and self.checkpoint_cfg.use_sharded(),
                "elastic training needs CheckpointConfig(sharded=True) — "
                "snapshots/serials are the recovery source",
            )
            from paddle_tpu import checkpoint_sharded as cks

            self._elastic = elastic_mod.ElasticSupervisor(
                self.resilience, devices=list(np.ravel(self._dp.mesh.devices))
            )
            # feed every save's device->host snapshot to the supervisor so
            # recovery has the freshest state without touching disk
            cks.set_snapshot_listener(self._elastic.note_snapshot)

        # auto-resume (reference Trainer.__init__ -> _load_checkpoint,
        # trainer.py:594-629)
        if self.checkpoint_cfg is not None:
            root = self.checkpoint_cfg.checkpoint_dir
            if self.checkpoint_cfg.use_sharded():
                from paddle_tpu import checkpoint_sharded as cks

                if cks.latest_sharded_checkpoint(root):
                    tree = (self.variables, self.opt_state)
                    tree, meta = cks.load_sharded(root, tree)
                    self.variables, self.opt_state = tree
                    self.epoch = int(meta.get("next_epoch", meta.get("epoch", 0)))
                    self.global_step = int(meta.get("step", 0))
                    self._last_saved_step = self.global_step
                    ptlog.vlog(
                        0, "resumed from sharded checkpoint: epoch %d step %d",
                        self.epoch, self.global_step,
                    )
                return
            if ckpt_mod.latest_checkpoint(root):
                tree = (self.variables, self.opt_state)
                tree, meta = ckpt_mod.load_checkpoint(root, tree, self.trainer_id)
                self.variables, self.opt_state = tree
                # next_epoch: epoch+1 for end-of-epoch saves, same epoch for
                # mid-epoch saves (reference restarts the interrupted epoch)
                self.epoch = int(meta.get("next_epoch", meta.get("epoch", 0)))
                self.global_step = int(meta.get("step", 0))
                self._last_saved_step = self.global_step
                ptlog.vlog(
                    0, "resumed from checkpoint: continuing at epoch %d step %d",
                    self.epoch, self.global_step,
                )

    def _compiled_step(self):
        if self._step_fn is None:
            raw = self.optimizer.minimize(self.model)
            # the step consumes the state it is handed (the Trainer is its
            # only holder between steps), so every state output takes its
            # input's buffer and the enqueue allocates only what is new
            self._step_fn = self.exe.prepare(
                raw, donate_argnums=(0, 1), key=("trainer_step", id(self)))
        return self._step_fn

    # -- train loop ---------------------------------------------------------
    def train(
        self,
        num_epochs: int,
        event_handler: Optional[Callable[[Any], None]] = None,
        reader: Optional[Callable[[], Iterable[Tuple]]] = None,
        feed_order=None,  # accepted for API parity; batches are positional
        allow_ragged: bool = False,
    ):
        """Run the training loop (reference ``Trainer.train`` →
        ``_train_by_executor``/``_train_by_parallel_executor``,
        trainer.py:404,541).

        ``allow_ragged``: in parallel mode, a batch whose leading dim does
        not divide the mesh trains through ``DataParallel.step_ragged``
        (replicated batch, sharded params — numerically a single-device
        step) instead of raising, so ``drop_last=False`` readers train on
        EVERY sample, the reference's data_balance guarantee
        (``details/data_balance_op_handle.cc:154``)."""
        enforce(reader is not None, "Trainer.train needs a batched reader")
        self._allow_ragged = allow_ragged
        handler = event_handler or (lambda event: None)
        # a Trainer may be re-entered after a preempted run (in-process
        # resume): stale flags must not end the new loop after one step
        self.preempted = False
        self._preempt_requested = False
        # initialize (and auto-resume) BEFORE choosing the start epoch, so a
        # fresh Trainer with a checkpoint on disk skips completed epochs
        if self.variables is None:
            first = next(iter(reader()), None)
            enforce(first is not None, "reader yielded no batches")
            self._ensure_initialized(first)
        if self._elastic is not None and self._elastic.lost:
            # re-entered after an elastic shrink: the global batch may not
            # divide the shrunken mesh — keep the ragged path open
            self._allow_ragged = True
        prev_handlers = self._install_preemption_handlers()
        res = self.resilience
        if res is not None and res.stall_timeout_s is not None and self._watchdog is None:
            self._watchdog = StepWatchdog(
                res.stall_timeout_s, on_stall=self._on_stall)
        try:
            # while (not for-range): elastic recovery rewinds self.epoch to
            # the restored checkpoint's epoch and restarts it — the same
            # restart-the-interrupted-epoch semantics a cold resume has
            epoch_id = self.epoch
            while epoch_id < num_epochs:
                self.epoch = epoch_id
                handler(BeginEpochEvent(epoch_id))
                # manual next() instead of a for-loop: the wait for the
                # reader is measured and belongs INSIDE the step's trace
                batches = iter(self._batches(reader))
                step_id = -1
                recovered = False
                while True:
                    # stall escalation: between steps (state consistent) ask
                    # the supervisor to probe device liveness; a dead device
                    # recovers through the same shrink path as a raised loss
                    if self._elastic is not None and self._elastic.escalation_due():
                        probe_err = self._elastic.escalate()
                        if probe_err is not None:
                            self._elastic.recover(self, probe_err)
                            recovered = True
                            break
                    try:
                        with tracing.start_trace(
                            "trainer.step", epoch=epoch_id,
                        ) as step_span:
                            # the step's trace begins with the wait for the
                            # reader; at the end of the epoch there is no step
                            with tracing.start_span("trainer.data_wait") as wait_span:
                                batch = next(batches, None)
                                if batch is None:
                                    wait_span.cancel()
                            if batch is None:
                                step_span.cancel()
                                break
                            step_id += 1
                            step_span.set(step=self.global_step)
                            begin_ev = BeginStepEvent(epoch_id, step_id)
                            with tracing.start_span("trainer.begin_event"):
                                handler(begin_ev)
                            # elastic fault points: a scheduler's advance
                            # preemption notice ("preempt" -> SIGTERM, handled
                            # at the boundary below) and a device vanishing
                            # ("error" -> DeviceLostError, recovered below)
                            faults.inject(
                                faults.PREEMPT_NOTICE, epoch=epoch_id, step=step_id
                            )
                            faults.inject(
                                faults.DEVICE_LOST, epoch=epoch_id, step=step_id
                            )
                            # fault point: "error" raises here (a crashing step),
                            # "nan" forces this step to count as non-finite,
                            # "preempt" delivers SIGTERM (handled at the boundary below)
                            spec = faults.inject(
                                faults.TRAINER_STEP, epoch=epoch_id, step=step_id
                            )
                            t_step = time.perf_counter()
                            # an injected "nan" is known before the step: the
                            # step is not run, so the state stays as it is
                            # (no second program, no operand of the one)
                            bad = spec is not None and spec.kind == "nan"
                            if bad:
                                out = None
                            elif self._watchdog is not None:
                                with self._watchdog.watch(f"epoch {epoch_id} step {step_id}"):
                                    out = self._run_step(batch)
                            else:
                                out = self._run_step(batch)
                            # the wait for the device: the step was only
                            # enqueued. Honoring fetch_metrics avoids a host
                            # sync per step (reference
                            # BeginStepEvent.fetch_metrics, trainer.py:158)
                            with tracing.start_span("trainer.fetch"):
                                bad = bad or (
                                    out.finite is not None and not bool(out.finite))
                                metrics = None
                                if begin_ev.fetch_metrics:
                                    metrics = float("nan") if bad else float(out.loss)
                            if out is not None:
                                # the step consumed the state it was handed, so
                                # what it returned is the state to carry, on a
                                # bad step too (where the program computed
                                # `finite` it kept the old values). The old
                                # arrays' buffers went into the new ones;
                                # dropping the step's outputs frees the
                                # logits (1 GB at lm_big's batch on one
                                # chip; under DataParallel the chip's own
                                # rows, 262 MB on four chips) that would
                                # sit in HBM beside the next step's whole
                                # program
                                with tracing.start_span("trainer.commit"):
                                    self.variables, self.opt_state = out.variables, out.opt_state
                                    out = None
                            if bad:
                                step_span.set(status="bad_step")
                                # charge the wasted step to badput even if the policy
                                # raises below — the accounting outlives the run
                                self.goodput.record_bad(
                                    time.perf_counter() - t_step, "nan_skip")
                                # may raise (policy "raise", or rollback gave up)
                                self._handle_bad_step(epoch_id, step_id)
                            else:
                                self._consec_bad = 0
                                self._rollbacks_since_good = 0
                                self.global_step += 1
                                with tracing.start_span("trainer.record_step"):
                                    self._record_step(
                                        epoch_id, batch, time.perf_counter() - t_step,
                                        metrics)
                            with tracing.start_span("trainer.end_event"):
                                handler(EndStepEvent(epoch_id, step_id, metrics))
                            if self._preempt_requested:
                                with tracing.start_span("trainer.checkpoint",
                                                        reason="preempt"):
                                    self._preemption_save(next_epoch=epoch_id)
                                return
                            with tracing.start_span("trainer.checkpoint"):
                                self._maybe_checkpoint(epoch_id, step=True)
                            if self._elastic is not None:
                                # regrow only at a checkpoint boundary (the
                                # supervisor checks; state is durable there)
                                self._elastic.maybe_regrow(self)
                    except Exception as e:
                        if self._elastic is None or not elastic_mod.is_device_loss(e):
                            raise
                        # device loss: shrink the mesh to the survivors,
                        # restore the freshest snapshot/serial, restart the
                        # interrupted epoch from the restored step
                        self._elastic.recover(self, e)
                        recovered = True
                        break
                if recovered:
                    epoch_id = self.epoch  # the restored manifest's epoch
                    continue
                handler(EndEpochEvent(epoch_id))
                with tracing.start_span("trainer.checkpoint", boundary="epoch"):
                    self._maybe_checkpoint(epoch_id, step=False)
                if self._preempt_requested:
                    # the epoch just COMPLETED — resume must not re-train it
                    self._preemption_save(next_epoch=epoch_id + 1)
                    return
                epoch_id += 1
        finally:
            self._restore_signal_handlers(prev_handlers)
            if self._watchdog is not None:
                self._watchdog.close()
                self._watchdog = None
            if self.checkpoint_cfg is not None and getattr(self.checkpoint_cfg, "async_save", False):
                from paddle_tpu import checkpoint_sharded as cks

                import sys as _sys

                unwinding = _sys.exc_info()[1] is not None
                try:
                    cks.wait_pending_save()  # train() returning => saves durable
                except Exception as e:
                    if not unwinding:  # clean exit: surface it — "train()
                        raise  # returned" must imply a durable save
                    # the loop is already unwinding with its own exception —
                    # log the writer failure instead of masking the cause
                    ptlog.error("async checkpoint writer failed during train() exit: %s", e)

    # -- telemetry (paddle_tpu.observability) -------------------------------
    def _record_step(self, epoch_id: int, batch, dt: float,
                     loss: Optional[float]) -> None:
        """Registry + runlog record for one GOOD step: step-time histogram,
        throughput gauges (instant + EMA), goodput, and MFU from the step
        function's XLA cost-model FLOPs."""
        rows = int(np.shape(batch[0])[0]) if len(batch) else 0
        eps = rows / dt if dt > 0 else 0.0
        self._ema_eps = (
            eps if self._ema_eps is None else 0.9 * self._ema_eps + 0.1 * eps
        )
        prof.inc_counter("trainer.steps_total")
        prof.inc_counter("trainer.examples_total", rows)
        prof.observe("trainer.step_seconds", dt)
        prof.set_gauge("trainer.examples_per_sec", eps)
        prof.set_gauge("trainer.examples_per_sec_ema", self._ema_eps)
        self.goodput.record_good(dt)
        prof.set_gauge("trainer.goodput_frac", self.goodput.goodput_frac())
        if self._step_flops is None:
            self._step_flops = self._compute_step_flops(batch)
        mfu_val = None
        if self._step_flops:
            mfu_val = obs_mfu.mfu(self._step_flops, dt,
                                  device_count=self._device_count())
            if mfu_val is not None:
                prof.set_gauge("trainer.mfu", mfu_val)
        extra = {"mfu": round(mfu_val, 6)} if mfu_val is not None else {}
        runlog.emit(
            "step", step=self.global_step, epoch=epoch_id, loss=loss,
            step_time_s=round(dt, 6), examples_per_sec=round(eps, 3),
            ema_examples_per_sec=round(self._ema_eps, 3), **extra)
        # per-device HBM gauges (device.hbm.*) + temporal straggler watch:
        # a step far above this trainer's own recent median gets flagged
        tracing.sample_device_memory(self._devices_in_use())
        self._straggler.record("step", dt)

    def _devices_in_use(self):
        if self.parallel and self._dp is not None:
            mesh = getattr(self._dp, "mesh", None)
            if mesh is not None:
                return list(np.ravel(mesh.devices))
            return jax.local_devices()
        return [self.exe.device]

    def _compute_step_flops(self, batch) -> float:
        """Model FLOPs of one step from XLA's cost analysis — ``lower()``
        traces without compiling, so this is cheap and exact for the step
        actually being run. 0.0 (MFU suppressed) when the path doesn't
        lower (e.g. step_ragged) or the backend has no cost model."""
        target = self._dp.step if self.parallel else self._step_fn
        if target is None or not hasattr(target, "lower"):
            return 0.0
        try:
            args = [jax.numpy.asarray(b) for b in batch]
            return obs_mfu.lowered_flops(
                target, self.variables, self.opt_state, *args)
        except Exception:
            return 0.0

    def _device_count(self) -> int:
        if self.parallel and self._dp is not None:
            mesh = getattr(self._dp, "mesh", None)
            if mesh is not None:
                return int(mesh.size)
            return jax.local_device_count()
        return 1

    def _on_stall(self, tag: str, elapsed: float) -> None:
        # the watchdog already logged stacks + runlog'd the stall; charge
        # the stalled wall time against goodput here (trainer-side policy)
        self.goodput.record_bad(elapsed, "stall")
        prof.set_gauge("trainer.goodput_frac", self.goodput.goodput_frac())
        if self._elastic is not None:
            # repeated stalls without recovery escalate to a device-liveness
            # probe at the next step boundary (supervisor counts them)
            self._elastic.note_stall()

    # -- self-healing (resilience.ResilienceConfig) -------------------------
    def _handle_bad_step(self, epoch_id: int, step_id: int) -> None:
        """A non-finite step (in-step check_nan_inf, or an injected "nan"
        fault). Policy "raise" keeps the pre-resilience fatal behavior;
        "skip_step" drops the update and continues; "rollback" additionally
        restores the last good checkpoint after ``rollback_after``
        CONSECUTIVE bad steps — and gives up (raises) after
        ``max_rollbacks`` restores with no good step in between."""
        res = self.resilience
        msg = (
            f"NaN/Inf in loss or gradients at epoch {epoch_id} "
            f"step {step_id} (check_nan_inf)"
        )
        if res is None or res.nan_policy == "raise":
            raise EnforceError(msg)
        self.bad_steps += 1
        self._consec_bad += 1
        prof.inc_counter("resilience.bad_steps")
        runlog.emit("nan_skip", step=self.global_step, epoch=epoch_id,
                    consecutive=self._consec_bad)
        ptlog.warning(
            "%s — policy %r: update dropped (%d consecutive bad)",
            msg, res.nan_policy, self._consec_bad,
        )
        if res.nan_policy == "skip_step" or self._consec_bad < res.rollback_after:
            return
        # rollback due
        enforce(
            self.checkpoint_cfg is not None,
            f"nan_policy='rollback' needs a checkpoint_config to restore "
            f"from ({msg})",
        )
        enforce(
            self._rollbacks_since_good < res.max_rollbacks,
            f"giving up after {self._rollbacks_since_good} rollbacks without "
            f"a good step in between ({msg})",
        )
        self._rollback()

    def _rollback(self) -> None:
        """Restore params + optimizer state from the last good checkpoint
        (corrupt serials already fall back inside load_*)."""
        cfg = self.checkpoint_cfg
        root = cfg.checkpoint_dir
        tree = (self.variables, self.opt_state)
        t0 = time.perf_counter()
        rolled_back_from = self.global_step
        if cfg.use_sharded():
            from paddle_tpu import checkpoint_sharded as cks

            cks.wait_pending_save()
            enforce(
                cks.latest_sharded_checkpoint(root) is not None,
                f"rollback: no checkpoint under {root} to restore",
            )
            tree, meta = cks.load_sharded(root, tree)
        else:
            enforce(
                ckpt_mod.latest_checkpoint(root) is not None,
                f"rollback: no checkpoint under {root} to restore",
            )
            tree, meta = ckpt_mod.load_checkpoint(root, tree, self.trainer_id)
        self.variables, self.opt_state = tree
        self.global_step = int(meta.get("step", self.global_step))
        self._last_saved_step = self.global_step
        self.rollbacks += 1
        self._rollbacks_since_good += 1
        self._consec_bad = 0
        prof.inc_counter("resilience.rollbacks")
        restore_s = time.perf_counter() - t0
        self.goodput.record_bad(restore_s, "rollback")
        runlog.emit("rollback", step=self.global_step,
                    rolled_back_from=rolled_back_from,
                    restore_seconds=round(restore_s, 6))
        ptlog.error(
            "rolled back to checkpoint step %d (rollback %d this run)",
            self.global_step, self.rollbacks,
        )

    # -- preemption (SURVEY §5.3 failure detection / recovery) --------------
    def _install_preemption_handlers(self):
        """Catch SIGTERM (the cluster-preemption signal) during the loop;
        the actual save happens at the next step boundary, where params are
        a consistent, fully-materialized tree. Main thread only — signal
        handlers cannot be installed elsewhere."""
        if threading.current_thread() is not threading.main_thread():
            return None

        def on_signal(signum, frame):
            self._preempt_requested = True
            ptlog.vlog(0, "signal %d: checkpoint at next step boundary", signum)

        prev = {}
        for sig in (signal.SIGTERM,):
            try:
                prev[sig] = signal.signal(sig, on_signal)
            except (ValueError, OSError):  # non-main interpreter contexts
                pass
        return prev

    def _restore_signal_handlers(self, prev):
        if not prev:
            return
        for sig, old in prev.items():
            try:
                signal.signal(sig, old)
            except (ValueError, OSError):
                pass

    def _preemption_save(self, next_epoch: int):
        """Emergency save on preemption. ``next_epoch`` is the epoch resume
        should start at: the interrupted epoch for a mid-epoch save (it
        restarts, matching the reference's mid-epoch checkpoint semantics),
        epoch+1 when the signal landed on a completed epoch boundary."""
        self.preempted = True
        if self.checkpoint_cfg is not None and getattr(self.checkpoint_cfg, "async_save", False):
            # an async save may be in flight or may have FAILED — "already
            # saved" is only true once the publish is confirmed durable
            from paddle_tpu import checkpoint_sharded as cks

            try:
                cks.wait_pending_save()
            except Exception as e:
                ptlog.warning("pending async checkpoint failed (%s); re-saving", e)
                self._last_saved_step = -1
        if self.checkpoint_cfg is not None and self.global_step != self._last_saved_step:
            self._save_checkpoint({"next_epoch": next_epoch, "preempted": True})
            ptlog.vlog(0, "preempted: saved at epoch %d step %d", self.epoch, self.global_step)
        else:
            ptlog.vlog(
                0, "preempted at epoch %d step %d (no new checkpoint: %s)",
                self.epoch, self.global_step,
                "none configured" if self.checkpoint_cfg is None else "state already saved",
            )

    def _batches(self, reader):
        """One epoch's batch stream, optionally device-prefetched: transfers
        run on a producer thread ``prefetch_depth`` batches ahead, already
        placed with the step's input shardings, so the step never waits on
        host->device copies."""
        for batch in self._raw_batches(reader):
            # fault point: reader-side IO errors / stalls surface here, on
            # the consuming thread (a prefetcher producer re-raises anyway)
            faults.inject(faults.READER_NEXT, epoch=self.epoch, step=self.global_step)
            yield batch

    def _raw_batches(self, reader):
        it = iter(reader())
        if not self.prefetch:
            yield from it
            return
        from paddle_tpu.reader import DevicePrefetcher

        first = next(it, None)
        if first is None:
            return
        if self.parallel:
            shardings = tuple(self._dp._batch_shardings(first))
            if getattr(self, "_allow_ragged", False):
                # a ragged tail batch cannot take the sharded placement —
                # send it to the default device; step_ragged replicates it
                placement = lambda item: (
                    shardings if self._dp.batch_divisible(*item) else None
                )
            else:
                placement = shardings
        else:
            placement = self.exe._device
        yield first
        yield from DevicePrefetcher(it, device=placement)

    def _run_step(self, batch) -> StepOutput:
        if self.parallel:
            if getattr(self, "_allow_ragged", False) and \
                    not self._dp.batch_divisible(*batch):
                with tracing.start_span("trainer.h2d"):
                    args = [jax.numpy.asarray(b) for b in batch]
                with tracing.start_span("trainer.step_compute", ragged=True):
                    return self._dp.step_ragged(
                        self.variables, self.opt_state, *args,
                    )
            with tracing.start_span("trainer.h2d"):
                dev_batch = self._dp.put_batch(*batch)
            with tracing.start_span("trainer.step_compute"):
                return self._dp.step(self.variables, self.opt_state, *dev_batch)
        step_fn = self._compiled_step()
        with tracing.start_span("trainer.h2d"):
            args = [jax.numpy.asarray(b) for b in batch]
        # the first step says whether the state was consumed
        handed = None
        if not self._donation_noted:
            handed = jax.tree_util.tree_leaves_with_path((self.variables, self.opt_state))
        with tracing.start_span("trainer.step_compute"):
            out = step_fn(self.variables, self.opt_state, *args)
        if handed is not None:
            self._note_donation(handed)
        return out

    def _note_donation(self, handed) -> None:
        """Gauge ``trainer.state_donated``, set once: 1 when the first step
        consumed every leaf of the state it was handed, 0 (with a warning
        naming the first leaf it did not) when one was copied to the
        device instead: a host array someone put into the state."""
        self._donation_noted = True
        kept = [path for path, leaf in handed
                if not (isinstance(leaf, jax.Array) and leaf.is_deleted())]
        prof.set_gauge("trainer.state_donated", 0.0 if kept else 1.0)
        if kept:
            ptlog.warning(
                "trainer step did not consume %d of %d state leaves (first: %s): "
                "the step allocates those outputs anew",
                len(kept), len(handed), jax.tree_util.keystr(kept[0]))

    def _maybe_checkpoint(self, epoch_id: int, step: bool):
        cfg = self.checkpoint_cfg
        if cfg is None or self.variables is None:
            return
        due = (
            self.global_step % cfg.step_interval == 0
            if step
            else (epoch_id + 1) % cfg.epoch_interval == 0
        )
        if not due:
            return
        # if a step save already captured this state, don't save a duplicate
        # serial — but an epoch boundary must still bump next_epoch in the
        # metadata so resume skips the completed epoch
        if self.global_step == self._last_saved_step:
            if not step:
                if cfg.use_sharded():
                    from paddle_tpu import checkpoint_sharded as cks

                    cks.update_manifest(cfg.checkpoint_dir, {"next_epoch": self.epoch + 1})
                else:
                    ckpt_mod.update_meta(
                        cfg.checkpoint_dir, {"next_epoch": self.epoch + 1}
                    )
            return
        self._save_checkpoint({"next_epoch": self.epoch + (0 if step else 1)})

    def _save_checkpoint(self, extra_meta: dict):
        """Shared sharded/unsharded checkpoint dispatch."""
        cfg = self.checkpoint_cfg
        if cfg.use_sharded():
            from paddle_tpu import checkpoint_sharded as cks

            save = cks.save_sharded_async if getattr(cfg, "async_save", False) else cks.save_sharded
            save(
                cfg.checkpoint_dir,
                (self.variables, self.opt_state),
                step=self.global_step,
                epoch=self.epoch,
                max_num_checkpoints=cfg.max_num_checkpoints,
                extra_meta=extra_meta,
            )
        else:
            ckpt_mod.save_checkpoint(
                cfg.checkpoint_dir,
                (self.variables, self.opt_state),
                step=self.global_step,
                epoch=self.epoch,
                max_num_checkpoints=cfg.max_num_checkpoints,
                trainer_id=self.trainer_id,
                extra_meta=extra_meta,
            )
        self._last_saved_step = self.global_step

    # -- eval / predict -----------------------------------------------------
    def test(self, reader: Callable[[], Iterable[Tuple]], loss_index: int = 0):
        """Average loss over a reader (reference Trainer.test,
        trainer.py:438)."""
        enforce(self.variables is not None, "train (or init) before test")
        losses, count = [], 0
        for batch in reader():
            out, _ = self.model.apply(
                self.variables, *[jax.numpy.asarray(b) for b in batch], is_train=False
            )
            loss = out[loss_index] if isinstance(out, (tuple, list)) else out
            losses.append(float(jax.numpy.mean(loss)))
            count += 1
        return float(np.mean(losses)) if losses else float("nan")

    def evaluate(self, reader: Callable[[], Iterable[Tuple]], metric_fn,
                 pad_to_first: bool = True):
        """Exact test-set metric: every sample counts exactly once, INCLUDING
        a ragged final batch (N % (devices x bs) != 0) — the reference
        guarantees the same via data_balance
        (``details/data_balance_op_handle.cc:154``); here the ragged batch is
        padded to the shard multiple (``DataParallel.pad_batch``) and the
        validity mask zeroes the padding out of the metric.

        ``metric_fn(outputs, *batch) -> [B]`` per-sample values (e.g. a
        correct-prediction indicator); returns their mask-weighted mean.
        ``pad_to_first`` pads every ragged batch to the first batch's size so
        eval compiles exactly once."""
        enforce(self.variables is not None, "train (or init) before evaluate")
        total, count = 0.0, 0
        target = None
        for batch in reader():
            n = int(np.shape(batch[0])[0])
            if self.parallel:
                # a batch LARGER than the latched first-batch size (ragged
                # batch first in the stream) pads to its own multiple
                # instead of tripping pad_batch's target >= n enforce
                to = target if (target is not None and n <= target) else None
                padded, mask = self._dp.pad_batch(*batch, to=to)
                if target is None and pad_to_first:
                    # latch from what pad_batch actually produced — the
                    # multiple-selection rule lives in pad_batch alone
                    target = mask.shape[0]
                out = self._dp.eval_step(self.variables, *padded)
            else:
                padded, mask = batch, np.ones((n,), np.float32)
                out, _ = self.model.apply(
                    self.variables, *[jax.numpy.asarray(b) for b in padded],
                    is_train=False,
                )
            per_sample = np.asarray(metric_fn(out, *padded), np.float64)
            # exact shape: a [B, 1] column would broadcast against the [B]
            # mask into [B, B] and silently inflate the metric
            enforce(
                per_sample.shape == mask.shape,
                f"metric_fn must return one value per row (shape "
                f"{mask.shape}), got shape {per_sample.shape}",
            )
            total += float((per_sample * mask).sum())
            count += int(mask.sum())
        return total / count if count else float("nan")

    def save_params(self, dirname: str):
        """Persist current parameters (reference save_params, io.py:89)."""
        from paddle_tpu import io as io_mod

        enforce(self.variables is not None, "nothing to save: model not initialized")
        io_mod.save_params(dirname, self.variables)

    def stop(self):
        from paddle_tpu import checkpoint_sharded as cks

        # detach OUR snapshot listener (== not `is`: bound methods are
        # recreated per access) so a later trainer's saves don't feed a
        # dead supervisor
        if self._elastic is not None and cks._snapshot_listener == self._elastic.note_snapshot:
            cks.set_snapshot_listener(None)
        try:
            cks.wait_pending_save()  # last async checkpoint must be durable
        finally:
            self.exe.close()  # a failed writer must not leak the executor
