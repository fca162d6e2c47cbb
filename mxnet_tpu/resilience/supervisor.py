"""TrainSupervisor — self-healing training on top of the checkpoint
subsystem.

PR 6 made training state *capturable* (bit-identical resume); this
module makes long runs actually *survive* the three real killers:

1. **Preemption** — SIGTERM/SIGINT set a flag; at the next step
   boundary the supervisor flushes a SYNCHRONOUS checkpoint
   (``CheckpointManager.save_sync`` — it cannot queue behind earlier
   async saves) and returns ``"preempted"``. A SIGKILL gets no flush,
   by definition — there the commit-marker discipline carries: the
   next ``supervise()`` restores the latest *committed* step and
   continues, bit-identically.
2. **Divergence** — a :class:`DivergenceWatchdog` checks the loss at
   every step boundary (non-finite, spike-vs-EMA; AMP overflow-skips
   excluded — the loss scaler handles those). On a trip the
   supervisor REWINDS to the last committed checkpoint; a first trip
   replays the window (transient corruption reads clean the second
   time), a second trip on the same batch marks it poisoned and
   fast-forwards past it (``skip_batches``), and
   ``max_consecutive_rewinds`` trips without progress escalate as
   :class:`DivergenceError`.
3. **Hangs** — a :class:`HangWatchdog` deadline aborts a stuck step
   asynchronously (``StepHangError``); the in-process restart path
   (budget + exponential backoff) restores the last commit and
   continues.

Everything is observable under ``resilience.*``
(docs/OBSERVABILITY.md) and chaos-provable through
:class:`~mxnet_tpu.resilience.TrainFaultInjector`:
tests/test_resilience.py kills the run and demands that the final
parameters bitwise-match an uninterrupted control run, with goodput
counted over every step executed (docs/RESILIENCE.md).
"""
from __future__ import annotations

import os
import signal
import threading
import time

from .. import checkpoint as _ckpt
from .. import telemetry, tracing
from .watchdog import DivergenceWatchdog, HangWatchdog, StepHangError, \
    DivergenceError

__all__ = ["TrainSupervisor", "TrainingAborted"]


class TrainingAborted(RuntimeError):
    """The in-process restart budget is exhausted; the last failure is
    the ``__cause__``. At this point the process-level supervisor
    (cluster scheduler, a respawn loop) takes over — the
    latest committed checkpoint is still the resume point."""


class TrainSupervisor:
    """Run a Trainer/TrainStep step loop to completion through
    preemptions, divergence, and hangs.

    Exactly one of these step backends must be configured:

    - ``net`` + ``trainer`` + ``loss_fn`` — the imperative Gluon path
      (AMP-aware: a trainer holding an ``amp`` loss scaler gets
      ``scale_loss`` and overflow-skip classification for free);
    - ``train_step`` — a compiled ``parallel.TrainStep``;
    - ``step_fn(batch)`` → loss — custom logic (gradient-level fault
      injection and AMP classification unavailable).

    ``data_iter`` must be a resumable ``DataIter`` (``state_dict`` /
    ``load_state_dict`` / ``skip_batches`` — ``io.NDArrayIter``); the
    supervisor iterates it step-based with reset-on-exhaustion, and
    its cursor travels in every checkpoint.

    Parameters
    ----------
    manager : CheckpointManager or str
        The checkpoint target (a directory string builds an async
        manager owned — and closed — by the supervisor).
    save_every : int
        Commit cadence in optimizer steps; also the rewind granularity
        (a trip loses at most ``save_every - 1`` steps of work).
    max_restarts : int
        In-process restart budget per ``supervise()`` call; crossing
        it raises :class:`TrainingAborted`.
    restart_backoff_s : float
        Initial backoff before a restart, doubling per restart.
    watchdog : bool or DivergenceWatchdog
        ``True`` (default) builds a default watchdog.
    max_consecutive_rewinds : int
        Escalation threshold (see module docstring).
    step_timeout_s : float, optional
        Per-step hang deadline; ``None`` disables hang detection.
    injector : TrainFaultInjector, optional
        The chaos seam, consulted at every step boundary.
    handle_signals : bool
        Install SIGTERM/SIGINT handlers for the duration of
        ``supervise()`` (main thread only; restored on exit).
    stats_file : str, optional
        Path of a tiny text file persisting the total-executed-steps
        counter ACROSS process kills, so run-level goodput stays
        honest after a SIGKILL (the respawn loop hands every
        attempt the same file).
    """

    def __init__(self, manager, net=None, trainer=None, loss_fn=None,
                 train_step=None, step_fn=None, data_iter=None,
                 save_every: int = 50, max_restarts: int = 3,
                 restart_backoff_s: float = 0.05, watchdog=True,
                 max_consecutive_rewinds: int = 3,
                 step_timeout_s=None, injector=None,
                 handle_signals: bool = True, stats_file=None):
        backends = [net is not None and trainer is not None
                    and loss_fn is not None,
                    train_step is not None, step_fn is not None]
        if sum(backends) != 1:
            raise ValueError(
                "configure exactly one step backend: net+trainer+"
                "loss_fn, train_step, or step_fn")
        if data_iter is None:
            raise ValueError("data_iter is required")
        for attr in ("state_dict", "load_state_dict", "skip_batches"):
            if not hasattr(data_iter, attr):
                raise TypeError(
                    f"data_iter {type(data_iter).__name__} is not "
                    f"resumable: missing {attr}() (io.NDArrayIter "
                    f"has it)")
        if isinstance(manager, _ckpt.CheckpointManager):
            self.manager, self._own_manager = manager, False
        else:
            self.manager = _ckpt.CheckpointManager(str(manager))
            self._own_manager = True
        self.net = net
        self.trainer = trainer
        self.loss_fn = loss_fn
        self.train_step = train_step
        self.step_fn = step_fn
        self.data_iter = data_iter
        self.save_every = max(1, int(save_every))
        self.max_restarts = int(max_restarts)
        self.restart_backoff_s = float(restart_backoff_s)
        if watchdog is True:
            self.watchdog = DivergenceWatchdog()
        elif watchdog in (False, None):
            self.watchdog = None
        else:
            self.watchdog = watchdog
        self.max_consecutive_rewinds = int(max_consecutive_rewinds)
        self.step_timeout_s = step_timeout_s
        self.injector = injector
        self.handle_signals = bool(handle_signals)
        self.stats_file = stats_file

        self._step = 0            # completed optimizer steps
        self._batch_idx = 0       # global batches consumed (incl. skips)
        self._skip_set: set = set()
        self._preempted = False
        self._preempt_signum = None
        self._executed = 0        # steps executed by THIS process
        self._total_executed = self._read_stats()
        self._last_saved = None
        self._consec_rewinds = 0
        self._last_trip_batch = None
        self._trip_step = None
        self._counts = {"rewinds": 0, "restarts": 0, "preemptions": 0,
                        "hangs": 0, "resumes": 0, "skipped": 0}

    # -- cross-process stats -------------------------------------------
    def _read_stats(self) -> int:
        if not self.stats_file or not os.path.exists(self.stats_file):
            return 0
        try:
            with open(self.stats_file) as f:
                return int(f.read().strip() or 0)
        except (OSError, ValueError):
            return 0

    def _write_stats(self):
        if not self.stats_file:
            return
        try:
            # tmp + rename: the counter exists to survive SIGKILL — a
            # kill between truncate and write would zero it and
            # inflate reported goodput
            tmp = self.stats_file + ".tmp"
            with open(tmp, "w") as f:
                f.write(str(self._total_executed))
            os.replace(tmp, self.stats_file)
        except OSError:
            pass

    # -- state capture / restore ---------------------------------------
    def _capture(self):
        tree, meta = _ckpt.capture_training_state(
            net=self.net, trainer=self.trainer,
            train_step=self.train_step, data_iter=self.data_iter)
        meta["supervisor"] = {"batch_idx": self._batch_idx,
                              "skip": sorted(self._skip_set)}
        return tree, meta

    def _save(self, step: int, sync: bool = False):
        tree, meta = self._capture()
        if sync:
            self.manager.save_sync(step, tree, metadata=meta)
        else:
            self.manager.save(step, tree, metadata=meta)
        self._last_saved = step

    def _restore_latest(self):
        """Rewind live objects to the latest committed checkpoint."""
        try:
            # let queued async saves land first — the freshest commit
            # is the cheapest rewind; a failed save just means an
            # older commit wins
            self.manager.wait(timeout=60.0)
        except Exception:  # noqa: BLE001 — fall back to older commits
            pass
        step, tree, meta = self.manager.restore()
        _ckpt.apply_training_state(
            tree, meta, net=self.net, trainer=self.trainer,
            train_step=self.train_step, data_iter=self.data_iter)
        sup = meta.get("supervisor", {})
        self._step = int(step)
        self._batch_idx = int(sup.get("batch_idx", step))
        self._skip_set |= {int(b) for b in sup.get("skip", ())}
        self._last_saved = int(step)
        return step

    # -- signals --------------------------------------------------------
    def _install_signals(self):
        if not self.handle_signals or \
                threading.current_thread() is not threading.main_thread():
            return None
        prev = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            prev[sig] = signal.signal(sig, self._on_signal)
        return prev

    def _on_signal(self, signum, frame):  # noqa: ARG002 — signal API
        self._preempted = True
        self._preempt_signum = signum

    # -- the step backends ---------------------------------------------
    def _next_batch(self):
        """Pull the next batch, honoring the poisoned-batch skip set
        and resetting exhausted epochs (step-based iteration)."""
        empty_epochs = 0
        while True:
            idx = self._batch_idx
            if idx in self._skip_set:
                self.data_iter.skip_batches(1)
                self._batch_idx += 1
                self._counts["skipped"] += 1
                telemetry.counter("resilience.batches_skipped")
                empty_epochs = 0
                continue
            try:
                batch = self.data_iter.next()
            except StopIteration:
                # two exhaustions without a batch in between = the
                # epoch itself yields nothing (dataset smaller than
                # batch_size under 'discard') — error out instead of
                # spinning forever
                empty_epochs += 1
                if empty_epochs >= 2:
                    raise ValueError(
                        "data_iter yields no batches per epoch — "
                        "supervised training cannot progress")
                self.data_iter.reset()
                continue
            self._batch_idx += 1
            return batch, idx

    def _do_step(self, batch, batch_idx):
        """Execute one optimizer step; returns ``(host_loss,
        amp_overflow)``."""
        inj = self.injector
        if inj is not None and getattr(batch, "data", None):
            inj.corrupt_batch(batch_idx, batch.data)
        if self.step_fn is not None:
            loss = self.step_fn(batch)
            loss_host = float(loss.asnumpy()) \
                if hasattr(loss, "asnumpy") else float(loss)
            return loss_host, False
        if self.train_step is not None:
            loss = self.train_step(batch.data, batch.label,
                                   pad=batch.pad)
            return float(loss.asnumpy()), False
        # imperative Gluon path
        from .. import amp as _amp
        from .. import autograd
        y = batch.label[0] if batch.label else None
        scaler = getattr(self.trainer, "_amp_loss_scaler", None)
        overflow_before = getattr(scaler, "overflow_count", 0)
        with autograd.record():
            out = self.net(*batch.data)
            loss = self.loss_fn(out, y).mean()
            if scaler is not None:
                with _amp.scale_loss(loss, self.trainer) as scaled:
                    scaled.backward()
        if scaler is None:
            loss.backward()
        if inj is not None:
            inj.corrupt_grads(batch_idx, self.trainer._params)
        self.trainer.step(batch.data[0].shape[0])
        loss_host = float(loss.asnumpy())
        amp_overflow = scaler is not None and \
            getattr(scaler, "overflow_count", 0) > overflow_before
        return loss_host, amp_overflow

    # -- rewind ---------------------------------------------------------
    def _rewind(self, step_no: int, batch_idx: int):
        telemetry.counter("resilience.rewinds")
        tracing.flight.record("train.rewind", step=step_no,
                              batch=batch_idx,
                              consecutive=self._consec_rewinds + 1)
        self._counts["rewinds"] += 1
        self._consec_rewinds += 1
        if self._consec_rewinds > self.max_consecutive_rewinds:
            raise DivergenceError(
                f"watchdog tripped {self._consec_rewinds} consecutive "
                f"times without progress (last at step {step_no}) — "
                f"the run is diverging, not hitting a bad batch")
        if self._last_trip_batch == batch_idx:
            # same batch tripped twice: the data is poisoned, not the
            # transfer — fast-forward past it after the rewind
            self._skip_set.add(batch_idx)
        self._last_trip_batch = batch_idx
        self._trip_step = step_no
        self._restore_latest()

    # -- preemption flush ----------------------------------------------
    def _flush_preempt(self):
        telemetry.counter("resilience.preemptions")
        tracing.flight.record("train.preempt", step=self._step,
                              signum=self._preempt_signum)
        self._counts["preemptions"] += 1
        self._save(self._step, sync=True)

    # -- the loop -------------------------------------------------------
    def _run_loop(self, n_steps: int):
        hang = HangWatchdog(self.step_timeout_s) \
            if self.step_timeout_s else None
        try:
            while self._step < n_steps:
                if self._preempted:
                    self._flush_preempt()
                    return "preempted"
                step_no = self._step + 1
                try:
                    if hang is not None:
                        hang.arm()
                    if self.injector is not None:
                        self.injector.on_step_begin(step_no)
                    batch, batch_idx = self._next_batch()
                    loss_host, amp_overflow = self._do_step(batch,
                                                            batch_idx)
                finally:
                    if hang is not None:
                        hang.disarm()
                self._executed += 1
                self._total_executed += 1
                telemetry.counter("resilience.steps.executed")
                self._write_stats()
                if self.watchdog is not None and self.watchdog.check(
                        loss_host, params=self._param_datas(),
                        amp_overflow=amp_overflow):
                    telemetry.counter("resilience.watchdog.trips")
                    tracing.flight.record("train.watchdog_trip",
                                          step=step_no, batch=batch_idx,
                                          loss=loss_host)
                    self._rewind(step_no, batch_idx)
                    continue
                self._step = step_no
                if self._trip_step is not None and \
                        self._step > self._trip_step:
                    # progress past the trouble spot: the rewind
                    # streak is over
                    self._consec_rewinds = 0
                    self._trip_step = None
                telemetry.gauge("resilience.heartbeat_step", self._step)
                telemetry.gauge("resilience.heartbeat", time.time())
                if self._step % self.save_every == 0:
                    self._save(self._step)
            return "done"
        finally:
            if hang is not None:
                hang.close()

    def _param_datas(self):
        if self.watchdog is None or not self.watchdog.check_params:
            return None
        if self.trainer is not None:
            return [p._data._data for p in self.trainer._params
                    if p._data is not None]
        return None  # TrainStep params live inside compiled entries

    def supervise(self, n_steps: int):
        """Run until ``n_steps`` optimizer steps are committed (or a
        preemption lands). Returns a report dict with ``status``
        (``"done"`` | ``"preempted"``), the final ``step``, recovery
        counts, and the run-level ``goodput`` fraction."""
        n_steps = int(n_steps)
        self._preempted = False
        self._preempt_signum = None  # a prior preemption's signal
        # must not leak into this run's report
        prev_handlers = self._install_signals()
        t0 = time.perf_counter()
        status = "done"
        try:
            if self.manager.latest_step() is None:
                # anchor commit: the rewind target before the first
                # periodic save exists
                self._save(0, sync=True)
            else:
                self._restore_latest()
                telemetry.counter("resilience.resumes")
                self._counts["resumes"] += 1
            restarts = 0
            last_exc = None
            while True:
                try:
                    status = self._run_loop(n_steps)
                    break
                except (DivergenceError, KeyboardInterrupt,
                        SystemExit):
                    raise
                except Exception as e:  # noqa: BLE001 — crash/hang:
                    # anything a step can throw is a restart candidate
                    # inside the budget
                    if isinstance(e, StepHangError):
                        self._counts["hangs"] += 1
                    restarts += 1
                    last_exc = e
                    telemetry.counter("resilience.restarts")
                    self._counts["restarts"] += 1
                    if restarts > self.max_restarts:
                        tracing.flight.dump(
                            "train.abort", step=self._step,
                            restarts=restarts,
                            error=f"{type(e).__name__}: {e}")
                        raise TrainingAborted(
                            f"restart budget ({self.max_restarts}) "
                            f"exhausted; last failure: "
                            f"{type(e).__name__}: {e}") from e
                    tracing.flight.dump(
                        "train.restart", step=self._step,
                        restart=restarts,
                        error=f"{type(e).__name__}: {e}")
                    time.sleep(self.restart_backoff_s
                               * (2 ** (restarts - 1)))
                    self._restore_latest()
            # final flush. A periodic save that failed mid-run (flaky
            # FS) must not crash a run that actually FINISHED — the
            # caller holds the final params in memory; the failure is
            # reported, counted, and an older commit remains on disk.
            # A StepHangError landing HERE is stale (the hang watchdog
            # decided to fire in the instant the last step completed;
            # the async raise cannot be recalled) — retry the flush
            # once instead of failing a completed run.
            save_error = None
            for _attempt in range(2):
                try:
                    try:
                        self.manager.wait()
                    except StepHangError:
                        raise
                    except Exception as e:  # noqa: BLE001 — reported
                        save_error = f"{type(e).__name__}: {e}"
                    if status == "done" and (
                            save_error is not None
                            or self._last_saved != self._step):
                        # _last_saved only proves the save was QUEUED;
                        # if the async path failed, re-commit the
                        # in-memory final state synchronously. Keyed
                        # on _step, not n_steps: a checkpoint already
                        # PAST n_steps must not be re-labeled under a
                        # smaller step number
                        try:
                            self._save(self._step, sync=True)
                            if save_error is not None:
                                save_error += " (recovered: final " \
                                    "state committed synchronously)"
                        except StepHangError:
                            raise
                        except Exception as e:  # noqa: BLE001
                            save_error = f"{type(e).__name__}: {e}"
                    break
                except StepHangError:
                    telemetry.counter("resilience.hangs.stale")
                    continue
            report = self._report(status, time.perf_counter() - t0)
            if save_error is not None:
                report["save_error"] = save_error
            return report
        finally:
            if prev_handlers:
                for sig, h in prev_handlers.items():
                    signal.signal(sig, h)
            # an owned manager stays OPEN: supervise() is re-entrant
            # (preempt → supervise again on the same instance is the
            # resume pattern) and the manager's own atexit/GC flush
            # covers abandonment; close() is the explicit teardown

    def close(self, timeout: float = 60.0):
        """Flush and close an owned CheckpointManager (a manager the
        caller passed in is the caller's to close)."""
        if self._own_manager:
            self.manager.close(timeout=timeout)

    def _report(self, status, wall_s):
        useful = self._step
        total = max(self._total_executed, useful, 1)
        goodput = useful / total
        telemetry.gauge("resilience.goodput", goodput)
        return {
            "status": status,
            "step": self._step,
            "signal": self._preempt_signum,
            "steps_executed": self._executed,
            "total_steps_executed": self._total_executed,
            "goodput": goodput,
            "wall_s": wall_s,
            **self._counts,
        }
