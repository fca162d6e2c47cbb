"""TrainFaultInjector — the training loop's deterministic chaos seam.

The discipline from ``checkpoint/_fs.py`` (PR 6) and
``serving/faults.py`` (PR 7) applied to training: every failure mode a
long run actually dies of is routed through ONE seeded, deterministic
seam that the :class:`~mxnet_tpu.resilience.TrainSupervisor` consults
at step boundaries. Chaos tests become exact reproductions instead of
wall-clock races:

- ``crash``        — raise :class:`InjectedTrainingFault` at the step
  boundary (an in-process failure the supervisor's restart budget
  absorbs);
- ``kill``         — ``SIGKILL`` the process at the step boundary (a
  real preemption with NO cleanup: atexit does not run, queued async
  saves die — the commit-marker discipline is what survives);
- ``preempt``      — ``SIGTERM`` the process at the step boundary (a
  polite preemption: the supervisor's handler flushes a synchronous
  checkpoint and returns ``"preempted"``);
- ``slow``         — sleep ``duration_ms`` at the step boundary, in
  small chunks so the hang watchdog's asynchronous abort lands
  promptly (emulates a stuck host/device step);
- ``nan_batch``    — overwrite the step's input data with NaN before
  the forward pass. An ``at_batch`` rule retires after firing (a
  transient corruption: the watchdog's rewind replays the CLEAN
  batch, so the healed run stays bitwise identical to an undisturbed
  one); ``persistent=True`` keeps firing on that batch index — the
  data itself is poisoned, and the supervisor must fast-forward past
  it (``skip_batches``);
- ``nan_grad``     — overwrite one parameter's gradient with NaN
  after backward, before the optimizer update (bad reduction /
  flaky interconnect);
- ``kill_mid_save``— die while writing the checkpoint of
  ``save_step`` via the :meth:`checkpoint_fs` wrapper: shards land,
  the ``COMMITTED`` marker never does — restore must fall back.

Rules keyed ``at_step`` fire on the supervisor's 1-based optimizer
step and retire after firing once; rules keyed ``at_batch`` fire on
the 0-based global batch index (monotone across rewinds, so a
persistent rule tracks the *data*, not the replay). ``rate`` rules
draw from the injector's own seeded RNG.
"""
from __future__ import annotations

import os
import random
import signal
import threading
import time

from .. import telemetry, tracing

__all__ = ["TrainFaultInjector", "TrainFaultRule", "InjectedTrainingFault"]

_KINDS = ("crash", "kill", "preempt", "slow", "nan_batch", "nan_grad",
          "kill_mid_save")
_STEP_KINDS = ("crash", "kill", "preempt", "slow")
_BATCH_KINDS = ("nan_batch", "nan_grad")


class InjectedTrainingFault(RuntimeError):
    """A deterministic, injector-originated training failure. Distinct
    from organic errors so tests can assert provenance."""


class TrainFaultRule:
    """One training-fault specification (see module docstring for the
    kinds and their keying)."""

    __slots__ = ("kind", "at_step", "at_batch", "rate", "duration_ms",
                 "save_step", "persistent")

    def __init__(self, kind, at_step=None, at_batch=None, rate=None,
                 duration_ms=0.0, save_step=None, persistent=False):
        if kind not in _KINDS:
            raise ValueError(f"fault kind must be one of {_KINDS}, "
                             f"got {kind!r}")
        if kind == "kill_mid_save":
            if save_step is None:
                raise ValueError("kill_mid_save needs save_step=")
        elif kind in _STEP_KINDS:
            if (at_step is None) == (rate is None):
                raise ValueError(
                    f"{kind} needs exactly one of at_step / rate")
        else:  # batch-keyed corruption
            if at_batch is None:
                raise ValueError(f"{kind} needs at_batch=")
        if kind == "slow" and duration_ms <= 0:
            raise ValueError("slow fault needs duration_ms > 0")
        if persistent and at_batch is None:
            raise ValueError(
                "persistent rules must be at_batch-keyed (a persistent "
                "at_step rule would re-fire on whatever batch lands on "
                "that step after a skip — tracking the data, not the "
                "replay, is the point)")
        self.kind = kind
        self.at_step = None if at_step is None else int(at_step)
        self.at_batch = None if at_batch is None else int(at_batch)
        self.rate = None if rate is None else float(rate)
        self.duration_ms = float(duration_ms)
        self.save_step = None if save_step is None else int(save_step)
        self.persistent = bool(persistent)

    def __repr__(self):
        when = f"at_step={self.at_step}" if self.at_step is not None \
            else (f"at_batch={self.at_batch}"
                  if self.at_batch is not None
                  else (f"save_step={self.save_step}"
                        if self.save_step is not None
                        else f"rate={self.rate}"))
        return f"TrainFaultRule({self.kind}, {when})"


class _KillMidSaveFS:
    """Filesystem wrapper (the ``checkpoint/_fs.py`` seam) that dies
    while writing the checkpoint of an armed ``save_step``: the FIRST
    write into that step's directory triggers the fault — the step dir
    exists, the ``COMMITTED`` marker never lands, and restore must
    skip the debris. (Firing on the first write rather than the
    marker keeps the kill prompt and deterministic relative to the
    training loop — an async writer draining its queue would
    otherwise let a load-dependent number of extra steps execute.)"""

    def __init__(self, inner, injector):
        self._inner = inner
        self._injector = injector

    def write_bytes(self, path, data):
        self._injector._maybe_kill_mid_save(path)
        return self._inner.write_bytes(path, data)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class TrainFaultInjector:
    """Seeded, deterministic training-fault source (thread-safe: rule
    matching under one lock, effects outside it)."""

    def __init__(self, rules=(), seed: int = 0):
        self._rules = list(rules)
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._retired: set = set()

    @classmethod
    def from_spec(cls, spec: str, seed: int = 0):
        """Build an injector from a compact schedule string — a
        respawn loop's per-attempt fault plan, e.g.
        ``"kill@27;nan_batch@32;kill_mid_save@45;preempt@51"``. Each
        entry is ``kind@N`` with ``N`` applied to the kind's natural
        key (step for crash/kill/preempt/slow, batch index for
        nan_batch/nan_grad, save step for kill_mid_save); ``slow``
        accepts ``slow@N:ms``."""
        rules = []
        for part in (spec or "").split(";"):
            part = part.strip()
            if not part:
                continue
            kind, _, at = part.partition("@")
            dur = 0.0
            if ":" in at:
                at, _, ms = at.partition(":")
                dur = float(ms)
            n = int(at)
            if kind == "kill_mid_save":
                rules.append(TrainFaultRule(kind, save_step=n))
            elif kind in _BATCH_KINDS:
                rules.append(TrainFaultRule(kind, at_batch=n))
            else:
                rules.append(TrainFaultRule(kind, at_step=n,
                                            duration_ms=dur or 0.0))
        return cls(rules, seed=seed)

    def add_rule(self, rule: TrainFaultRule):
        with self._lock:
            self._rules.append(rule)
        return rule

    def _match(self, kinds, *, step=None, batch=None):
        """Fired rules of the given kinds for this step/batch, with
        retirement bookkeeping done under the lock."""
        fired = []
        with self._lock:
            for rule in self._rules:
                if rule.kind not in kinds:
                    continue
                if rule.at_step is not None:
                    if step != rule.at_step or id(rule) in self._retired:
                        continue
                    self._retired.add(id(rule))
                elif rule.at_batch is not None:
                    if batch != rule.at_batch:
                        continue
                    if not rule.persistent:
                        if id(rule) in self._retired:
                            continue
                        self._retired.add(id(rule))
                elif rule.rate is not None:
                    if step is None or \
                            not (self._rng.random() < rule.rate):
                        continue
                else:
                    continue
                fired.append(rule)
        return fired

    # -- the seams ------------------------------------------------------
    def on_step_begin(self, step: int):
        """Called by the supervisor at the top of optimizer step
        ``step`` (1-based), inside the hang watchdog's armed window.
        May sleep, signal, or raise."""
        for rule in self._match(_STEP_KINDS, step=step):
            tracing.flight.record("fault.train", fault=rule.kind,
                                  step=step)
            if rule.kind == "slow":
                telemetry.counter("resilience.faults.slow")
                # chunked so an async abort (hang watchdog) lands at a
                # bytecode boundary instead of after the full sleep
                deadline = time.monotonic() + rule.duration_ms / 1e3
                while time.monotonic() < deadline:
                    time.sleep(0.005)
            elif rule.kind == "preempt":
                telemetry.counter("resilience.faults.preempts")
                os.kill(os.getpid(), signal.SIGTERM)
            elif rule.kind == "kill":
                telemetry.counter("resilience.faults.kills")
                os.kill(os.getpid(), signal.SIGKILL)
            else:  # crash
                telemetry.counter("resilience.faults.crashes")
                raise InjectedTrainingFault(
                    f"injected crash at step {step}")

    def corrupt_batch(self, batch_idx: int, arrays) -> bool:
        """NaN-poison the data leaves of global batch ``batch_idx``
        (in place — the iterator slices a fresh copy per ``next()``,
        so a rewind-replay of a retired rule reads clean data).
        Returns True if a rule fired."""
        fired = self._match(("nan_batch",), batch=batch_idx)
        if not fired:
            return False
        telemetry.counter("resilience.faults.nan_batches")
        tracing.flight.record("fault.nan_batch", batch=batch_idx)
        for arr in arrays:
            arr[:] = float("nan")
        return True

    def corrupt_grads(self, batch_idx: int, params) -> bool:
        """Overwrite the first live gradient with NaN (post-backward,
        pre-update) for global batch ``batch_idx``."""
        fired = self._match(("nan_grad",), batch=batch_idx)
        if not fired:
            return False
        telemetry.counter("resilience.faults.nan_grads")
        tracing.flight.record("fault.nan_grad", batch=batch_idx)
        for p in params:
            if p.grad_req != "null" and p._data is not None and \
                    p._data._grad is not None:
                p.grad()[:] = float("nan")
                return True
        return False

    def checkpoint_fs(self, inner=None):
        """Wrap a checkpoint filesystem so armed ``kill_mid_save``
        rules can die mid-commit (pass the result as
        ``CheckpointManager(fs=...)``)."""
        from ..checkpoint._fs import LocalFS
        return _KillMidSaveFS(inner or LocalFS(), self)

    def _maybe_kill_mid_save(self, path: str):
        stepdir = os.path.basename(os.path.dirname(path))
        with self._lock:
            for rule in self._rules:
                if rule.kind != "kill_mid_save" or \
                        id(rule) in self._retired:
                    continue
                if stepdir == f"step_{rule.save_step:08d}":
                    self._retired.add(id(rule))
                    break
            else:
                return
        telemetry.counter("resilience.faults.kill_mid_save")
        os.kill(os.getpid(), signal.SIGKILL)
