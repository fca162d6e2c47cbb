"""FaultInjector — the serving fleet's deterministic chaos seam.

The checkpoint subsystem proved a discipline in PR 6: route every
fallible effect through ONE seam (`checkpoint/_fs.py` LocalFS), and
fault-injection tests become deterministic wrappers instead of global
monkeypatching. This module is the serving analog. The
:class:`~mxnet_tpu.serving.Router` calls
``injector.on_dispatch(replica_idx, engine)`` immediately before every
replica dispatch; a seeded :class:`FaultInjector` turns that call into
reproducible production pathology:

- ``error``  — raise :class:`InjectedFault` from the dispatch (a
  transport/submit failure the Router must fail over);
- ``crash``  — kill the replica's worker the way a real crash does
  (``engine._fail_all``): every in-flight stream fails with
  :class:`~mxnet_tpu.serving.ReplicaFailedError`, later submits are
  rejected as a FAILED (not closed) replica;
- ``stall``  — sleep ``duration_ms`` once (a GC pause / page-in);
- ``slow``   — sleep ``duration_ms`` on every matching dispatch (a
  degraded replica).

Rules fire deterministically: ``after_n`` triggers on exactly the n-th
dispatch of the matching replica (each rule at most once), ``rate``
draws from the injector's own seeded RNG. Tests may also call
:meth:`FaultInjector.crash` directly to kill a replica at a scripted
moment.
"""
from __future__ import annotations

import random
import threading
import time

from .. import telemetry, tracing

__all__ = ["FaultInjector", "FaultRule", "InjectedFault"]

_KINDS = ("error", "crash", "stall", "slow")


class InjectedFault(RuntimeError):
    """A deterministic, injector-originated failure. Distinct from the
    organic serving errors so tests can assert provenance."""


class FaultRule:
    """One fault specification.

    Parameters
    ----------
    kind : {"error", "crash", "stall", "slow"}
    replica : int, optional
        Target replica index; ``None`` matches every replica.
    after_n : int, optional
        Fire on exactly the ``after_n``-th dispatch of a matching
        replica (1-based, counted per replica); the rule then retires.
    rate : float, optional
        Per-dispatch firing probability from the injector's seeded RNG
        (mutually exclusive with ``after_n``).
    duration_ms : float
        Sleep length for ``stall``/``slow``.
    """

    __slots__ = ("kind", "replica", "after_n", "rate", "duration_ms")

    def __init__(self, kind, replica=None, after_n=None, rate=None,
                 duration_ms=0.0):
        if kind not in _KINDS:
            raise ValueError(f"fault kind must be one of {_KINDS}, "
                             f"got {kind!r}")
        if (after_n is None) == (rate is None):
            raise ValueError("exactly one of after_n / rate is required")
        if kind in ("stall", "slow") and duration_ms <= 0:
            raise ValueError(f"{kind} fault needs duration_ms > 0")
        self.kind = kind
        self.replica = replica
        self.after_n = None if after_n is None else int(after_n)
        self.rate = None if rate is None else float(rate)
        self.duration_ms = float(duration_ms)

    def __repr__(self):
        where = "any" if self.replica is None else self.replica
        when = f"after_n={self.after_n}" if self.after_n is not None \
            else f"rate={self.rate}"
        return f"FaultRule({self.kind}, replica={where}, {when})"


class FaultInjector:
    """Seeded, deterministic dispatch-path fault source.

    Thread-safe: rule matching and the RNG draw happen under one lock;
    the injected effect (sleep, crash, raise) runs outside it so a
    stall on one replica cannot serialize the whole fleet's dispatch.
    """

    def __init__(self, rules=(), seed: int = 0):
        self._rules = list(rules)
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._counts: dict = {}    # replica idx -> dispatch count
        self._retired: set = set()  # ids of fired after_n rules

    def add_rule(self, rule: FaultRule):
        with self._lock:
            self._rules.append(rule)
        return rule

    def clear(self):
        """Drop every rule (a chaos window ending; retired state and
        dispatch counts are kept so determinism is preserved)."""
        with self._lock:
            self._rules = []

    def dispatches(self, replica_idx: int) -> int:
        with self._lock:
            return self._counts.get(replica_idx, 0)

    # -- the seam -------------------------------------------------------
    def on_dispatch(self, replica_idx: int, engine):
        """Called by the Router immediately before dispatching to
        ``engine`` (replica ``replica_idx``). May sleep, crash the
        replica, or raise :class:`InjectedFault`."""
        sleep_ms = 0.0
        crash = False
        error = False
        with self._lock:
            n = self._counts.get(replica_idx, 0) + 1
            self._counts[replica_idx] = n
            for rule in self._rules:
                if rule.replica is not None and rule.replica != replica_idx:
                    continue
                if rule.after_n is not None:
                    if n != rule.after_n or id(rule) in self._retired:
                        continue
                    self._retired.add(id(rule))
                elif not (self._rng.random() < rule.rate):
                    continue
                if rule.kind in ("stall", "slow"):
                    sleep_ms += rule.duration_ms
                elif rule.kind == "crash":
                    crash = True
                else:
                    error = True
        if sleep_ms:
            telemetry.counter("serving.faults.stalls")
            tracing.flight.record("fault.stall", replica=replica_idx,
                                  sleep_ms=sleep_ms)
            time.sleep(sleep_ms / 1e3)
        if crash:
            self.crash(engine)
        if error:
            telemetry.counter("serving.faults.errors")
            tracing.flight.record("fault.error", replica=replica_idx)
            raise InjectedFault(
                f"injected dispatch error on replica {replica_idx}")

    def crash(self, engine):
        """Kill ``engine`` the way an organic worker crash does: every
        in-flight stream/future fails with ``ReplicaFailedError``
        (cause: :class:`InjectedFault`) and later submits are rejected
        as a FAILED replica. Serialized on the engine's generation lock
        when it has one, so the kill lands at a decode-step boundary —
        deterministic, never mid-XLA-dispatch."""
        telemetry.counter("serving.faults.crashes")
        tracing.flight.record("fault.crash")
        exc = InjectedFault("injected replica crash")
        exclusive = getattr(engine, "_gen_exclusive", None)
        if exclusive is not None:
            # registered-waiter acquisition: the engine's step loop
            # yields between decode steps, so the kill lands within
            # one step even under continuous traffic
            with exclusive():
                engine._fail_all(exc)
        else:
            engine._fail_all(exc)
