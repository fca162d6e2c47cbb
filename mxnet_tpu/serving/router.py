"""Router — a fault-tolerant serving fleet behind one ``submit()``.

One :class:`~mxnet_tpu.serving.GenerationEngine` (or
:class:`~mxnet_tpu.serving.InferenceEngine`) is one failure domain: a
crashed worker fails every in-flight stream and closes the only
engine. Serving millions of users means replicas fail *routinely*, so
the Router fronts N engine replicas with the exact submit semantics
callers already have and absorbs replica death instead of surfacing
it:

- **Join-shortest-queue balancing** — each request goes to the
  available replica with the least live load (queued requests + active
  slots: the same values the ``serving.generate.slots`` /
  ``queue.depth`` telemetry gauges publish, read per replica).
- **Health states** — per replica, ``HEALTHY`` / ``DEGRADED`` (recent
  errors or timeouts inside ``degraded_window_s``) / ``DOWN`` (worker
  dead, engine closed, or circuit open), from passive outcome tracking
  plus a cheap periodic probe thread (no model call — it checks worker
  liveness and drives breaker cooldowns even when traffic is idle).
- **Circuit breaker** — per replica, closed → open after
  ``breaker_threshold`` consecutive failures, open → half-open after
  ``breaker_cooldown_s``; a half-open replica gets exactly ONE trial
  request (success closes the breaker, failure re-opens it). A replica
  whose worker died is DOWN outright — in-process engines cannot
  resurrect, so no trial traffic is wasted on them.
- **Budget-capped retry on a different replica** — a request that
  fails because its replica broke (``ReplicaFailedError``, an injected
  dispatch fault, a replica closed mid-stream) is retried on another
  replica, up to ``max_retries`` times, with the *remaining* deadline.
  Greedy decode is deterministic, so a retry regenerates the same
  tokens — the router stream skips the prefix it already delivered and
  the caller sees one uninterrupted, token-identical stream.
- **Admission: tenant quotas, priorities, brownout shedding** — per
  tenant outstanding-request quotas (``TenantQuotaError``); under
  overload (fleet outstanding ≥ ``brownout_frac * queue_limit``) the
  lowest-priority classes are shed first (``LoadShedError``; priority
  0 is highest and sheds last) and, optionally, admitted generation
  budgets are capped to ``brownout_max_new_tokens`` (brownout: degrade
  answer length before availability); at ``queue_limit`` everything
  sheds.
- **Rolling fleet rollover** — :meth:`Router.load_weights` drains and
  swaps one replica at a time over PR 6's per-engine zero-downtime
  rollover: cordoned replicas stop taking new traffic while their
  queue drains, in-flight slots finish on the new weights, and no
  request is dropped fleet-wide.
- **Multi-tenant LoRA propagation** — ``submit(adapter=name)`` rides
  every dispatch and retry; :meth:`Router.load_adapter` /
  :meth:`unload_adapter` roll an adapter across the fleet (the
  ``load_weights`` pattern, zero retraces per engine), and a fleet
  whose adapter registries diverge is rejected AT DISPATCH — a
  cross-replica retry must be able to re-bind the same adapter on
  whichever replica catches it.

Every replica dispatch passes through the
:class:`~mxnet_tpu.serving.FaultInjector` seam (``fault_injector=``),
so each behavior above is provable with seeded, deterministic faults
(tests/test_router.py).

Telemetry (docs/OBSERVABILITY.md): counters
``serving.router.{requests,completed,retries,replica_failures,
replica_crashes,replica_full,rejected_shed,rejected_quota,
rejected_full,rejected_closed,brownout_capped,breaker_opens,
breaker_half_opens,breaker_closes,fail_open,prefix_affinity_hits,
timeouts,errors,rollovers,probes}``, gauges
``serving.router.{outstanding,healthy_replicas}`` (with peaks), and
the ``serving.router.latency`` histogram (submit → final outcome).
"""
from __future__ import annotations

import collections
import os
import threading
import time
import weakref
from concurrent.futures import Future

from .. import telemetry, tracing
from .engine import (
    EngineClosedError, InferenceEngine, QueueFullError,
    ReplicaFailedError, RequestTimeoutError,
)
from .generate import GenerationEngine, GenerationStream

__all__ = ["Router", "RouterStream", "LoadShedError", "TenantQuotaError",
           "HEALTHY", "DEGRADED", "DOWN"]

#: health states (docs/SERVING.md "Fleet")
HEALTHY, DEGRADED, DOWN = "HEALTHY", "DEGRADED", "DOWN"
#: breaker states
_CLOSED, _OPEN, _HALF = "closed", "open", "half-open"


class LoadShedError(QueueFullError):
    """Brownout/overload shedding: the fleet rejected this request to
    protect higher-priority traffic (retry later, or at priority 0)."""


class TenantQuotaError(QueueFullError):
    """The tenant is at its outstanding-request quota."""


class RouterStream(GenerationStream):
    """A :class:`GenerationStream` with fleet provenance: ``tenant``,
    ``priority``, ``retries`` (cross-replica re-dispatches this request
    survived), and ``replicas`` (replica index per dispatch attempt).
    Token-stream semantics are unchanged — a retried request's stream
    continues seamlessly (greedy decode makes the retry prefix
    token-identical, so already-delivered tokens are skipped)."""

    def __init__(self, prompt_len, tenant, priority):
        super().__init__(prompt_len)
        self.tenant = tenant
        self.priority = priority
        self.retries = 0
        self.replicas: list = []


class _Replica:
    __slots__ = ("engine", "idx", "breaker", "opened_at", "consec",
                 "half_open_trial", "inflight", "dispatches", "failures",
                 "successes", "timeouts", "cordoned", "last_failure_at",
                 "last_error", "crash_seen")

    def __init__(self, engine, idx):
        self.engine = engine
        self.idx = idx
        self.breaker = _CLOSED
        self.opened_at = 0.0
        self.consec = 0            # consecutive failures (breaker input)
        self.half_open_trial = 0   # 1 while the single trial is out
        self.inflight = 0          # router-dispatched, not yet finished
        self.dispatches = 0
        self.failures = 0
        self.successes = 0
        self.timeouts = 0
        self.cordoned = False      # rolling rollover: prefer others
        self.last_failure_at = None
        self.last_error = None
        self.crash_seen = False


class _Req:
    __slots__ = ("payload", "max_new", "eos_id", "deadline", "tenant",
                 "priority", "retries_left", "sink", "t0", "finished",
                 "prefix_key", "sampling", "adapter")

    def __init__(self, payload, max_new, eos_id, deadline, tenant,
                 priority, retries_left, sink, t0, prefix_key=None,
                 sampling=None, adapter=None):
        self.payload = payload
        self.max_new = max_new
        self.eos_id = eos_id
        self.deadline = deadline   # absolute monotonic, or None
        self.tenant = tenant
        self.priority = priority
        self.retries_left = retries_left
        self.sink = sink           # RouterStream (generate) / Future
        self.t0 = t0
        self.finished = False
        self.prefix_key = prefix_key
        #: per-request sampling kwargs forwarded verbatim to EVERY
        #: dispatch attempt (the seed is pinned at admission, so a
        #: cross-replica retry replays the same stochastic stream and
        #: the prefix-skip stays token-identical — up to the seeded-
        #: stream schedule caveat of docs/SERVING.md: the new
        #: replica's co-tenant schedule differs, which can shift an
        #: ulp-knife-edge accept draw in rare cases; greedy retries
        #: are exact)
        self.sampling = sampling
        #: LoRA adapter name, forwarded verbatim to every dispatch
        #: attempt (registry homogeneity is checked at admission, so
        #: a cross-replica retry re-binds the same adapter and stays
        #: token-identical)
        self.adapter = adapter


class _Prober(threading.Thread):
    """Cheap periodic health sweep: worker liveness, breaker cooldowns,
    fleet gauges. No model call — the passive outcome tracking is the
    expensive signal; the probe exists so state advances (half-open
    after cooldown, DOWN on a silent death) even with zero traffic."""

    def __init__(self, router: "Router", interval_s: float):
        super().__init__(daemon=True, name="Router.prober")
        self._router = weakref.ref(router)
        self._interval = interval_s
        # NB: threading.Thread reserves the _stop name internally
        self._halt = threading.Event()
        self.start()

    def stop(self):
        self._halt.set()

    def run(self):
        while not self._halt.wait(self._interval):
            router = self._router()
            if router is None or router._closed:
                return
            try:
                router._probe_once()
            except Exception:  # noqa: BLE001 — the prober must survive
                pass
            del router


class Router:
    """Load-balance ``submit()`` across N engine replicas with health
    checks, circuit breakers, retries, load shedding, and rolling
    weight rollover (module docstring has the full semantics).

    Parameters
    ----------
    replicas : sequence of GenerationEngine | sequence of InferenceEngine
        The fleet (homogeneous: one engine kind, identically
        configured, identical weights — retry token-identity depends
        on it). The Router takes ownership: ``close()`` closes them.
    max_retries : int
        Cross-replica re-dispatch budget per request (0 disables).
    breaker_threshold : int
        Consecutive failures that open a replica's circuit.
    breaker_cooldown_s : float
        Open → half-open delay.
    degraded_window_s : float
        How long after a failure/timeout a replica reports DEGRADED.
    probe_interval_s : float
        Health-probe period.
    queue_limit : int, optional
        Fleet-wide outstanding-request bound (default: the sum of the
        replicas' own ``queue_limit``s). At the bound every submit
        sheds; from ``brownout_frac * queue_limit`` upward only
        priority 0 is admitted.
    brownout_frac : float
        Overload threshold as a fraction of ``queue_limit``.
    brownout_max_new_tokens : int, optional
        During brownout, cap admitted generation budgets to this many
        tokens (generation fleets only).
    tenant_quota : int | dict, optional
        Outstanding-request cap per tenant (int: every tenant; dict:
        per-tenant, ``None``/missing = unlimited).
    timeout_ms : float, optional
        Default end-to-end deadline per request; the *remaining*
        budget propagates to every dispatch attempt, including retries.
    fault_injector : FaultInjector, optional
        Chaos seam: consulted before every replica dispatch.
    prefix_affinity_slack : int
        How many queued requests of extra load a prefix-warm replica
        may carry and still win a ``submit(prefix_key=...)`` dispatch
        over the shortest queue (soft preference: health, breaker
        state, and larger imbalances always win).
    """

    def __init__(self, replicas, *, max_retries: int = 2,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 5.0,
                 degraded_window_s: float = 5.0,
                 probe_interval_s: float = 0.5,
                 queue_limit=None, brownout_frac: float = 0.8,
                 brownout_max_new_tokens=None, tenant_quota=None,
                 timeout_ms=None, fault_injector=None,
                 prefix_affinity_slack: int = 4):
        replicas = list(replicas)
        if not replicas:
            raise ValueError("Router needs at least one replica")
        if all(isinstance(e, GenerationEngine) for e in replicas):
            self._mode = "generate"
        elif all(isinstance(e, InferenceEngine) for e in replicas):
            self._mode = "infer"
        else:
            raise TypeError(
                "replicas must be a homogeneous fleet of "
                "GenerationEngine or InferenceEngine instances")
        precisions = {getattr(e, "precision", "fp32") for e in replicas}
        if len(precisions) > 1:
            # a retried request re-runs on ANOTHER replica; mixing
            # fp32 and int8 replicas would make the retry's output
            # depend on which replica caught it — token-identity and
            # the bounded-divergence contract both break
            raise TypeError(
                f"replicas must be precision-homogeneous, got "
                f"{sorted(precisions)} (replica capabilities: "
                f"{self._fleet_capabilities(replicas)})")
        specs = {getattr(e, "speculation", "off") for e in replicas}
        if len(specs) > 1:
            # same rule for the speculation config (the draft model
            # and spec_k): a retried STOCHASTIC request replays its
            # seed, and its committed stream depends on the
            # draft/spec_k key-consumption schedule — a draft-model-
            # heterogeneous fleet would make the retry's tokens depend
            # on which replica caught it
            raise TypeError(
                f"replicas must be speculation-homogeneous, got "
                f"{sorted(specs)} (replica capabilities: "
                f"{self._fleet_capabilities(replicas)})")
        meshes = {getattr(e, "mesh_config", "off") for e in replicas}
        if len(meshes) > 1:
            # and for the mesh layout (shape included): a retried
            # request must replay the IDENTICAL numeric config, and a
            # tensor-parallel replica's logits differ from an
            # unsharded one's in the tp partial-sum reduction order —
            # token-identity across a retry only holds when every
            # replica computes the same way
            raise TypeError(
                f"replicas must be mesh-homogeneous (same mesh_layout "
                f"and mesh shape), got {sorted(meshes)} (replica "
                f"capabilities: {self._fleet_capabilities(replicas)})")
        loras = {getattr(e, "lora", "off") for e in replicas}
        if len(loras) > 1:
            # and for the LoRA bank config: an adapter= binding only
            # means the same thing fleet-wide when every replica's
            # bank has the same rank/capacity — a retry must be able
            # to land anywhere (per-NAME registry homogeneity is
            # enforced per dispatch; this is the structural half)
            raise TypeError(
                f"replicas must be LoRA-config-homogeneous, got "
                f"{sorted(loras)} (replica capabilities: "
                f"{self._fleet_capabilities(replicas)})")
        self._replicas = [_Replica(e, i) for i, e in enumerate(replicas)]
        self.max_retries = int(max_retries)
        self.breaker_threshold = max(1, int(breaker_threshold))
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self.degraded_window_s = float(degraded_window_s)
        self.queue_limit = int(queue_limit) if queue_limit is not None \
            else sum(e.queue_limit for e in replicas)
        if not 0.0 < float(brownout_frac) <= 1.0:
            raise ValueError("brownout_frac must be in (0, 1]")
        self._brownout_at = max(1, int(float(brownout_frac)
                                       * self.queue_limit))
        self.brownout_max_new_tokens = brownout_max_new_tokens
        self._tenant_quota = tenant_quota
        self.timeout_ms = timeout_ms
        self._faults = fault_injector
        self.prefix_affinity_slack = int(prefix_affinity_slack)
        #: prefix_key -> replica idx that last held that prefix's
        #: pages (bounded FIFO; a soft routing hint, never load-bearing)
        self._affinity: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._affinity_cap = 4096
        self._lock = threading.Lock()
        self._outstanding = 0
        self._tenant_out: dict = {}
        #: router-level adapter pins: name -> count of in-flight
        #: requests bound to it (pins survive retries — the engines'
        #: per-replica pin only covers the replica actually serving,
        #: but a cross-replica retry must be able to re-bind the
        #: adapter on ANY replica, so a fleet unload defers while any
        #: router request holds the name)
        self._adapter_inflight: dict = {}
        #: adapter names whose fleet-wide unload is deferred behind
        #: the pins above (new submits with them are rejected now)
        self._adapter_draining: set = set()
        #: drained names whose rolling unload is waiting for the
        #: prober thread (a stream-finish callback may hold an engine
        #: worker's step lock, where running the roll inline could
        #: deadlock against a load_adapter waiting on that engine's
        #: step boundary under the roll lock)
        self._adapter_drain_pending: set = set()
        #: serializes fleet-wide adapter rolls — a concurrent
        #: load_adapter/unload_adapter pair on one name must not
        #: interleave per replica, or the two rolls can finish in
        #: opposite orders on different replicas and leave the name
        #: PERSISTENTLY heterogeneous with both calls reporting
        #: success
        self._adapter_roll_lock = threading.Lock()
        self._closed = False
        self._prober = _Prober(self, float(probe_interval_s))

    @staticmethod
    def _fleet_capabilities(engines):
        """Per-replica capability summary for heterogeneity errors —
        names what each engine actually does instead of leaving the
        caller to diff constructors (the shared submit-kwarg-error
        discipline, fleet-shaped)."""
        caps = []
        for i, e in enumerate(engines):
            fn = getattr(e, "capabilities", None)
            caps.append(f"[{i}] {fn() if callable(fn) else 'n/a'}")
        return "; ".join(caps)

    # -- lifecycle -----------------------------------------------------
    @property
    def replicas(self):
        """The fleet's engines, in replica-index order."""
        return [rep.engine for rep in self._replicas]

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def outstanding(self) -> int:
        """Requests admitted and not yet finished, fleet-wide."""
        return self._outstanding

    def warmup(self, *args):
        """AOT-warm every live replica (generation fleets take no
        args; inference fleets forward ``args`` to each engine's
        ``warmup``)."""
        for rep in self._replicas:
            if not rep.engine.closed:
                rep.engine.warmup(*args)
        return self

    def close(self, timeout: float = 5.0, close_replicas: bool = True):
        """Stop admission, stop the prober, and (by default) close
        every replica — their drain/reject semantics apply, so no
        stream or future is ever left hanging. Idempotent."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._prober.stop()
        if close_replicas:
            for rep in self._replicas:
                try:
                    rep.engine.close(timeout)
                except Exception:  # noqa: BLE001 — close the rest
                    pass
        self._prober.join(timeout=2.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- health / breaker ----------------------------------------------
    def _replica_load(self, rep: _Replica):
        """Live load key (JSQ): queued + active on the engine — the
        same values the slot/queue-depth telemetry gauges publish —
        tie-broken by router-side inflight, then index (deterministic)."""
        eng = rep.engine
        worker = getattr(eng, "_worker", None)
        if worker is None:
            worker = getattr(eng, "_batcher", None)
        queued = worker._queue.qsize() if worker is not None else 0
        return (queued + getattr(eng, "_n_active", 0), rep.inflight,
                rep.idx)

    def _dead(self, rep: _Replica) -> bool:
        """Worker died or engine deliberately closed: permanently out
        (an in-process engine cannot resurrect — no trial traffic)."""
        return rep.engine._failure is not None or rep.engine.closed

    def _pick(self, exclude, affinity=None):
        """Select the dispatch target: the half-open trial slot first
        (the breaker can only close by observing a success), else the
        least-loaded closed-breaker replica; cordoned replicas (mid-
        rollover) are used only when nothing else is available. Last
        resort is FAIL-OPEN: when every live replica's breaker is
        open, route to the least-loaded one anyway — shedding every
        request because the whole fleet tripped (e.g. a retry burst
        meeting a transient error spike) would turn a partial outage
        into a total one; a success then closes the breaker.

        ``affinity`` is a SOFT prefix-affinity hint: among the healthy
        closed-breaker candidates, the replica already holding that
        prefix's KV pages wins as long as its queued load is within
        ``prefix_affinity_slack`` of the shortest queue — a warm
        prefix beats a marginally shorter queue, but health, breaker
        state, cordons, and real imbalance always win."""
        now = time.monotonic()
        with self._lock:
            half = best = best_cord = best_open = aff = None
            best_load = best_cord_load = best_open_load = aff_load = None
            for rep in self._replicas:
                if rep.idx in exclude or self._dead(rep):
                    continue
                if rep.breaker == _OPEN \
                        and now - rep.opened_at >= self.breaker_cooldown_s:
                    rep.breaker = _HALF
                    rep.half_open_trial = 0
                    telemetry.counter(
                        "serving.router.breaker_half_opens")
                    tracing.flight.record("router.breaker_half_open",
                                          replica=rep.idx)
                if rep.breaker == _HALF and rep.half_open_trial == 0:
                    if half is None:
                        half = rep
                    continue
                load = self._replica_load(rep)
                if rep.breaker in (_OPEN, _HALF):
                    if best_open is None or load < best_open_load:
                        best_open, best_open_load = rep, load
                elif rep.cordoned:
                    if best_cord is None or load < best_cord_load:
                        best_cord, best_cord_load = rep, load
                else:
                    if rep.idx == affinity:
                        aff, aff_load = rep, load
                    if best is None or load < best_load:
                        best, best_load = rep, load
            if half is not None:
                half.half_open_trial = 1
                return half
            if aff is not None and \
                    aff_load[0] <= best_load[0] + self.prefix_affinity_slack:
                if aff is not best:
                    # count only dispatches the hint actually CHANGED —
                    # an idle fleet where JSQ already picks the warm
                    # replica must not read as 100% affinity routing
                    telemetry.counter(
                        "serving.router.prefix_affinity_hits")
                return aff
            if best is not None:
                return best
            if best_cord is not None:
                return best_cord
            if best_open is not None:
                telemetry.counter("serving.router.fail_open")
            return best_open

    def _record_failure(self, rep: _Replica, exc):
        telemetry.counter("serving.router.replica_failures")
        now = time.monotonic()
        opened = False
        with self._lock:
            rep.failures += 1
            rep.consec += 1
            rep.last_failure_at = now
            rep.last_error = exc
            if rep.breaker == _HALF:
                rep.breaker = _OPEN
                rep.opened_at = now
                rep.half_open_trial = 0
                telemetry.counter("serving.router.breaker_opens")
                opened = True
            elif rep.breaker == _CLOSED \
                    and rep.consec >= self.breaker_threshold:
                rep.breaker = _OPEN
                rep.opened_at = now
                telemetry.counter("serving.router.breaker_opens")
                opened = True
        if opened:
            # incident post-mortem — dumped OUTSIDE the router lock
            # (the dump may write a file when MXTPU_FLIGHT_DIR is set)
            tracing.flight.dump(
                "router.breaker_open", replica=rep.idx,
                consecutive_failures=rep.consec,
                error=f"{type(exc).__name__}: {exc}")

    def _record_success(self, rep: _Replica):
        with self._lock:
            rep.successes += 1
            rep.consec = 0
            if rep.breaker in (_HALF, _OPEN):
                # a real success is the definitive health signal — it
                # closes a half-open (trial) AND an open (fail-open
                # dispatch) breaker
                rep.breaker = _CLOSED
                rep.half_open_trial = 0
                telemetry.counter("serving.router.breaker_closes")
                tracing.flight.record("router.breaker_close",
                                      replica=rep.idx)

    def _record_timeout(self, rep: _Replica):
        # a deadline miss marks the replica DEGRADED (slow) but never
        # trips the breaker: the deadline may simply have been tight.
        # An inconclusive half-open trial returns its slot so the next
        # request can probe again.
        with self._lock:
            rep.timeouts += 1
            rep.last_failure_at = time.monotonic()
            if rep.breaker == _HALF:
                rep.half_open_trial = 0

    def _abort_trial(self, rep: _Replica):
        """Return an unused half-open trial slot (the dispatch never
        reached the replica — e.g. its queue was full)."""
        with self._lock:
            if rep.breaker == _HALF:
                rep.half_open_trial = 0

    def _probe_once(self):
        telemetry.counter("serving.router.probes")
        now = time.monotonic()
        healthy = 0
        silent_dead = []
        with self._lock:
            for rep in self._replicas:
                eng = rep.engine
                worker = getattr(eng, "_worker", None)
                if worker is None:
                    worker = getattr(eng, "_batcher", None)
                dead_now = (worker is not None
                            and not worker.is_alive()
                            and not eng.closed
                            and eng._failure is None)
                if dead_now:
                    # silent death: the worker left no failure record
                    # (a BaseException escaped its handler, or the
                    # thread was torn down externally) — without this
                    # check the corpse reads HEALTHY and JSQ keeps
                    # routing to it
                    silent_dead.append(rep)
                if eng._failure is not None and not rep.crash_seen:
                    rep.crash_seen = True
                    rep.last_error = eng._failure
                    telemetry.counter("serving.router.replica_crashes")
                if rep.breaker == _OPEN and not self._dead(rep) \
                        and now - rep.opened_at >= self.breaker_cooldown_s:
                    rep.breaker = _HALF
                    rep.half_open_trial = 0
                    telemetry.counter("serving.router.breaker_half_opens")
                    tracing.flight.record("router.breaker_half_open",
                                          replica=rep.idx)
                if rep.breaker == _CLOSED and not self._dead(rep) \
                        and not dead_now:
                    healthy += 1
        # declare the deaths OUTSIDE the router lock: _fail_all fires
        # stream watchers whose retry path re-enters it
        for rep in silent_dead:
            exc = ReplicaFailedError(
                "replica worker died silently (thread not alive)")
            exclusive = getattr(rep.engine, "_gen_exclusive", None)
            if exclusive is not None:
                with exclusive():
                    rep.engine._fail_all(exc)
            else:
                rep.engine._fail_all(exc)
        telemetry.gauge("serving.router.healthy_replicas", healthy)
        self._run_pending_drains()

    def _run_pending_drains(self):
        """Deferred fleet unloads whose last router pin dropped —
        executed here on the prober thread, never inline in the
        releasing thread (a stream-finish callback may hold an engine
        worker's step lock, where blocking on the roll lock could
        deadlock against a ``load_adapter`` waiting on that same
        engine's step boundary)."""
        while True:
            with self._lock:
                if not self._adapter_drain_pending:
                    return
                name = self._adapter_drain_pending.pop()
            self._unload_adapter_now(name)

    def health(self) -> dict:
        """Snapshot per replica: ``{idx: {state, breaker, inflight,
        dispatches, failures, successes, timeouts, cordoned, load}}``
        with ``state`` in {HEALTHY, DEGRADED, DOWN}."""
        now = time.monotonic()
        out = {}
        with self._lock:
            for rep in self._replicas:
                if self._dead(rep) or rep.breaker == _OPEN:
                    state = DOWN
                elif rep.breaker == _HALF or (
                        rep.last_failure_at is not None
                        and now - rep.last_failure_at
                        < self.degraded_window_s):
                    state = DEGRADED
                else:
                    state = HEALTHY
                out[rep.idx] = {
                    "state": state, "breaker": rep.breaker,
                    "inflight": rep.inflight,
                    "dispatches": rep.dispatches,
                    "failures": rep.failures,
                    "successes": rep.successes,
                    "timeouts": rep.timeouts,
                    "cordoned": rep.cordoned,
                    "load": self._replica_load(rep)[0],
                }
        return out

    # -- admission -----------------------------------------------------
    def _quota_for(self, tenant):
        q = self._tenant_quota
        if q is None:
            return None
        if isinstance(q, dict):
            return q.get(tenant)
        return int(q)

    def _admit(self, tenant, priority, max_new, adapter=None):
        """Shedding + quota gate; reserves one outstanding slot and —
        atomically with it — the request's router-level adapter pin,
        so an ``unload_adapter`` can never slip between validation and
        admission (the pin defers the fleet unload until the last
        bound request releases). Returns the (possibly
        brownout-capped) generation budget."""
        with self._lock:
            if adapter is not None and adapter in self._adapter_draining:
                raise ValueError(
                    f"submit() adapter={adapter!r} is unloading "
                    f"fleet-wide (pinned by in-flight requests); it "
                    f"no longer accepts new submits")
            out = self._outstanding
            if out >= self.queue_limit:
                telemetry.counter("serving.router.rejected_shed")
                raise LoadShedError(
                    f"fleet at queue_limit={self.queue_limit} "
                    f"(outstanding={out}); all priorities shed")
            if out >= self._brownout_at:
                if priority > 0:
                    telemetry.counter("serving.router.rejected_shed")
                    raise LoadShedError(
                        f"brownout at outstanding={out} (>= "
                        f"{self._brownout_at}): shedding priority "
                        f"{priority}; only priority 0 admitted")
                if self.brownout_max_new_tokens is not None \
                        and max_new is not None \
                        and max_new > self.brownout_max_new_tokens:
                    max_new = int(self.brownout_max_new_tokens)
                    telemetry.counter("serving.router.brownout_capped")
            quota = self._quota_for(tenant)
            if quota is not None \
                    and self._tenant_out.get(tenant, 0) >= quota:
                telemetry.counter("serving.router.rejected_quota")
                raise TenantQuotaError(
                    f"tenant {tenant!r} at quota={quota} outstanding "
                    f"requests")
            self._outstanding = out + 1
            self._tenant_out[tenant] = \
                self._tenant_out.get(tenant, 0) + 1
            if adapter is not None:
                self._adapter_inflight[adapter] = \
                    self._adapter_inflight.get(adapter, 0) + 1
            telemetry.gauge("serving.router.outstanding",
                            self._outstanding)
        return max_new

    def _release(self, req: _Req) -> bool:
        """Undo the admission reservation; returns False if the
        request was already finished (idempotence — the single place
        the finished flag and the outstanding/tenant accounting
        change together). Dropping the last router-level pin on a
        draining adapter queues the deferred fleet-wide unload for
        the prober thread."""
        with self._lock:
            if req.finished:
                return False
            req.finished = True
            self._outstanding -= 1
            n = self._tenant_out.get(req.tenant, 1) - 1
            if n <= 0:
                self._tenant_out.pop(req.tenant, None)
            else:
                self._tenant_out[req.tenant] = n
            if getattr(req, "adapter", None) is not None:
                a = req.adapter
                left = self._adapter_inflight.get(a, 1) - 1
                if left <= 0:
                    self._adapter_inflight.pop(a, None)
                    if a in self._adapter_draining:
                        # keep the draining mark (no submit can
                        # re-pin the name) and hand the roll to the
                        # prober thread: this release may run in a
                        # stream-finish callback under an engine
                        # worker's step lock, where taking the roll
                        # lock could deadlock against a load_adapter
                        # waiting on that engine's step boundary
                        self._adapter_drain_pending.add(a)
                else:
                    self._adapter_inflight[a] = left
            telemetry.gauge("serving.router.outstanding",
                            self._outstanding)
        return True

    # -- submit --------------------------------------------------------
    def submit(self, *args, max_new_tokens=None, eos_id=None,
               timeout_ms=None, tenant: str = "default",
               priority: int = 0, prefix_key=None, temperature=None,
               top_k=None, top_p=None, seed=None, adapter=None,
               trace=None):
        """Queue one request on the fleet.

        Generation fleets take exactly one positional ``prompt`` and
        return a :class:`RouterStream`; inference fleets take the
        request args and return a ``Future``. ``tenant`` scopes the
        quota, ``priority`` (0 = highest) orders load shedding.
        ``prefix_key`` is an opaque caller-chosen label for the
        request's shared prompt prefix (e.g. a system-prompt id):
        requests with the same key are soft-biased toward the replica
        that served that key last, so its paged-KV prefix cache stays
        warm — health, breaker state, and join-shortest-queue still
        win (``serving.router.prefix_affinity_hits`` counts the
        dispatches the hint changed).
        ``temperature``/``top_k``/``top_p``/``seed`` are the engines'
        per-request sampling knobs, forwarded to every dispatch; a
        stochastic request without an explicit seed gets one pinned at
        admission, so a cross-replica retry replays the identical
        stream and the prefix-skip stays token-identical.
        ``adapter`` names a LoRA adapter the request decodes under
        (generation fleets; ``Router.load_adapter`` installs it
        fleet-wide): the name must resolve on EVERY live replica —
        the fleet's registries are compared at dispatch and a
        heterogeneous fleet is rejected, because a cross-replica
        retry must be able to re-bind the same adapter anywhere.
        ``trace`` arms per-request tracing (generation fleets):
        ``True`` forces a span trace for this request, ``False``
        suppresses it, ``None`` defers to the ``MXTPU_TRACING``
        process default. The ONE trace object follows the request
        across replica retry hops, so ``stream.trace()`` reconstructs
        the full fleet-level lifecycle including the hop.
        Raises :class:`EngineClosedError` / :class:`LoadShedError` /
        :class:`TenantQuotaError` / :class:`QueueFullError` /
        ``ValueError`` immediately, never via a hung stream."""
        if self._closed:
            telemetry.counter("serving.router.rejected_closed")
            raise EngineClosedError("submit on a closed Router")
        tmo = self.timeout_ms if timeout_ms is None else timeout_ms
        deadline = time.monotonic() + tmo / 1e3 if tmo is not None \
            else None
        if self._mode == "generate":
            if len(args) != 1:
                raise TypeError(
                    "a generation fleet's submit takes exactly one "
                    "positional prompt")
            lead = self._replicas[0].engine
            prompt, max_new, eos = lead._validate(
                args[0], max_new_tokens, eos_id)
            temp, tk, tp, seed = lead._validate_sampling(
                temperature, top_k, top_p, seed)
            if adapter is not None:
                self._validate_adapter(adapter)
            sampling = None
            if temp > 0:
                if seed is None:
                    # pin the seed NOW: a retry must replay the exact
                    # stochastic stream on the next replica
                    seed = int.from_bytes(os.urandom(4), "little")
                sampling = {"temperature": temp, "top_k": tk,
                            "top_p": tp, "seed": seed}
            max_new = self._admit(tenant, priority, max_new,
                                  adapter=adapter)
            sink = RouterStream(int(prompt.size), tenant, priority)
            tr = tracing.start_trace(trace, source="router",
                                     tenant=tenant,
                                     prompt_len=int(prompt.size),
                                     max_new=max_new)
            if tr is not None:
                sink._trace = tr
            req = _Req(prompt, max_new, eos, deadline, tenant, priority,
                       self.max_retries, sink, telemetry.clock(),
                       prefix_key=prefix_key, sampling=sampling,
                       adapter=adapter)
        else:
            if max_new_tokens is not None or eos_id is not None \
                    or temperature is not None or top_k is not None \
                    or top_p is not None or seed is not None \
                    or adapter is not None:
                raise TypeError(
                    "max_new_tokens/eos_id, the sampling knobs and "
                    "adapter= apply to generation fleets only")
            self._admit(tenant, priority, None)
            sink = Future()
            sink.tenant, sink.priority = tenant, priority
            sink.retries, sink.replicas = 0, []
            req = _Req(args, None, None, deadline, tenant, priority,
                       self.max_retries, sink, telemetry.clock(),
                       prefix_key=prefix_key)
        telemetry.counter("serving.router.requests")
        try:
            self._dispatch(req, frozenset(), inline=True)
        except BaseException:
            self._release(req)
            raise
        return sink

    def _validate_adapter(self, adapter):
        """Resolve an ``adapter=`` binding against the fleet at
        dispatch time: the REQUESTED name must be loaded on every
        LIVE replica (a cross-replica retry re-binds the name on
        whichever replica catches it — a fleet where this name is
        missing, or unloading, on some replicas cannot honor that).
        The check is scoped to the requested name: an in-progress
        rolling load/unload of an UNRELATED adapter must not shed
        valid tenant traffic. Rejected requests raise here, at the
        router edge, before any admission state is reserved."""
        lead = self._replicas[0].engine
        if not getattr(lead, "lora_enabled", False):
            raise lead._submit_error(
                "adapter", adapter, "this fleet has no LoRA bank "
                "(replicas constructed without lora_rank=)")
        live = [rep for rep in self._replicas if not self._dead(rep)]
        # one dict lookup per replica (has_adapter) — the submit hot
        # path never materializes/sorts whole registries; those are
        # built only to compose a failing request's error message
        have = {rep.idx for rep in live
                if rep.engine.has_adapter(adapter)}
        if have and len(have) < len(live):
            raise TypeError(
                f"adapter={adapter!r} rejected: the fleet's "
                f"registries are heterogeneous for this name (loaded "
                f"on replicas {sorted(have)!r}, missing on "
                f"{sorted({r.idx for r in live} - have)!r}) — a "
                f"cross-replica retry could not re-bind the adapter; "
                f"roll the load fleet-wide via Router.load_adapter")
        if not have:
            loaded = sorted({n for rep in live
                             for n in rep.engine.adapters})
            raise ValueError(
                f"unknown adapter {adapter!r}: not loaded on the "
                f"fleet (loaded adapters: {loaded!r})")

    def generate(self, prompt, timeout=None, **kwargs):
        """Blocking convenience (generation fleets):
        ``submit(prompt, **kwargs).result(timeout)``."""
        return self.submit(prompt, **kwargs).result(timeout)

    def predict(self, *args, timeout=None, **kwargs):
        """Blocking convenience (inference fleets):
        ``submit(*args, **kwargs).result(timeout)``."""
        return self.submit(*args, **kwargs).result(timeout)

    # -- dispatch ------------------------------------------------------
    def _remaining_ms(self, req: _Req):
        if req.deadline is None:
            return None, False
        rem = req.deadline - time.monotonic()
        return rem * 1e3, rem <= 0

    def _fail(self, req: _Req, exc, inline: bool):
        """Terminal failure: raise synchronously from ``submit`` when
        the first dispatch never succeeded, deliver through the sink
        otherwise."""
        if inline:
            # admission is released by submit's except hook; outcome
            # counters for the raise path:
            if isinstance(exc, RequestTimeoutError):
                telemetry.counter("serving.router.timeouts")
            elif not isinstance(exc, (QueueFullError, ValueError,
                                      TypeError)):
                telemetry.counter("serving.router.errors")
            raise exc
        self._finish_req(req, exc=exc)

    def _dispatch(self, req: _Req, exclude, inline: bool = False):
        exclude = set(exclude)
        while True:
            if self._closed:
                return self._fail(req, EngineClosedError(
                    "Router closed while the request was in flight"),
                    inline)
            rem_ms, expired = self._remaining_ms(req)
            if expired:
                if self._mode == "generate" and req.sink.tokens:
                    # partial output already delivered: finish the
                    # stream the way an engine-side deadline would
                    return self._finish_req(req, reason="timeout")
                return self._fail(req, RequestTimeoutError(
                    "request deadline expired before a replica could "
                    "serve it"), inline)
            affinity = None
            if req.prefix_key is not None:
                with self._lock:
                    affinity = self._affinity.get(req.prefix_key)
            rep = self._pick(exclude, affinity=affinity)
            if rep is None:
                return self._fail(req, ReplicaFailedError(
                    f"no available replica in the fleet "
                    f"({len(self._replicas)} total: down, circuit-open, "
                    f"or already tried)"), inline)
            tr = getattr(req.sink, "_trace", None)
            try:
                if self._faults is not None:
                    self._faults.on_dispatch(rep.idx, rep.engine)
                if self._mode == "generate":
                    akw = {} if req.adapter is None \
                        else {"adapter": req.adapter}
                    if tr is not None:
                        tr.event("dispatch", replica=rep.idx)
                    # the ONE trace object rides along to the replica
                    # engine (its spans accumulate under this request);
                    # an untraced router request must also suppress any
                    # process-default engine trace, so the replica
                    # stream never grows a second, router-invisible one
                    attempt = rep.engine.submit(
                        req.payload, max_new_tokens=req.max_new,
                        eos_id=req.eos_id, timeout_ms=rem_ms,
                        trace=tr if tr is not None else False,
                        **(req.sampling or {}), **akw)
                else:
                    attempt = rep.engine.submit(*req.payload,
                                                timeout_ms=rem_ms)
            except QueueFullError:
                # saturation, not sickness: never trips the breaker —
                # spill to the next-shortest queue, shed only when
                # every candidate is full
                self._abort_trial(rep)
                telemetry.counter("serving.router.replica_full")
                exclude.add(rep.idx)
                if len(exclude) >= len(self._replicas):
                    telemetry.counter("serving.router.rejected_full")
                    return self._fail(req, QueueFullError(
                        "every available replica's queue is full"),
                        inline)
                continue
            except (ValueError, TypeError) as e:
                self._abort_trial(rep)  # the request is malformed,
                return self._fail(req, e, inline)  # not the replica
            except Exception as e:  # noqa: BLE001 — replica failure
                self._record_failure(rep, e)
                if req.retries_left > 0 and not self._closed:
                    req.retries_left -= 1
                    req.sink.retries += 1
                    telemetry.counter("serving.router.retries")
                    if tr is not None:
                        tr.event("retry", replica=rep.idx,
                                 error=f"{type(e).__name__}: {e}")
                    tracing.flight.record(
                        "router.retry", replica=rep.idx,
                        error=type(e).__name__,
                        trace_id=None if tr is None else tr.trace_id)
                    exclude.add(rep.idx)
                    continue
                return self._fail(req, e, inline)
            with self._lock:
                rep.inflight += 1
                rep.dispatches += 1
                if req.prefix_key is not None:
                    # this replica now holds the prefix's pages — bias
                    # the key's future requests toward it
                    self._affinity.pop(req.prefix_key, None)
                    self._affinity[req.prefix_key] = rep.idx
                    while len(self._affinity) > self._affinity_cap:
                        self._affinity.popitem(last=False)
            req.sink.replicas.append(rep.idx)
            if self._mode == "generate":
                self._attach_gen(req, rep, attempt)
            else:
                self._attach_infer(req, rep, attempt)
            return

    # -- per-attempt completion ----------------------------------------
    def _attach_gen(self, req: _Req, rep: _Replica,
                    stream: GenerationStream):
        """Mirror the replica stream into the router stream. On a
        retry, ``skip`` tokens were already delivered — greedy decode
        regenerates the identical prefix, which is skipped instead of
        re-emitted (the caller's stream never stutters)."""
        skip = len(req.sink.tokens)
        seen = [0]

        def on_token(tok):
            seen[0] += 1
            if seen[0] > skip:
                req.sink._emit(tok)

        def on_finish(reason, exc):
            try:
                self._attempt_done(req, rep, reason, exc)
            except Exception as e:  # noqa: BLE001 — never strand the
                self._finish_req(req, exc=e)  # caller on a router bug

        stream._watch(on_token, on_finish)

    def _attach_infer(self, req: _Req, rep: _Replica, fut: Future):
        def on_done(f):
            exc = f.exception()
            try:
                self._attempt_done(req, rep, None, exc,
                                   result=None if exc else f.result())
            except Exception as e:  # noqa: BLE001
                self._finish_req(req, exc=e)

        fut.add_done_callback(on_done)

    def _attempt_done(self, req, rep, reason, exc, result=None):
        with self._lock:
            rep.inflight -= 1
        if exc is None and reason in (None, "length", "eos"):
            self._record_success(rep)
            return self._finish_req(req, reason=reason, result=result)
        if exc is None and reason == "timeout":
            # engine-side deadline: partial output is already out
            self._record_timeout(rep)
            return self._finish_req(req, reason=reason)
        if isinstance(exc, RequestTimeoutError):
            self._record_timeout(rep)
            return self._finish_req(req, exc=exc)
        if exc is None and reason == "closed":
            # the replica shut down mid-stream (rolling restart): the
            # partial generation continues on another replica; an
            # inconclusive half-open trial returns its slot
            self._abort_trial(rep)
            exc = EngineClosedError("replica closed mid-generation")
        else:
            self._record_failure(rep, exc)
        self._maybe_retry(req, rep, exc, reason=reason)

    def _maybe_retry(self, req, rep, exc, reason=None):
        if req.retries_left > 0 and not self._closed:
            req.retries_left -= 1
            req.sink.retries += 1
            telemetry.counter("serving.router.retries")
            tr = getattr(req.sink, "_trace", None)
            if tr is not None:
                tr.event("retry", replica=rep.idx,
                         error=f"{type(exc).__name__}: {exc}"
                         if exc is not None else reason)
            tracing.flight.record(
                "router.retry", replica=rep.idx,
                error=type(exc).__name__ if exc is not None else reason,
                trace_id=None if tr is None else tr.trace_id)
            return self._dispatch(req, frozenset({rep.idx}))
        if reason is not None and self._mode == "generate":
            return self._finish_req(req, reason=reason)
        self._finish_req(req, exc=exc)

    def _finish_req(self, req: _Req, reason=None, exc=None, result=None):
        """Deliver the request's final outcome exactly once and release
        its admission reservation."""
        if not self._release(req):
            return
        if exc is not None:
            telemetry.counter(
                "serving.router.timeouts"
                if isinstance(exc, RequestTimeoutError)
                else "serving.router.errors")
        else:
            telemetry.counter("serving.router.completed")
            if reason == "timeout":
                telemetry.counter("serving.router.timeouts")
        telemetry.hist_since("serving.router.latency", req.t0)
        if self._mode == "generate":
            req.sink._finish(reason=reason, exc=exc)
        else:
            try:
                if exc is not None:
                    req.sink.set_exception(exc)
                else:
                    req.sink.set_result(result)
            except Exception:  # noqa: BLE001 — already resolved
                pass

    # -- rolling rollover ----------------------------------------------
    def load_weights(self, source, strict: bool = True,
                     drain_timeout_s: float = 10.0):
        """Fleet-wide zero-downtime weight rollover, one replica at a
        time: cordon (new traffic prefers the others), wait for the
        replica's queue to drain (bounded by ``drain_timeout_s`` —
        in-flight slots are safe to swap under, per PR 6's per-engine
        contract), swap via the engine's own ``load_weights``, restore.
        No request is dropped fleet-wide; a single-replica fleet keeps
        serving through its cordon (cordoning is a preference, not a
        hard exclusion). Returns the number of replicas swapped.

        ``source`` is a checkpoint path (read ONCE, then installed
        into every replica) or an in-memory ``{name: array}`` mapping."""
        if self._closed:
            raise EngineClosedError("load_weights on a closed Router")
        if isinstance(source, dict):
            new_params = source
        else:
            from .. import checkpoint as _ckpt
            new_params, _meta = _ckpt.read_params(source)
        swapped = 0
        for rep in self._replicas:
            if self._dead(rep):
                continue
            with self._lock:
                rep.cordoned = True
            try:
                deadline = time.monotonic() + drain_timeout_s
                worker = getattr(rep.engine, "_worker", None)
                if worker is None:
                    worker = getattr(rep.engine, "_batcher", None)
                while worker is not None \
                        and worker._queue.qsize() > 0 \
                        and time.monotonic() < deadline:
                    time.sleep(0.005)
                rep.engine.load_weights(new_params, strict=strict)
                swapped += 1
            except EngineClosedError:
                # the replica died/closed between the _dead() check and
                # its swap: skip it and KEEP ROLLING — aborting here
                # would strand the rest of the fleet on the old weights
                # (mixed versions break retry token-identity fleet-wide;
                # one dead replica is already routed around)
                continue
            finally:
                with self._lock:
                    rep.cordoned = False
        telemetry.counter("serving.router.rollovers")
        return swapped

    # -- fleet-wide adapter management ----------------------------------
    def load_adapter(self, name, params, alpha=1.0):
        """Fleet-wide LoRA adapter rollover, one replica at a time —
        the ``load_weights`` rolling pattern on the tenant axis:
        cordon (new traffic prefers the others), install via the
        engine's own zero-retrace ``load_adapter``, restore. No drain
        wait is needed: a NEW adapter touches no in-flight request,
        and a refresh of an existing one has the per-engine rollover
        semantics (in-flight slots continue on the refreshed
        factors). Returns the number of replicas that installed it.
        ``submit(adapter=name)`` requires the name on EVERY live
        replica, so route traffic at it only after this returns. A
        per-replica rejection (e.g. one engine still draining the
        name's previous unload) does NOT abort the roll — the rest of
        the fleet still installs and the first error re-raises at the
        end (aborting mid-roll would strand the fleet heterogeneous
        on every replica AFTER the failed one; re-running converges,
        refresh is idempotent)."""
        if self._closed:
            raise EngineClosedError("load_adapter on a closed Router")
        with self._adapter_roll_lock:
            # the roll lock serializes fleet rolls per name: a
            # concurrent unload roll interleaving per replica could
            # otherwise finish in opposite orders on different
            # replicas and leave the name persistently heterogeneous
            # with both calls reporting success
            with self._lock:
                if name in self._adapter_draining:
                    # the engine-level rule, fleet-shaped: a reload
                    # now would report success and then be silently
                    # evicted when the pending deferred unload drains
                    raise ValueError(
                        f"adapter {name!r} is unloading fleet-wide "
                        f"(pinned by in-flight requests); retry once "
                        f"they finish")
            swapped, first_err = 0, None
            for rep in self._replicas:
                if self._dead(rep):
                    continue
                with self._lock:
                    rep.cordoned = True
                try:
                    rep.engine.load_adapter(name, params, alpha=alpha)
                    swapped += 1
                except EngineClosedError:
                    continue  # keep rolling — the load_weights rule
                except ValueError as e:
                    if first_err is None:
                        first_err = e
                    continue
                finally:
                    with self._lock:
                        rep.cordoned = False
        if first_err is not None:
            raise first_err
        return swapped

    def unload_adapter(self, name):
        """Fleet-wide adapter unload. While ANY router request is
        in flight bound to the name, the whole fleet keeps it loaded
        and the unload DEFERS (returns 0): a cross-replica retry must
        be able to re-bind the adapter on whichever replica catches
        it, so no replica may free its slot while another still
        serves the name — the engine-level pin generalized to the
        fleet. The name stops accepting new submits immediately; the
        last bound request's release runs the rolling per-replica
        unload. With nothing in flight the unload rolls now; returns
        the number of replicas that freed the slot immediately."""
        if self._closed:
            raise EngineClosedError("unload_adapter on a closed Router")
        loaded = any(
            rep.engine.has_adapter(name) for rep in self._replicas
            if not self._dead(rep)
            and getattr(rep.engine, "lora_enabled", False))
        if not loaded:
            raise ValueError(
                f"unknown adapter {name!r}: not loaded on the fleet")
        with self._lock:
            # mark the name draining in BOTH paths before any slot is
            # freed: a submit sitting between _validate_adapter and
            # _admit must hit the draining rejection, not pin a name
            # whose rolling unload is already freeing replicas
            self._adapter_draining.add(name)
            if self._adapter_inflight.get(name, 0) > 0:
                return 0
        return self._unload_adapter_now(name)

    def _unload_adapter_now(self, name):
        """The rolling per-replica unload (the ``load_adapter``
        loop): called with the name already in ``_adapter_draining``
        (set by ``unload_adapter``, or kept by the last bound
        request's release) so no new submit can pin it mid-roll; the
        draining mark clears when the roll finishes. Per replica the
        engine's own deferred-unload semantics still apply."""
        freed = 0
        try:
            with self._adapter_roll_lock:
                with self._lock:
                    if name not in self._adapter_draining:
                        # another roll of this name ran while we
                        # waited on the roll lock (e.g. a retried
                        # inline unload beat the prober's queued
                        # drain) — and a reload may have installed
                        # fresh factors since; rolling now would
                        # silently evict them
                        return 0
                for rep in self._replicas:
                    if self._dead(rep):
                        continue
                    with self._lock:
                        rep.cordoned = True
                    try:
                        if rep.engine.unload_adapter(name):
                            freed += 1
                    except (EngineClosedError, ValueError):
                        # dead-mid-roll, or a replica that never had
                        # the name (crashed and replaced mid-load) —
                        # keep rolling
                        continue
                    finally:
                        with self._lock:
                            rep.cordoned = False
        finally:
            with self._lock:
                self._adapter_draining.discard(name)
                # a queued drain is satisfied by ANY roll of the
                # name: a stale pending entry would later evict a
                # freshly reloaded adapter
                self._adapter_drain_pending.discard(name)
        return freed
