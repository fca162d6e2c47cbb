"""Profiler (parity: python/mxnet/profiler.py over src/profiler/).

The reference emits chrome://tracing JSON from its engine hooks. On TPU
the equivalent timeline comes from the XLA/PJRT profiler (Xprof): we
wrap jax.profiler — traces are written as TensorBoard/Xprof protobufs
AND a chrome-trace .json.gz (viewable at chrome://tracing or Perfetto),
which covers the reference's `profile_all` surface. Python-side scopes
go through tracing.phase, so custom Task/Frame markers land in the
same timeline as the program's own phases (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import os
import threading

import jax

from . import telemetry, tracing

_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": True,
    "profile_api": True,
    "aggregate_stats": False,
}
# One trace session spans start()..dump(): pause()/resume() keep the
# SAME logdir (the reference keeps one trace file per session); a new
# dir is derived only when no session is open.
_state = {"running": False, "dir": None, "paused": False}


def set_config(**kwargs):
    """Parity: mx.profiler.set_config (filename→output directory stem)."""
    _config.update(kwargs)


def profiler_set_config(mode="symbolic", filename="profile.json"):
    set_config(filename=filename)


def set_state(state="stop", profile_process="worker"):
    if state == "run":
        start()
    else:
        stop()


def start(profile_process="worker"):
    if _state["running"]:
        return
    if _state["paused"] and _state["dir"]:
        logdir = _state["dir"]  # resuming: stay in this session's dir
    else:
        logdir = os.path.splitext(_config["filename"])[0] + "_xprof"
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    _state["running"] = True
    _state["paused"] = False
    _state["dir"] = logdir


def stop(profile_process="worker"):
    _state["paused"] = False
    if not _state["running"]:
        return
    jax.profiler.stop_trace()
    _state["running"] = False


def dump(finished=True, profile_process="worker"):
    stop()


def dumps(reset=False, format="table", sort_by="total", ascending=False,
          aggregate_stats=None):
    """Aggregate-stats report (parity: mx.profiler.dumps).

    With ``aggregate_stats=True`` (or set_config(aggregate_stats=True))
    renders the telemetry registry — every counter/gauge/duration the
    instrumented hot paths recorded — as the reference's aggregate
    table (``format="table"``) or as JSON (``format="json"``), ordered
    by ``sort_by`` in {"total","count","min","max","avg","name"}.
    ``reset=True`` clears the registry after rendering. Without
    aggregate stats, returns the Xprof trace location (the timeline
    lives in TensorBoard/Perfetto, not in a string).

    When per-request tracing has produced finished traces
    (``MXTPU_TRACING=1`` / ``submit(trace=True)``), the report grows a
    spans section: the JSON document gains a ``"spans"`` key holding
    ``tracing.recent_traces()``, the table gains a "Recent request
    traces" listing.
    """
    if aggregate_stats is None:
        aggregate_stats = _config.get("aggregate_stats", False)
    if not aggregate_stats:
        return f"profiler traces under {_state['dir']}" \
            if _state["dir"] else ""
    out = telemetry.render(format=format, sort_by=sort_by,
                           ascending=ascending, trace_dir=_state["dir"],
                           reset_after=reset)
    traces = tracing.recent_traces()
    if not traces:
        return out
    if format == "json":
        import json as _json
        doc = _json.loads(out)
        doc["spans"] = traces
        return _json.dumps(doc, indent=2)
    lines = [out, "", "Recent request traces", "====================="]
    for t in traces:
        dropped = f", {t['dropped']} dropped" if t["dropped"] else ""
        lines.append(f"{t['trace_id']}  ({len(t['spans'])} spans"
                     f"{dropped})")
        for s in t["spans"]:
            attrs = s.get("attrs") or {}
            a = " ".join(f"{k}={v}" for k, v in attrs.items())
            lines.append(f"  {s['t0']:10.3f}ms  {s['dur']:9.3f}ms  "
                         f"{s['name']}{'  ' + a if a else ''}")
    return "\n".join(lines)


def pause(profile_process="worker"):
    """Suspend tracing without closing the session (parity:
    profiler.pause): resume() continues into the SAME logdir."""
    if not _state["running"]:
        return
    jax.profiler.stop_trace()
    _state["running"] = False
    _state["paused"] = True


def resume(profile_process="worker"):
    start()  # start() reuses the paused session's logdir


class Task:
    """Named scope (parity: mx.profiler.Task)."""

    def __init__(self, domain=None, name="task"):
        self.name = name
        self._ann = None

    def start(self):
        self._ann = tracing.phase(self.name)
        self._ann.__enter__()

    def stop(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


class Frame(Task):
    pass


class Event(Task):
    pass


class Counter:
    """User-visible profiler counter (parity: mx.profiler.Counter).

    Mutations are serialized under a per-counter lock (the reference's
    counters live in the C++ profiler and are atomic; the old shim
    mutated ``self.value`` unlocked). Every update mirrors into a
    telemetry gauge ``counter.<name>`` so it appears in
    ``dumps(aggregate_stats=True)``.
    """

    def __init__(self, domain=None, name="counter", value=None):
        self.name = name
        self._lock = threading.Lock()
        self._value = value or 0
        telemetry.gauge(self._gauge_name, self._value)

    @property
    def _gauge_name(self):
        return f"counter.{self.name}"

    @property
    def value(self):
        with self._lock:
            return self._value

    @value.setter
    def value(self, v):
        self.set_value(v)

    def set_value(self, value):
        # gauge publish stays inside the lock: outside it, a slower
        # thread could overwrite the registry with a stale value
        with self._lock:
            self._value = value
            telemetry.gauge(self._gauge_name, value)

    def increment(self, delta=1):
        with self._lock:
            self._value += delta
            telemetry.gauge(self._gauge_name, self._value)

    def decrement(self, delta=1):
        self.increment(-delta)

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self


class Domain:
    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_event(self, name):
        return Event(self, name)


class Scope(Task):
    """Annotation scope also used by memory profiling in the reference."""


def dump_memory_profile(path=None):
    """Write a device-memory profile (parity: the reference's storage
    profiler, src/profiler/storage_profiler.h:223 — per-allocation
    tracking dumped for offline analysis). On PJRT this is the
    pprof-format device memory profile (live buffers attributed to the
    HLO that allocated them); inspect with `pprof` or any pprof
    viewer. Returns the path written."""
    data = jax.profiler.device_memory_profile()
    if path is None:
        base = os.path.splitext(_config["filename"])[0]
        path = base + "_memory.pprof"
    with open(path, "wb") as f:
        f.write(data)
    return path


# -- reference-spelling shims (profiler.py:30,112,146,477,507) --------
import contextlib as _contextlib
import threading as _threading

_scope_tls = _threading.local()


class Marker:
    """Instant-in-time marker within a Domain (parity:
    profiler.py:477). Recorded as a zero-duration trace event."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        with tracing.phase(
                f"{getattr(self.domain, 'name', 'domain')}:"
                f"{self.name}@{scope}"):
            pass


@_contextlib.contextmanager
def scope(name="<unk>:", append_mode=True):
    """Profiler scope for memory attribution (parity:
    profiler.py:507); nests by prepending the enclosing scope."""
    name = name if name.endswith(":") else name + ":"
    prev = getattr(_scope_tls, "scope", "<unk>:")
    if append_mode and prev != "<unk>:":
        name = prev + name
    _scope_tls.scope = name
    try:
        with tracing.phase(name):
            yield
    finally:
        _scope_tls.scope = prev


def current_scope():
    return getattr(_scope_tls, "scope", "<unk>:")


def dump_profile():
    """Deprecated reference spelling of dump() (profiler.py:146)."""
    import warnings
    warnings.warn("profiler.dump_profile(...) is deprecated. "
                  "Please use profiler.dump(...) instead")
    dump()


def set_kvstore_handle(handle):  # noqa: ARG001 - parity no-op
    """Parity shim (profiler.py:30): the reference wires the kvstore
    server's profiler through a C handle; our PS profiles in-process,
    so there is nothing to hand over."""
    return None


def profiler_set_state(state="stop"):
    """Deprecated reference spelling of set_state (profiler.py:112)."""
    import warnings
    warnings.warn("profiler.profiler_set_state(...) is deprecated. "
                  "Please use profiler.set_state(...) instead")
    set_state(state)
