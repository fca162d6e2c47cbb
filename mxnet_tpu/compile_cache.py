"""Persistent AOT compilation cache — cold-start compiles survive
process restarts.

JAX ships a persistent compilation cache (executables keyed by HLO
fingerprint, written to a directory); wiring it up means the second
process launch replays every XLA compile from disk instead of
re-running the compiler. The directory is placed from OUTSIDE:

- ``JAX_COMPILATION_CACHE_DIR`` (JAX's own variable) — when set, the
  cache lives there and nothing in this repo points it elsewhere;
  `configure()` at package import only adopts it.
- unset — the library runs without a persistent cache; the repo's
  entry scripts (``chip_smoke.py``, ``chipbench/run.py``) pass
  ``CHECKOUT_DIR``, a fixed git-ignored directory inside the checkout
  (the path is part of the cache key, so it never moves).

Every compile persists (min compile time 0, no size floor) so the
hit/miss classification below is sound within one process; processes
sharing the directory can skew each other's counts.

Telemetry: every instrumented compile site (`CachedOp`,
`TrainStep.__call__`/`warmup`) wraps its first dispatch in
`measure()`, which classifies the compile as a persistent-cache *hit*
(no new cache entry appeared → XLA replayed from disk) or *miss* (a
new entry was written) and records the wall time:

- ``compile_cache.hit`` / ``compile_cache.miss`` counters
- ``compile_cache.compile`` duration (ms)
- ``compile_cache.entries`` gauge (files in the cache dir)
"""
from __future__ import annotations

import contextlib
import os

from . import telemetry

__all__ = ["CHECKOUT_DIR", "configure", "enabled", "cache_dir",
           "entry_count", "measure"]

#: the entry scripts' cache directory when the environment names none
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache")

_dir: str | None = None


def configure(path: str | None = None) -> str | None:
    """Turn the persistent compilation cache on. The directory is
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (it
    always wins — one resolution, from outside), else ``path``; with
    neither this is a no-op returning None. Returns the active dir."""
    global _dir
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or path
    if not path:
        return None
    import jax
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _dir = path
    telemetry.gauge("compile_cache.entries", entry_count())
    return _dir


def enabled() -> bool:
    return _dir is not None


def cache_dir() -> str | None:
    return _dir


def entry_count() -> int:
    """Number of persisted executables in the cache dir."""
    if _dir is None:
        return 0
    try:
        return sum(1 for e in os.scandir(_dir) if e.is_file())
    except OSError:
        return 0


@contextlib.contextmanager
def measure(site: str = "compile"):
    """Wrap one compile; classify persistent-cache hit/miss by whether
    the cache directory grew, and record the wall time. Free (yields
    immediately, no fs access) when the cache is disabled."""
    if _dir is None or not telemetry.enabled():
        yield
        return
    before = entry_count()
    t0 = telemetry.clock()
    try:
        yield
    finally:
        telemetry.duration_since("compile_cache.compile", t0)
        after = entry_count()
        telemetry.gauge("compile_cache.entries", after)
        telemetry.counter("compile_cache.miss" if after > before
                          else "compile_cache.hit")
