"""What every generator shares: finding files by name, the device and its
peaks, the compile counter, the traced window, and small statistics."""
from __future__ import annotations

import importlib
import json
import os
import shutil
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    path = os.path.join(HERE, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SystemExit(f"chipbench: no file {os.path.relpath(path, ROOT)}")


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def by_name(package, name):
    """``chipbench/<package>/<name>.py``, found by name (``package`` may be
    dotted: ``families.<family>``)."""
    target = f"chipbench.{package}.{name}"
    try:
        return importlib.import_module(target)
    except ModuleNotFoundError as e:
        # the module itself or its package is missing, not one it imports
        if not (target + ".").startswith((e.name or "?") + "."):
            raise
        raise SystemExit(f"chipbench: no {package.replace('.', '/')}/"
                         f"{name}.py")


def family(named, module):
    """``chipbench/families/<family>/<module>.py`` of the family that a
    configuration, or a window's facts, name under ``"family"``: one of
    ``weights``, ``reference``, ``costs``, ``program`` (which alone imports
    the system under test). None named is an error, never a default."""
    name = named.get("family")
    if not name:
        raise SystemExit('chipbench: no "family" named: a configuration '
                         'says which chipbench/families/<name>/ it is')
    return by_name(f"families.{name}", module)


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def device_info(chips, rehearse):
    """The device as JAX reports it. No accelerator, or fewer chips than
    the cell asks for, ends the run without a result (``--rehearse``
    excepted, whose output names ``cpu``)."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if not rehearse:
        if d0.platform != "tpu":
            raise SystemExit(f"chipbench: no TPU: jax runs on "
                             f"{d0.platform!r} (--rehearse is the CPU run)")
        if len(devs) < chips:
            raise SystemExit(f"chipbench: the cell asks for {chips} "
                             f"chip(s), jax sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips if not rehearse else len(devs)}


def peaks_for(device_kind):
    """The chip's published peaks. A device that is not in the table is an
    error, never a default."""
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"chipbench: no peaks for device_kind "
                         f"{device_kind!r} in chipbench/peaks.json")
    return table[device_kind]


def least_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which peak bounds it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "hbm")


def memory_peak_bytes(chips):
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts XLA backend compiles through ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class TracedWindow:
    """The profiler over the first ``seconds`` of the measured window, in a
    directory of its own under ``TMPDIR`` that is removed once the trace
    is reduced. ``start`` and ``stop`` return the host clock, so that the
    counters read beside them bound the same interval."""

    def __init__(self, keep_dir=None):
        self.dir, self.keep_dir = None, keep_dir
        self.t0 = self.t1 = None

    def start(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self.dir)
        self.t0 = time.perf_counter()
        return self.t0

    def stop(self):
        import jax
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        return self.t1

    def reduce(self):
        from chipbench import trace_reduce
        try:
            if self.keep_dir:
                os.makedirs(self.keep_dir, exist_ok=True)
                trace_reduce.describe(self.dir, os.path.join(
                    self.keep_dir, "described.json"))
            return trace_reduce.load(self.dir, self.t1 - self.t0)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def percentile(values, q):
    """The q-th percentile (0-100) by linear interpolation."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


class Check:
    """One number compared, beside its limit: at most ``limit``, or with
    ``at_least`` no less than it."""

    def __init__(self, name, value, limit, at_least=False):
        self.name, self.value, self.limit = name, float(value), float(limit)
        self.at_least = at_least

    @property
    def ok(self):                            # NaN compares false
        return self.value >= self.limit if self.at_least \
            else self.value <= self.limit

    def as_json(self):
        return {"value": self.value, "limit": self.limit,
                "holds": "at_least" if self.at_least else "at_most",
                "ok": self.ok}
