"""From a profiler trace to the few things the per-layer metrics read.

``load(dir, window_s)`` reads the ``.xplane.pb`` that ``jax.profiler``
wrote (with ``jax.profiler.ProfileData``, nothing but JAX) and returns a
``Trace``. ``from_recorded(obj)`` builds the same object from the small
JSON form that ``record()`` writes: ``chipbench/testdata/`` keeps one,
cut from a chip run, and ``python chipbench/run.py --self-check`` holds
the reduction to the busy share and kernel time known for it.

What a TPU trace looks like (read by hand, PR 23, jax 0.9.0 on a v5e):
one plane per chip named ``/device:TPU:<n>``; on it the line ``XLA
Modules`` has one event per executed program, named
``jit_<function>(<fingerprint>)``, and the line ``XLA Ops`` one event per
HLO operation inside it, whose *name is the whole HLO instruction*
(``%wrapper.48 = bf16[8,20,1,64]{...} custom-call(s32[8]{...} %copy-done.368,
... custom_call_target="tpu_custom_call" ...``) and whose statistics carry
no source name (``kernel_metadata={}``: the program passes no ``name=`` to
its ``pallas_call``s). So an event here gets a short *name*, ``<opcode>
<instruction name without its number> <result shape>`` (``custom-call
wrapper bf16[8,20,1,64]``), under which the 36 layers' copies of one
operation add up, and keeps the instruction as its *text* where it is a
custom call: a Pallas kernel can then only be told by the signature of its
operands. The ``Async XLA Ops`` line (copy-start/slice-start windows that
overlap the operations) is not read. Operations can overlap, so busy time
is the union of the intervals, never their sum.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: ``%name.12 = shape{layout} opcode(`` at the head of an HLO instruction
_INSTRUCTION = re.compile(
    r"^%([\w\-]+?)(?:\.\d+)* = \(?(\w+\[[\d,]*\]).*? ([\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")
TEXT_LIMIT = 1200
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union_seconds(intervals):
    """Total length of the union of ``(start, duration)`` intervals."""
    busy, end = 0.0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            busy += d
            end = e
        elif e > end:
            busy += e - end
            end = e
    return busy


def gaps(intervals, t0, t1):
    """The idle gaps ``(start, duration)`` of the union within [t0, t1]."""
    out, end = [], t0
    for s, d in sorted(intervals):
        if s > end:
            out.append((end, min(s, t1) - end))
        end = max(end, s + d)
        if end >= t1:
            break
    if end < t1:
        out.append((end, t1 - end))
    return [g for g in out if g[1] > 0]


class Trace:
    """``ops[device]``, ``modules[device]``: lists of ``(name, text,
    start_s, dur_s)``; ``host``: ``(name, start_s, dur_s)`` of host
    threads; ``window_s``: the traced window's length by the host clock."""

    def __init__(self, ops, modules, host, window_s):
        self.ops, self.modules, self.host = ops, modules, host
        self.window_s = float(window_s)

    @property
    def devices(self):
        return sorted(self.ops)

    def busy_seconds(self, device):
        return union_seconds((s, d) for _, _, s, d in self.ops[device])

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.ops:
            return None
        return sum(self.busy_seconds(d) for d in self.ops) / len(self.ops)

    def matching(self, pattern, line="ops"):
        """Events whose name or instruction text matches ``pattern``, per
        device."""
        rx = re.compile(pattern)
        return {dev: [e for e in evs if rx.search(e[0]) or rx.search(e[1])]
                for dev, evs in getattr(self, line).items()}

    def seconds_matching(self, pattern, line="ops"):
        """Device seconds of the matching events, averaged over the chips
        that ran any; ``None`` where none did."""
        per = [sum(e[3] for e in evs)
               for evs in self.matching(pattern, line).values() if evs]
        return sum(per) / len(per) if per else None

    def count_matching(self, pattern, line="ops"):
        per = [len(evs) for evs in self.matching(pattern, line).values()
               if evs]
        return sum(per) / len(per) if per else 0

    # -- the breakdown the driver copies into the ledger -----------------
    def top_ops(self, n=10):
        """The device operations of the first chip that took most time,
        summed under their short names (all layers' copies of one
        operation together)."""
        if not self.ops:
            return []
        total = {}
        for name, _, _, d in self.ops[self.devices[0]]:
            total[name] = total.get(name, 0.0) + d
        return [[k, v] for k, v in sorted(
            total.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n=10):
        """The longest idle gaps of the first chip, each named by the host
        event that covers most of it."""
        if not self.ops:
            return []
        dev = self.devices[0]
        evs = self.ops[dev]
        if not evs:
            return []
        t0 = min(e[2] for e in evs)
        t1 = max(e[2] + e[3] for e in evs)
        longest = sorted(gaps(((s, d) for _, _, s, d in evs), t0, t1),
                         key=lambda g: -g[1])[:n]
        out = []
        for s, d in longest:
            best, cover = "host: nothing traced", 0.0
            for name, hs, hd in self.host:
                ov = min(s + d, hs + hd) - max(s, hs)
                # the narrowest host event that still covers the gap
                # says most about what the host was doing
                if ov > 0.5 * d and (cover == 0.0 or hd < cover):
                    best, cover = name, hd
            out.append([best, d])
        return out


def short(instruction):
    """``(name, text)`` of an ``XLA Ops`` event (see the module's text)."""
    m = _INSTRUCTION.match(_LAYOUT.sub("", instruction[:600]))
    if not m:
        return instruction[:80], ""
    name = f"{m.group(3)} {m.group(1)} {m.group(2)}"
    text = instruction[:TEXT_LIMIT] if m.group(3) == "custom-call" else ""
    return name, text


def _profile(trace_dir):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise SystemExit(f"chipbench: the profiler wrote no trace under "
                         f"{trace_dir}")
    return ProfileData.from_file(paths[-1])


def load(trace_dir, window_s, host_limit=20000):
    data = _profile(trace_dir)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        short(ev.name) + (ev.start_ns * 1e-9,
                                          ev.duration_ns * 1e-9)
                        for ev in line.events]
                else:
                    modules[plane.name] = [
                        (ev.name, "", ev.start_ns * 1e-9,
                         ev.duration_ns * 1e-9) for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns >= 200_000 and len(host) < host_limit:
                        host.append((f"{line.name}: {ev.name}",
                                     ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9))
    for dev in ops:
        modules.setdefault(dev, [])
    return Trace(ops, modules, host, window_s)


def describe(trace_dir, out_path, top=60):
    """Everything needed to read a trace by hand: planes, lines, event
    counts, and the most expensive names of each line with one event's
    statistics. Written as JSON to ``out_path``."""
    data = _profile(trace_dir)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            total, sample, count = {}, {}, 0
            for ev in line.events:
                count += 1
                total[ev.name] = total.get(ev.name, 0) + ev.duration_ns
                if ev.name not in sample:
                    try:
                        sample[ev.name] = {k: str(v)[:300]
                                           for k, v in ev.stats}
                    except Exception as e:
                        sample[ev.name] = {"unreadable": repr(e)}
            names = sorted(total, key=lambda k: -total[k])[:top]
            lines.append({"line": line.name, "events": count,
                          "top": [[n, total[n] * 1e-9, sample[n]]
                                  for n in names]})
        planes.append({"plane": plane.name, "lines": lines})
    with open(out_path, "w") as f:
        json.dump(planes, f, indent=1)


def record(trace, path, seconds=0.25):
    """Cut the first ``seconds`` of ``trace`` into the small JSON form,
    gzipped (how ``testdata/trace_small.json.gz`` was made)."""
    t0 = min(e[2] for evs in trace.ops.values() for e in evs)
    t1 = t0 + seconds
    obj = {"window_s": seconds, "host": [
        [n, s - t0, d] for n, s, d in trace.host
        if s >= t0 and s + d <= t1][:100]}
    for key in ("ops", "modules"):
        obj[key] = {dev: [[n, x[:700], s - t0, d] for n, x, s, d in evs
                          if s >= t0 and s + d <= t1]
                    for dev, evs in getattr(trace, key).items()}
    with gzip.open(path, "wt") as f:
        json.dump(obj, f, separators=(",", ":"))


def from_recorded(obj):
    return Trace({d: [tuple(e) for e in evs]
                  for d, evs in obj["ops"].items()},
                 {d: [tuple(e) for e in evs]
                  for d, evs in obj["modules"].items()},
                 [tuple(e) for e in obj["host"]], obj["window_s"])
