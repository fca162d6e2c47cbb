#!/usr/bin/env python3
"""chipbench/run.py — the one command of the benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name: the cell in
``BENCHMARK.json`` names a configuration (``chipbench/configs/<config>.json``)
and a traffic mix (``chipbench/traffic/<traffic>.json``); the mix names its
``kind``, and ``chipbench/generators/<kind>.py`` runs it; each per-layer
metric of ``BENCHMARK.json`` has ``chipbench/layer_metrics/<metric>.json``,
which names a reducer ``chipbench/reducers/<reducer>.py`` and its
arguments; the limits of ``correct`` are ``chipbench/limits/<cell>.json``.
See ``chipbench/README.md``.

The last line of standard output is the result. A run that finds no TPU
exits non-zero and prints none, unless ``--rehearse`` (the same code at
the toy size of ``chipbench/configs/rehearsal.json`` on the CPU, whose
result names ``cpu`` and carries counts only). ``--self-check`` holds the
trace reduction to the recorded trace under ``chipbench/testdata/``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402


class Context:
    """What a generator is given: the cell's data and the run's arguments."""

    def __init__(self, cell, config, mix, limits, args, device, peaks,
                 compiles):
        self.cell, self.config, self.mix, self.limits = (
            cell, config, mix, limits)
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.chips = int(cell["chips"]) if not args.rehearse \
            else device["count"]
        self.device, self.peaks, self.compiles = device, peaks, compiles
        self.keep_trace, self.control = args.keep_trace, args.control
        self.notes = {}
        self._setup_s = None

    def mark_setup_done(self):
        """Set-up ends here: loading, warming up and compiling, from the
        start of the process."""
        self._setup_s = time.perf_counter() - T_START
        return self._setup_s

    def note(self, key, value):
        self.notes[key] = value

    def part(self, name):
        """Seconds since the start of the process at the end of a part of
        set-up: ``notes["setup_parts_s"]`` says where set-up's time goes."""
        self.notes.setdefault("setup_parts_s", {})[name] = \
            time.perf_counter() - T_START


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU run at toy size: counts only, names cpu")
    ap.add_argument("--self-check", action="store_true",
                    help="check the trace reduction on the recorded trace")
    ap.add_argument("--control", action="store_true",
                    help="also read the control (the reference in int8, "
                         "and the planted faults) and note its readings: "
                         "how the limits of chipbench/limits/ were set")
    ap.add_argument("--keep-trace", default=None,
                    help="write a description and a recorded cut of the "
                         "trace into this directory (for reading by hand)")
    return ap.parse_args(argv)


def reduce_layers(bench, cell, outcome, e2e_names):
    """Each per-layer metric of the cell through its own reader. A reader
    that finds nothing to read returns ``None`` and the metric is left out
    of the line."""
    out = {}
    for m in bench["per_layer"]:
        if not harness.applies(m, cell["name"]) \
                or m["moves"] not in e2e_names:
            continue
        spec = harness.load_json("layer_metrics", m["name"] + ".json")
        reducer = harness.by_name("reducers", spec["reducer"])
        value = reducer.reduce(spec.get("args", {}), outcome["facts"],
                               outcome["trace"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None):
    args = parse(argv)
    if args.self_check:
        from chipbench import self_check
        return self_check.main()
    bench = harness.load_benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"chipbench: no workload {args.workload!r} in "
                         f"BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[args.workload]
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    config = harness.load_json(
        "configs", ("rehearsal" if args.rehearse else cell["config"])
        + ".json")
    mix = harness.load_json("traffic", cell["traffic"] + ".json")
    limits = harness.load_json("limits", cell["name"] + ".json")
    if args.rehearse:
        mix = dict(mix, **mix.get("rehearsal", {}))
        limits = dict(limits["limits"], **limits.get("rehearsal", {}))
    else:
        limits = limits["limits"]

    from chipbench import program
    device = harness.device_info(int(cell["chips"]), args.rehearse)
    peaks = None if args.rehearse else harness.peaks_for(device["kind"])
    cache_dir = program.configure_compile_cache()
    ctx = Context(cell, config, mix, limits, args, device, peaks,
                  harness.CompileCounter())
    ctx.note("compile_cache_dir", cache_dir)
    ctx.part("imports_and_device")

    generator = harness.by_name("generators", mix["kind"])
    outcome = generator.run(ctx)

    e2e_defs = [m for m in bench["end_to_end"]
                if harness.applies(m, cell["name"])]
    e2e_names = {m["name"] for m in e2e_defs}
    trace = outcome["trace"]
    if args.keep_trace and trace is not None and trace.ops:
        from chipbench import trace_reduce
        os.makedirs(args.keep_trace, exist_ok=True)
        trace_reduce.record(trace, os.path.join(
            args.keep_trace, f"{cell['name']}.recorded.json.gz"))
    if args.trace:
        metrics = reduce_layers(bench, cell, outcome, e2e_names)
    else:
        metrics = {m["name"]: {"value": outcome["e2e"][m["name"]],
                               "unit": m["unit"]} for m in e2e_defs}
    if args.rehearse:
        # a CPU run gives counts, never a time, a rate or a share
        metrics = {k: v for k, v in metrics.items()
                   if v["unit"] in ("1", "count", "1/token")}
        ctx.note("rehearsal", "cpu: counts only")
    device = dict(device, memory_peak_bytes=outcome["memory_peak_bytes"])
    result = {"correct": all(c.ok for c in outcome["checks"]),
              "attempted": outcome["attempted"],
              "failed": outcome["failed"], "metrics": metrics,
              "device": device}
    if args.trace and trace is not None and trace.ops:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(10),
                               "idle_gaps": trace.idle_gaps(10)}
    result["notes"] = ctx.notes
    result["checks"] = {c.name: c.as_json() for c in outcome["checks"]}
    if any(v["value"] is None for v in metrics.values()):
        raise SystemExit(f"chipbench: a metric has no value: {metrics}")
    sys.stdout.flush()
    for c in outcome["checks"]:
        print(f"chipbench check {c.name}: value {c.value!r} "
              f"{'>=' if c.at_least else '<='} limit {c.limit!r}: "
              f"{'ok' if c.ok else 'NOT CORRECT'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
