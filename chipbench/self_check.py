"""``python chipbench/run.py --self-check``: the trace reduction held to the
small recorded trace under ``chipbench/testdata/`` (cut from a chip run of
``gpt2-large.serve.decode-heavy`` by ``trace_reduce.record``), whose busy
share and kernel time were worked out by hand and are kept beside it in
``trace_small.expected.json``; and to a made-up trace small enough to
check in the head. Needs no accelerator and imports nothing of the
program."""
from __future__ import annotations

import gzip
import json
import os

from chipbench import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def made_up():
    """Two programs of 10 ms on a 40 ms window; in each a 4 ms kernel inside
    a 6 ms ``while``, and a 2 ms copy beside them: busy 16 ms (a nest counts
    once), kernel 8 ms, idle 60 %."""
    ops, modules = [], []
    for base in (0.000, 0.020):
        modules.append(("jit_wrapper(1)", "", base, 0.010))
        ops.append(("while.1", "", base, 0.006))
        ops.append(("custom-call.7", "pallas_call _paged_decode_fwd_kernel",
                    base + 0.001, 0.004))
        ops.append(("copy.3", "", base + 0.007, 0.002))
    dev = "/device:TPU:0"
    return trace_reduce.Trace({dev: ops}, {dev: modules},
                              [("python: sync", 0.010, 0.010)], 0.040)


def check(name, got, want, rel=1e-6):
    ok = abs(got - want) <= rel * max(abs(want), 1e-12)
    print(f"self-check {name}: {got!r} (expected {want!r}) "
          f"{'ok' if ok else 'WRONG'}")
    return ok


def main():
    t = made_up()
    ok = [check("made-up busy_s", t.busy_s(), 0.016),
          check("made-up kernel_s",
                t.seconds_matching("paged_decode"), 0.008),
          check("made-up kernel count",
                t.count_matching("paged_decode"), 2),
          check("made-up module_s",
                t.seconds_matching("^jit_wrapper", line="modules"), 0.020),
          check("made-up longest gap", t.idle_gaps(1)[0][1], 0.011)]
    path = os.path.join(HERE, "testdata", "trace_small.json.gz")
    if os.path.exists(path):
        with gzip.open(path, "rt") as f:
            rec = trace_reduce.from_recorded(json.load(f))
        with open(os.path.join(HERE, "testdata",
                               "trace_small.expected.json")) as f:
            want = json.load(f)
        ok.append(check("recorded busy_s", rec.busy_s(), want["busy_s"]))
        for pattern, secs in want["kernel_s"].items():
            ok.append(check(f"recorded kernel_s[{pattern}]",
                            rec.seconds_matching(pattern), secs))
    else:
        print("self-check: no recorded trace under chipbench/testdata")
        ok.append(False)
    print("self-check", "passed" if all(ok) else "FAILED")
    return 0 if all(ok) else 1
