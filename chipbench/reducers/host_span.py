"""Host time of the program's own spans in the traced window, per span.

The program writes its phases into the profiler's trace with
``jax.profiler.TraceAnnotation`` (``mxnet_tpu.tracing.phase``); the loader
keeps them in ``trace.host`` as ``("<thread's line>: <span name>", start_s,
dur_s)`` on the clock of the device operations. This reducer returns

    scale * (sum of the durations of the events matching ``span``
             - sum of those matching ``minus`` that lie inside one of them)
          / number of events matching ``per`` (default: ``span``)

``span``, ``minus`` and ``per`` are regular expressions searched in
``"<line>: <name>"``, so a name is anchored as ``": serve\\.iter$"``
whatever thread wrote it. A ``minus`` event counts only where a ``span``
event covers it: the profiler keeps a span when it *ends* inside the
session, so at the window's edges a child can be there without its parent
(a 100 ms wait taken off a parent that was never added would outweigh fifty
parents' few milliseconds each). Nothing matching ``span`` or ``per``:
nothing returned, never 0; so on a program that writes no such span the
metric is left out of the line.

What the loader (``trace_reduce.load``) keeps, and so what this can see:
host events of 200 us or more, and the first 20,000 of them in the order
of the trace's planes and lines. A span shorter than 200 us is neither
summed nor counted; past the cap whole lines go missing (``PERF.md``
section 6 gives the count a 5 s window holds).
"""
from __future__ import annotations

import bisect
import re


def _matching(host, pattern):
    rx = re.compile(pattern)
    return [(s, d) for name, s, d in host if rx.search(name)]


def reduce(args, facts, trace):
    if trace is None or not trace.host:
        return None
    spans = sorted(_matching(trace.host, args["span"]))
    per = len(_matching(trace.host, args["per"])) if "per" in args \
        else len(spans)
    if not spans or not per:
        return None
    total = sum(d for _, d in spans)
    if "minus" in args:
        starts = [s for s, _ in spans]
        for s, d in _matching(trace.host, args["minus"]):
            i = bisect.bisect_right(starts, s) - 1
            # the latest span that starts no later: nested spans of one
            # name aside, the only one that can cover this event
            if i >= 0 and s + d <= spans[i][0] + spans[i][1]:
                total -= d
    return float(args.get("scale", 1.0)) * total / per
