"""``chipbench/reducers/idle_under_span`` on made-up traces that can be
checked in the head, and the four metrics that read the serving worker's
calls of compiled programs. Needs no accelerator and imports nothing of
the program."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import harness, trace_reduce  # noqa: E402
from chipbench.reducers import idle_under_span  # noqa: E402

LINE = "GenerationEngine.worker/140211"
DEV = "/device:TPU:0"
CALLS = {"span": r": serve\.call\.\w+$"}
NAMED = r": serve\.(admit|cow|prefill\.(dispatch|sync)|decode\.(dispatch|sync)" \
    r"|commit|idle|call\.\w+)$"
UNNAMED = {"span": NAMED, "invert": True}
NEW = ("sched.call_ms_per_iter", "sched.decode_call_ms",
       "device.idle_in_call.serve", "device.idle_unattributed.serve")


def trace(ops, host, window_s=0.020):
    return trace_reduce.Trace(
        {DEV: [("fusion", "", s, e - s) for s, e in ops]}, {DEV: []},
        [(f"{LINE}: {name}", s, e - s) for name, s, e in host], window_s)


#: two passes of 10 ms; the device idles 4-6 ms and 10-12 ms (20 % of the
#: 20 ms the passes cover)
OPS = [(0.000, 0.004), (0.006, 0.010), (0.012, 0.020)]
PASSES = [("serve.iter", 0.000, 0.010), ("serve.iter", 0.010, 0.020)]


def serving():
    """Of the 4 ms idle: 1 ms inside the decode call (4-5), 1.5 ms inside
    the chunk's call (10.5-12), 1 ms in the dispatch phases around them
    (5-5.5, 10-10.5) and 0.5 ms in no phase (5.5-6)."""
    return PASSES + [
        ("serve.admit", 0.000, 0.002),
        ("serve.decode.dispatch", 0.002, 0.0055),
        ("serve.call.decode", 0.003, 0.005),
        ("serve.prefill.dispatch", 0.010, 0.013),
        ("serve.call.prefill", 0.0105, 0.0125),
        ("serve.commit", 0.014, 0.016)]


def test_idle_inside_calls():
    assert idle_under_span.reduce(CALLS, {}, trace(OPS, serving())) == \
        pytest.approx(100 * 0.0025 / 0.020)


def test_idle_in_no_phase_with_invert():
    t = trace(OPS, serving())
    unnamed = idle_under_span.reduce(UNNAMED, {}, t)
    assert unnamed == pytest.approx(100 * 0.0005 / 0.020)
    # each idle second counted once: in a call, in a named phase outside
    # any call, or in none
    named = idle_under_span.reduce({"span": NAMED}, {}, t)
    in_call = idle_under_span.reduce(CALLS, {}, t)
    assert in_call + (named - in_call) + unnamed == pytest.approx(20.0)
    assert named - in_call == pytest.approx(100 * 0.0010 / 0.020)


def test_a_worker_line_cut_off_divides_by_the_covered_window():
    """The loader kept the first 20 ms of the worker's line; the device
    worked and idled for 80 ms more. The share is of the 20 ms, not of
    ``window_s``."""
    ops = OPS + [(0.030, 0.040), (0.090, 0.100)]
    t = trace(ops, serving(), window_s=0.100)
    assert idle_under_span.reduce(CALLS, {}, t) == \
        pytest.approx(100 * 0.0025 / 0.020)
    assert idle_under_span.reduce(UNNAMED, {}, t) == \
        pytest.approx(100 * 0.0005 / 0.020)


def test_no_pass_loaded_gives_none():
    host = [h for h in serving() if h[0] != "serve.iter"]
    assert idle_under_span.reduce(CALLS, {}, trace(OPS, host)) is None
    assert idle_under_span.reduce(UNNAMED, {}, trace(OPS, host)) is None
    # nor where the spans themselves are missing (the parent commit)
    assert idle_under_span.reduce(CALLS, {}, trace(OPS, PASSES)) is None
    assert idle_under_span.reduce(CALLS, {}, trace([], serving())) is None
    assert idle_under_span.reduce(CALLS, {}, None) is None


def test_spans_but_no_idle_gives_zero():
    t = trace([(0.000, 0.020)], serving())
    assert idle_under_span.reduce(CALLS, {}, t) == 0.0
    assert idle_under_span.reduce(UNNAMED, {}, t) == 0.0


def test_overlapping_spans_of_one_pattern_count_once():
    """Two calls that overlap, one of them on another line: the 1.5 ms of
    idle under either is counted once, not 2.5 ms."""
    host = PASSES + [("serve.call.decode", 0.003, 0.005),
                     ("serve.call.copy", 0.0035, 0.0055)]
    t = trace(OPS, host)
    t.host.append(("other-thread: serve.call.bind", 0.004, 0.0055 - 0.004))
    assert idle_under_span.reduce(CALLS, {}, t) == \
        pytest.approx(100 * 0.0015 / 0.020)


def test_the_four_metrics_name_files_that_load():
    bench = harness.load_benchmark()
    cells = {"gpt2-medium.serve.prefill-heavy",
             "gpt2-large.serve.prefill-heavy",
             "dots3-note-prev.serve.longctx-mixed"}
    # the block-diffusion cell reads all but the decode call's time: its
    # worker calls a denoising step in place of a decode tick
    blockdiff = "sdar-30b-a3b-chat.serve.blockdiff-chat"
    for name in NEW:
        entry, = [m for m in bench["per_layer"] if m["name"] == name]
        assert set(entry["workloads"]) == (
            cells if name == "sched.decode_call_ms" else cells | {blockdiff})
        assert entry["moves"] == "serve_tokens_per_s"
        spec = harness.load_json("layer_metrics", name + ".json")
        reducer = harness.by_name("reducers", spec["reducer"])
        # a trace that holds passes and no phase leaves each out
        assert reducer.reduce(spec["args"], {}, trace(OPS, PASSES)) is None
        assert reducer.reduce(spec["args"], {}, trace(OPS, [])) is None
    spec = harness.load_json("layer_metrics",
                             "device.idle_unattributed.serve.json")
    assert spec["args"]["span"] == NAMED
    assert idle_under_span.reduce(spec["args"], {},
                                  trace(OPS, serving())) == \
        pytest.approx(100 * 0.0005 / 0.020)
