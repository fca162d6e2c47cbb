"""``reducers/host_span`` on made-up traces that can be checked in the
head. Needs no accelerator and imports nothing of the program."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, trace_reduce  # noqa: E402
from chipbench.reducers import host_span  # noqa: E402

LINE = "GenerationEngine.worker/140211"
ITER = {"span": r": serve\.iter$",
        "minus": r": serve\.(prefill|decode)\.sync$", "scale": 1000}


def trace(host):
    return trace_reduce.Trace({}, {}, host, 1.0)


def serving():
    """Three passes of 100, 120 and 50 ms; the first two wait 90 and 95 ms
    for the device, the second also 10 ms for a prefill's logits; the third
    only admits. Host time 10 + 15 + 50 = 75 ms over three passes."""
    return [(f"{LINE}: serve.iter", 0.000, 0.100),
            (f"{LINE}: serve.admit", 0.000, 0.001),
            (f"{LINE}: serve.decode.dispatch", 0.001, 0.004),
            (f"{LINE}: serve.decode.sync", 0.005, 0.090),
            (f"{LINE}: serve.commit", 0.095, 0.004),
            (f"{LINE}: serve.iter", 0.100, 0.120),
            (f"{LINE}: serve.prefill.sync", 0.105, 0.010),
            (f"{LINE}: serve.decode.sync", 0.120, 0.095),
            (f"{LINE}: serve.iter", 0.220, 0.050),
            # other threads, other names: a Python frame that mentions the
            # span's name, and a longer name with the span's as its head
            ("python3: $generate.py:401 serve.iter wrapper", 0.0, 0.5),
            ("caller-3: serve.iterate", 0.0, 0.5)]


def test_nested_spans_and_a_minus():
    assert host_span.reduce(ITER, {}, trace(serving())) == \
        pytest.approx(75.0 / 3)


def test_without_minus_and_per_another_span():
    t = trace(serving())
    assert host_span.reduce({"span": r": serve\.iter$", "scale": 1000},
                            {}, t) == pytest.approx(270.0 / 3)
    # device waits per pass that decoded
    assert host_span.reduce(
        {"span": r": serve\.(prefill|decode)\.sync$",
         "per": r": serve\.decode\.dispatch$"}, {}, t) == \
        pytest.approx(0.195)


def test_a_child_without_its_parent_is_not_taken_off():
    """The session began inside a pass and ended inside another: their
    waits are in the trace, the passes are not."""
    host = [(f"{LINE}: serve.decode.sync", 0.000, 0.080),
            (f"{LINE}: serve.iter", 0.100, 0.100),
            (f"{LINE}: serve.decode.sync", 0.105, 0.090),
            (f"{LINE}: serve.decode.sync", 0.205, 0.090)]
    assert host_span.reduce(ITER, {}, trace(host)) == pytest.approx(10.0)


def test_nothing_matching_gives_none():
    t = trace(serving())
    assert host_span.reduce({"span": r": train\.step$"}, {}, t) is None
    assert host_span.reduce({"span": r": serve\.iter$",
                             "per": r": train\.step$"}, {}, t) is None
    assert host_span.reduce(ITER, {}, trace([])) is None
    assert host_span.reduce(ITER, {}, None) is None


def test_the_new_entries_name_files_that_load():
    bench = harness.load_benchmark()
    for name in ("model.decode_device_ms", "model.chunk_device_ms",
                 "sched.host_ms_per_iter", "train.host_call_ms",
                 "train.host_enqueue_ms"):
        assert [m for m in bench["per_layer"] if m["name"] == name]
        spec = harness.load_json("layer_metrics", name + ".json")
        reducer = harness.by_name("reducers", spec["reducer"])
        # on a program that names nothing (the parent commit) each finds
        # nothing to read and says so
        assert reducer.reduce(spec["args"], {}, trace([])) is None
