"""Tier-1 guards on the observability fast paths: the disabled
telemetry AND tracing paths must record NOTHING (no entries, no span
objects allocated), the enabled pure-counter / span paths must stay in
the single-digit-microsecond range (regressions here tax every engine
op), and arming a trace must not compile anything beyond the untraced
baseline (spans are host-side only)."""
import time

import numpy as onp
import pytest

from mxnet_tpu import telemetry, tracing


@pytest.fixture(autouse=True)
def _restore_state():
    prev = telemetry.enabled()
    prev_tr = tracing.enabled()
    telemetry.reset()
    yield
    telemetry.set_enabled(prev)
    tracing.set_enabled(prev_tr)
    tracing.clear_recent()
    telemetry.reset()


def test_disabled_path_records_nothing():
    telemetry.set_enabled(False)
    telemetry.counter("x")
    telemetry.gauge("g", 1.0, peak=5.0)
    telemetry.value("v", 2.0)
    telemetry.duration_since("d", telemetry.clock())
    telemetry.hist("h", 1.5)
    telemetry.hist_since("h2", telemetry.clock())
    snap = telemetry.snapshot()
    assert snap["version"] == telemetry.SNAPSHOT_VERSION
    assert tuple(snap["hist_bounds"]) == telemetry.hist_bounds()
    assert snap["durations"] == {} and snap["counters"] == {}
    assert snap["gauges"] == {} and snap["histograms"] == {}
    assert telemetry.names() == []
    # clock() short-circuits too: no syscall, sentinel 0.0
    assert telemetry.clock() == 0.0


def test_disabled_clock_pairs_safely_across_toggle():
    """A t0 taken while disabled must not produce a bogus sample if
    recording is enabled before the matching duration_since."""
    telemetry.set_enabled(False)
    t0 = telemetry.clock()
    telemetry.set_enabled(True)
    telemetry.duration_since("d", t0)
    assert "d" not in telemetry.snapshot()["durations"]


def test_enabled_counter_overhead_under_5us():
    telemetry.set_enabled(True)
    n = 20000
    telemetry.counter("warm")  # dict entry + lock warm-up
    t0 = time.perf_counter()
    for _ in range(n):
        telemetry.counter("warm")
    per_event = (time.perf_counter() - t0) / n
    assert telemetry.snapshot()["counters"]["warm"] == n + 1
    # budget: ~5µs/event (a lock + dict add is ~0.5µs; 5µs leaves CI
    # headroom without masking an accidental O(n) or I/O regression)
    assert per_event < 5e-6, f"counter path took {per_event * 1e6:.2f}µs"


def test_enabled_disabled_roundtrip_keeps_data():
    telemetry.set_enabled(True)
    telemetry.counter("kept", 3)
    telemetry.set_enabled(False)
    telemetry.counter("kept", 100)   # ignored
    telemetry.set_enabled(True)
    assert telemetry.snapshot()["counters"]["kept"] == 3


# -- tracing fast paths -------------------------------------------------

def test_tracing_disabled_allocates_no_spans():
    """The off path must be ``trace is None`` everywhere: not one Span
    object constructed, not even the root span of a would-be trace."""
    tracing.set_enabled(False)
    a0 = tracing.spans_allocated()
    assert tracing.start_trace(None) is None   # process default: off
    assert tracing.start_trace(False) is None  # explicit off
    assert tracing.spans_allocated() == a0


def test_tracing_disabled_engine_run_allocates_no_spans():
    from mxnet_tpu.gluon.model_zoo.gpt import gpt_small
    from mxnet_tpu.serving.generate import GenerationEngine
    tracing.set_enabled(False)
    net = gpt_small(vocab_size=97, units=32, num_layers=2,
                    num_heads=4, max_length=128)
    net.initialize()
    eng = GenerationEngine(net, max_slots=2, max_length=64)
    try:
        prompt = onp.arange(5, dtype="i4")
        a0 = tracing.spans_allocated()
        stream = eng.submit(prompt, max_new_tokens=4)
        stream.result()
        assert stream.trace() is None and stream.trace_id is None
        assert tracing.spans_allocated() == a0
    finally:
        eng.close()


def test_phase_without_a_profiler_session_costs_under_5us():
    """The hot paths call ``tracing.phase`` about ten times a decode
    tick and four times a training step whether or not anyone is
    profiling: with no session live it is a constructor and a flag
    check (about 1 us here), and it allocates no ``Span``."""
    n = 20000
    a0 = tracing.spans_allocated()
    with tracing.phase("warm", slot=0):
        pass
    t_start = time.perf_counter()
    for i in range(n):
        with tracing.phase("serve.decode.dispatch", slot=3, tokens=i):
            pass
    per_phase = (time.perf_counter() - t_start) / n
    assert tracing.spans_allocated() == a0
    assert per_phase < 5e-6, f"phase took {per_phase * 1e6:.2f}us"


def test_tracing_enabled_span_overhead_under_10us():
    n = 20000
    tr = tracing.Trace(max_spans=n + 16)
    t0 = tr.clock()
    tr.add("warm", t0)
    t_start = time.perf_counter()
    for _ in range(n):
        tr.event("tick", slot=1)
    per_span = (time.perf_counter() - t_start) / n
    assert len(tr) == n + 2 and tr.dropped == 0
    # budget: ~10µs/span (a perf_counter read + object + list append
    # under a lock is ~1µs; 10µs leaves CI headroom without masking an
    # accidental O(n) or I/O regression)
    assert per_span < 10e-6, f"span path took {per_span * 1e6:.2f}µs"


def test_traced_engine_run_compiles_nothing_extra():
    """Arming a trace must not retrace the fixed-shape programs: the
    compile counters stay FLAT between an untraced warm-up request and
    a traced request on the same engine (spans record host-side only,
    never inside a jitted closure)."""
    from mxnet_tpu.gluon.model_zoo.gpt import gpt_small
    from mxnet_tpu.serving.generate import GenerationEngine
    telemetry.set_enabled(True)
    net = gpt_small(vocab_size=97, units=32, num_layers=2,
                    num_heads=4, max_length=128)
    net.initialize()
    eng = GenerationEngine(net, max_slots=2, max_length=64)
    try:
        prompt = onp.arange(5, dtype="i4")
        eng.submit(prompt, max_new_tokens=4).result()   # warm
        before = telemetry.counter_value("model.gpt.trace")
        before_s = telemetry.counter_value("ops.sampling.trace")
        stream = eng.submit(prompt, max_new_tokens=4, trace=True)
        stream.result()
        assert stream.trace() is not None   # the trace really armed
        assert telemetry.counter_value("model.gpt.trace") == before
        assert telemetry.counter_value("ops.sampling.trace") == before_s
    finally:
        eng.close()
