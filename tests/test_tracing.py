"""Per-request tracing, the flight recorder, windowed SLO metrics,
and the exporters (mxnet_tpu/tracing.py + the PR-16 telemetry
extensions).

Guarantees under test:
- a traced request's span tree reconstructs its FULL lifecycle:
  queue → admission → prefill (chunked, in paged mode) → decode ticks
  → emit → finish, including a cross-replica Router retry hop, with
  spans in chronological order and every parent resolvable;
- the flight recorder dumps on engine ``_fail_all`` and Router
  breaker-open with the triggering event LAST, and writes a JSON file
  when ``MXTPU_FLIGHT_DIR`` is set;
- ``telemetry.window()`` quantiles over an interval match a
  from-scratch registry fed the same samples;
- ``SLOTracker`` turns windowed histograms into goodput / error-budget
  gauges; ``export_prometheus`` emits parseable text exposition;
  ``MetricsLogger`` appends JSONL snapshots;
- the point-read helpers (``gauge_value``, ``hist_quantiles``) and the
  version-2 snapshot (bucket bounds included) behave.
"""
import json
import os
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry, tracing
from mxnet_tpu.gluon.model_zoo.gpt import gpt_small
from mxnet_tpu.serving.faults import FaultInjector, FaultRule
from mxnet_tpu.serving.generate import GenerationEngine
from mxnet_tpu.serving.router import Router

VOCAB = 97


@pytest.fixture(autouse=True)
def _restore_state():
    prev = telemetry.enabled()
    prev_tr = tracing.enabled()
    telemetry.reset()
    telemetry.set_enabled(True)
    tracing.flight.clear()
    yield
    telemetry.set_enabled(prev)
    tracing.set_enabled(prev_tr)
    tracing.clear_recent()
    tracing.flight.clear()
    telemetry.reset()


@pytest.fixture(scope="module")
def net():
    onp.random.seed(42)
    mx.np.random.seed(42)
    model = gpt_small(vocab_size=VOCAB, units=32, num_layers=2,
                      num_heads=4, max_length=128)
    model.initialize(mx.init.Xavier())
    return model


def _prompt(n, seed=0):
    return onp.random.RandomState(seed).randint(
        0, VOCAB, size=n).astype("i4")


def _names(spans):
    return [s["name"] for s in spans]


# -- Trace / Span units -------------------------------------------------

def test_trace_spans_ordered_and_bounded():
    tr = tracing.Trace(max_spans=4)
    t0 = tr.clock()
    tr.add("a", t0)
    tr.event("b")
    tr.event("c")       # hits the bound
    tr.event("d")       # dropped
    assert tr.dropped >= 1
    spans = tr.spans()
    assert _names(spans) == ["request", "a", "b", "c"]
    assert spans[0]["parent"] == -1
    assert all(s["parent"] == 0 for s in spans[1:])


def test_trace_finish_is_multi_call_safe():
    """A router request finishes once per replica hop: every finish
    extends the root span; only the first registers the trace in the
    recent ring; the LAST finish event is the final outcome."""
    tracing.clear_recent()
    tr = tracing.Trace()
    tr.finish(reason="closed")
    time.sleep(0.002)
    tr.finish(reason="length")
    spans = tr.spans()
    fins = [s for s in spans if s["name"] == "finish"]
    assert [f["attrs"]["reason"] for f in fins] == ["closed", "length"]
    # root covers through the LAST finish
    assert spans[0]["dur"] >= fins[-1]["t0"]
    assert len(tracing.recent_traces()) == 1


def test_start_trace_resolution():
    tracing.set_enabled(False)
    assert tracing.start_trace(None) is None
    assert tracing.start_trace(False) is None
    assert isinstance(tracing.start_trace(True), tracing.Trace)
    tracing.set_enabled(True)
    assert isinstance(tracing.start_trace(None), tracing.Trace)
    assert tracing.start_trace(False) is None
    tr = tracing.Trace()
    assert tracing.start_trace(tr) is tr   # passthrough (router hop)


# -- engine lifecycle span tree -----------------------------------------

def test_dense_engine_span_tree_covers_lifecycle(net):
    eng = GenerationEngine(net, max_slots=2, max_length=64)
    try:
        stream = eng.submit(_prompt(6), max_new_tokens=4, trace=True)
        stream.result()
        spans = stream.trace()
    finally:
        eng.close()
    names = _names(spans)
    assert names[0] == "request" and names[-1] == "finish"
    # every lifecycle stage present, in causal order, no gaps: each
    # stage's first occurrence is at or after the previous stage's
    order = ["submit", "queue", "admission", "prefill", "decode",
             "evict", "finish"]
    idxs = [names.index(n) for n in order]
    assert idxs == sorted(idxs), names
    assert "emit" in names
    # decode ticks: max_new - 1 (prefill emits the first token)
    assert names.count("decode") == 3
    assert names.count("emit") == 4
    # chronology and parent integrity
    t0s = [s["t0"] for s in spans[1:]]
    assert t0s == sorted(t0s)
    assert all(0 <= s["parent"] < len(spans) for s in spans[1:])
    assert stream.trace_id and "-" in stream.trace_id


def test_paged_engine_span_tree_chunked_prefill_and_prefix_hit(net):
    eng = GenerationEngine(net, max_slots=2, max_length=64,
                           max_new_tokens=8, paged=True, page_size=8,
                           prefill_chunk=16, n_pages=17)
    try:
        p = _prompt(40, seed=7)
        s1 = eng.submit(p, max_new_tokens=3, trace=True)
        s1.result()
        names1 = _names(s1.trace())
        # 40-token prompt at chunk 16 → 3 prefill chunks
        assert names1.count("prefill_chunk") == 3, names1
        adm1 = next(s for s in s1.trace() if s["name"] == "admission")
        assert adm1["attrs"]["mode"] == "paged"
        # identical prompt again: the prefix index serves the shared
        # pages, the admission span says how many tokens were reused
        s2 = eng.submit(p, max_new_tokens=3, trace=True)
        s2.result()
        adm2 = next(s for s in s2.trace() if s["name"] == "admission")
        assert adm2["attrs"]["prefix_tokens"] > 0
    finally:
        eng.close()


def test_queue_wait_span_records_blocked_admission(net):
    """With one slot, the second concurrent request's queue span
    covers the wait for the first to finish."""
    eng = GenerationEngine(net, max_slots=1, max_length=64,
                           queue_limit=8)
    try:
        a = eng.submit(_prompt(6), max_new_tokens=6, trace=True)
        b = eng.submit(_prompt(6, seed=1), max_new_tokens=3,
                       trace=True)
        a.result()
        b.result()
        q = next(s for s in b.trace() if s["name"] == "queue")
        assert q["dur"] > 0.0
    finally:
        eng.close()


# -- router: cross-replica hop ------------------------------------------

def test_router_retry_hop_lands_in_one_trace(net):
    engines = [GenerationEngine(net, max_slots=2, max_length=64)
               for _ in range(2)]
    inj = FaultInjector()
    inj.add_rule(FaultRule("crash", after_n=1))  # first dispatch dies
    router = Router(engines, fault_injector=inj, max_retries=2,
                    probe_interval_s=60.0)
    try:
        stream = router.submit(_prompt(6), max_new_tokens=3,
                               trace=True)
        toks = stream.result()
        assert len(toks) == 3
        assert stream.retries == 1
        names = _names(stream.trace())
    finally:
        router.close()
    # ONE trace shows both dispatch attempts and the hop between them
    assert names.count("dispatch") == 2, names
    r = names.index("retry")
    assert names.index("dispatch") < r < len(names) - 1 \
        and "dispatch" in names[r:], names
    # the second attempt's full lifecycle follows the hop
    for stage in ("submit", "queue", "admission", "prefill", "decode",
                  "emit"):
        assert stage in names[r:], (stage, names)
    assert names[-1] == "finish"


def test_router_untraced_suppresses_engine_process_default(net):
    """MXTPU_TRACING=1-style process default + submit(trace=False)
    must yield NO trace anywhere — router-level resolution is
    authoritative, the replica engine must not mint a shadow trace."""
    tracing.set_enabled(True)
    engines = [GenerationEngine(net, max_slots=2, max_length=64)]
    router = Router(engines, probe_interval_s=60.0)
    try:
        a0 = tracing.spans_allocated()
        stream = router.submit(_prompt(6), max_new_tokens=2,
                               trace=False)
        stream.result()
        assert stream.trace() is None
        assert tracing.spans_allocated() == a0
    finally:
        router.close()


# -- flight recorder ----------------------------------------------------

def test_flight_dump_on_fail_all_trigger_last(net):
    eng = GenerationEngine(net, max_slots=2, max_length=64)
    stream = eng.submit(_prompt(6), max_new_tokens=64)
    deadline = time.time() + 30.0
    while not stream.tokens and time.time() < deadline:
        time.sleep(0.005)   # wait for admission (gen.admit recorded)
    inj = FaultInjector()
    inj.crash(eng)
    with pytest.raises(Exception):
        stream.result()
    dump = tracing.flight.last_dump()
    assert dump is not None and dump["trigger"] == "engine.fail_all"
    kinds = [e["kind"] for e in dump["events"]]
    assert kinds[-1] == "engine.fail_all"
    assert "gen.admit" in kinds and "fault.crash" in kinds
    assert telemetry.counter_value("tracing.flight.dumps") == 1
    eng.close()


def test_flight_dump_on_breaker_open_trigger_last(net):
    engines = [GenerationEngine(net, max_slots=2, max_length=64)]
    inj = FaultInjector()
    inj.add_rule(FaultRule("error", after_n=1))
    router = Router(engines, fault_injector=inj, max_retries=0,
                    breaker_threshold=1, probe_interval_s=60.0)
    try:
        with pytest.raises(Exception):
            router.submit(_prompt(6), max_new_tokens=2).result()
        dump = tracing.flight.last_dump()
        assert dump is not None \
            and dump["trigger"] == "router.breaker_open"
        kinds = [e["kind"] for e in dump["events"]]
        assert kinds[-1] == "router.breaker_open"
        assert "fault.error" in kinds
    finally:
        router.close()


def test_flight_dump_writes_file_when_dir_set(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_FLIGHT_DIR", str(tmp_path))
    tracing.flight.record("unit.event", k=1)
    doc = tracing.flight.dump("unit.trigger", why="test")
    files = list(tmp_path.glob("flight-*-unit.trigger.json"))
    assert len(files) == 1
    on_disk = json.loads(files[0].read_text())
    assert on_disk == doc
    assert on_disk["events"][-1]["kind"] == "unit.trigger"


def test_flight_ring_is_bounded():
    fr = tracing.FlightRecorder(capacity=8)
    for i in range(20):
        fr.record("e", i=i)
    assert len(fr) == 8
    assert [e["i"] for e in fr.events()] == list(range(12, 20))


def test_flight_disabled_records_nothing(monkeypatch):
    monkeypatch.setattr(tracing, "_flight_enabled", False)
    fr = tracing.FlightRecorder()
    fr.record("e")
    assert len(fr) == 0


# -- windowed metrics ---------------------------------------------------

def test_window_quantiles_match_from_scratch_registry():
    """Bucket-snapshot subtraction over [open, read] must agree with a
    registry that saw ONLY the window's samples."""
    rng = onp.random.RandomState(3)
    pre = rng.lognormal(1.0, 1.0, size=200)    # before the window
    during = rng.lognormal(2.0, 1.2, size=500)
    for v in pre:
        telemetry.hist("h", float(v))
    telemetry.counter("c", 7)
    w = telemetry.window()
    for v in during:
        telemetry.hist("h", float(v))
    telemetry.counter("c", 4)
    got = w.read()

    telemetry.reset()
    for v in during:
        telemetry.hist("h", float(v))
    want = telemetry.hist_quantiles("h")

    wh = got["histograms"]["h"]
    assert wh["count"] == want["count"] == 500
    assert wh["total"] == pytest.approx(want["total"])
    for q in ("p50", "p95", "p99"):
        assert wh[q] == pytest.approx(want[q], rel=1e-9), q
    assert got["counters"]["c"] == 4
    assert got["elapsed_s"] >= 0.0


def test_window_restart_rebase():
    telemetry.counter("c", 5)
    w = telemetry.window()
    telemetry.counter("c", 2)
    assert w.read(restart=True)["counters"]["c"] == 2
    telemetry.counter("c", 3)
    assert w.read()["counters"]["c"] == 3


def test_window_survives_registry_reset():
    telemetry.counter("c", 5)
    w = telemetry.window()
    telemetry.reset()
    telemetry.counter("c", 2)
    # count went backwards vs the baseline → rebase, not negative
    assert w.read()["counters"].get("c", 0) == 2


def test_slo_tracker_goodput_and_error_budget():
    # the tracker windows from its construction: open it FIRST, then
    # feed 90 fast + 10 slow TTFTs against a 50ms target at 99%
    slo = telemetry.SLOTracker(ttft_ms=50.0, tpot_ms=20.0, target=0.99)
    for _ in range(90):
        telemetry.hist("serving.generate.ttft", 10.0)
    for _ in range(10):
        telemetry.hist("serving.generate.ttft", 400.0)
    for _ in range(100):
        telemetry.hist("serving.generate.decode", 5.0)
    out = slo.update()
    assert out["ttft_count"] == 100
    assert out["ttft_goodput"] == pytest.approx(0.9, abs=0.02)
    assert out["tpot_goodput"] == pytest.approx(1.0)
    assert out["goodput"] == out["ttft_goodput"]
    # 10% violations against a 1% budget → deeply negative budget
    assert out["error_budget_remaining"] < -5
    assert telemetry.gauge_value("serving.slo.goodput") == \
        pytest.approx(out["goodput"])


# -- point reads, snapshot v2, exporters --------------------------------

def test_gauge_value_and_hist_quantiles_point_reads():
    assert telemetry.gauge_value("nope") == 0.0
    telemetry.gauge("g", 3.0, peak=9.0)
    assert telemetry.gauge_value("g") == 3.0
    assert telemetry.gauge_value("g", peak=True) == 9.0
    assert telemetry.hist_quantiles("nope")["count"] == 0
    for v in (1.0, 2.0, 3.0, 4.0):
        telemetry.hist("h", v)
    q = telemetry.hist_quantiles("h")
    assert q["count"] == 4 and q["min"] == 1.0 and q["max"] == 4.0
    assert q["avg"] == pytest.approx(2.5)
    assert 1.0 <= q["p50"] <= q["p95"] <= q["p99"] <= 4.0


def test_snapshot_v2_includes_bucket_bounds():
    telemetry.hist("h", 2.0)
    snap = telemetry.snapshot()
    assert snap["version"] == 2
    assert tuple(snap["hist_bounds"]) == telemetry.hist_bounds()
    h = snap["histograms"]["h"]
    assert len(h["buckets"]) == len(snap["hist_bounds"]) + 1
    assert sum(h["buckets"]) == 1
    doc = json.loads(telemetry.render(format="json"))
    assert doc["version"] == 2
    assert doc["hist_bounds"] == snap["hist_bounds"]


def test_export_prometheus_parses():
    telemetry.counter("serving.router.requests", 3)
    telemetry.gauge("serving.generate.slots", 2.0, peak=4.0)
    telemetry.value("step.ms", 12.5)
    telemetry.hist("serving.generate.ttft", 42.0)
    text = telemetry.export_prometheus()
    seen_bucket = inf_bucket = 0
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name_part, val = line.rsplit(" ", 1)
        float(val)  # every sample value parses
        assert name_part.startswith("mxtpu_")
        if "_bucket{" in name_part:
            seen_bucket += 1
            if 'le="+Inf"' in name_part:
                inf_bucket += 1
    assert seen_bucket == len(telemetry.hist_bounds()) + 1
    assert inf_bucket == 1
    assert "mxtpu_serving_router_requests_total 3" in text
    assert "mxtpu_serving_generate_ttft_count 1" in text


def test_metrics_logger_appends_jsonl(tmp_path):
    telemetry.counter("c", 2)
    path = tmp_path / "metrics.jsonl"
    with telemetry.MetricsLogger(str(path), interval_s=0.05) as log:
        time.sleep(0.18)
    assert log.lines_written >= 2
    lines = path.read_text().strip().splitlines()
    assert len(lines) == log.lines_written
    for line in lines:
        doc = json.loads(line)
        assert doc["version"] == 2 and doc["counters"]["c"] == 2
        assert "ts" in doc


# -- profiler spans section ---------------------------------------------

def test_profiler_dumps_grows_spans_section(net):
    eng = GenerationEngine(net, max_slots=2, max_length=64)
    try:
        stream = eng.submit(_prompt(6), max_new_tokens=2, trace=True)
        stream.result()
    finally:
        eng.close()
    doc = json.loads(profiler.dumps(aggregate_stats=True,
                                    format="json"))
    assert any(t["trace_id"] == stream.trace_id for t in doc["spans"])
    table = profiler.dumps(aggregate_stats=True, format="table")
    assert "Recent request traces" in table
    assert stream.trace_id in table


def test_obs_dump_script_pretty_prints(tmp_path, capsys):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "obs_dump", os.path.join(os.path.dirname(__file__), os.pardir,
                                 "scripts", "obs_dump.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    tracing.flight.record("gen.admit", slot=0, trace_id="t-1")
    doc = tracing.flight.dump("engine.fail_all", error="boom")
    path = tmp_path / "dump.json"
    path.write_text(json.dumps(doc))
    assert mod.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "engine.fail_all" in out and "gen.admit" in out


# -- phases in the profiler's trace (tracing.phase) ---------------------

def _host_lines(trace_dir):
    """The host threads' lines of the ``.xplane.pb`` under
    ``trace_dir``: one ``[(name, start_ns, end_ns, stats)]`` per line,
    as ``jax.profiler.ProfileData`` reads them."""
    import glob

    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats)) for ev in line.events])
    return lines


def _profiled(trace_dir, fn):
    """Run ``fn`` under a profiler session without the Python tracer
    (the spans are TraceMe events; frames would only slow the test)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_lines(trace_dir)


def _line_with(lines, name):
    """The one line that holds events called ``name``."""
    hits = [ln for ln in lines if any(e[0] == name for e in ln)]
    assert len(hits) == 1, f"{name} on {len(hits)} lines"
    return hits[0]


def _inside(children, parents):
    """Every child lies, in time, within one of the parents."""
    return all(any(p[1] <= c[1] and c[2] <= p[2] for p in parents)
               for c in children)


def test_serving_phases_land_in_the_profiler_trace(net, tmp_path):
    eng = GenerationEngine(net, max_slots=2, max_length=64, paged=True,
                           page_size=8).warmup()
    try:
        def serve():
            streams = [eng.submit(_prompt(40, 1), max_new_tokens=5),
                       eng.submit(_prompt(9, 2), max_new_tokens=5)]
            for s in streams:
                s.result(timeout=60)
            # the worker ends its last pass inside the session: the
            # profiler keeps a span that ENDS in it, so a session that
            # stops mid-pass holds a serve.admit without its serve.iter
            eng.close()
        lines = _profiled(tmp_path, serve)
    finally:
        eng.close()
    line = _line_with(lines, "serve.iter")
    by_name = {}
    for ev in line:
        by_name.setdefault(ev[0], []).append(ev)
    iters = by_name["serve.iter"]
    # every phase of the pass is on the worker's line, under its bare
    # name (attributes travel as statistics), inside a serve.iter
    for name in ("serve.admit", "serve.prefill.dispatch",
                 "serve.prefill.sync", "serve.decode.dispatch",
                 "serve.decode.sync", "serve.commit"):
        assert name in by_name, sorted(by_name)
        assert _inside(by_name[name], iters), name
    assert not any(ln is not line and any(
        e[0].startswith("serve.") and e[0] != "serve.idle" for e in ln)
        for ln in lines)
    assert not any("#" in n or "=" in n for n in by_name
                   if n.startswith("serve."))
    chunk = by_name["serve.prefill.dispatch"][0][3]
    assert {"slot", "start", "tokens", "fresh"} <= set(chunk)
    # 40 tokens at page 8: whole chunks of the prompt, then one tick a
    # token after the first (which the last chunk's logits give)
    assert sum(e[3]["tokens"] for e in by_name["serve.prefill.dispatch"]) \
        == 40 + 9
    assert len(by_name["serve.decode.dispatch"]) \
        == len(by_name["serve.decode.sync"]) \
        == len(by_name["serve.commit"]) >= 4


def test_training_phases_land_in_the_profiler_trace(tmp_path):
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    rng = onp.random.RandomState(0)
    x = mx.np.array(rng.randn(16, 8).astype("f4"))
    y = mx.np.array(rng.randint(0, 4, size=16).astype("i4"))
    mlp = nn.HybridSequential()
    mlp.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    mlp.initialize(mx.init.Xavier())
    step = parallel.TrainStep(mlp, gluon.loss.SoftmaxCrossEntropyLoss(),
                              "adam", {"learning_rate": 0.01}, mesh=None)
    step(x, y)                                   # compiles, untraced
    lines = _profiled(tmp_path, lambda: [step(x, y), step(x, y)])
    line = _line_with(lines, "train.step")
    steps = [e for e in line if e[0] == "train.step"]
    assert [e[3]["step_num"] for e in steps] == [2, 3]
    for name in ("train.prepare", "train.enqueue", "train.writeback"):
        children = [e for e in line if e[0] == name]
        assert len(children) == 2, name
        assert _inside(children, steps), name
    # the children do not overlap, and come in this order
    order = [e[0] for e in sorted(line, key=lambda e: e[1])
             if e[0].startswith("train.") and e[0] != "train.step"]
    assert order == ["train.prepare", "train.enqueue",
                     "train.writeback"] * 2


def test_every_generation_program_has_a_name_of_its_own(net):
    """Each jitted generation closure is its own ``jit_<name>`` program
    in a device trace: no two roles share a name, none is ``wrapper``."""
    dense = list(net._ensure_gen()[1:])
    paged = [f for role, f in net._ensure_paged().items()
             if role != "params"]
    fused = [net._ensure_spec(kind, 3, sampled)[1]
             for kind in ("propose", "verify_commit",
                          "verify_commit_paged")
             for sampled in (False, True)]
    fused += [net._ensure_spec(kind, 3, True)[1]
              for kind in ("decode_multi", "decode_multi_paged")]
    names = [f.__name__ for f in dense + paged + fused]
    assert len(names) == 4 + 8 + 8
    assert len(set(names)) == len(names), sorted(names)
    assert all(n.startswith(("gpt_dense_", "gpt_paged_"))
               for n in names), sorted(names)
    assert {"gpt_paged_fresh", "gpt_paged_chunk", "gpt_paged_decode",
            "gpt_dense_prefill", "gpt_dense_decode",
            "gpt_paged_decode_multi"} <= set(names)
    eng = GenerationEngine(net, max_slots=2, max_length=64)
    try:
        assert eng._ensure_samplers()["sample"].__name__ \
            == "sampling_sample"
    finally:
        eng.close()


class _SlowToFetch:
    """A tick's result that takes 20 ms to reach the host, as a busy
    device's does: ``onp.asarray`` of it is the tick's host sync."""

    def __init__(self, arr):
        self._arr = arr

    def __array__(self, dtype=None, copy=None):
        time.sleep(0.02)
        return onp.asarray(self._arr, dtype=dtype)


@pytest.mark.parametrize("mode,program,kw", [
    ("plain", None, {}),
    ("multi-tick", "decode_multi", {"decode_ticks": 3}),
    ("speculative", "verify_commit", {"spec_k": 2}),
], ids=["plain", "multi-tick", "speculative"])
def test_decode_histogram_closes_after_the_host_sync(net, monkeypatch,
                                                     mode, program, kw):
    """``serving.generate.decode`` (the SLO tracker's time per output
    token) is the tick as a caller feels it in every engine mode: with
    the tick's result 20 ms late (in plain mode: the tokens picked on
    the device from its logits) no sample is shorter. Closed before the
    sync, as the plain tick did, it timed the enqueue."""
    if mode == "speculative":
        kw = dict(kw, draft_model=gpt_small(
            vocab_size=VOCAB, units=16, num_layers=1, num_heads=4,
            max_length=128))
        kw["draft_model"].initialize(mx.init.Xavier())
    eng = GenerationEngine(net, max_slots=2, max_length=64, **kw).warmup()
    if program is None:
        samplers = eng._ensure_samplers()
        pick = samplers["greedy"]
        monkeypatch.setitem(samplers, "greedy",
                            lambda logits: _SlowToFetch(pick(logits)))
    else:
        real = getattr(net, program)

        def slow(*args, **kwargs):
            first, *rest = real(*args, **kwargs)
            return (_SlowToFetch(first), *rest)
        monkeypatch.setattr(net, program, slow)
    telemetry.reset()
    try:
        eng.submit(_prompt(6), max_new_tokens=7).result(timeout=60)
    finally:
        eng.close()
    got = telemetry.hist_quantiles("serving.generate.decode")
    assert got["count"] >= 2
    assert got["avg"] >= 20.0 and got["min"] >= 20.0, got
