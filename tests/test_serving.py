"""Serving fast path: the dynamic micro-batching `InferenceEngine`.

Guarantees under test:
- engine results are BIT-identical to per-request ``block(x)`` under
  the engine's bucketing policy (same compiled width — see
  docs/SERVING.md);
- concurrent requests actually coalesce (batches << requests) with
  zero steady-state compiles after ``warmup()``;
- admission control: queue_limit sheds load, per-request timeouts
  reject queued-too-long requests, a closed engine rejects
  immediately (the PR2 stale-iterator lesson applied to futures: no
  waiter may ever hang on a stopped worker);
- ``close()`` drains queued work under a deadline, also via atexit/GC;
- latency histograms (p50/p95/p99) land in ``profiler.dumps()``.
"""
import gc
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np, bucketing, profiler, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.block import HybridBlock
from mxnet_tpu.serving import (
    InferenceEngine, EngineClosedError, QueueFullError,
    ReplicaFailedError, RequestTimeoutError,
)


def _mlp(classes=4, feat=8):
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(classes))
    net.initialize(mx.init.Xavier())
    net(np.array(onp.zeros((1, feat), "f4")))  # materialize shapes
    return net


def _x(rng, n=1, feat=8):
    return np.array(rng.randn(n, feat).astype(onp.float32))


# -- correctness -------------------------------------------------------

def test_engine_bit_identical_to_per_request_dispatch():
    """Coalesced-and-sliced results must equal per-request block(x)
    under the same bucketing policy, bit for bit — single-sample and
    small-batch requests alike."""
    rng = onp.random.RandomState(0)
    net = _mlp()
    eng = InferenceEngine(net, max_batch_size=8, max_queue_ms=5.0)
    eng.warmup(_x(rng))
    reqs = [_x(rng, n) for n in (1, 1, 3, 1, 2, 1, 8, 1)]
    futs = [eng.submit(r) for r in reqs]
    outs = [f.result(timeout=30) for f in futs]
    with bucketing.policy_scope(eng.policy):
        for r, out in zip(reqs, outs):
            ref = net(r)
            assert out.shape == ref.shape
            assert out.asnumpy().tobytes() == ref.asnumpy().tobytes()
    eng.close()


def test_engine_coalesces_with_zero_steady_state_compiles():
    rng = onp.random.RandomState(1)
    net = _mlp()
    eng = InferenceEngine(net, max_batch_size=16, max_queue_ms=10.0,
                          queue_limit=512)
    x = _x(rng)
    eng.warmup(x)
    eng.predict(x)  # prime host-assembly code paths
    telemetry.reset()
    futs = [eng.submit(_x(rng)) for _ in range(64)]
    for f in futs:
        f.result(timeout=30)
    snap = telemetry.snapshot()
    assert snap["counters"]["serving.requests"] == 64
    batches = snap["counters"]["serving.batches"]
    assert batches < 64, "no coalescing happened"
    occ = snap["durations"]["serving.batch.occupancy"]["avg"]
    assert occ > 1.0
    # zero steady-state compiles: every dispatch hit the warmed entry
    assert "gluon.cachedop.cache_miss" not in snap["counters"]
    assert "gluon.cachedop.compile" not in snap["durations"]
    assert snap["counters"]["gluon.cachedop.infer"] == batches
    # the interned-signature satellite: the fast path records its cost
    assert "gluon.cachedop.signature" in snap["durations"]
    eng.close()


class _TwoHead(HybridBlock):
    def __init__(self):
        super().__init__()
        self.a = nn.Dense(4)
        self.b = nn.Dense(2)

    def forward(self, x):
        return self.a(x), self.b(x)


def test_engine_slices_structured_outputs():
    rng = onp.random.RandomState(2)
    net = _TwoHead()
    net.initialize(mx.init.Xavier())
    net(np.array(onp.zeros((1, 8), "f4")))
    eng = InferenceEngine(net, max_batch_size=4, max_queue_ms=5.0)
    eng.warmup(_x(rng))
    reqs = [_x(rng, n) for n in (1, 2, 1)]
    outs = [f.result(timeout=30)
            for f in [eng.submit(r) for r in reqs]]
    with bucketing.policy_scope(eng.policy):
        for r, out in zip(reqs, outs):
            ref_a, ref_b = net(r)
            got_a, got_b = out
            assert got_a.asnumpy().tobytes() == ref_a.asnumpy().tobytes()
            assert got_b.asnumpy().tobytes() == ref_b.asnumpy().tobytes()
    eng.close()


# -- admission control -------------------------------------------------

def test_request_shape_and_size_validation():
    rng = onp.random.RandomState(3)
    eng = InferenceEngine(_mlp(), max_batch_size=4)
    eng.warmup(_x(rng))
    with pytest.raises(ValueError, match="exceeds max_batch_size"):
        eng.submit(_x(rng, 5))
    with pytest.raises(ValueError, match="template"):
        eng.submit(np.array(onp.zeros((1, 9), "f4")))  # wrong feat dim
    with pytest.raises(ValueError, match="template"):
        eng.submit(np.array(onp.zeros((1, 8), "i4")))  # wrong dtype
    with pytest.raises(ValueError, match="axis 0"):
        eng.submit(np.array(1.0))  # 0-d leaf can't be coalesced
    eng.close()


def test_queue_limit_sheds_load():
    rng = onp.random.RandomState(4)
    eng = InferenceEngine(_mlp(), max_batch_size=1, max_queue_ms=0.0,
                          queue_limit=2)
    x = _x(rng)
    eng.warmup(x)
    rejected = 0
    futs = []
    for _ in range(300):
        try:
            futs.append(eng.submit(x))
        except QueueFullError:
            rejected += 1
    assert rejected > 0, "queue_limit never rejected under flood"
    for f in futs:  # admitted requests still complete
        assert f.result(timeout=30).shape == (1, 4)
    assert telemetry.snapshot()["counters"]["serving.rejected_full"] \
        == rejected
    eng.close()


def test_request_timeout_rejects_queued_request():
    """A request whose timeout expires before the batcher reaches it
    gets RequestTimeoutError, not a hung future."""
    rng = onp.random.RandomState(5)
    eng = InferenceEngine(_mlp(), max_batch_size=4, max_queue_ms=0.0)
    x4 = _x(rng, 4)
    eng.warmup(x4)
    # keep the batcher busy with full batches, then queue an
    # already-expired request behind them
    busy = [eng.submit(x4) for _ in range(4)]
    doomed = eng.submit(_x(rng), timeout_ms=0.0)
    with pytest.raises(RequestTimeoutError):
        doomed.result(timeout=30)
    for f in busy:
        f.result(timeout=30)
    eng.close()


def test_timeout_caps_coalescing_window():
    """A long max_queue_ms must not hold a request past its own
    timeout — the batcher dispatches early instead of expiring work
    it already holds."""
    rng = onp.random.RandomState(6)
    eng = InferenceEngine(_mlp(), max_batch_size=32,
                          max_queue_ms=10_000.0, timeout_ms=50.0)
    x = _x(rng)
    eng.warmup(x)
    t0 = time.perf_counter()
    out = eng.predict(x, timeout=30)
    elapsed = time.perf_counter() - t0
    assert out.shape == (1, 4)
    assert elapsed < 5.0, f"window ignored request deadline ({elapsed:.1f}s)"
    eng.close()


class _WithTable(HybridBlock):
    """Returns (per-row logits, fixed-size table whose leading dim
    COLLIDES with the engine's bucket width)."""

    def __init__(self, width):
        super().__init__()
        self.head = nn.Dense(4)
        self._w = width

    def forward(self, x):
        return self.head(x), np.ones((self._w, 3)) * 2.5


def test_fixed_output_colliding_with_bucket_width_not_sliced():
    """A non-batched output whose leading dim equals the bucket width
    must come back whole — warmup resolves batch-carrying leaves by
    eval_shape at two widths instead of guessing from the shape.
    (The variable-width CachedOp pad path still slices on this
    collision — the engine, which pins ONE width, must not.)"""
    rng = onp.random.RandomState(31)
    net = _WithTable(8)
    net.initialize(mx.init.Xavier())
    net(np.array(onp.zeros((1, 8), "f4")))
    eng = InferenceEngine(net, max_batch_size=8, max_queue_ms=2.0)
    eng.warmup(_x(rng))
    assert eng._out_batched == [True, False]
    x = _x(rng)
    logits, table = eng.predict(x, timeout=30)
    assert logits.shape == (1, 4)
    assert table.shape == (8, 3), "fixed table was mis-sliced"
    onp.testing.assert_array_equal(table.asnumpy(),
                                   onp.full((8, 3), 2.5, "f4"))
    with bucketing.policy_scope(eng.policy):
        ref_logits = net(x)[0]
    assert logits.asnumpy().tobytes() == ref_logits.asnumpy().tobytes()
    eng.close()


def test_zero_window_still_coalesces_backlog():
    """max_queue_ms=0 means 'don't wait', not 'don't batch': requests
    already queued when a batch opens must coalesce."""
    rng = onp.random.RandomState(30)
    eng = InferenceEngine(_mlp(), max_batch_size=16, max_queue_ms=0.0,
                          queue_limit=512)
    x = _x(rng)
    eng.warmup(x)
    eng.predict(x)
    telemetry.reset()
    futs = [eng.submit(_x(rng)) for _ in range(64)]
    for f in futs:
        f.result(timeout=30)
    snap = telemetry.snapshot()
    occ = snap["durations"]["serving.batch.occupancy"]["avg"]
    assert occ > 2.0, f"zero-window dispatch never batched (occ={occ})"
    eng.close()


def test_explicit_ladder_gets_implicit_top_bucket():
    """An explicit ladder topping out below max_batch_size must not
    create one compiled width per occupancy above its largest bucket."""
    eng = InferenceEngine(_mlp(), max_batch_size=32,
                          bucketing=bucketing.BucketingPolicy(
                              buckets=[4, 8]))
    assert eng.policy.sizes(32) == [4, 8, 32]
    eng.close()


# -- shutdown robustness (satellite: alongside the PR2 stale-iterator
#    guarantee — no waiter may hang on a stopped worker) ---------------

def test_submit_after_close_rejects_immediately():
    rng = onp.random.RandomState(7)
    eng = InferenceEngine(_mlp(), max_batch_size=4)
    x = _x(rng)
    eng.warmup(x)
    eng.predict(x)
    eng.close()
    t0 = time.perf_counter()
    with pytest.raises(EngineClosedError):
        eng.submit(x)
    assert time.perf_counter() - t0 < 1.0, "rejection was not immediate"
    eng.close()  # idempotent


def test_close_drains_queued_requests():
    """close() finishes work already admitted (drain+join), under its
    deadline — queued futures resolve instead of hanging."""
    rng = onp.random.RandomState(8)
    eng = InferenceEngine(_mlp(), max_batch_size=2, max_queue_ms=0.0,
                          queue_limit=128)
    x = _x(rng)
    eng.warmup(x)
    futs = [eng.submit(x) for _ in range(32)]
    eng.close(timeout=30.0)
    assert not eng._batcher.is_alive()
    for f in futs:
        assert f.result(timeout=1).shape == (1, 4)  # already resolved


def test_close_deadline_rejects_rather_than_hangs():
    """Even a hard-stopped batcher leaves no future unresolved: the
    drain hook rejects leftovers with EngineClosedError."""
    rng = onp.random.RandomState(9)
    eng = InferenceEngine(_mlp(), max_batch_size=2, max_queue_ms=0.0,
                          queue_limit=128)
    x = _x(rng)
    eng.warmup(x)
    futs = [eng.submit(x) for _ in range(64)]
    eng.close(timeout=0.0)  # no grace at all
    done, rejected = 0, 0
    for f in futs:
        try:
            f.result(timeout=5)
            done += 1
        except EngineClosedError:
            rejected += 1
    assert done + rejected == 64  # nobody hung


def test_engine_context_manager_and_gc():
    rng = onp.random.RandomState(10)
    with InferenceEngine(_mlp(), max_batch_size=4) as eng:
        eng.warmup(_x(rng))
        assert eng.predict(_x(rng), timeout=30).shape == (1, 4)
    assert eng.closed
    # an abandoned engine's batcher exits once the engine is collected
    eng2 = InferenceEngine(_mlp(), max_batch_size=4)
    eng2.warmup(_x(rng))
    thread = eng2._batcher
    del eng2
    gc.collect()
    thread.join(timeout=10.0)
    assert not thread.is_alive(), "batcher leaked after engine GC"


def test_escape_hatch_serving_disabled(monkeypatch):
    """MXTPU_SERVING=0: per-request synchronous dispatch, no batcher
    thread, results already resolved (and identical to block(x))."""
    monkeypatch.setenv("MXTPU_SERVING", "0")
    rng = onp.random.RandomState(11)
    net = _mlp()
    eng = InferenceEngine(net, max_batch_size=8)
    assert eng._batcher is None
    x = _x(rng)
    fut = eng.submit(x)
    assert fut.done()
    assert fut.result().asnumpy().tobytes() == net(x).asnumpy().tobytes()
    eng.close()
    with pytest.raises(EngineClosedError):
        eng.submit(x)


def test_batcher_death_surfaces_replica_failed():
    """A batcher thread that DIES (not a per-batch dispatch error,
    which only fails its own batch) marks the engine FAILED: queued
    futures and later submits raise ReplicaFailedError carrying the
    original exception — distinguishable from a deliberate close()."""
    rng = onp.random.RandomState(17)
    eng = InferenceEngine(_mlp(), max_batch_size=4, max_queue_ms=50.0)
    x = _x(rng)
    eng.warmup(x)
    eng.predict(x)
    boom = RuntimeError("batcher exploded")

    def dying_dispatch(batch):
        raise boom

    eng._dispatch = dying_dispatch
    fut = eng.submit(x)
    with pytest.raises(ReplicaFailedError) as ei:
        fut.result(timeout=30)
    assert ei.value.cause is boom
    with pytest.raises(ReplicaFailedError) as ei:
        eng.submit(x)
    assert ei.value.cause is boom
    assert isinstance(ei.value, EngineClosedError)  # old handlers work
    # the futures fail before the dying thread has left its frame
    eng._batcher.join(timeout=30)
    assert not eng._batcher.is_alive()

    # a DELIBERATE close stays a plain EngineClosedError
    eng2 = InferenceEngine(_mlp(), max_batch_size=4)
    eng2.close()
    with pytest.raises(EngineClosedError) as ei:
        eng2.submit(x)
    assert not isinstance(ei.value, ReplicaFailedError)


# -- observability -----------------------------------------------------

def test_latency_histograms_render_in_profiler_dumps():
    import json
    rng = onp.random.RandomState(12)
    eng = InferenceEngine(_mlp(), max_batch_size=8, max_queue_ms=2.0)
    x = _x(rng)
    eng.warmup(x)
    telemetry.reset()
    for f in [eng.submit(_x(rng)) for _ in range(16)]:
        f.result(timeout=30)
    table = profiler.dumps(format="table", aggregate_stats=True)
    assert "serving.request.latency" in table
    assert "p50" in table and "p95" in table and "p99" in table
    doc = json.loads(profiler.dumps(format="json", aggregate_stats=True))
    hist = doc["histograms"]["serving.request.latency"]
    assert hist["count"] == 16
    assert 0.0 < hist["p50"] <= hist["p95"] <= hist["p99"] <= hist["max"]
    assert doc["histograms"]["serving.queue.wait"]["count"] == 16
    snap = telemetry.snapshot()
    assert snap["gauges"]["serving.queue.depth"]["peak"] >= 1
    eng.close()


# -- soak (excluded from tier-1 via the slow marker) -------------------

@pytest.mark.slow
def test_soak_sustained_concurrent_load():
    """Sustained multi-threaded traffic: every request correct, no
    thread/future leak, clean close."""
    rng = onp.random.RandomState(13)
    net = _mlp()
    eng = InferenceEngine(net, max_batch_size=16, max_queue_ms=1.0,
                          queue_limit=2048)
    eng.warmup(_x(rng))
    X = rng.randn(64, 8).astype(onp.float32)
    with bucketing.policy_scope(eng.policy):
        refs = [net(np.array(X[i:i+1])).asnumpy().tobytes()
                for i in range(64)]
    errors = []

    def client(seed):
        r = onp.random.RandomState(seed)
        for _ in range(500):
            i = r.randint(64)
            out = eng.predict(np.array(X[i:i+1]), timeout=60)
            if out.asnumpy().tobytes() != refs[i]:
                errors.append(i)
                return

    threads = [threading.Thread(target=client, args=(s,))
               for s in range(4)]
    n_before = threading.active_count()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, f"wrong results for rows {errors[:5]}"
    eng.close(timeout=30.0)
    assert not eng._batcher.is_alive()
    assert threading.active_count() <= n_before
    snap = telemetry.snapshot()
    assert snap["counters"]["serving.requests"] >= 2000
