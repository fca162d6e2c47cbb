"""Signature stability: with bucketing active, a multi-epoch run over a
dataset whose size is NOT divisible by the batch size must compile
exactly once — the odd last batch reuses the full-batch entry instead
of forcing a rebuild (telemetry-asserted, CPU-only, tier-1)."""
import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import np, gluon, parallel, bucketing, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.data import ArrayDataset, DataLoader


def _mlp(classes=4):
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(classes))
    net.initialize(mx.init.Xavier())
    return net


def _counters():
    return telemetry.snapshot()["counters"]


def test_train_step_single_build_across_epochs():
    """45 % 16 != 0: three epochs, ONE TrainStep build."""
    rng = onp.random.RandomState(0)
    X = mx.np.array(rng.randn(45, 8).astype(onp.float32))
    Y = mx.np.array(rng.randint(0, 4, 45).astype(onp.int32))
    loader = DataLoader(ArrayDataset(X, Y), batch_size=16,
                        bucketing=bucketing.BucketingPolicy(mode="pow2"))
    net = _mlp()
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              "adam", {"learning_rate": 0.01}, mesh=None)
    telemetry.reset()
    per_epoch_builds = []
    for _ in range(3):
        for d, l in loader:
            step(d, l)
        per_epoch_builds.append(
            _counters().get("parallel.train_step.build", 0))
    assert per_epoch_builds == [1, 1, 1], per_epoch_builds


def test_train_step_epoch2_zero_new_builds_without_loader_help():
    """Even when the raw odd batch reaches TrainStep (no loader-side
    padding), an attached policy pads it in-step: epoch 2 performs zero
    new builds."""
    rng = onp.random.RandomState(1)
    X = rng.randn(45, 8).astype(onp.float32)
    Y = rng.randint(0, 4, 45).astype(onp.int32)
    net = _mlp()
    step = parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, mesh=None,
        bucketing=bucketing.BucketingPolicy(mode="pow2").clamped(16))
    telemetry.reset()
    for _ in range(2):
        for lo in range(0, 45, 16):
            step(np.array(X[lo:lo + 16]), np.array(Y[lo:lo + 16]))
    c = _counters()
    # (16,...) entry + the 13-row tail bucketed to 16 -> one build total
    assert c.get("parallel.train_step.build") == 1, c
    assert c.get("parallel.train_step.bucket_pad") == 2  # one per epoch


def test_cachedop_builds_flat_after_epoch_one():
    """Hybridized inference over the same odd-sized dataset: entry
    builds happen in epoch 1 only; epochs 2-3 are pure cache hits."""
    rng = onp.random.RandomState(2)
    X = rng.randn(45, 8).astype(onp.float32)
    net = _mlp()
    net.hybridize()
    with bucketing.policy_scope(
            bucketing.BucketingPolicy(mode="pow2").clamped(16)):
        telemetry.reset()
        builds = []
        for _ in range(3):
            for lo in range(0, 45, 16):
                net(np.array(X[lo:lo + 16]))
            snap = telemetry.snapshot()
            builds.append(
                snap["durations"].get("gluon.cachedop.build",
                                      {"count": 0})["count"])
        misses = snap["counters"].get("gluon.cachedop.cache_miss", 0)
    # epoch 1 compiles once (tail bucketed into the full-batch entry);
    # after epoch 1 the build count never moves
    assert builds[0] == builds[1] == builds[2] == 1, builds
    assert misses == 1, misses
    assert snap["counters"].get("gluon.cachedop.cache_hit", 0) == 8


def test_run_chain_telemetry_split():
    """chain_build books the (cheap) trace-graph construction, the
    first dispatch books chain_compile, and subsequent dispatches book
    run_chain — a warm chain must never relabel its run as compile."""
    rng = onp.random.RandomState(4)
    xs = np.array(rng.randn(2, 16, 8).astype(onp.float32))
    ys = np.array(rng.randint(0, 4, (2, 16)).astype(onp.int32))
    net = _mlp()
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              "sgd", {"learning_rate": 0.1}, mesh=None)
    telemetry.reset()
    step.run_chain(xs, ys)
    snap = telemetry.snapshot()
    assert snap["durations"]["parallel.train_step.chain_build"]["count"] == 1
    assert snap["durations"]["parallel.train_step.chain_compile"]["count"] == 1
    assert "parallel.train_step.run_chain" not in snap["durations"]
    step.run_chain(xs, ys)
    snap = telemetry.snapshot()
    assert snap["durations"]["parallel.train_step.chain_compile"]["count"] == 1
    assert snap["durations"]["parallel.train_step.run_chain"]["count"] == 1
    # the chain trace really is the cheap part of the first dispatch
    d = snap["durations"]
    assert d["parallel.train_step.chain_build"]["total"] < \
        d["parallel.train_step.chain_compile"]["total"]


def test_mixed_epoch_without_bucketing_rebuilds():
    """Control: the same run with bucketing disabled really does build
    a second entry for the odd batch (the cost bucketing removes)."""
    rng = onp.random.RandomState(3)
    X = rng.randn(45, 8).astype(onp.float32)
    Y = rng.randint(0, 4, 45).astype(onp.int32)
    net = _mlp()
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              "sgd", {"learning_rate": 0.1}, mesh=None)
    telemetry.reset()
    for lo in range(0, 45, 16):
        step(np.array(X[lo:lo + 16]), np.array(Y[lo:lo + 16]))
    assert _counters().get("parallel.train_step.build") == 2


def test_one_device_train_step_compiles_once():
    """Without a mesh, fresh parameters and optimizer state are
    uncommitted arrays while a jitted step returns committed ones; jit
    keeps one executable per commitment signature, so step 2 used to
    pay a second whole XLA compile that no build/trace counter saw.
    Counted at the backend: steps 2 and 3 compile nothing."""
    import jax
    rng = onp.random.RandomState(0)
    X = mx.np.array(rng.randn(16, 8).astype(onp.float32))
    Y = mx.np.array(rng.randint(0, 4, 16).astype(onp.int32))
    step = parallel.TrainStep(_mlp(), gluon.loss.SoftmaxCrossEntropyLoss(),
                              "adam", {"learning_rate": 0.01}, mesh=None)
    compiles = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        float(step(X, Y).asnumpy())
        assert compiles, "the first step compiles the program"
        del compiles[:]
        for _ in range(2):
            float(step(X, Y).asnumpy())
        assert compiles == []
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
