"""Resilience subsystem (ISSUE 6): async sharded checkpointing,
bit-identical resume, zero-downtime serving weight rollover.

The contracts under test:

- CheckpointManager: arbitrary-pytree roundtrip through the sharded
  on-disk format; commit-via-marker atomicity (a kill mid-save leaves
  only the last committed step visible); truncated/corrupt shards fall
  back to the previous committed step; write failures retry with
  backoff through the injectable filesystem seam; retention GC.
- Full-state resume: train 6 steps vs checkpoint-at-3 + resume in a
  FRESH instance — steps 4-6 losses and final params bitwise equal
  under a 2-device mesh, for plain / fused-trainer / AMP configs.
- Trainer.load_states no longer clobbers begin_num_update (warmup
  scheduler regression).
- GenerationEngine.load_weights swaps weights under live traffic with
  zero dropped requests and zero steady-state recompiles
  (model.gpt.trace flat); InferenceEngine.load_weights is
  batch-boundary atomic.
"""
import os
import threading

import numpy as onp
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import (autograd, amp, checkpoint as ckpt, gluon,
                       lr_scheduler, parallel, random_state, telemetry)
from mxnet_tpu import np as mnp
from mxnet_tpu.checkpoint import (
    CheckpointCorruptError, CheckpointManager, CheckpointWriteError,
    LocalFS, MARKER_FILE,
)
from mxnet_tpu.gluon import nn


# ---------------------------------------------------------------------------
# fault-injection filesystems
# ---------------------------------------------------------------------------

class FlakyFS(LocalFS):
    """Fails the first ``n_failures`` write_bytes calls with OSError
    (a transient NFS hiccup)."""

    def __init__(self, n_failures):
        self.n_failures = n_failures
        self.attempts = 0

    def write_bytes(self, path, data):
        self.attempts += 1
        if self.attempts <= self.n_failures:
            raise OSError(f"injected write failure #{self.attempts}")
        super().write_bytes(path, data)


class DyingFS(LocalFS):
    """Dies (raises) after ``n_ok`` successful write_bytes calls —
    simulates a preemption mid-save: some shards on disk, no marker."""

    def __init__(self, n_ok):
        self.n_ok = n_ok
        self.writes = 0

    def write_bytes(self, path, data):
        if self.writes >= self.n_ok:
            raise OSError("process killed mid-save")
        self.writes += 1
        super().write_bytes(path, data)


def _tree():
    return {
        "params": {"w": mnp.array(onp.arange(12.0, dtype="f4")
                                  .reshape(3, 4))._data,
                   "b": mnp.zeros((4,))._data},
        "opt": (mnp.ones((4,))._data, None, 7, "adam"),
        "by_idx": {0: onp.arange(3), 5: onp.arange(2)},
    }


# ---------------------------------------------------------------------------
# manager core
# ---------------------------------------------------------------------------

def test_manager_async_roundtrip(tmp_path):
    tree = _tree()
    with CheckpointManager(str(tmp_path), keep_last_n=3) as mgr:
        mgr.save(1, tree, metadata={"epoch": 0})
        mgr.save(2, tree, metadata={"epoch": 1})
        mgr.wait()
        assert mgr.all_steps() == [1, 2]
        step, got, meta = mgr.restore()
    assert step == 2 and meta["epoch"] == 1 and meta["step"] == 2
    onp.testing.assert_array_equal(got["params"]["w"],
                                   onp.arange(12.0).reshape(3, 4))
    assert isinstance(got["opt"], tuple)
    assert got["opt"][1] is None and got["opt"][2] == 7
    assert got["opt"][3] == "adam"
    # int dict keys survive the manifest
    onp.testing.assert_array_equal(got["by_idx"][5], onp.arange(2))


def test_kill_mid_save_leaves_last_commit_visible(tmp_path):
    """Marker-file atomicity: a save that dies after writing some
    shards is invisible; restore sees only the committed step, and the
    debris is GC'd once a newer commit lands."""
    root = str(tmp_path)
    mgr = CheckpointManager(root, async_save=False)
    mgr.save(1, _tree())
    # step 2 dies after 2 shard writes (no manifest, no marker)
    dying = CheckpointManager(root, async_save=False, max_retries=0,
                              fs=DyingFS(n_ok=2))
    with pytest.raises(CheckpointWriteError):
        dying.save(2, _tree())
    assert os.path.isdir(os.path.join(root, "step_00000002"))
    assert not os.path.exists(
        os.path.join(root, "step_00000002", MARKER_FILE))
    assert mgr.all_steps() == [1]
    step, _, _ = mgr.restore()
    assert step == 1
    # a newer commit GCs the partial dir
    mgr.save(3, _tree())
    assert not os.path.exists(os.path.join(root, "step_00000002"))
    mgr.close()


def test_truncated_shard_falls_back(tmp_path):
    root = str(tmp_path)
    mgr = CheckpointManager(root, async_save=False)
    mgr.save(1, _tree())
    mgr.save(2, _tree())
    shard = os.path.join(mgr.step_dir(2), "shard_00000.bin")
    with open(shard, "wb") as f:
        f.write(b"\x00\x01")  # truncated under the marker
    before = telemetry.counter_value(
        "checkpoint.restore.corrupt_fallbacks")
    with pytest.warns(UserWarning, match="corrupt"):
        step, got, _ = mgr.restore()
    assert step == 1
    onp.testing.assert_array_equal(got["params"]["w"],
                                   onp.arange(12.0).reshape(3, 4))
    assert telemetry.counter_value(
        "checkpoint.restore.corrupt_fallbacks") == before + 1
    # an explicit step is strict
    with pytest.raises(CheckpointCorruptError):
        mgr.restore(step=2)
    mgr.close()


def test_crc_mismatch_detected(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, _tree())
    shard = os.path.join(mgr.step_dir(1), "shard_00000.bin")
    size = os.path.getsize(shard)
    with open(shard, "r+b") as f:  # same length, flipped bytes
        f.write(b"\xff" * size)
    with pytest.raises(CheckpointCorruptError, match="crc"):
        ckpt.read_checkpoint(mgr.step_dir(1))
    mgr.close()


def test_flaky_fs_retry_backoff(tmp_path):
    fs = FlakyFS(n_failures=2)
    before = telemetry.counter_value("checkpoint.save.retries")
    mgr = CheckpointManager(str(tmp_path), async_save=False,
                            max_retries=3, backoff_s=0.001, fs=fs)
    mgr.save(1, _tree())  # survives two injected failures
    assert mgr.all_steps() == [1]
    assert telemetry.counter_value(
        "checkpoint.save.retries") == before + 2
    mgr.close()
    # beyond the retry budget the save fails loudly and commits nothing
    mgr2 = CheckpointManager(str(tmp_path / "b"), async_save=False,
                             max_retries=1, backoff_s=0.001,
                             fs=FlakyFS(n_failures=5))
    with pytest.raises(CheckpointWriteError):
        mgr2.save(1, _tree())
    assert mgr2.all_steps() == []
    mgr2.close()


def test_async_write_failure_surfaces_on_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path), max_retries=0,
                            backoff_s=0.001,
                            fs=FlakyFS(n_failures=100))
    mgr.save(1, _tree())
    with pytest.raises(CheckpointWriteError):
        mgr.wait()
    assert mgr.pending == 0
    mgr.close()


def test_retention_keep_last_n(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last_n=2,
                            async_save=False)
    for s in range(1, 6):
        mgr.save(s, _tree())
    assert mgr.all_steps() == [4, 5]
    names = sorted(n for n in os.listdir(str(tmp_path))
                   if n.startswith("step_"))
    assert names == ["step_00000004", "step_00000005"]
    mgr.close()


def test_save_on_closed_manager_rejected(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.close()
    with pytest.raises(ckpt.CheckpointError):
        mgr.save(1, _tree())


# ---------------------------------------------------------------------------
# full-state capture: bit-identical resume
# ---------------------------------------------------------------------------

def _make_run(with_amp=False):
    mx.np.random.seed(7)
    onp.random.seed(7)
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    sched = lr_scheduler.FactorScheduler(
        step=2, factor=0.5, base_lr=0.05, warmup_steps=3,
        warmup_begin_lr=0.005)
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.05, "lr_scheduler": sched})
    if with_amp:
        amp.init_trainer(tr)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    return net, tr, loss_fn


def _run_steps(net, tr, loss_fn, lo, hi, with_amp=False):
    out = []
    for s in range(lo, hi):
        x = mnp.array(onp.random.RandomState(s).randn(4, 8)
                      .astype("f4"))
        y = mnp.array(onp.random.RandomState(100 + s)
                      .randint(0, 4, 4).astype("i4"))
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
            if with_amp:
                with amp.scale_loss(loss, tr) as scaled:
                    scaled.backward()
        if not with_amp:
            loss.backward()
        tr.step(4)
        out.append(float.hex(float(loss.asnumpy())))
    return out


@pytest.mark.parametrize("config", ["plain", "fused", "amp"])
def test_bit_identical_resume(config, tmp_path, monkeypatch):
    """Train 6 steps; checkpoint at step 3; resume in a FRESH
    net/trainer instance; steps 4-6 losses and the final params must
    be bitwise equal to the uninterrupted run — under a 2-device
    mesh, for the plain loops, the fused trainer, and AMP."""
    monkeypatch.setenv("MXTPU_FUSED_TRAINER",
                       "0" if config == "plain" else "1")
    with_amp = config == "amp"
    mesh = parallel.make_mesh((2,), ("dp",),
                              devices=jax.devices("cpu")[:2])
    parallel.set_mesh(mesh)
    try:
        net, tr, loss_fn = _make_run(with_amp)
        direct = _run_steps(net, tr, loss_fn, 0, 6, with_amp)
        w_direct = {k: p.data().asnumpy().copy()
                    for k, p in net.collect_params().items()}

        net, tr, loss_fn = _make_run(with_amp)
        _run_steps(net, tr, loss_fn, 0, 3, with_amp)
        mgr = CheckpointManager(str(tmp_path / config))
        ckpt.save_training_state(mgr, 3, net=net, trainer=tr)
        mgr.wait()
        mgr.close()

        net2, tr2, loss_fn2 = _make_run(with_amp)
        step, meta = ckpt.restore_training_state(
            str(tmp_path / config), net=net2, trainer=tr2)
        assert step == 3
        assert tr2._optimizer.num_update == 3
        assert tr2._optimizer.begin_num_update == 0
        resumed = _run_steps(net2, tr2, loss_fn2, 3, 6, with_amp)
    finally:
        parallel.set_mesh(None)
    assert direct[3:] == resumed, \
        f"post-resume losses diverged: {direct[3:]} vs {resumed}"
    for k, p in net2.collect_params().items():
        onp.testing.assert_array_equal(p.data().asnumpy(), w_direct[k],
                                       err_msg=k)


def test_resume_restores_scheduler_and_amp_scale(tmp_path):
    """lr-scheduler position (base_lr mutations included) and the AMP
    dynamic loss scale travel with the checkpoint — the pieces the old
    opt_counters.json sidecar silently dropped."""
    net, tr, loss_fn = _make_run(with_amp=True)
    _run_steps(net, tr, loss_fn, 0, 2, with_amp=True)
    tr._optimizer.lr_scheduler.base_lr = 0.123  # user mutation
    tr._amp_loss_scaler.loss_scale = 1024.0
    tr._amp_loss_scaler._unskipped = 17
    ckpt.save_training_state(str(tmp_path), 2, net=net, trainer=tr)

    net2, tr2, _ = _make_run(with_amp=True)
    ckpt.restore_training_state(str(tmp_path), net=net2, trainer=tr2)
    assert tr2._optimizer.lr_scheduler.base_lr == 0.123
    assert tr2._amp_loss_scaler.loss_scale == 1024.0
    assert tr2._amp_loss_scaler._unskipped == 17


def test_rng_state_roundtrip():
    mx.np.random.seed(42)
    _ = mnp.random.uniform(size=(3,))  # advance
    key, counter = random_state.get_state()
    a = mnp.random.uniform(size=(4,)).asnumpy()
    b = mnp.random.uniform(size=(4,)).asnumpy()
    random_state.set_state(key, counter)
    a2 = mnp.random.uniform(size=(4,)).asnumpy()
    b2 = mnp.random.uniform(size=(4,)).asnumpy()
    onp.testing.assert_array_equal(a, a2)
    onp.testing.assert_array_equal(b, b2)


def test_data_iter_cursor_resume():
    from mxnet_tpu import io
    data = onp.arange(40, dtype="f4").reshape(20, 2)
    onp.random.seed(3)
    it = io.NDArrayIter(data, batch_size=4, shuffle=True)
    first = [it.next().data[0].asnumpy() for _ in range(2)]
    state = it.state_dict()
    rest_direct = [b.data[0].asnumpy() for b in it]

    onp.random.seed(99)  # resume must NOT depend on ambient RNG
    it2 = io.NDArrayIter(data, batch_size=4, shuffle=True)
    it2.load_state_dict(state)
    rest_resumed = [b.data[0].asnumpy() for b in it2]
    assert len(rest_direct) == len(rest_resumed) == 3
    for a, b in zip(rest_direct, rest_resumed):
        onp.testing.assert_array_equal(a, b)
    del first


def test_numpy_rng_travels_across_epoch_boundary(tmp_path):
    """NDArrayIter.reset() shuffles with numpy's GLOBAL generator, so
    a resumed run must replay the NEXT epoch's shuffle too — the
    mid-epoch order alone (cursor state) only covers the current
    epoch."""
    from mxnet_tpu import io
    data = onp.arange(32, dtype="f4").reshape(16, 2)

    def epochs(it, n_batches):
        out = []
        for _ in range(n_batches):
            try:
                b = it.next()
            except StopIteration:
                it.reset()
                b = it.next()
            out.append(b.data[0].asnumpy())
        return out

    onp.random.seed(21)
    it = io.NDArrayIter(data, batch_size=4, shuffle=True)
    epochs(it, 2)  # mid-epoch 1
    tree, meta = ckpt.capture_training_state(data_iter=it)
    ckpt.CheckpointManager(str(tmp_path), async_save=False).save(
        0, tree, metadata=meta)
    direct = epochs(it, 6)  # rest of epoch 1 + shuffled epoch 2

    onp.random.seed(77)  # ambient numpy state differs in the new proc
    it2 = io.NDArrayIter(data, batch_size=4, shuffle=True)
    _, tree2, meta2 = CheckpointManager(
        str(tmp_path), async_save=False).restore()
    ckpt.apply_training_state(tree2, meta2, data_iter=it2)
    resumed = epochs(it2, 6)
    for a, b in zip(direct, resumed):
        onp.testing.assert_array_equal(a, b)


def test_estimator_mid_epoch_resume_does_not_skip_epoch(tmp_path):
    """A batch_period (mid-epoch) checkpoint must not label the
    interrupted epoch as trained — resume re-runs it (the fit loop is
    epoch-granular), rather than silently skipping its tail."""
    from mxnet_tpu.gluon.contrib.estimator.event_handler import (
        CheckpointHandler)

    mgr = CheckpointManager(str(tmp_path), async_save=False)
    h = CheckpointHandler(str(tmp_path), manager=mgr)
    mgr.save(7, {"params": {}},
             metadata={"epoch": 2, "batch": 7, "tag": "batch7"})

    class _Est:
        net = None
        trainer = None
    h.resume_from_checkpoint = True
    h.manager = mgr
    h._resume(_Est())
    assert h.trained_epoch == 1  # epoch 2 was interrupted, NOT done
    assert h.current_epoch == 2

    mgr.save(8, {"params": {}},
             metadata={"epoch": 2, "batch": 8, "tag": "epoch2"})
    h._resume(_Est())
    assert h.trained_epoch == 2  # epoch-boundary save: 2 is complete
    assert h.current_epoch == 3
    mgr.close()


def test_legacy_orbax_checkpoint_still_loads(tmp_path):
    """Directories written by the pre-subsystem Orbax wrapper (no
    manifest.json) must stay restorable through the shim, sidecar
    included."""
    ocp = pytest.importorskip("orbax.checkpoint")
    import json

    net = nn.Dense(3, in_units=4)
    net.initialize()
    net(mnp.zeros((1, 4)))
    legacy = str(tmp_path / "legacy")
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(legacy, {"params": {
        name: p.data()._data
        for name, p in net.collect_params().items()}})
    ckptr.wait_until_finished()
    with open(os.path.join(legacy, "opt_counters.json"), "w") as f:
        json.dump({"num_update": 9, "begin_num_update": 2,
                   "index_update_count": {"0": 9}}, f)

    net2 = nn.Dense(3, in_units=4)
    net2.initialize()
    net2(mnp.zeros((1, 4)))

    class _Step:
        optimizer = mx.optimizer.SGD()
    step = _Step()
    with pytest.warns(DeprecationWarning):
        parallel.load_sharded(legacy, net2, step=step)
    onp.testing.assert_array_equal(net2.weight.data().asnumpy(),
                                   net.weight.data().asnumpy())
    assert step.optimizer.num_update == 9
    assert step.optimizer.begin_num_update == 2


def test_inference_engine_sync_mode_swap(tmp_path, monkeypatch):
    """MXTPU_SERVING=0 per-request dispatch honors the same swap
    atomicity contract (and plain functionality) as the batcher
    path."""
    monkeypatch.setenv("MXTPU_SERVING", "0")
    from mxnet_tpu.serving import InferenceEngine

    def mlp(seed):
        mx.np.random.seed(seed)
        net = nn.Dense(3, in_units=5)
        net.initialize()
        net(mnp.zeros((1, 5)))
        return net

    net_a, net_b = mlp(0), mlp(1)
    x = mnp.array(onp.random.RandomState(2).randn(2, 5).astype("f4"))
    eng = InferenceEngine(net_a, max_batch_size=4)
    eng.load_weights({k: p.data().asnumpy()
                      for k, p in net_b.collect_params().items()})
    got = eng.predict(x, timeout=60).asnumpy()
    eng.close()
    onp.testing.assert_allclose(got, net_b(x).asnumpy(), rtol=1e-6)


def test_trainer_load_states_preserves_begin_num_update(tmp_path):
    """Regression (gluon/trainer.py:358): load_states used to set
    begin_num_update = num_update, so a parameter first touched after
    resume had its update count initialized at N instead of 0 —
    skewing Adam bias correction and any schedule keyed off
    updates-since-begin."""
    net, tr, loss_fn = _make_run()
    _run_steps(net, tr, loss_fn, 0, 3)
    f = str(tmp_path / "t.states")
    tr.save_states(f)
    lr_direct = tr.learning_rate

    net2, tr2, _ = _make_run()
    tr2.load_states(f)
    assert tr2._optimizer.num_update == 3
    assert tr2._optimizer.begin_num_update == 0  # was == num_update
    assert tr2._optimizer._index_update_count == \
        tr._optimizer._index_update_count
    # warmup scheduler position unchanged by the roundtrip
    assert tr2.learning_rate == lr_direct


def test_restore_into_deferred_init_net(tmp_path):
    """The docs quick-start resume case: a FRESH process builds the
    net without in_units and restores BEFORE any forward pass — the
    checkpoint shape must finish the deferred init (the set_data path
    Block.load_parameters uses), not raise
    DeferredInitializationError."""
    net = nn.Sequential()
    net.add(nn.Dense(6, activation="relu"), nn.Dense(3))
    net.initialize()
    x = mnp.array(onp.random.RandomState(0).randn(2, 5).astype("f4"))
    net(x)  # shapes inferred; now checkpoint
    ckpt.save_training_state(str(tmp_path), 1, net=net)

    net2 = nn.Sequential()
    net2.add(nn.Dense(6, activation="relu"), nn.Dense(3))
    net2.initialize()  # deferred — no forward yet
    step, _ = ckpt.restore_training_state(str(tmp_path), net=net2)
    assert step == 1
    onp.testing.assert_array_equal(net2(x).asnumpy(), net(x).asnumpy())


def test_save_training_state_dir_convenience(tmp_path):
    net, tr, loss_fn = _make_run()
    _run_steps(net, tr, loss_fn, 0, 2)
    ckpt.save_training_state(str(tmp_path), 2, net=net, trainer=tr)
    params, meta = ckpt.read_params(str(tmp_path))
    assert meta["optimizer"]["num_update"] == 2
    assert "lr_scheduler" in meta["optimizer"]
    got = set(params)
    want = set(net.collect_params())
    assert got == want


# ---------------------------------------------------------------------------
# estimator integration
# ---------------------------------------------------------------------------

def test_estimator_checkpoint_manager_resume(tmp_path):
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    from mxnet_tpu.gluon.contrib.estimator.event_handler import (
        CheckpointHandler)

    def make():
        mx.np.random.seed(5)
        net = nn.Dense(2, in_units=4)
        net.initialize()
        est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                        trainer=gluon.Trainer(net.collect_params(),
                                              "sgd",
                                              {"learning_rate": 0.1}))
        return net, est

    x = onp.random.RandomState(0).randn(16, 4).astype("f4")
    y = onp.random.RandomState(1).randint(0, 2, 16).astype("i4")
    data = [(mnp.array(x[i:i + 8]), mnp.array(y[i:i + 8]))
            for i in range(0, 16, 8)]

    net, est = make()
    mgr = CheckpointManager(str(tmp_path), keep_last_n=3)
    h = CheckpointHandler(str(tmp_path), manager=mgr)
    est.fit(data, epochs=2, event_handlers=[h])
    mgr.wait()
    assert mgr.latest_step() is not None
    w = net.weight.data().asnumpy().copy()

    net2, est2 = make()
    h2 = CheckpointHandler(str(tmp_path), manager=mgr,
                           resume_from_checkpoint=True)
    h2.train_begin(est2)
    assert h2.current_epoch == 2  # continues AFTER the trained epochs
    onp.testing.assert_array_equal(net2.weight.data().asnumpy(), w)
    assert est2.trainer._optimizer.num_update == \
        est.trainer._optimizer.num_update
    mgr.close()


# ---------------------------------------------------------------------------
# serving weight rollover
# ---------------------------------------------------------------------------

def _gpt(seed):
    from mxnet_tpu.gluon.model_zoo.gpt import gpt_small
    mx.np.random.seed(seed)
    net = gpt_small(vocab_size=50, units=32, num_layers=2, num_heads=2,
                    max_length=64)
    net.initialize(mx.init.Xavier())
    net(mnp.array(onp.zeros((1, 4), "i4")))
    return net


def test_generation_engine_weight_rollover(tmp_path):
    """load_weights under live traffic: in-flight slots finish their
    full budget (zero dropped requests), post-swap output is
    token-identical to an engine built on the new weights, and the
    steady state recompiles NOTHING (model.gpt.trace flat across the
    swap)."""
    from mxnet_tpu.serving import GenerationEngine

    net_a, net_b = _gpt(0), _gpt(1)
    tree, meta = ckpt.capture_training_state(net=net_b)
    ckpt.write_checkpoint(str(tmp_path), ckpt.snapshot_tree(tree),
                          metadata=meta)

    eng = GenerationEngine(net_a, max_slots=4, max_length=64,
                           max_new_tokens=8)
    eng.warmup()
    pre = eng.generate(onp.array([3, 4, 5]), max_new_tokens=6,
                       timeout=120)
    traces0 = telemetry.counter_value("model.gpt.trace")
    swaps0 = telemetry.counter_value("serving.generate.weight_swaps")

    # a request IN FLIGHT across the swap completes its full budget
    live = eng.submit(onp.array([7, 8]), max_new_tokens=16)
    eng.load_weights(str(tmp_path))
    r_live = live.result(timeout=120)
    assert len(r_live.tokens) == 16
    assert r_live.finish_reason == "length"

    post = eng.generate(onp.array([3, 4, 5]), max_new_tokens=6,
                        timeout=120)
    assert telemetry.counter_value("model.gpt.trace") == traces0
    assert telemetry.counter_value(
        "serving.generate.weight_swaps") == swaps0 + 1
    eng.close()

    ref_eng = GenerationEngine(net_b, max_slots=4, max_length=64,
                               max_new_tokens=8)
    ref = ref_eng.generate(onp.array([3, 4, 5]), max_new_tokens=6,
                           timeout=120)
    ref_eng.close()
    assert post.tokens == ref.tokens
    assert pre.tokens != ref.tokens  # the swap actually changed weights


def test_generation_engine_load_weights_validates_before_swap(tmp_path):
    from mxnet_tpu.serving import GenerationEngine
    net = _gpt(0)
    eng = GenerationEngine(net, max_slots=2, max_length=64)
    before = {k: p.data().asnumpy().copy()
              for k, p in net.collect_params().items()}
    bad = {k: onp.zeros((1, 1), "f4") for k in before}
    with pytest.raises(ValueError, match="shape mismatch"):
        eng.load_weights(bad)
    with pytest.raises(ValueError, match="does not match"):
        eng.load_weights({"nope": onp.zeros(3)})
    # nothing was half-swapped
    for k, p in net.collect_params().items():
        onp.testing.assert_array_equal(p.data().asnumpy(), before[k])
    eng.close()


def test_inference_engine_weight_rollover(tmp_path):
    """The micro-batching engine's rollover: post-swap results equal
    the new block's outputs; requests racing the swap all complete."""
    from mxnet_tpu.serving import InferenceEngine

    def mlp(seed):
        mx.np.random.seed(seed)
        net = nn.Dense(3, in_units=5)
        net.initialize()
        net(mnp.zeros((1, 5)))
        return net

    net_a, net_b = mlp(0), mlp(1)
    tree, meta = ckpt.capture_training_state(net=net_b)
    ckpt.write_checkpoint(str(tmp_path), ckpt.snapshot_tree(tree),
                          metadata=meta)
    x = mnp.array(onp.random.RandomState(2).randn(2, 5).astype("f4"))

    eng = InferenceEngine(net_a, max_batch_size=4, max_queue_ms=1.0)
    eng.warmup(x)
    futs = [eng.submit(x) for _ in range(8)]
    eng.load_weights(str(tmp_path))
    for f in futs:
        f.result(timeout=60)  # zero dropped requests across the swap
    got = eng.predict(x, timeout=60).asnumpy()
    eng.close()
    want = net_b(x).asnumpy()
    onp.testing.assert_allclose(got, want, rtol=1e-6)


# ---------------------------------------------------------------------------
# deprecation shim + bench schema
# ---------------------------------------------------------------------------

def test_parallel_shim_delegates_and_warns(tmp_path):
    net = nn.Dense(3, in_units=4)
    net.initialize()
    net(mnp.zeros((1, 4)))
    with pytest.warns(DeprecationWarning):
        parallel.save_sharded(str(tmp_path), net)
    # new on-disk format: manifest + marker, counters in the manifest
    assert os.path.exists(str(tmp_path / "manifest.json"))
    assert os.path.exists(str(tmp_path / MARKER_FILE))
    assert not os.path.exists(str(tmp_path / "opt_counters.json"))
    net2 = nn.Dense(3, in_units=4)
    net2.initialize()
    net2(mnp.zeros((1, 4)))
    with pytest.warns(DeprecationWarning):
        parallel.load_sharded(str(tmp_path), net2)
    onp.testing.assert_array_equal(net2.weight.data().asnumpy(),
                                   net.weight.data().asnumpy())


@pytest.mark.slow
def test_concurrent_saves_with_rollover_soak(tmp_path):
    """Training loop checkpointing async while a serving engine
    repeatedly rolls the committed weights in — the full resilience
    loop under thread pressure."""
    from mxnet_tpu.serving import GenerationEngine

    net = _gpt(0)
    mgr = CheckpointManager(str(tmp_path), keep_last_n=2)
    eng = GenerationEngine(net, max_slots=2, max_length=64,
                           max_new_tokens=4)
    eng.warmup()
    stop = threading.Event()
    errors = []

    def roll():
        while not stop.is_set():
            if mgr.latest_step() is not None:
                try:
                    eng.load_weights(mgr.directory)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                    return

    t = threading.Thread(target=roll, daemon=True)
    t.start()
    try:
        for s in range(6):
            tree, meta = ckpt.capture_training_state(net=net)
            mgr.save(s, tree, metadata=meta)
            r = eng.generate(onp.array([1, 2, 3]), timeout=120)
            assert len(r.tokens) >= 1
        mgr.wait()
    finally:
        stop.set()
        t.join(timeout=10)
        eng.close()
        mgr.close()
    assert not errors
