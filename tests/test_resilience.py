"""Self-healing training (ISSUE 8): TrainSupervisor, divergence/hang
watchdogs, TrainFaultInjector chaos seam, and the satellite surfaces.

The contracts under test:

- Supervised training is numerically INVISIBLE: a clean supervised run
  is bitwise identical to the manual loop it wraps.
- Preemption: SIGTERM flushes a synchronous checkpoint at the next
  step boundary; a fresh supervisor resumes and finishes bitwise
  identical to an uninterrupted run.
- Divergence: a transient NaN batch trips the watchdog, rewinds to
  the last commit, replays clean — bitwise identical; a PERSISTENT
  NaN batch is skipped after the second trip (skip_batches); a run
  that keeps tripping escalates as DivergenceError.
- Hangs: a slow step is aborted by the per-step deadline and the run
  restarts from the last commit.
- AMP overflow-skips are NOT divergence (the loss scaler handles
  them) and the fused all-finite reduction counts them
  (`amp.overflow`).
- CheckpointManager.save_sync commits on the caller thread; a queued
  async save survives interpreter exit via the atexit flush.
- NDArrayIter.skip_batches / DataLoader.skip_batches fast-forward
  with cursor math identical to real consumption, across epoch
  boundaries.
- Estimator ResilienceHandler: SIGTERM mid-epoch, resume, tag-aware
  epoch accounting, final weights/metrics match an uninterrupted fit.
"""
import math
import os
import signal
import subprocess
import sys
import tempfile

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import (amp, autograd, checkpoint as ckpt, gluon, io,
                       resilience, telemetry)
from mxnet_tpu import np as mnp
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience import (
    DivergenceError, DivergenceWatchdog, InjectedTrainingFault,
    TrainFaultInjector, TrainFaultRule, TrainingAborted,
    TrainSupervisor,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# shared fixtures
# ---------------------------------------------------------------------------

def _make_run(seed=7, with_amp=False):
    mx.np.random.seed(seed)
    onp.random.seed(seed)
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.05})
    if with_amp:
        amp.init_trainer(tr)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    data = onp.random.RandomState(0).randn(40, 8).astype("f4")
    label = onp.random.RandomState(1).randint(0, 4, 40).astype("i4")
    it = io.NDArrayIter(data, label, batch_size=4, shuffle=True)
    return net, tr, loss_fn, it


def _control_params(n_steps=12, with_amp=False):
    """The uninterrupted manual loop the supervisor must match."""
    net, tr, loss_fn, it = _make_run(with_amp=with_amp)
    for _ in range(n_steps):
        try:
            b = it.next()
        except StopIteration:
            it.reset()
            b = it.next()
        with autograd.record():
            loss = loss_fn(net(b.data[0]), b.label[0]).mean()
            if with_amp:
                with amp.scale_loss(loss, tr) as scaled:
                    scaled.backward()
        if not with_amp:
            loss.backward()
        tr.step(4)
    return {k: p.data().asnumpy().copy()
            for k, p in net.collect_params().items()}


def _assert_params_equal(net, want):
    for k, p in net.collect_params().items():
        onp.testing.assert_array_equal(p.data().asnumpy(), want[k],
                                       err_msg=k)


def _supervise(tmpdir, n_steps=12, injector=None, **kw):
    net, tr, loss_fn, it = _make_run(
        with_amp=kw.pop("with_amp", False))
    sup = TrainSupervisor(str(tmpdir), net=net, trainer=tr,
                          loss_fn=loss_fn, data_iter=it, save_every=5,
                          injector=injector, handle_signals=False,
                          **kw)
    return net, sup.supervise(n_steps)


# ---------------------------------------------------------------------------
# satellite: fused all-finite + amp.overflow counter
# ---------------------------------------------------------------------------

def test_all_finite_fused():
    from mxnet_tpu.amp.loss_scaler import all_finite
    a = mnp.arange(6.0)._data
    b = mnp.ones((2, 3))._data
    assert all_finite([a, b])
    bad = (mnp.ones((3,)) * float("nan"))._data
    assert not all_finite([a, bad])
    # integer leaves pass trivially; empty input is vacuously finite
    assert all_finite([mnp.arange(3)._data])
    assert all_finite([])


def test_loss_scaler_overflow_counts_and_skips():
    """A NaN gradient must skip the update (params untouched), shrink
    the scale, and count the trip — amp.overflow telemetry AND the
    scaler's own monotone overflow_count."""
    net, tr, loss_fn, it = _make_run(with_amp=True)
    b = it.next()
    with autograd.record():
        loss = loss_fn(net(b.data[0]), b.label[0]).mean()
        with amp.scale_loss(loss, tr) as scaled:
            scaled.backward()
    before = {k: p.data().asnumpy().copy()
              for k, p in net.collect_params().items()}
    scale0 = tr._amp_loss_scaler.loss_scale
    c0 = telemetry.counter_value("amp.overflow")
    for p in tr._params:  # poison every grad
        p.grad()[:] = float("nan")
    tr.step(4)
    assert tr._amp_loss_scaler.overflow_count == 1
    assert telemetry.counter_value("amp.overflow") == c0 + 1
    assert tr._amp_loss_scaler.loss_scale == scale0 / 2
    _assert_params_equal(net, before)  # update was skipped


# ---------------------------------------------------------------------------
# satellite: save_sync + atexit flush
# ---------------------------------------------------------------------------

def test_save_sync_commits_on_caller_thread(tmp_path):
    mgr = ckpt.CheckpointManager(str(tmp_path))  # async worker active
    tree = {"w": mnp.arange(4.0)._data}
    mgr.save_sync(3, tree, metadata={"via": "signal"})
    # committed the moment save_sync returns — no wait() needed
    assert mgr.all_steps() == [3]
    step, got, meta = mgr.restore()
    assert step == 3 and meta["via"] == "signal"
    onp.testing.assert_array_equal(got["w"], onp.arange(4.0))
    mgr.close()


def test_async_save_survives_interpreter_exit(tmp_path):
    """Regression (ISSUE 8 satellite): save() followed by immediate
    interpreter exit — no wait(), no close() — must still commit its
    marker via the atexit flush."""
    script = (
        "import tpu_platform; tpu_platform.force_cpu(n_devices=2)\n"
        "from mxnet_tpu import checkpoint as ckpt\n"
        "from mxnet_tpu import np as mnp\n"
        "mgr = ckpt.CheckpointManager(%r)\n"
        "mgr.save(5, {'w': mnp.arange(8.0)._data})\n"
        "# fall off the end: atexit must flush the queued save\n"
        % str(tmp_path))
    out = subprocess.run(
        [sys.executable, "-c", script], cwd=REPO, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-800:]
    assert os.path.exists(
        os.path.join(str(tmp_path), "step_00000005", "COMMITTED"))


# ---------------------------------------------------------------------------
# satellite: skip_batches fast-forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [3, 7, 13])
def test_ndarrayiter_skip_matches_replay(n):
    """skip_batches(n) must leave the iterator in EXACTLY the state of
    consuming n batches with reset-on-exhaustion — shuffled, across an
    epoch boundary (epoch = 5 batches), including the ambient-numpy
    RNG draws the boundary reshuffle burns."""
    data = onp.arange(40, dtype="f4").reshape(20, 2)

    onp.random.seed(3)
    it_a = io.NDArrayIter(data, batch_size=4, shuffle=True)
    for _ in range(n):
        try:
            it_a.next()
        except StopIteration:
            it_a.reset()
            it_a.next()
    state_a = it_a.state_dict()
    rng_a = onp.random.get_state()

    onp.random.seed(3)
    it_b = io.NDArrayIter(data, batch_size=4, shuffle=True)
    assert it_b.skip_batches(n) == n
    state_b = it_b.state_dict()
    rng_b = onp.random.get_state()

    assert state_a["cursor"] == state_b["cursor"]
    onp.testing.assert_array_equal(state_a["order"], state_b["order"])
    onp.testing.assert_array_equal(state_a["idx"], state_b["idx"])
    onp.testing.assert_array_equal(rng_a[1], rng_b[1])  # numpy keys
    # and the streams stay aligned from here
    onp.testing.assert_array_equal(it_a.next().data[0].asnumpy(),
                                   it_b.next().data[0].asnumpy())


def test_ndarrayiter_skip_validates():
    data = onp.arange(8, dtype="f4").reshape(4, 2)
    it = io.NDArrayIter(data, batch_size=4)
    with pytest.raises(ValueError):
        it.skip_batches(-1)
    # dataset smaller than batch_size under 'discard': zero-batch
    # epochs can never satisfy the skip
    it2 = io.NDArrayIter(data[:2], batch_size=4,
                         last_batch_handle="discard")
    with pytest.raises(ValueError):
        it2.skip_batches(1)


def test_dataloader_skip_batches():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    ds = ArrayDataset(mnp.arange(16.0).reshape(8, 2))
    dl = DataLoader(ds, batch_size=2)  # 4 batches/epoch
    full = [b.asnumpy() for b in dl]
    dl.skip_batches(2)
    got = [b.asnumpy() for b in dl]
    assert len(got) == 2
    onp.testing.assert_array_equal(got[0], full[2])
    # a skip larger than one epoch carries the remainder over the
    # epoch boundary into the next __iter__
    dl.skip_batches(5)
    assert [b.asnumpy().tolist() for b in dl] == []  # 4 consumed
    rest = [b.asnumpy() for b in dl]                 # 1 carried
    assert len(rest) == 3
    onp.testing.assert_array_equal(rest[0], full[1])
    with pytest.raises(ValueError):
        dl.skip_batches(-2)


# ---------------------------------------------------------------------------
# watchdog units
# ---------------------------------------------------------------------------

def test_divergence_watchdog_detection():
    wd = DivergenceWatchdog(warmup_steps=4, spike_factor=5.0)
    for i in range(8):
        assert not wd.check(1.0 + 0.01 * (i % 2))
    assert wd.check(float("nan"))
    assert wd.check(float("inf"))
    assert wd.check(100.0)          # spike vs EMA
    ema_before = wd._ema
    assert wd.check(100.0)          # tripped samples stay out of EMA
    assert wd._ema == ema_before
    assert not wd.check(1.0)        # healthy stream continues
    # downward spikes are progress, not divergence
    assert not wd.check(0.001)
    # AMP overflow-skip stands down even on a wild loss
    assert not wd.check(float("nan"), amp_overflow=True)


def test_divergence_watchdog_param_check():
    wd = DivergenceWatchdog(check_params=True)
    good = [mnp.ones((3,))._data]
    bad = [(mnp.ones((3,)) * float("inf"))._data]
    assert not wd.check(1.0, params=good)
    assert wd.check(1.0, params=bad)


def test_fault_rule_validation():
    with pytest.raises(ValueError):
        TrainFaultRule("bogus", at_step=1)
    with pytest.raises(ValueError):
        TrainFaultRule("crash")                 # needs at_step or rate
    with pytest.raises(ValueError):
        TrainFaultRule("crash", at_step=1, rate=0.5)
    with pytest.raises(ValueError):
        TrainFaultRule("slow", at_step=1)       # needs duration
    with pytest.raises(ValueError):
        TrainFaultRule("nan_batch", at_step=3)  # batch-keyed kind
    with pytest.raises(ValueError):
        TrainFaultRule("kill_mid_save")         # needs save_step
    with pytest.raises(ValueError):  # persistent must be batch-keyed
        TrainFaultRule("crash", at_step=1, persistent=True)
    inj = TrainFaultInjector.from_spec(
        "kill@27;nan_batch@30;kill_mid_save@45;preempt@51;slow@3:250")
    kinds = sorted(r.kind for r in inj._rules)
    assert kinds == ["kill", "kill_mid_save", "nan_batch", "preempt",
                     "slow"]


# ---------------------------------------------------------------------------
# the supervisor
# ---------------------------------------------------------------------------

def test_supervisor_clean_run_bit_identical(tmp_path):
    """Supervision (snapshots, saves, watchdog) must be numerically
    invisible: same params as the bare manual loop, bitwise."""
    want = _control_params()
    net, rep = _supervise(tmp_path)
    assert rep["status"] == "done" and rep["step"] == 12
    assert rep["goodput"] == 1.0
    _assert_params_equal(net, want)


def test_supervisor_transient_nan_rewind_replay(tmp_path):
    """A transient NaN batch (bad DMA, flaky host read): the watchdog
    trips, rewinds to the last commit, replays the CLEAN data — and
    the healed run is bitwise identical to an undisturbed one."""
    want = _control_params()
    inj = TrainFaultInjector([TrainFaultRule("nan_batch", at_batch=7)])
    net, rep = _supervise(tmp_path, injector=inj)
    assert rep["status"] == "done"
    assert rep["rewinds"] == 1 and rep["skipped"] == 0
    _assert_params_equal(net, want)


def test_supervisor_persistent_nan_skips_batch(tmp_path):
    """Persistently-poisoned data: the first rewind replays (and trips
    again on the same batch), the second marks the batch poisoned and
    fast-forwards past it — the run completes without escalating."""
    inj = TrainFaultInjector(
        [TrainFaultRule("nan_batch", at_batch=7, persistent=True)])
    net, rep = _supervise(tmp_path, injector=inj)
    assert rep["status"] == "done" and rep["step"] == 12
    assert rep["rewinds"] == 2 and rep["skipped"] == 1
    assert telemetry.counter_value("resilience.batches_skipped") >= 1


def test_supervisor_divergence_escalates(tmp_path):
    """A run that keeps tripping (real divergence, not a bad batch)
    must escalate after max_consecutive_rewinds instead of burning the
    schedule on futile rewinds."""
    class _NaNLoss:
        def asnumpy(self):
            return onp.array(float("nan"))

    _, _, _, it = _make_run()
    sup = TrainSupervisor(
        str(tmp_path), step_fn=lambda batch: _NaNLoss(), data_iter=it,
        save_every=5, max_consecutive_rewinds=3, handle_signals=False)
    with pytest.raises(DivergenceError):
        sup.supervise(12)
    assert telemetry.counter_value("resilience.rewinds") >= 3


def test_supervisor_crash_restart_and_budget(tmp_path):
    """An in-process crash restores the last commit and retries within
    the restart budget — bitwise identical; a crash storm past the
    budget aborts with the cause chained."""
    want = _control_params()
    inj = TrainFaultInjector([TrainFaultRule("crash", at_step=8)])
    net, rep = _supervise(tmp_path / "ok", injector=inj)
    assert rep["status"] == "done" and rep["restarts"] == 1
    _assert_params_equal(net, want)

    # every step crashes: budget must bound the retries
    inj2 = TrainFaultInjector(
        [TrainFaultRule("crash", rate=1.0)], seed=1)
    with pytest.raises(TrainingAborted) as ei:
        _supervise(tmp_path / "storm", injector=inj2, max_restarts=2)
    assert isinstance(ei.value.__cause__, InjectedTrainingFault)


def test_supervisor_preemption_flush_and_resume(tmp_path):
    """SIGTERM: flush-on-signal commits the current step exactly; a
    FRESH supervisor (different init — restore must overwrite it)
    resumes and finishes bitwise identical to the uninterrupted run."""
    want = _control_params()
    inj = TrainFaultInjector([TrainFaultRule("preempt", at_step=7)])
    net, tr, loss_fn, it = _make_run()
    sup = TrainSupervisor(str(tmp_path), net=net, trainer=tr,
                          loss_fn=loss_fn, data_iter=it, save_every=5,
                          injector=inj, handle_signals=True)
    rep = sup.supervise(12)
    assert rep["status"] == "preempted" and rep["step"] == 7
    assert rep["signal"] == signal.SIGTERM
    assert rep["preemptions"] == 1
    mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False)
    assert mgr.latest_step() == 7  # the flush committed step 7 exactly
    mgr.close()

    net2, tr2, loss_fn2, it2 = _make_run(seed=99)
    sup2 = TrainSupervisor(str(tmp_path), net=net2, trainer=tr2,
                           loss_fn=loss_fn2, data_iter=it2,
                           save_every=5, handle_signals=False)
    rep2 = sup2.supervise(12)
    assert rep2["status"] == "done" and rep2["resumes"] == 1
    _assert_params_equal(net2, want)


def test_goodput_counts_the_steps_a_kill_wasted(tmp_path):
    """A run that dies past its restart budget and is resumed by a
    FRESH supervisor (the respawn after a kill) reports goodput over
    every step executed by both, carried in ``stats_file``: one death
    wastes at most one save window, and the result is still bitwise
    the uninterrupted run's."""
    want = _control_params()
    stats = str(tmp_path / "steps.txt")
    inj = TrainFaultInjector([TrainFaultRule("crash", at_step=8)])
    with pytest.raises(TrainingAborted):
        _supervise(tmp_path / "ckpt", injector=inj, max_restarts=0,
                   stats_file=stats)
    with open(stats) as f:
        died_after = int(f.read())
    assert 5 < died_after <= 8     # past the commit of step 5
    net, rep = _supervise(tmp_path / "ckpt", stats_file=stats)
    assert rep["status"] == "done" and rep["resumes"] == 1
    total = rep["total_steps_executed"]
    assert total == died_after + rep["steps_executed"]
    assert 12 < total <= 12 + 5    # save_every=5: one window at most
    assert rep["goodput"] == 12 / total
    with open(stats) as f:
        assert int(f.read()) == total
    _assert_params_equal(net, want)


def test_supervisor_hang_watchdog_aborts_and_resumes(tmp_path):
    """A stuck step (injected 3s stall vs a 0.4s deadline) is aborted
    asynchronously and the run restarts from the last commit — and
    still finishes bitwise identical."""
    want = _control_params()
    inj = TrainFaultInjector(
        [TrainFaultRule("slow", at_step=6, duration_ms=3000)])
    net, rep = _supervise(tmp_path, injector=inj, step_timeout_s=0.4)
    assert rep["status"] == "done"
    assert rep["hangs"] >= 1 and rep["restarts"] >= 1
    _assert_params_equal(net, want)


def test_supervisor_amp_overflow_is_not_divergence(tmp_path):
    """An fp16 overflow-skip (NaN grads, scaler skips the update) must
    NOT trip the watchdog — it is the loss scaler's job, and a rewind
    would turn every overflow into a lost save window."""
    inj = TrainFaultInjector([TrainFaultRule("nan_grad", at_batch=6)])
    net, rep = _supervise(tmp_path, injector=inj, with_amp=True)
    assert rep["status"] == "done" and rep["step"] == 12
    assert rep["rewinds"] == 0
    assert telemetry.counter_value("amp.overflow") >= 1


def test_supervisor_kill_mid_save_falls_back(tmp_path):
    """The checkpoint_fs seam: a save that dies mid-write (emulated
    in-process via a failing FS) never commits; the rewind falls back
    to the previous committed step."""
    class _FailStep10FS(ckpt.LocalFS):
        def write_bytes(self, path, data):
            if "step_00000010" in path:
                raise OSError("injected mid-save death")
            super().write_bytes(path, data)

    want = _control_params()
    net, tr, loss_fn, it = _make_run()
    mgr = ckpt.CheckpointManager(str(tmp_path), max_retries=0,
                                 fs=_FailStep10FS())
    inj = TrainFaultInjector([TrainFaultRule("nan_batch", at_batch=10)])
    sup = TrainSupervisor(mgr, net=net, trainer=tr, loss_fn=loss_fn,
                          data_iter=it, save_every=5, injector=inj,
                          handle_signals=False)
    # save(10) fails asynchronously; the NaN at batch 10 (step 11)
    # forces a rewind that must fall back to the commit at step 5
    rep = sup.supervise(12)
    assert rep["status"] == "done" and rep["rewinds"] >= 1
    _assert_params_equal(net, want)
    assert 10 not in mgr.all_steps()
    mgr.close()


def test_supervisor_already_past_target_does_not_relabel(tmp_path):
    """Review regression: supervise(n) against a checkpoint already
    past n used to re-commit the restored LATER state under the
    smaller step number n — a mislabeled checkpoint."""
    net, tr, loss_fn, it = _make_run()
    sup = TrainSupervisor(str(tmp_path), net=net, trainer=tr,
                          loss_fn=loss_fn, data_iter=it, save_every=5,
                          handle_signals=False)
    sup.supervise(10)
    rep = sup.supervise(6)  # shorter target than the commit on disk
    assert rep["status"] == "done" and rep["step"] == 10
    sup.close()
    mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False)
    assert 6 not in mgr.all_steps()
    assert mgr.latest_step() == 10
    mgr.close()


def test_supervisor_validation():
    _, _, _, it = _make_run()
    with pytest.raises(ValueError):  # no step backend
        TrainSupervisor(tempfile.mkdtemp(), data_iter=it)
    with pytest.raises(ValueError):  # no data_iter
        TrainSupervisor(tempfile.mkdtemp(), step_fn=lambda b: 0.0)
    with pytest.raises(TypeError):   # non-resumable iterator
        TrainSupervisor(tempfile.mkdtemp(), step_fn=lambda b: 0.0,
                        data_iter=iter([1, 2, 3]))


# ---------------------------------------------------------------------------
# estimator integration (ResilienceHandler e2e)
# ---------------------------------------------------------------------------

def test_estimator_resilience_handler_e2e(tmp_path):
    """SIGTERM mid-epoch during Estimator.fit: the handler flushes a
    batch-tag checkpoint and stops; a fresh estimator resumes from the
    last EPOCH-boundary commit (tag-aware accounting — the interrupted
    epoch is re-run, not skipped, not double-counted) and the final
    weights and metrics match an uninterrupted fit."""
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    from mxnet_tpu.gluon.contrib.estimator.event_handler import (
        BatchEnd, ResilienceHandler)

    def make(seed=5):
        mx.np.random.seed(seed)
        onp.random.seed(seed)
        net = nn.Dense(2, in_units=4)
        net.initialize(mx.init.Xavier())
        est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                        trainer=gluon.Trainer(net.collect_params(),
                                              "sgd",
                                              {"learning_rate": 0.1}))
        return net, est

    x = onp.random.RandomState(0).randn(16, 4).astype("f4")
    y = onp.random.RandomState(1).randint(0, 2, 16).astype("i4")
    data = [(mnp.array(x[i:i + 8]), mnp.array(y[i:i + 8]))
            for i in range(0, 16, 8)]  # 2 batches/epoch

    # uninterrupted control: 3 epochs
    net_c, est_c = make()
    est_c.fit(data, epochs=3)
    w_control = net_c.weight.data().asnumpy().copy()
    loss_control = est_c.train_loss_metric.get()[1]

    class _Killer(BatchEnd):
        priority = -5000  # before ResilienceHandler sees the flag

        def __init__(self):
            self.n = 0

        def batch_end(self, estimator, *a, **k):
            self.n += 1
            if self.n == 3:  # first batch of epoch 1: mid-epoch
                os.kill(os.getpid(), signal.SIGTERM)

    net1, est1 = make()
    mgr = ckpt.CheckpointManager(str(tmp_path), keep_last_n=5)
    h1 = ResilienceHandler(str(tmp_path), manager=mgr)
    est1.fit(data, epochs=3, event_handlers=[h1, _Killer()])
    assert est1.stop_training
    assert telemetry.counter_value("resilience.preemptions") >= 1
    # the flush landed as a batch tag; epoch 0's boundary commit exists
    tags = [mgr.restore(step=s)[2].get("tag")
            for s in mgr.all_steps()]
    assert any(str(t).startswith("batch") for t in tags)
    assert any(str(t).startswith("epoch") for t in tags)

    # resume in a FRESH process-equivalent (different seed: restore
    # must overwrite), running the remaining epochs
    net2, est2 = make(seed=42)
    h2 = ResilienceHandler(str(tmp_path), manager=mgr)
    h2.train_begin(est2)  # probe: resume restores epoch-0 state
    assert h2.trained_epoch == 0 and h2.current_epoch == 1
    est2.fit(data, epochs=2, event_handlers=[h2])  # epochs 1 and 2
    onp.testing.assert_array_equal(net2.weight.data().asnumpy(),
                                   w_control)
    assert math.isclose(est2.train_loss_metric.get()[1], loss_control,
                        rel_tol=0, abs_tol=0)
    mgr.close()


def test_resilience_handler_reuse_after_preemption(tmp_path):
    """Review regression: a preempted fit left _preempted_stop set, so
    a RESUMED fit on the same handler instance silently skipped every
    epoch_end checkpoint forever — resume points never advanced."""
    from mxnet_tpu.gluon.contrib.estimator.event_handler import (
        ResilienceHandler)

    mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False)
    h = ResilienceHandler(str(tmp_path), manager=mgr)
    h._preempted_stop = True  # state left by a preempted fit

    class _Est:
        net = None
        trainer = None
        stop_training = False
    h.train_begin(_Est())
    assert h._preempted_stop is False
    mgr.close()


def test_resilience_handler_resume_fallback_when_epochs_evicted(
        tmp_path):
    """Review regression: retention (keep_last_n) can GC-evict every
    epoch-boundary commit in a preemption-heavy window of batch-tag
    flushes; resume must then fall back to the latest commit with
    tag-aware accounting instead of silently restarting from random
    init."""
    from mxnet_tpu.gluon.contrib.estimator.event_handler import (
        ResilienceHandler)

    net = nn.Dense(2, in_units=4)
    net.initialize(mx.init.Xavier())
    tree, meta = ckpt.capture_training_state(net=net)
    want = net.weight.data().asnumpy().copy()

    mgr = ckpt.CheckpointManager(str(tmp_path), keep_last_n=2,
                                 async_save=False)
    mgr.save(2, tree, metadata=dict(meta, epoch=0, batch=2,
                                    tag="epoch0"))
    # two preemption flushes evict the epoch commit (keep_last_n=2)
    mgr.save(3, tree, metadata=dict(meta, epoch=1, batch=3,
                                    tag="batch3", preempted=True))
    mgr.save(4, tree, metadata=dict(meta, epoch=1, batch=4,
                                    tag="batch4", preempted=True))
    assert mgr.all_steps() == [3, 4]

    net2 = nn.Dense(2, in_units=4)
    mx.np.random.seed(99)
    net2.initialize(mx.init.Xavier(), force_reinit=True)
    h = ResilienceHandler(str(tmp_path), manager=mgr)

    class _Est:
        net = net2
        trainer = None
    h._resume(_Est())
    # fell back to the latest batch-tag commit: params restored,
    # interrupted epoch NOT counted trained
    onp.testing.assert_array_equal(net2.weight.data().asnumpy(), want)
    assert h.trained_epoch == 0 and h.current_epoch == 1
    mgr.close()


def test_estimator_fit_exception_restores_signal_handlers(tmp_path):
    """Review regression: an exception inside fit skipped train_end,
    leaking the handler's SIGTERM/SIGINT handlers for the life of the
    process (Ctrl+C permanently disabled)."""
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    from mxnet_tpu.gluon.contrib.estimator.event_handler import (
        BatchEnd, ResilienceHandler)

    net = nn.Dense(2, in_units=4)
    net.initialize(mx.init.Xavier())
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    trainer=gluon.Trainer(net.collect_params(), "sgd"))
    data = [(mnp.zeros((4, 4)), mnp.zeros((4,), dtype="int32"))]

    class _Boom(BatchEnd):
        def batch_end(self, estimator, *a, **k):
            raise RuntimeError("boom")

    prev_term = signal.getsignal(signal.SIGTERM)
    prev_int = signal.getsignal(signal.SIGINT)
    mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False)
    h = ResilienceHandler(str(tmp_path), manager=mgr)
    with pytest.raises(RuntimeError, match="boom"):
        est.fit(data, epochs=1, event_handlers=[h, _Boom()])
    assert signal.getsignal(signal.SIGTERM) is prev_term
    assert signal.getsignal(signal.SIGINT) is prev_int
    mgr.close()


def test_estimator_train_begin_failure_still_cleans_up(tmp_path):
    """Review regression: a LATER handler's train_begin raising left
    the already-installed signal handlers leaked — train_begin must
    run inside the same run_on_error guard as the fit loop."""
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    from mxnet_tpu.gluon.contrib.estimator.event_handler import (
        ResilienceHandler, TrainBegin)

    net = nn.Dense(2, in_units=4)
    net.initialize(mx.init.Xavier())
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    trainer=gluon.Trainer(net.collect_params(), "sgd"))

    class _BoomBegin(TrainBegin):
        priority = 100  # after ResilienceHandler installed handlers

        def train_begin(self, estimator, *a, **k):
            raise RuntimeError("begin boom")

    prev_term = signal.getsignal(signal.SIGTERM)
    mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False)
    h = ResilienceHandler(str(tmp_path), manager=mgr)
    with pytest.raises(RuntimeError, match="begin boom"):
        est.fit([(mnp.zeros((4, 4)), mnp.zeros((4,), dtype="int32"))],
                epochs=1, event_handlers=[h, _BoomBegin()])
    assert signal.getsignal(signal.SIGTERM) is prev_term
    mgr.close()


def test_estimator_train_end_failure_still_cleans_up(tmp_path):
    """Review regression: an EARLIER handler's train_end raising on
    the success path skipped later run_on_error handlers, leaking the
    signal handlers again."""
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    from mxnet_tpu.gluon.contrib.estimator.event_handler import (
        ResilienceHandler, TrainEnd)

    net = nn.Dense(2, in_units=4)
    net.initialize(mx.init.Xavier())
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    trainer=gluon.Trainer(net.collect_params(), "sgd"))

    class _BoomEnd(TrainEnd):
        priority = -10  # runs before ResilienceHandler's train_end

        def train_end(self, estimator, *a, **k):
            raise RuntimeError("end boom")

    prev_term = signal.getsignal(signal.SIGTERM)
    mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False)
    h = ResilienceHandler(str(tmp_path), manager=mgr)
    with pytest.raises(RuntimeError, match="end boom"):
        est.fit([(mnp.zeros((4, 4)), mnp.zeros((4,), dtype="int32"))],
                epochs=1, event_handlers=[h, _BoomEnd()])
    assert signal.getsignal(signal.SIGTERM) is prev_term
    mgr.close()


def test_supervisor_empty_epoch_errors_instead_of_spinning():
    """Review regression: an iterator whose epochs yield zero batches
    (dataset < batch_size under 'discard') made _next_batch spin
    forever; it must error out."""
    data = onp.arange(4, dtype="f4").reshape(2, 2)
    it = io.NDArrayIter(data, batch_size=4,
                        last_batch_handle="discard")
    sup = TrainSupervisor(tempfile.mkdtemp(),
                          step_fn=lambda b: 0.5, data_iter=it,
                          handle_signals=False, watchdog=False,
                          max_restarts=0)
    with pytest.raises(TrainingAborted):
        sup.supervise(3)


def test_dataloader_skip_does_not_touch_inflight_epoch():
    """Review regression: skip_batches() armed mid-epoch used to eat
    batches out of the CURRENT epoch's stream; the count must be
    claimed at __iter__ time, leaving an in-flight iterator whole."""
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    ds = ArrayDataset(mnp.arange(16.0).reshape(8, 2))
    dl = DataLoader(ds, batch_size=2, prefetch=0)
    full = [b.asnumpy() for b in dl]
    it = iter(dl)
    first = next(it).asnumpy()
    dl.skip_batches(2)          # armed mid-epoch: affects NEXT epoch
    rest = [b.asnumpy() for b in it]
    onp.testing.assert_array_equal(first, full[0])
    assert len(rest) == 3       # current epoch untouched
    nxt = [b.asnumpy() for b in dl]
    assert len(nxt) == 2        # next epoch starts at batch 2
    onp.testing.assert_array_equal(nxt[0], full[2])


def test_dataloader_abandoned_iterator_drops_its_skip():
    """Review regression: an abandoned epoch iterator's finally block
    used to re-arm its unconsumed skip remainder at GC time, silently
    dropping batches from an arbitrary later epoch."""
    import gc
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    ds = ArrayDataset(mnp.arange(16.0).reshape(8, 2))
    dl = DataLoader(ds, batch_size=2, prefetch=0)
    dl.skip_batches(3)
    it1 = iter(dl)  # claims the 3, never consumed
    del it1
    gc.collect()
    assert len([b for b in dl]) == 4  # later epochs stay whole
    assert dl._skip_next == 0


def test_supervisor_report_signal_not_stale(tmp_path):
    """Review regression: a resumed run that completed used to report
    the PREVIOUS preemption's signal number."""
    inj = TrainFaultInjector([TrainFaultRule("preempt", at_step=5)])
    net, tr, loss_fn, it = _make_run()
    sup = TrainSupervisor(str(tmp_path), net=net, trainer=tr,
                          loss_fn=loss_fn, data_iter=it, save_every=5,
                          injector=inj)
    rep = sup.supervise(8)
    assert rep["status"] == "preempted" and rep["signal"] is not None
    # same-instance resume (the owned manager must still be open —
    # drive-verified regression) commits its final step cleanly
    rep2 = sup.supervise(8)
    assert rep2["status"] == "done" and rep2["signal"] is None
    assert "save_error" not in rep2
    mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False)
    assert mgr.latest_step() == 8
    mgr.close()
    sup.close()


def test_manager_read_metadata_without_shard_reads(tmp_path):
    """read_metadata answers tag/epoch inspection from the manifest
    alone — no shard I/O, no CRC pass."""
    class _CountingFS(ckpt.LocalFS):
        shard_reads = 0

        def read_bytes(self, path):
            if os.path.basename(path).startswith("shard_"):
                type(self).shard_reads += 1
            return super().read_bytes(path)

    mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False,
                                 fs=_CountingFS())
    mgr.save(4, {"w": mnp.arange(6.0)._data},
             metadata={"tag": "epoch1", "epoch": 1})
    assert mgr.read_metadata(4)["tag"] == "epoch1"
    assert _CountingFS.shard_reads == 0
    with pytest.raises(ckpt.CheckpointCorruptError):
        mgr.read_metadata(99)
    mgr.close()


def test_supervisor_final_save_recovers_synchronously(tmp_path):
    """Review regression: the final periodic async save was recorded
    as done when merely queued — if it then failed, the sync fallback
    was skipped and the run ended without its final commit. The flush
    must retry synchronously from the in-memory state."""
    class _FlakyFinalFS(ckpt.LocalFS):
        failures = 0

        def write_bytes(self, path, data):
            # fail the FIRST write attempt into step_12 (the async
            # writer); the sync retry then succeeds
            if "step_00000012" in path and type(self).failures < 1:
                type(self).failures += 1
                raise OSError("injected final-save failure")
            super().write_bytes(path, data)

    net, tr, loss_fn, it = _make_run()
    mgr = ckpt.CheckpointManager(str(tmp_path), max_retries=0,
                                 fs=_FlakyFinalFS())
    sup = TrainSupervisor(mgr, net=net, trainer=tr, loss_fn=loss_fn,
                          data_iter=it, save_every=6,
                          handle_signals=False)
    rep = sup.supervise(12)  # 12 % 6 == 0: final save is the async one
    assert rep["status"] == "done"
    assert "recovered" in rep.get("save_error", "")
    assert mgr.latest_step() == 12  # the sync retry committed it
    mgr.close()


# ---------------------------------------------------------------------------
# slow soak
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_multi_kill_soak(tmp_path):
    """Process-level chaos: a respawn loop SIGKILLs the training run
    twice at deterministic steps, then lets it finish — the final
    params must be bitwise identical to an uninterrupted in-process
    control run (the full preemption story end-to-end)."""
    script = r"""
import os, sys, json
import tpu_platform; tpu_platform.force_cpu(n_devices=2)
import numpy as onp
import mxnet_tpu as mx
from mxnet_tpu import gluon, io, resilience, autograd
from mxnet_tpu.gluon import nn

def make():
    mx.np.random.seed(7); onp.random.seed(7)
    net = nn.Sequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.05})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    data = onp.random.RandomState(0).randn(40, 8).astype("f4")
    label = onp.random.RandomState(1).randint(0, 4, 40).astype("i4")
    it = io.NDArrayIter(data, label, batch_size=4, shuffle=True)
    return net, tr, loss_fn, it

mode = sys.argv[1]
net, tr, loss_fn, it = make()
if mode == "control":
    for _ in range(30):
        try: b = it.next()
        except StopIteration:
            it.reset(); b = it.next()
        with autograd.record():
            loss = loss_fn(net(b.data[0]), b.label[0]).mean()
        loss.backward(); tr.step(4)
else:
    inj = resilience.TrainFaultInjector.from_spec(
        os.environ.get("SOAK_FAULTS", ""))
    sup = resilience.TrainSupervisor(
        sys.argv[2], net=net, trainer=tr, loss_fn=loss_fn,
        data_iter=it, save_every=5, injector=inj)
    rep = sup.supervise(30)
    if rep["status"] != "done":
        sys.exit(3)
import hashlib
h = hashlib.sha256()
for name in sorted(net.collect_params()):
    h.update(net.collect_params()[name].data().asnumpy().tobytes())
print(json.dumps({"digest": h.hexdigest()}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def run(mode, faults=""):
        return subprocess.run(
            [sys.executable, "-c", script, mode, str(tmp_path)],
            cwd=REPO, env=dict(env, SOAK_FAULTS=faults), timeout=240,
            capture_output=True, text=True)

    control = run("control")
    assert control.returncode == 0, control.stderr[-800:]
    want = [l for l in control.stdout.splitlines()
            if l.startswith("{")][-1]

    rcs = []
    final = None
    for faults in ("kill@8", "kill@19", ""):
        out = run("chaos", faults)
        rcs.append(out.returncode)
        if out.returncode == 0:
            final = [l for l in out.stdout.splitlines()
                     if l.startswith("{")][-1]
            break
        assert out.returncode == -signal.SIGKILL, out.stderr[-800:]
    assert rcs[:2] == [-signal.SIGKILL, -signal.SIGKILL]
    assert final is not None and final == want
