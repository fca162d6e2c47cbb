"""Fused TrainStep: single-program forward+backward+update, with and
without a device mesh (dp batch sharding + tp param sharding)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np, gluon, parallel
from mxnet_tpu.gluon import nn
from jax.sharding import PartitionSpec as P


def _data(n=64, d=16, classes=4, seed=0):
    rng = onp.random.RandomState(seed)
    protos = rng.randn(classes, d).astype(onp.float32)
    y = rng.randint(0, classes, size=n)
    x = protos[y] + 0.1 * rng.randn(n, d).astype(onp.float32)
    return np.array(x), np.array(y.astype(onp.int32))


def _mlp(classes=4):
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(classes))
    net.initialize(mx.init.Xavier())
    return net


def test_train_step_single_device():
    x, y = _data()
    net = _mlp()
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              "adam", {"learning_rate": 0.01}, mesh=None)
    losses = [float(step(x, y)) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.5


def test_train_step_matches_imperative():
    """One fused step == record/backward/trainer.step with same init."""
    x, y = _data(n=32)
    net_a, net_b = _mlp(), _mlp()
    net_a(x), net_b(x)  # materialize deferred shapes
    # copy weights so both start identical
    for (ka, pa), (kb, pb) in zip(net_a.collect_params().items(),
                                  net_b.collect_params().items()):
        pb.set_data(pa.data().copy())  # real copy: TrainStep donates buffers
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = parallel.TrainStep(net_a, loss_fn, "sgd",
                              {"learning_rate": 0.1}, mesh=None)
    step(x, y)

    trainer = gluon.Trainer(net_b.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    with mx.autograd.record():
        loss = loss_fn(net_b(x), y).mean()
    loss.backward()
    trainer.step(1)

    for (ka, pa), (kb, pb) in zip(net_a.collect_params().items(),
                                  net_b.collect_params().items()):
        onp.testing.assert_allclose(pa.data().asnumpy(),
                                    pb.data().asnumpy(),
                                    rtol=2e-5, atol=2e-6)


def test_train_step_mesh_dp_tp():
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = parallel.make_mesh((4, 2), ("dp", "tp"))
    x, y = _data(n=64)
    net = _mlp()
    with parallel.mesh_scope(mesh):
        step = parallel.TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            "sgd", {"learning_rate": 0.1},
            param_rules=[(r"\.weight$", P("tp", None))])
        losses = [float(step(x, y)) for _ in range(20)]
    assert losses[-1] < losses[0] * 0.7
    # parameter really landed sharded over tp
    w = net[0].weight.data()._data
    assert w.sharding.spec == P("tp", None)
    assert len(set(d.id for d in w.sharding.device_set)) == 8


def test_parallel_allreduce_is_real_reduction():
    """parallel.allreduce must SUM across the mesh axis, not just
    re-lay-out (round-2 VERDICT Weak #8)."""
    import jax
    import numpy as onp
    from jax.sharding import NamedSharding, PartitionSpec as P
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    mesh = parallel.make_mesh((8,), ("dp",))
    old = parallel.get_mesh()
    parallel.set_mesh(mesh)
    try:
        host = onp.concatenate(
            [onp.full((2, 3), i + 1.0, onp.float32) for i in range(8)])
        a = mx.np.array(host)
        a._install(jax.device_put(a._data, NamedSharding(mesh, P("dp"))))
        parallel.allreduce(a, axis_name="dp")
        assert a.shape == (2, 3)
        onp.testing.assert_allclose(a.asnumpy(),
                                    onp.full((2, 3), 36.0))
        b = mx.np.ones((4,))
        parallel.allreduce(b, axis_name="dp")
        onp.testing.assert_allclose(b.asnumpy(), onp.full((4,), 8.0))
        c = mx.np.array(host)
        c._install(jax.device_put(c._data, NamedSharding(mesh, P("dp"))))
        parallel.allreduce(c, op="max", axis_name="dp")
        onp.testing.assert_allclose(c.asnumpy(), onp.full((2, 3), 8.0))
    finally:
        parallel.set_mesh(old)


def test_run_chain_matches_sequential_steps():
    """Bulk mode (lax.scan of N steps in one XLA program) must land on
    the same parameters and losses as N sequential step() calls —
    including BatchNorm running-stat threading and Adam t advance."""
    import copy

    def _bn_net():
        net = nn.HybridSequential()
        net.add(nn.Dense(16), nn.BatchNorm(), nn.Dense(4))
        net.initialize(mx.init.Xavier())
        return net

    n_steps, batch = 4, 16
    x, y = _data(n=n_steps * batch)
    xs = x.asnumpy().reshape(n_steps, batch, -1)
    ys = y.asnumpy().reshape(n_steps, batch)

    mx.npx.random.seed(7) if hasattr(mx.npx, "random") else None
    net_a, net_b = _bn_net(), _bn_net()
    net_a(np.array(xs[0])), net_b(np.array(xs[0]))
    for pa, pb in zip(net_a.collect_params().values(),
                      net_b.collect_params().values()):
        pb.set_data(pa.data().copy())

    mk = lambda net: parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 0.01}, mesh=None)
    step_a, step_b = mk(net_a), mk(net_b)

    seq_losses = [float(step_a(np.array(xs[i]), np.array(ys[i])))
                  for i in range(n_steps)]
    chain_losses = step_b.run_chain(np.array(xs), np.array(ys))

    assert chain_losses.shape == (n_steps,)
    onp.testing.assert_allclose(chain_losses.asnumpy(), seq_losses,
                                rtol=2e-4, atol=2e-5)
    for (na, pa), (nb, pb) in zip(net_a.collect_params().items(),
                                  net_b.collect_params().items()):
        onp.testing.assert_allclose(
            pa.data().asnumpy(), pb.data().asnumpy(),
            rtol=2e-4, atol=2e-5, err_msg=f"{na} vs {nb}")


def test_run_chain_on_mesh():
    """Bulk mode composes with dp sharding on the virtual mesh."""
    mesh = parallel.make_mesh((8,), ("dp",))
    old = parallel.get_mesh()
    parallel.set_mesh(mesh)
    try:
        n_steps, batch = 3, 32
        x, y = _data(n=n_steps * batch)
        xs = np.array(x.asnumpy().reshape(n_steps, batch, -1))
        ys = np.array(y.asnumpy().reshape(n_steps, batch))
        net = _mlp()
        step = parallel.TrainStep(net,
                                  gluon.loss.SoftmaxCrossEntropyLoss(),
                                  "sgd", {"learning_rate": 0.1},
                                  mesh=mesh)
        l1 = step.run_chain(xs, ys).asnumpy()
        l2 = step.run_chain(xs, ys).asnumpy()
        assert l2[-1] < l1[0]
    finally:
        parallel.set_mesh(old)


def test_sharded_checkpoint_roundtrip(tmp_path):
    """save_sharded/load_sharded over a tp-sharded mesh: params +
    optimizer states survive, placement restored (no host-0 gather)."""
    mesh = parallel.make_mesh((8,), ("tp",))
    old = parallel.get_mesh()
    parallel.set_mesh(mesh)
    try:
        x, y = _data(n=32)
        net = _mlp()
        step = parallel.TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 0.01}, mesh=mesh, batch_axis="tp",
            param_rules=[(r"^0\.weight$", P("tp", None))])
        for _ in range(3):
            step(x, y)
        want = {k: p.data().asnumpy()
                for k, p in net.collect_params().items()}
        want_states = [s for s in step._opt_states]
        d = str(tmp_path / "ckpt")
        parallel.save_sharded(d, net, step=step)

        # clobber everything, then restore
        net2 = _mlp()
        step2 = parallel.TrainStep(
            net2, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 0.01}, mesh=mesh, batch_axis="tp",
            param_rules=[(r"^0\.weight$", P("tp", None))])
        step2(x, y)  # materialize opt states with the build layout
        parallel.load_sharded(d, net2, step=step2, mesh=mesh,
                              rules=[(r"^0\.weight$", P("tp", None))])
        for k, p in net2.collect_params().items():
            onp.testing.assert_allclose(p.data().asnumpy(), want[k],
                                        rtol=1e-6, err_msg=k)
        # weight placement restored as tp-sharded
        w = net2[0].weight.data()._data
        assert w.sharding.spec == P("tp", None)
        # optimizer step counters restored: Adam bias correction must
        # resume at t≈4, not restart near 1 with warm moments
        assert step2.optimizer.num_update == step.optimizer.num_update
        assert (step2.optimizer._index_update_count
                == step.optimizer._index_update_count)
        # training continues from the restored state
        l1 = float(step2(x, y).asnumpy())
        assert onp.isfinite(l1)
        assert len(step2._opt_states) == len(want_states)
        assert step2.optimizer.num_update == step.optimizer.num_update + 1
    finally:
        parallel.set_mesh(old)


@pytest.mark.parametrize("req", ["write", "add"])
def test_train_step_releases_imperative_grad_buffers(req):
    """The compiled step never touches the imperative ``.grad``
    buffers ``initialize()`` allocated (one dead array per parameter —
    3.4 GB at GPT-2 large, with which the step did not fit a 16 GB
    chip): it releases them, the parameters stay variables, and the
    next imperative backward brings full gradients back."""
    from mxnet_tpu import autograd
    x, y = _data(n=32)
    net = _mlp()
    net(x)
    params = list(net.collect_params().values())
    for p in params:
        p.grad_req = req
    assert all(p.grad().shape == p.shape for p in params)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              "sgd", {"learning_rate": 0.1}, mesh=None)
    float(step(x, y))
    assert all(p.grad().shape == () for p in params)
    assert all(p.grad_req == req for p in params)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        loss = loss_fn(net(x), y).mean()
    loss.backward()
    grads = [p.grad().asnumpy() for p in params]
    assert all(g.shape == p.shape for g, p in zip(grads, params))
    assert any(onp.abs(g).max() > 0 for g in grads)


# -- stacked hyperparameters -------------------------------------------
# The step program takes ONE (n_params,) numpy array a hyper field
# (Optimizer._stack_hypers), not one scalar a field a parameter.

_VOCAB, _SEQ = 48, 12

_HYPER_OPTS = {
    "sgd-momentum": ("sgd", {"learning_rate": 0.1, "momentum": 0.9,
                             "wd": 0.01}),
    "adam": ("adam", {"learning_rate": 0.01, "wd": 0.01}),
    "adamw-correct": ("adamw", {"learning_rate": 0.01, "wd": 0.05}),
    "adamw-nocorrect": ("adamw", {"learning_rate": 0.01, "wd": 0.05,
                                  "correct_bias": False}),
    "nag-clip": ("nag", {"learning_rate": 0.1, "momentum": 0.8,
                         "clip_gradient": 0.01}),
    "lamb-clip": ("lamb", {"learning_rate": 0.01, "wd": 0.02,
                           "clip_gradient": 0.05}),
}


def _tiny_gpt(seed=0):
    from mxnet_tpu.gluon.model_zoo.gpt import GPTModel
    mx.np.random.seed(seed)
    net = GPTModel(vocab_size=_VOCAB, units=16, num_layers=1,
                   num_heads=2, max_length=16)
    net.initialize(mx.init.Xavier())
    return net


def _lm_batch(n=8, seed=1):
    rng = onp.random.RandomState(seed)
    x = rng.randint(0, _VOCAB, (n, _SEQ + 1)).astype("i4")
    return np.array(x[:, :-1]), np.array(x[:, 1:])


def _lm_loss(out, label):
    return gluon.loss.SoftmaxCrossEntropyLoss()(
        out.reshape(-1, out.shape[-1]), label.reshape(-1))


def _uneven(opt, n):
    """Per-parameter multipliers that all differ and unequal update
    counts: a field permuted between parameters, or one stacked in the
    wrong dtype, changes some parameter's update."""
    opt.set_lr_mult({k: 0.5 + 0.125 * k for k in range(n)})
    opt.set_wd_mult({k: 2.0 - 0.0625 * k for k in range(n)})
    opt._index_update_count = {k: (3 * k) % 7 for k in range(n)}
    opt.num_update = max(opt._index_update_count.values())


def _host(tree):
    import jax
    return [onp.asarray(l) for l in jax.tree.leaves(tree)]


@pytest.mark.parametrize("layout", [None, "dp", "fsdp"])
@pytest.mark.parametrize("opt_id", sorted(_HYPER_OPTS))
def test_stacked_hypers_bitwise_equal_per_param_dicts(opt_id, layout,
                                                      monkeypatch):
    """Three TrainStep steps give BITWISE the weights and optimizer
    state of the same step_fn fed what the step took before the hypers
    were stacked: the list of ``opt._hyper(k)`` dicts, one numpy
    scalar a field a parameter."""
    import contextlib
    import jax
    if layout is not None and len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    name, kwargs = _HYPER_OPTS[opt_id]
    mesh = None if layout is None \
        else parallel.make_mesh((8,), ("dp",))
    x, y = _lm_batch()

    def mk(like=None):
        net = _tiny_gpt()
        net(x)  # materialize deferred shapes
        if like is not None:
            for pa, pb in zip(like.collect_params().values(),
                              net.collect_params().values()):
                pb.set_data(pa.data().copy())  # TrainStep donates
        step = parallel.TrainStep(
            net, _lm_loss, name, dict(kwargs), mesh=mesh,
            layout=None if layout == "dp" else layout)
        return net, step

    net_a, step_a = mk()
    net_b, step_b = mk(like=net_a)
    with parallel.mesh_scope(mesh) if mesh is not None \
            else contextlib.nullcontext():
        # -- the change: TrainStep as it is
        n = len([p for p in net_a.collect_params().values()
                 if p.grad_req != "null"])
        _uneven(step_a.optimizer, n)
        for _ in range(3):
            step_a(x, y)

        # -- the reference: the same step_fn, per-parameter dicts
        _uneven(step_b.optimizer, n)
        opt = step_b.optimizer
        ref = None
        for _ in range(3):
            entry, args, pad = step_b._prepare(x, y, None)
            dicts = [opt._hyper(k) for k in range(n)]
            assert all(isinstance(v, onp.generic) or v is None
                       or isinstance(v, float)
                       for d in dicts for v in d.values())
            if ref is None:
                kw = dict(entry["jit_kwargs"])
                if "in_shardings" in kw:
                    rep = kw["in_shardings"][0]
                    sh = list(kw["in_shardings"])
                    sh[4] = [jax.tree.map(lambda _: rep, d)
                             for d in dicts]
                    kw["in_shardings"] = tuple(sh)
                monkeypatch.setattr(
                    type(opt), "_hyper_at",
                    staticmethod(lambda hypers, k: hypers[k]))
                ref = jax.jit(entry["step_fn"], **kw)
            out = ref(*args[:4], dicts, *args[5:])
            step_b._writeback(entry, out, pad)

    assert n > 8 and len(step_a._opt_states) == n
    for (ka, pa), (kb, pb) in zip(net_a.collect_params().items(),
                                  net_b.collect_params().items()):
        onp.testing.assert_array_equal(pa.data().asnumpy(),
                                       pb.data().asnumpy(), err_msg=ka)
    for sa, sb in zip(_host(step_a._opt_states),
                      _host(step_b._opt_states)):
        onp.testing.assert_array_equal(sa, sb)
    assert step_a.optimizer._index_update_count \
        == step_b.optimizer._index_update_count


def test_run_chain_advances_the_stacked_update_counts():
    """run_chain of 3 steps equals 3 sequential calls when every
    parameter has its own update count and multipliers: the scan body
    advances ``t`` on the stacked (n,) field."""
    n_steps, batch = 3, 16
    x, y = _data(n=n_steps * batch)
    xs = x.asnumpy().reshape(n_steps, batch, -1)
    ys = y.asnumpy().reshape(n_steps, batch)
    net_a, net_b = _mlp(), _mlp()
    net_a(np.array(xs[0])), net_b(np.array(xs[0]))
    for pa, pb in zip(net_a.collect_params().values(),
                      net_b.collect_params().values()):
        pb.set_data(pa.data().copy())
    mk = lambda net: parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 0.01, "wd": 0.01}, mesh=None)
    step_a, step_b = mk(net_a), mk(net_b)
    _uneven(step_a.optimizer, 4)
    _uneven(step_b.optimizer, 4)
    seq = [float(step_a(np.array(xs[i]), np.array(ys[i])))
           for i in range(n_steps)]
    chain = step_b.run_chain(np.array(xs), np.array(ys))
    onp.testing.assert_allclose(chain.asnumpy(), seq, rtol=2e-4,
                                atol=2e-5)
    assert step_a.optimizer._index_update_count \
        == step_b.optimizer._index_update_count \
        == {k: (3 * k) % 7 + n_steps for k in range(4)}
    for (na, pa), pb in zip(net_a.collect_params().items(),
                            net_b.collect_params().values()):
        onp.testing.assert_allclose(pa.data().asnumpy(),
                                    pb.data().asnumpy(),
                                    rtol=2e-4, atol=2e-5, err_msg=na)
    for sa, sb in zip(_host(step_a._opt_states),
                      _host(step_b._opt_states)):
        onp.testing.assert_allclose(sa, sb, rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("opt_id", ["sgd-momentum", "adam",
                                    "adamw-correct", "nag-clip"])
def test_warmup_lowers_the_avals_the_call_passes(opt_id):
    """After ``warmup()`` the first ``__call__`` goes through the AOT
    executable: no fallback, no compile. The stacked fields, ``None``
    fields and AdamW's Python-float ``correct`` lower to the avals the
    call hands over."""
    import jax
    from mxnet_tpu import telemetry
    name, kwargs = _HYPER_OPTS[opt_id]
    x, y = _data(n=16)
    net = _mlp()
    net(x)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              name, dict(kwargs), mesh=None)
    step.warmup([((16, 16), (16,))])
    _uneven(step.optimizer, 4)
    telemetry.reset()
    compiles = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        losses = [float(step(x, y)) for _ in range(2)]
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    snap = telemetry.snapshot()
    assert "parallel.train_step.aot_fallback" not in snap["counters"]
    assert "parallel.train_step.build" not in snap["counters"]
    assert "parallel.train_step.compile" not in snap["durations"]
    assert snap["durations"]["parallel.train_step.run"]["count"] == 2
    assert not compiles, compiles
    assert all(onp.isfinite(l) for l in losses)


@pytest.mark.parametrize("opt_id", ["adam", "adamw-correct"])
def test_host_arg_leaves_do_not_grow_with_the_parameters(opt_id):
    """``parallel.train_step.host_arg_leaves``: the leaves of a call's
    arguments that are no device arrays. One a hyper field and
    ``n_valid``, whatever the number of parameters."""
    from mxnet_tpu import telemetry
    name, kwargs = _HYPER_OPTS[opt_id]
    x, y = _data(n=16)
    reads = {}
    for layers in (2, 20):
        net = nn.HybridSequential()
        for _ in range(layers - 1):
            net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(4))
        net.initialize(mx.init.Xavier())
        step = parallel.TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), name,
            dict(kwargs), mesh=None)
        step(x, y)
        assert len(step._opt_states) == 2 * layers
        reads[2 * layers] = telemetry.gauge_value(
            "parallel.train_step.host_arg_leaves")
    assert reads[4] == reads[40], reads
    assert 0 < reads[4] < 10, reads


def test_unstackable_hyper_field_is_refused_at_build():
    """A hyper field that is no numpy scalar is not stacked: it is ONE
    value for every parameter, and an optimizer whose `_hyper` varies
    one is told so when the program is built, not given parameter 0's
    value for all."""
    from mxnet_tpu.optimizer import SGD

    class PerParamFloat(SGD):
        def _hyper(self, index):
            h = super()._hyper(index)
            h["scale"] = 1.0 + index   # a Python float a parameter
            return h

    x, y = _data(n=16)
    net = _mlp()
    net(x)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              PerParamFloat(learning_rate=0.1), mesh=None)
    with pytest.raises(TypeError, match="'scale' differs"):
        step(x, y)
