"""Gluon block/layer tests (model: tests/python/unittest/test_gluon.py)."""
import os

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np, npx, autograd, gluon
from mxnet_tpu.gluon import nn


def test_dense_shapes_and_deferred_init():
    layer = nn.Dense(5)
    layer.initialize()
    x = np.random.uniform(size=(4, 3))
    out = layer(x)
    assert out.shape == (4, 5)
    assert layer.weight.shape == (5, 3)
    assert layer.bias.shape == (5,)


def test_dense_no_flatten_and_activation():
    layer = nn.Dense(7, flatten=False, activation="relu", in_units=3)
    layer.initialize()
    x = np.random.normal(size=(2, 6, 3))
    out = layer(x)
    assert out.shape == (2, 6, 7)
    assert float(out.min().item()) >= 0.0


def test_sequential_and_collect_params():
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(8))
    net.initialize()
    x = np.random.uniform(size=(2, 4))
    net(x)
    params = net.collect_params()
    assert set(params.keys()) == {"0.weight", "0.bias", "1.weight", "1.bias"}
    assert params["0.weight"].shape == (16, 4)


def test_save_load_parameters(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(2))
    net.initialize()
    x = np.random.uniform(size=(1, 3))
    y0 = net(x).asnumpy()
    f = str(tmp_path / "net.params")
    net.save_parameters(f)

    net2 = nn.HybridSequential()
    net2.add(nn.Dense(4), nn.Dense(2))
    net2.load_parameters(f)
    y1 = net2(x).asnumpy()
    onp.testing.assert_allclose(y0, y1, rtol=1e-6)


def test_conv2d_and_pooling():
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, kernel_size=3, padding=1, activation="relu"),
            nn.MaxPool2D(2),
            nn.Conv2D(4, kernel_size=1),
            nn.GlobalAvgPool2D())
    net.initialize()
    x = np.random.uniform(size=(2, 3, 8, 8))
    out = net(x)
    assert out.shape == (2, 4, 1, 1)
    assert net[0].weight.shape == (8, 3, 3, 3)


def test_conv_groups_depthwise():
    layer = nn.Conv2D(6, kernel_size=3, groups=3, in_channels=3, padding=1)
    layer.initialize()
    out = layer(np.ones((1, 3, 5, 5)))
    assert out.shape == (1, 6, 5, 5)
    assert layer.weight.shape == (6, 1, 3, 3)


def test_conv_transpose():
    layer = nn.Conv2DTranspose(4, kernel_size=2, strides=2, in_channels=3)
    layer.initialize()
    out = layer(np.ones((1, 3, 5, 5)))
    assert out.shape == (1, 4, 10, 10)


def test_batchnorm_train_vs_eval():
    bn = nn.BatchNorm(in_channels=3)
    bn.initialize()
    x = np.random.normal(2.0, 3.0, size=(8, 3, 4, 4))
    with autograd.record():
        y = bn(x)
    # normalized activations: near zero mean / unit var per channel
    a = y.asnumpy()
    assert abs(a.mean()) < 0.1
    assert abs(a.std() - 1.0) < 0.1
    # running stats moved toward batch stats
    rm = bn.running_mean.data().asnumpy()
    assert abs(rm.mean() - 0.2) < 0.15  # 0.9*0 + 0.1*~2.0
    y_eval = bn(x)
    assert y_eval.shape == x.shape


def test_layernorm_groupnorm_instancenorm():
    x = np.random.normal(size=(2, 6, 4))
    ln = nn.LayerNorm()
    ln.initialize()
    out = ln(x).asnumpy()
    onp.testing.assert_allclose(out.mean(axis=-1), 0, atol=1e-5)
    gn = nn.GroupNorm(num_groups=3)
    gn.initialize()
    assert gn(x).shape == x.shape
    inorm = nn.InstanceNorm()
    inorm.initialize()
    assert inorm(x).shape == x.shape


def test_embedding():
    emb = nn.Embedding(10, 4)
    emb.initialize()
    idx = np.array([[1, 2], [3, 4]], dtype="int32")
    out = emb(idx)
    assert out.shape == (2, 2, 4)


def test_gradient_flow_through_net():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="tanh"), nn.Dense(1))
    net.initialize()
    x = np.random.uniform(size=(4, 3))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    for name, p in net.collect_params().items():
        g = p.grad().asnumpy()
        assert onp.isfinite(g).all(), name
    assert onp.abs(net[0].weight.grad().asnumpy()).sum() > 0


def test_trainer_sgd_converges():
    # linear regression closed-form check: loss should drop fast
    onp.random.seed(0)
    w_true = onp.array([[2.0], [-3.0]])
    X = onp.random.randn(128, 2).astype(onp.float32)
    Y = (X @ w_true).astype(onp.float32)

    net = nn.Dense(1, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    loss_fn = gluon.loss.L2Loss()
    first = last = None
    for _ in range(50):
        x, y = np.array(X), np.array(Y)
        with autograd.record():
            l = loss_fn(net(x), y).mean()
        l.backward()
        trainer.step(1)
        last = float(l.item())
        if first is None:
            first = last
    assert last < first * 0.01, (first, last)
    onp.testing.assert_allclose(net.weight.data().asnumpy(), w_true.T,
                                atol=0.05)


def test_trainer_save_load_states(tmp_path):
    net = nn.Dense(2, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    x = np.ones((1, 2))
    with autograd.record():
        l = net(x).sum()
    l.backward()
    trainer.step(1)
    f = str(tmp_path / "trainer.states")
    trainer.save_states(f)
    trainer2 = gluon.Trainer(net.collect_params(), "adam",
                             {"learning_rate": 0.01})
    trainer2.load_states(f)
    assert trainer2._optimizer.num_update == 1


@pytest.mark.parametrize("loss_cls,pred_shape,label_shape", [
    (gluon.loss.L2Loss, (4, 3), (4, 3)),
    (gluon.loss.L1Loss, (4, 3), (4, 3)),
    (gluon.loss.HuberLoss, (4, 3), (4, 3)),
    (gluon.loss.HingeLoss, (4, 3), (4, 3)),
    (gluon.loss.SquaredHingeLoss, (4, 3), (4, 3)),
    (gluon.loss.LogisticLoss, (4,), (4,)),
])
def test_losses_shapes(loss_cls, pred_shape, label_shape):
    loss = loss_cls()
    pred = np.random.normal(size=pred_shape)
    label = np.random.normal(size=label_shape)
    out = loss(pred, label)
    assert out.shape[0] == pred_shape[0]
    assert onp.isfinite(out.asnumpy()).all()


def test_softmax_ce_loss_matches_manual():
    pred = np.random.normal(size=(5, 4))
    label = np.array([0, 1, 2, 3, 0], dtype="int64")
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    out = loss(pred, label).asnumpy()
    p = pred.asnumpy()
    logp = p - onp.log(onp.exp(p - p.max(1, keepdims=True)).sum(1, keepdims=True)) - p.max(1, keepdims=True)
    manual = -logp[onp.arange(5), label.asnumpy().astype(int)]
    onp.testing.assert_allclose(out, manual, rtol=1e-4)


def test_sigmoid_bce_loss():
    pred = np.random.normal(size=(4, 3))
    label = (np.random.uniform(size=(4, 3)) > 0.5).astype("float32")
    loss = gluon.loss.SigmoidBinaryCrossEntropyLoss()
    out = loss(pred, label).asnumpy()
    p = 1 / (1 + onp.exp(-pred.asnumpy()))
    manual = -(label.asnumpy() * onp.log(p) +
               (1 - label.asnumpy()) * onp.log(1 - p)).mean(axis=1)
    onp.testing.assert_allclose(out, manual, rtol=1e-4)


def test_ctc_loss_runs():
    pred = np.random.uniform(size=(2, 20, 30))
    label = np.array(onp.random.randint(1, 30, size=(2, 10)).astype("float32"))
    loss = gluon.loss.CTCLoss()
    out = loss(pred, label)
    assert out.shape == (2,)
    assert onp.isfinite(out.asnumpy()).all()


def test_metrics():
    from mxnet_tpu.gluon import metric
    acc = metric.Accuracy()
    acc.update(np.array([1, 0, 1]), np.array([[0.2, 0.8], [0.9, 0.1],
                                              [0.4, 0.6]]))
    assert acc.get()[1] == 1.0
    topk = metric.TopKAccuracy(top_k=2)
    topk.update(np.array([2]), np.array([[0.3, 0.2, 0.25]]))
    assert topk.get()[1] == 1.0
    mae = metric.create("mae")
    mae.update(np.array([1., 2.]), np.array([2., 3.]))
    assert abs(mae.get()[1] - 1.0) < 1e-6
    comp = metric.CompositeEvalMetric()
    comp.add(metric.Accuracy())
    comp.add(metric.CrossEntropy())
    comp.update(np.array([1]), np.array([[0.1, 0.9]]))
    names, values = comp.get()
    assert len(names) == 2
    assert values[0] == 1.0


def test_block_cast():
    net = nn.Dense(3, in_units=2)
    net.initialize()
    net.cast("float16")
    assert net.weight.data().dtype == onp.float16
    out = net(np.ones((1, 2), dtype="float16"))
    assert out.dtype == onp.float16


def test_x64_opt_in():
    """float64 is opt-in via MXTPU_ENABLE_X64 (kept off by default so
    TPU hot paths never silently hit emulated f64)."""
    import subprocess
    import sys
    code = ("import mxnet_tpu as mx; "
            "a = mx.np.array([1.0], dtype='float64'); "
            "print(a.dtype)")
    env = dict(os.environ, MXTPU_ENABLE_X64="1", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "float64" in out.stdout


def test_dataloader_and_dataset():
    X = onp.random.randn(37, 5).astype(onp.float32)
    Y = onp.arange(37).astype(onp.int64)
    ds = gluon.data.ArrayDataset(X, Y)
    assert len(ds) == 37
    loader = gluon.data.DataLoader(ds, batch_size=8, shuffle=True,
                                   last_batch="keep")
    seen = 0
    for xb, yb in loader:
        assert xb.shape[1] == 5
        seen += xb.shape[0]
    assert seen == 37
    # discard mode drops the tail
    loader2 = gluon.data.DataLoader(ds, batch_size=8, last_batch="discard")
    assert sum(x.shape[0] for x, _ in loader2) == 32
    # num_workers path
    loader3 = gluon.data.DataLoader(ds, batch_size=8, num_workers=2)
    assert sum(x.shape[0] for x, _ in loader3) == 37


def test_transforms_compose():
    from mxnet_tpu.gluon.data.vision import transforms
    t = transforms.Compose([transforms.ToTensor(),
                            transforms.Normalize(0.5, 0.5)])
    img = np.array((onp.random.rand(8, 8, 3) * 255).astype(onp.uint8))
    out = t(img)
    assert out.shape == (3, 8, 8)
    assert out.dtype == onp.float32


def test_split_and_load():
    data = np.arange(12).reshape(6, 2)
    parts = gluon.utils.split_and_load(data, [mx.cpu(), mx.cpu()])
    assert len(parts) == 2 and parts[0].shape == (3, 2)


def test_clip_global_norm():
    arrays = [np.ones((3,)) * 3, np.ones((4,)) * 4]
    total = gluon.utils.clip_global_norm(arrays, 1.0)
    norm = onp.sqrt(sum((a.asnumpy() ** 2).sum() for a in arrays))
    assert norm <= 1.01


def test_batchify_stack_pad_group():
    """batchify.Stack/Pad/Group (parity: gluon/data/batchify.py);
    Pad(round_to) is the TPU static-shape bucketing knob."""
    from mxnet_tpu.gluon.data import batchify

    s = batchify.Stack()([[1, 2], [3, 4]])
    assert s.shape == (2, 2)

    p = batchify.Pad(val=0)([[1, 2, 3, 4], [4, 5, 6], [8, 2]])
    onp.testing.assert_array_equal(
        p.asnumpy(), [[1, 2, 3, 4], [4, 5, 6, 0], [8, 2, 0, 0]])

    pr = batchify.Pad(val=-1, round_to=8)([[1, 2, 3]])
    assert pr.shape == (1, 8)
    assert pr.asnumpy()[0, 3] == -1

    p2 = batchify.Pad(val=-1)([onp.array([[1, 2, 3, 4], [5, 6, 7, 8]]),
                               onp.array([[5, 8], [1, 2]])])
    assert p2.shape == (2, 2, 4)
    assert p2.asnumpy()[1, 0].tolist() == [5, 8, -1, -1]

    g = batchify.Group(batchify.Stack(), batchify.Pad(val=0))
    data, labels = g([([1, 2], [1]), ([3, 4], [2, 3])])
    assert data.shape == (2, 2) and labels.shape == (2, 2)
    with pytest.raises(ValueError):
        g([([1], [2], [3])])

    # DataLoader integration
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    ds = SimpleSeqDataset()
    dl = DataLoader(ds, batch_size=2,
                    batchify_fn=batchify.Pad(val=0, round_to=4))
    batch = next(iter(dl))
    assert batch.shape[1] == 4


class SimpleSeqDataset:
    _data = [[1.0, 2.0], [3.0], [1.0, 2.0, 3.0], [4.0]]

    def __len__(self):
        return len(self._data)

    def __getitem__(self, i):
        return self._data[i]


def test_vision_transforms_extended():
    """CropResize/RandomGray/RandomHue/Rotate/RandomRotation/
    RandomApply/HybridCompose (parity: gluon/data/vision/transforms)."""
    from mxnet_tpu.gluon.data.vision import transforms as T
    img = np.array(onp.random.RandomState(0).randint(
        0, 255, (32, 48, 3)).astype(onp.uint8))
    cr = T.CropResize(4, 2, 20, 16, size=(10, 8))(img)
    assert cr.shape == (8, 10, 3)
    g = T.RandomGray(p=1.0)(img)
    onp.testing.assert_allclose(g.asnumpy()[..., 0], g.asnumpy()[..., 1])
    assert T.RandomHue(0.2)(img).shape == img.shape
    # rotating a SQUARE image 4x90 degrees returns the original
    # (PIL keeps the canvas, so non-square content would be cropped)
    sq = np.array(onp.random.RandomState(1).randint(
        0, 255, (32, 32, 3)).astype(onp.uint8))
    r = sq
    for _ in range(4):
        r = T.Rotate(90)(r)
    onp.testing.assert_allclose(r.asnumpy(), sq.asnumpy(), atol=2)
    assert T.RandomRotation(15)(img).shape == img.shape
    skip = T.RandomApply(T.RandomGray(p=1.0), p=0.0)(img)
    onp.testing.assert_array_equal(skip.asnumpy(), img.asnumpy())
    hc = T.HybridCompose([T.Cast("float32"), T.Normalize(0.0, 255.0)])
    assert float(hc(img).asnumpy().max()) <= 1.0
