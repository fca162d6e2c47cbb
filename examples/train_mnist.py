"""Gluon MLP on MNIST — the reference's hello-world training loop
(parity: example/gluon/mnist/mnist.py) on the imperative autograd path.

Falls back to a synthetic MNIST-shaped dataset when the real download
is unavailable (offline CI)."""
from __future__ import annotations

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run from anywhere

import argparse

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, np
from mxnet_tpu.gluon import nn


def _flatten_dataset(ds, limit=None):
    """Pre-transform once on host (batched), not per-sample on device:
    the per-sample path costs one dispatch per example."""
    xs, ys = [], []
    n = len(ds) if limit is None else min(limit, len(ds))
    for i in range(n):
        data, label = ds[i]
        a = onp.asarray(getattr(data, "asnumpy", lambda: data)())
        xs.append(a.reshape(-1))
        ys.append(int(label))
    x = onp.stack(xs).astype("float32")
    if x.max() > 1.5:  # uint8 pixel range
        x /= 255.0
    return gluon.data.ArrayDataset(
        np.array(x), np.array(onp.asarray(ys, dtype="int32")))


def load_data(batch_size, limit=2048):
    try:
        train = _flatten_dataset(gluon.data.vision.MNIST(train=True),
                                 limit)
        val = _flatten_dataset(gluon.data.vision.MNIST(train=False),
                               limit // 4)
    except Exception:
        print("MNIST unavailable; using synthetic digits")
        rng = onp.random.RandomState(0)
        protos = rng.rand(10, 28 * 28).astype("float32")
        y = rng.randint(0, 10, limit + limit // 4)
        x = (protos[y] + 0.1 * rng.rand(len(y), 28 * 28)) \
            .astype("float32")
        train = gluon.data.ArrayDataset(
            np.array(x[:limit]), np.array(y[:limit].astype("int32")))
        val = gluon.data.ArrayDataset(
            np.array(x[limit:]), np.array(y[limit:].astype("int32")))
    return (gluon.data.DataLoader(train, batch_size, shuffle=True),
            gluon.data.DataLoader(val, batch_size))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint-prefix", default=None)
    args = ap.parse_args()

    train_iter, val_iter = load_data(args.batch_size)

    net = nn.HybridSequential()
    net.add(nn.Dense(128, activation="relu"),
            nn.Dense(64, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net.hybridize()

    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = gluon.metric.Accuracy()

    for epoch in range(args.epochs):
        metric.reset()
        for data, label in train_iter:
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label).mean()
            loss.backward()
            trainer.step(1)
            metric.update(label, out)
        name, acc = metric.get()
        print(f"epoch {epoch}: train-{name}={acc:.4f}")

        metric.reset()
        for data, label in val_iter:
            metric.update(label, net(data))
        name, acc = metric.get()
        print(f"epoch {epoch}: val-{name}={acc:.4f}")

    if args.checkpoint_prefix:
        net.save_parameters(args.checkpoint_prefix + ".params")
        print("saved", args.checkpoint_prefix + ".params")
    return acc


if __name__ == "__main__":
    main()
