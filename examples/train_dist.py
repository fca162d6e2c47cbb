"""Multi-process data-parallel training via kvstore `dist_sync`
(parity: example/distributed_training/cifar10_dist.py). Launch with:

    python tools/launch.py -n 2 --launcher local \
        python examples/train_dist.py --epochs 1
"""
from __future__ import annotations

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run from anywhere

import argparse

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, np, parallel
from mxnet_tpu.gluon import nn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=32)
    args = ap.parse_args()

    parallel.initialize_distributed()
    kv = mx.kvstore.create("dist_sync")
    rank, nworker = kv.rank, kv.num_workers
    print(f"worker {rank}/{nworker} up")

    rng = onp.random.RandomState(7)  # same model/data seed per worker
    protos = rng.rand(4, 16).astype("float32")
    y_all = rng.randint(0, 4, 512)
    x_all = protos[y_all] + 0.1 * rng.rand(512, 16).astype("float32")
    # shard the dataset by rank (parity: SplitSampler in the reference)
    x, y = x_all[rank::nworker], y_all[rank::nworker]

    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 5e-3}, kvstore=kv)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    bs = args.batch_size
    for epoch in range(args.epochs):
        losses = []
        for s in range(len(x) // bs):
            d = np.array(x[s * bs:(s + 1) * bs])
            l = np.array(y[s * bs:(s + 1) * bs].astype("int32"))
            with autograd.record():
                loss = loss_fn(net(d), l).mean()
            loss.backward()
            trainer.step(bs)
            losses.append(float(loss.asnumpy()))
        print(f"worker {rank} epoch {epoch}: loss "
              f"{losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
