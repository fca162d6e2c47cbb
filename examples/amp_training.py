"""Automatic mixed precision (parity: the reference's AMP tutorial,
example/automatic-mixed-precision): `amp.init()` turns on cast-list
autocast at op dispatch; fp16 adds dynamic loss scaling through
`amp.init_trainer` + `amp.scale_loss`."""
from __future__ import annotations

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run from anywhere

import argparse

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, np
from mxnet_tpu.gluon import nn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float16"])
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args()

    amp.init(target_dtype=args.dtype)

    rng = onp.random.RandomState(0)
    protos = rng.rand(4, 32).astype("float32")
    y = rng.randint(0, 4, 256)
    x = protos[y] + 0.1 * rng.rand(256, 32).astype("float32")

    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-2})
    if args.dtype == "float16":
        amp.init_trainer(trainer)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    losses = []
    for s in range(args.steps):
        i = (s * 32) % 224
        d, l = np.array(x[i:i + 32]), np.array(y[i:i + 32].astype("int32"))
        with autograd.record():
            loss = loss_fn(net(d), l).mean()
            if args.dtype == "float16":
                with amp.scale_loss(loss, trainer) as scaled:
                    scaled.backward()
            else:
                loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    print(f"{args.dtype}: loss {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
