"""Model-zoo ResNet on CIFAR-shaped data with the fused TrainStep
(parity: example/gluon/image_classification.py, the reference's
multi-GPU training example — here the dp axis is a jax.sharding mesh).

Shows the TPU-first throughput path: hybridized whole-graph step,
bf16 params, optional bulk mode (N steps per XLA program)."""
from __future__ import annotations

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run from anywhere

import argparse

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import gluon, np, parallel


def synthetic_cifar(n=2048):
    rng = onp.random.RandomState(0)
    protos = rng.rand(10, 32, 32, 3).astype("float32")
    y = rng.randint(0, 10, n)
    x = protos[y] + 0.05 * rng.rand(n, 32, 32, 3).astype("float32")
    return x, y.astype("int32")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--model", default="resnet18_v1")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--bulk", type=int, default=0,
                    help="steps per XLA program (0 = stepwise)")
    args = ap.parse_args()

    import jax
    n_dev = jax.local_device_count()
    mesh = parallel.make_mesh((n_dev,), ("dp",))
    parallel.set_mesh(mesh)

    net = getattr(gluon.model_zoo.vision, args.model)(
        classes=10, layout="NHWC")
    net.initialize(mx.init.Xavier())
    if args.bf16:
        net.cast("bfloat16")

    step = parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                          "multi_precision": args.bf16},
        mesh=mesh, batch_axis="dp")

    x, y = synthetic_cifar()
    bs = args.batch_size
    dtype = "bfloat16" if args.bf16 else "float32"
    steps = len(x) // bs
    for epoch in range(args.epochs):
        losses = []
        if args.bulk > 1:
            k = args.bulk
            for s in range(0, steps - k + 1, k):
                d = np.array(x[s * bs:(s + k) * bs].reshape(
                    k, bs, 32, 32, 3), dtype=dtype)
                l = np.array(y[s * bs:(s + k) * bs].reshape(k, bs))
                losses.extend(step.run_chain(d, l).asnumpy().tolist())
        else:
            for s in range(steps):
                d = np.array(x[s * bs:(s + 1) * bs], dtype=dtype)
                l = np.array(y[s * bs:(s + 1) * bs])
                losses.append(float(step(d, l).asnumpy()))
        print(f"epoch {epoch}: first loss {losses[0]:.4f} "
              f"last loss {losses[-1]:.4f} ({len(losses)} steps)")
    return losses[-1]


if __name__ == "__main__":
    main()
