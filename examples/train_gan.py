"""Tiny GAN on a 2-D Gaussian-mixture (reference example/gluon/dc_gan
training pattern, shrunk to run on CPU in seconds).

Pins the adversarial idioms a switching user needs: two Trainers over
disjoint parameter sets, `detach()` cutting the generator out of the
discriminator's backward, and label flipping for the generator step.
The quantitative check: generated samples must cover most mixture
modes (mode coverage >= threshold), not just fool the discriminator.

Run (CPU smoke):
    JAX_PLATFORMS=cpu python examples/train_gan.py
"""
from __future__ import annotations

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run from anywhere

import argparse

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, np
from mxnet_tpu.gluon import nn

MODES = onp.array([[2.0, 0.0], [-2.0, 0.0], [0.0, 2.0], [0.0, -2.0],
                   [1.5, 1.5], [-1.5, 1.5], [1.5, -1.5], [-1.5, -1.5]],
                  "f4")


def real_batch(rng, n):
    idx = rng.randint(0, len(MODES), n)
    return (MODES[idx] + 0.1 * rng.randn(n, 2)).astype("f4")


def mlp(out_units, hidden, act_last=None):
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, activation="relu"),
            nn.Dense(hidden, activation="relu"),
            nn.Dense(out_units, activation=act_last))
    return net


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=600)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--latent", type=int, default=8)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--min-modes", type=int, default=5)
    args = ap.parse_args()

    gen = mlp(2, 64)
    disc = mlp(1, 64)
    gen.initialize(mx.init.Xavier())
    disc.initialize(mx.init.Xavier())
    g_tr = gluon.Trainer(gen.collect_params(), "adam",
                         {"learning_rate": args.lr, "beta1": 0.5})
    d_tr = gluon.Trainer(disc.collect_params(), "adam",
                         {"learning_rate": args.lr, "beta1": 0.5})
    bce = gluon.loss.SigmoidBinaryCrossEntropyLoss()

    rng = onp.random.RandomState(0)
    ones = np.ones((args.batch,))
    zeros = np.zeros((args.batch,))
    for step in range(args.steps):
        real = np.array(real_batch(rng, args.batch))
        noise = np.array(rng.randn(args.batch, args.latent)
                         .astype("f4"))
        # --- discriminator step: real -> 1, fake(detached) -> 0 ---
        with autograd.record():
            fake = gen(noise)
            d_loss = (bce(disc(real), ones)
                      + bce(disc(fake.detach()), zeros)).mean()
        d_loss.backward()
        d_tr.step(args.batch)
        # --- generator step: make disc call fakes real ---
        with autograd.record():
            g_loss = bce(disc(gen(noise)), ones).mean()
        g_loss.backward()
        g_tr.step(args.batch)
        if step % 150 == 0 or step == args.steps - 1:
            print(f"step {step}  d_loss {float(d_loss.asnumpy()):.3f}"
                  f"  g_loss {float(g_loss.asnumpy()):.3f}")

    # ---- mode coverage: fraction of mixture modes with a nearby
    # generated sample ----
    noise = np.array(rng.randn(1024, args.latent).astype("f4"))
    samples = gen(noise).asnumpy()
    d2 = ((samples[:, None, :] - MODES[None]) ** 2).sum(-1)
    nearest = d2.argmin(1)
    covered = len({int(m) for m, dist in
                   zip(nearest, d2.min(1)) if dist < 1.0})
    print(f"modes_covered {covered}/8")
    assert covered >= args.min_modes, \
        f"mode collapse: only {covered} modes covered"


if __name__ == "__main__":
    main()
