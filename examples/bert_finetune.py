"""BERT sequence-classification fine-tuning (BASELINE.json config 4;
parity: the reference ecosystem's GluonNLP finetune_classifier.py).

Synthetic sentence-pair task: class = whether the two segments share a
majority token. Uses the fused TrainStep (one XLA program per step)
with pad masking via valid_length, the config-4 training shape.
"""
from __future__ import annotations

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run from anywhere

import argparse

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import gluon, np, parallel
from mxnet_tpu.gluon.model_zoo.bert import BERTClassifier, bert_small


def synthetic_pairs(n, seq_len, vocab, rng):
    """Token pairs with a learnable signal: positive examples repeat a
    marker token in both segments."""
    toks = rng.randint(4, vocab, (n, seq_len))
    seg = onp.zeros((n, seq_len), "int32")
    seg[:, seq_len // 2:] = 1
    labels = rng.randint(0, 2, n)
    marker = 2
    for i in range(n):
        if labels[i]:
            toks[i, 1] = marker
            toks[i, seq_len // 2 + 1] = marker
    valid = rng.randint(seq_len // 2 + 2, seq_len + 1, n)
    return (toks.astype("int32"), seg, valid.astype("int32"),
            labels.astype("int32"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--lr", type=float, default=5e-4)
    args = ap.parse_args()

    import jax
    n_dev = jax.local_device_count()
    mesh = parallel.make_mesh((n_dev,), ("dp",))
    parallel.set_mesh(mesh)

    vocab = 200
    net = BERTClassifier(bert_small(vocab_size=vocab,
                                    max_length=args.seq_len),
                         num_classes=2)
    net.initialize(mx.init.TruncNorm(stdev=0.02)
                   if hasattr(mx.init, "TruncNorm") else mx.init.Xavier())

    step = parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adamw"
        if "adamw" in dir(mx.optimizer) else "adam",
        optimizer_params={"learning_rate": args.lr}, mesh=mesh,
        batch_axis="dp")

    rng = onp.random.RandomState(0)
    bs = args.batch_size * n_dev
    losses = []
    for s in range(args.steps):
        toks, seg, valid, y = synthetic_pairs(bs, args.seq_len, vocab,
                                              rng)
        loss = step((np.array(toks), np.array(seg), np.array(valid)),
                    np.array(y))
        losses.append(float(loss.asnumpy()))
    print(f"bert finetune: loss {losses[0]:.4f} -> {losses[-1]:.4f}")

    # eval accuracy on fresh data; hybridize so eval is one jitted
    # program (eager ops can't mix mesh params with fresh host arrays)
    net.hybridize()
    toks, seg, valid, y = synthetic_pairs(256, args.seq_len, vocab, rng)
    ins = [parallel.replicate(np.array(a), mesh)
           for a in (toks, seg, valid)]
    out = net(*ins)
    acc = (out.asnumpy().argmax(1) == y).mean()
    print(f"eval accuracy: {acc:.3f}")


if __name__ == "__main__":
    main()
