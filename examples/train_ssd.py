"""Toy single-shot detector (SSD) on synthetic shapes.

Exercises the full detection op stack end to end, the workload of the
reference's `example/ssd`: anchors from `npx.multibox_prior`, training
targets from `npx.multibox_target` (IoU matching + hard negative
mining), offset regression (SmoothL1) + class scores (softmax CE),
and `npx.multibox_detection` (decode + per-class NMS) at eval — all on
a tiny conv backbone so it runs on CPU in seconds.

Task: each image contains ONE axis-aligned bright rectangle on a dark
noisy background; class = rectangle orientation (wide vs tall). The
detector must localize it (IoU vs ground truth) and classify it.

Run (CPU smoke):
    JAX_PLATFORMS=cpu python examples/train_ssd.py
"""
from __future__ import annotations

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run from anywhere

import argparse

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, np, npx
from mxnet_tpu.gluon import nn

HW = 32
N_CLASSES = 2  # wide vs tall (background is id 0 inside the op stack)


def synth_batch(rng, batch):
    """Images (B,3,HW,HW) + labels (B,1,5) [cls, xmin,ymin,xmax,ymax]
    in normalized corner coords."""
    imgs = rng.uniform(0.0, 0.2, (batch, 3, HW, HW)).astype("f4")
    labels = onp.zeros((batch, 1, 5), "f4")
    for i in range(batch):
        wide = rng.randint(0, 2)
        w, h = (rng.randint(12, 18), rng.randint(5, 8)) if wide \
            else (rng.randint(5, 8), rng.randint(12, 18))
        x0 = rng.randint(1, HW - w - 1)
        y0 = rng.randint(1, HW - h - 1)
        chan = rng.randint(0, 3)
        imgs[i, chan, y0:y0 + h, x0:x0 + w] = 1.0
        labels[i, 0] = [wide, x0 / HW, y0 / HW,
                        (x0 + w) / HW, (y0 + h) / HW]
    return imgs, labels


class TinySSD(nn.HybridBlock):
    """Conv backbone -> one 8x8 feature map -> per-anchor heads."""

    def __init__(self, n_anchor_shapes):
        super().__init__()
        self.backbone = nn.HybridSequential()
        for ch in (16, 32):
            self.backbone.add(
                nn.Conv2D(ch, 3, padding=1, strides=2),
                nn.BatchNorm(), nn.Activation("relu"))
        k = n_anchor_shapes
        # class head: (background + classes) per anchor shape
        self.cls_head = nn.Conv2D(k * (N_CLASSES + 1), 3, padding=1)
        self.box_head = nn.Conv2D(k * 4, 3, padding=1)

    def forward(self, x):
        f = self.backbone(x)                       # (B, C, 8, 8)
        B = f.shape[0]
        cls = self.cls_head(f)                     # (B, k*(C+1), 8, 8)
        box = self.box_head(f)                     # (B, k*4, 8, 8)
        cls = cls.transpose(0, 2, 3, 1).reshape(B, -1, N_CLASSES + 1)
        box = box.transpose(0, 2, 3, 1).reshape(B, -1)
        return cls, box, f


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--eval-iou", type=float, default=0.4)
    args = ap.parse_args()

    sizes, ratios = (0.35, 0.5), (1.0, 2.0, 0.5)
    k = len(sizes) + len(ratios) - 1
    net = TinySSD(k)
    net.initialize(mx.init.Xavier())

    rng = onp.random.RandomState(0)
    box_loss = gluon.loss.HuberLoss(rho=1.0)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})

    # anchors depend only on the feature-map geometry: compute once,
    # outside any autograd tape
    imgs0, _ = synth_batch(rng, 1)
    _, _, feat0 = net(np.array(imgs0))
    anchors = npx.multibox_prior(feat0, sizes=sizes, ratios=ratios)

    for step in range(args.steps):
        imgs_np, labels_np = synth_batch(rng, args.batch)
        imgs = np.array(imgs_np)
        labels = np.array(labels_np)
        with autograd.record():
            cls_pred, box_pred, feat = net(imgs)
            box_t, box_m, cls_t = npx.multibox_target(
                anchors, labels, cls_pred.transpose(0, 2, 1),
                negative_mining_ratio=3.0)
            # cls_t: -1 = ignored by hard-negative mining — mask it
            # out of the class loss (the reference SSD recipe)
            keep = (cls_t >= 0).astype("float32")
            logp = npx.log_softmax(cls_pred, axis=-1)
            picked = npx.pick(logp, np.maximum(cls_t, 0), axis=-1)
            l_cls = -(picked * keep).sum() / np.maximum(
                keep.sum(), 1.0)
            l_box = box_loss(box_pred * box_m, box_t)  # box_t pre-masked
            loss = l_cls + l_box.mean() * 10.0
        loss.backward()
        trainer.step(args.batch)
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step}  loss {float(loss.asnumpy()):.4f}")

    # ---- eval: decode + NMS, check localization on fresh images ----
    imgs_np, labels_np = synth_batch(rng, 32)
    cls_pred, box_pred, _ = net(np.array(imgs_np))
    cls_prob = npx.softmax(cls_pred, axis=-1).transpose(0, 2, 1)
    out = npx.multibox_detection(cls_prob, box_pred, anchors,
                                 nms_threshold=0.45)
    out_np = out.asnumpy()
    # one batched IoU call for all best-detection/gt pairs
    bests = onp.full((len(imgs_np), 6), -1.0, "f4")
    for i in range(len(imgs_np)):
        dets = out_np[i]
        dets = dets[dets[:, 0] >= 0]
        if len(dets):
            bests[i] = dets[dets[:, 1].argmax()]
    ious = npx.box_iou(np.array(bests[:, None, 2:6]),
                       np.array(labels_np[:, :, 1:5])).asnumpy()
    hits = sum(1 for i in range(len(imgs_np))
               if ious[i, 0, 0] >= args.eval_iou
               and int(bests[i, 0]) == int(labels_np[i, 0, 0]))
    acc = hits / len(imgs_np)
    print(f"detection_accuracy {acc:.2f} (IoU>={args.eval_iou} + "
          "correct class)")
    assert acc >= 0.5, "detector failed to learn the toy task"


if __name__ == "__main__":
    main()
