"""INT8 post-training quantization with calibration (parity:
example/quantization/*: quantize a trained fp32 model, calibrate
activation ranges, compare accuracy)."""
from __future__ import annotations

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run from anywhere

import argparse

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import gluon, np
from mxnet_tpu.contrib.quantization import quantize_net


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18_v1")
    ap.add_argument("--calib-mode", default="entropy",
                    choices=["none", "naive", "entropy"])
    ap.add_argument("--batches", type=int, default=4)
    args = ap.parse_args()

    rng = onp.random.RandomState(0)
    protos = rng.rand(10, 32, 32, 3).astype("float32")
    y = rng.randint(0, 10, 512)
    x = protos[y] + 0.05 * rng.rand(512, 32, 32, 3).astype("float32")

    net = getattr(gluon.model_zoo.vision, args.model)(
        classes=10, layout="NHWC")
    net.initialize(mx.init.Xavier())
    net.hybridize()

    data = np.array(x)
    labels = np.array(y.astype("int32"))
    fp32_out = net(data[:128]).asnumpy()

    calib = [(data[i * 32:(i + 1) * 32],) for i in range(args.batches)]
    qnet = quantize_net(net, quantized_dtype="int8",
                        calib_mode=args.calib_mode, calib_data=calib)
    qnet.hybridize()
    int8_out = qnet(data[:128]).asnumpy()

    agree = (fp32_out.argmax(1) == int8_out.argmax(1)).mean()
    print(f"{args.model} int8 ({args.calib_mode} calibration): "
          f"top-1 agreement with fp32 on synthetic eval = {agree:.3f}")
    metric = gluon.metric.Accuracy()
    metric.update(labels[:128], np.array(int8_out))
    print("int8 accuracy vs labels:", metric.get()[1])


if __name__ == "__main__":
    main()
