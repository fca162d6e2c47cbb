"""Python custom operators three ways (parity:
example/extensions/lib_custom_op and python/mxnet/operator.py):

1. `mx.operator.CustomOp` — registered op with prop, shape/type
   inference, imperative forward/backward over NDArrays.
2. `autograd.Function` — inline custom-VJP callable.
3. `mx.rtc` — a user Pallas kernel (the NVRTC/CUDA-string analogue),
   jit-compiled for the accelerator.
"""
from __future__ import annotations

import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))  # run from anywhere

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, np, operator


@operator.register("softsign_x")
class SoftsignProp(operator.CustomOpProp):
    def list_arguments(self):
        return ["data"]

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return Softsign()


class Softsign(operator.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0]
        self.assign(out_data[0], req[0], x / (1 + abs(x)))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        x = in_data[0]
        g = out_grad[0] / (1 + abs(x)) ** 2
        self.assign(in_grad[0], req[0], g)


class ClipGrad(autograd.Function):
    """Identity forward, clipped gradient backward."""

    def forward(self, x):
        return x

    def backward(self, dy):
        return np.clip(dy, -0.1, 0.1)


def main():
    x = np.array(onp.linspace(-3, 3, 8, dtype="float32"))
    x.attach_grad()
    with autograd.record():
        y = mx.npx.custom(x, op_type="softsign_x")
        z = ClipGrad()(y * 4.0)
        loss = z.sum()
    loss.backward()
    print("softsign:", y.asnumpy().round(3))
    print("clipped grads:", x.grad.asnumpy().round(3))

    # Pallas path: runtime-compiled vector kernel through mx.rtc
    src = (
        "def scale2(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...] * 2.0\n")
    mod = mx.rtc.PallasModule(src)
    kernel = mod.get_kernel("scale2")
    out = kernel(np.array([1.0, 2.0, 3.0]))
    print("pallas scale2:", out.asnumpy())


if __name__ == "__main__":
    main()
